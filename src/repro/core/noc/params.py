"""FlooNoC microarchitecture parameters (paper Section III-V defaults)."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class NocParams:
    """FlooNoC microarchitecture + simulator configuration (paper defaults).

    Covers router buffer depths, NI ordering scheme and credits, cluster/
    memory latencies (calibrated to Fig. 7), the HBM model, link widths
    (Table I), physical channel count (``n_channels``), and the per-cycle
    router compute ``backend`` ("jnp" | "pallas").
    """

    # router microarchitecture
    depth_in: int = 2  # input FIFO depth (paper: minimal input buffers)
    depth_out: int = 2  # output buffers (timing closure across >1mm links)

    # virtual channels per physical channel. The paper's mesh routers are
    # VC-less (1, the default — bit-identical to the historical fabric);
    # 2 enables dateline VC-switching on torus wrap links, making
    # shortest-direction XY routing on a torus provably deadlock-free
    # (docs/ROUTING.md). Each (port, VC) pair gets its own depth_in input
    # FIFO and depth_out output buffer; physical links carry one flit per
    # cycle regardless of n_vcs.
    n_vcs: int = 1

    # endpoint / NI
    n_txn_ids: int = 8  # AXI TxnIDs tracked per endpoint
    ni_order: str = "robless"  # "robless" | "rob"
    rob_beats: int = 128  # RoB capacity in wide beats (8 kB / 64 B)
    max_outstanding: int = 32  # per DMA stream

    # cluster-internal latencies (calibrated to Fig. 7: 22-cycle neighbor
    # round trip = 8 router + 3 NI + 11 cluster/memory)
    cluster_req_lat: int = 4
    cluster_rsp_lat: int = 4
    mem_lat: int = 3
    ni_req_lat: int = 1  # AXI -> flit packing
    ni_rsp_lat: int = 1  # flit -> AXI unpacking (target side: 1 more)

    # HBM model (HBM2E MT54A16G808A00AC-36: 57.6 GB/s per channel)
    # wide link moves 64 B/cycle @ 1.26 GHz = 80.6 GB/s -> ratio 0.714
    hbm_rate: float = 57.6 / 80.6
    hbm_eff: float = 0.97  # refresh/row-miss derate (zero-load util ~97%)

    # link frequency / widths (Table I)
    freq_ghz: float = 1.26
    narrow_bits: int = 64
    wide_bits: int = 512

    # egress queue depths
    egress_depth: int = 8
    memq_depth: int = 256  # >= fan-in x max_outstanding for the workloads used

    # physical channels: req + rsp + (n_channels - 2) wide channels.
    # 3 = the paper's req/rsp/wide; >3 stripes wide traffic over extra wide
    # channels by TxnID (PATRONoC-style parallel AXI channels).
    n_channels: int = 3

    # per-cycle router compute backend: "jnp" (vmapped reference) or
    # "pallas" ((C, R/K)-gridded kernels, compiled on a TPU and
    # interpreted elsewhere; fused_cycles > 1 runs interpreted only and
    # raises on a TPU). Bit-identical; see repro.kernels.noc_router and
    # tests/test_noc_backend.py.
    backend: str = "jnp"

    # step implementation: "fast" (circular queues, fused FIFO updates,
    # scatter injection — the speed path) or "naive" (the roll-based
    # reference step the fast path is equivalence-pinned against, see
    # sim.canonical_state). Live behavior is identical; only dead queue
    # slots / buffer garbage differ.
    step_impl: str = "fast"

    # Pallas grid tiling: K routers per program (grid (C, R/K)). The
    # effective tile is the largest multiple of 8 that divides R and is
    # <= router_tile (what the TPU accepts as a block's sublane dim), else
    # the whole fabric, so any value is valid; 0 means K = R.
    router_tile: int = 8

    # multi-cycle super-stepping: cycles the fabric advances per fused
    # kernel call in sim.run(..., super_cycles=...) / Sim.step_super.
    # 1 (default) is bit-identical to per-cycle stepping; >1 quantizes
    # endpoint interaction to super-step boundaries (see core/noc/README).
    fused_cycles: int = 1

    # in-network collective offload (Colagrande et al. sequel paper):
    # routers fork WIDE_MC flits along a per-group multicast tree
    # (credit-checked on every branch before the single pop) and combine
    # WIDE_RED partial sums in a per-(router, group) ALU slot before
    # forwarding one flit toward the root. False (default) is bit-identical
    # to the historical fabric — the offload tables/state are never
    # materialized and the pinned router traces carry no extra operands.
    # Requires fused_cycles == 1 (offload state is not threaded through the
    # fused multi-cycle kernels); enforced at build_sim time.
    collective_offload: bool = False

    def __post_init__(self):
        """Validate the channel count, backend name, and stepping knobs."""
        if self.n_channels < 3:
            raise ValueError("n_channels must be >= 3 (req, rsp, >=1 wide)")
        if self.backend not in ("jnp", "pallas"):
            raise ValueError(
                f"backend must be 'jnp' or 'pallas', got {self.backend!r}")
        if self.step_impl not in ("fast", "naive"):
            raise ValueError(
                f"step_impl must be 'fast' or 'naive', got {self.step_impl!r}")
        if self.router_tile < 0:
            raise ValueError("router_tile must be >= 0 (0 = whole fabric)")
        if self.fused_cycles < 1:
            raise ValueError("fused_cycles must be >= 1")
        if self.n_vcs < 1:
            raise ValueError("n_vcs must be >= 1")
        if self.collective_offload and self.fused_cycles != 1:
            raise ValueError(
                "collective_offload requires fused_cycles == 1")


# flit kinds
NARROW_REQ = 0
NARROW_RSP = 1
WIDE_AR = 2  # wide read request (rides the narrow `req` link)
WIDE_R = 3  # wide read data beat (wide link)
WIDE_AW_W = 4  # wide write addr+data beats (wide link, wormhole)
WIDE_B = 5  # write response (rsp link)
WIDE_MC = 6  # multicast write beat (wide link; forked at tree fan-outs)
WIDE_RED = 7  # reduction partial-sum beat (wide link; combined per hop)

# physical channel roles (channel indices >= CH_WIDE are all wide channels;
# the channel *count* lives in NocParams.n_channels)
CH_REQ = 0
CH_RSP = 1
CH_WIDE = 2

# role channel a kind travels on (wide kinds ride wide_channel_of(txn, C))
KIND_CHANNEL = {
    NARROW_REQ: CH_REQ,
    NARROW_RSP: CH_RSP,
    WIDE_AR: CH_REQ,
    WIDE_R: CH_WIDE,
    WIDE_AW_W: CH_WIDE,
    WIDE_B: CH_RSP,
    WIDE_MC: CH_WIDE,
    WIDE_RED: CH_WIDE,
}


def wide_channel_of(txn, n_channels: int):
    """Physical channel carrying the wide beats of a transfer.

    Wide traffic stripes over channels CH_WIDE..n_channels-1 by TxnID, so all
    transfers of one TxnID share a channel (static routing + fixed channel
    keeps same-TxnID responses in order). With the paper's n_channels=3 this
    is always CH_WIDE."""
    return CH_WIDE + txn % (n_channels - CH_WIDE)
