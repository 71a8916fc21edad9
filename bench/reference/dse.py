"""The plain reference of a design-space pass: how one grid point becomes a
workload, the cycle budget its group runs for, and the frontier row it is
scored into.

A frozen copy of the simulator's DSE scoring as it stood when the
benchmark's ``dse`` traffic was defined: the Fig. 8 pattern lowering of a
point (read DMA, every stream to the pattern's destination), the budget
from the busiest endpoint, the route-table walk that gives the mean hop
count, and the Fig. 9 area and energy models of the FlooNoC paper
(arXiv:2409.17606, GF 12LP+ at 0.8 V). It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from bench.lib import gen
from bench.reference.topology import Fabric

# the simulator's DSE budget: base latency + cycles per wide beat of the
# busiest endpoint
CYCLES_BASE = 600
CYCLES_PER_BEAT = 12
BEAT_BYTES = 64
FREQ_GHZ = 1.26

# Fig. 9 / Fig. 10 area and energy calibration
TILE_AREA_MM2 = 1.125
NOC_TILE_FRACTION = 0.035
ROUTER_BUFFER_FRACTION = 0.53
ROUTER_REF_RADIX = 5
ROUTER_REF_CHANNELS = 3
NI_ROBLESS_KGE = 25.0
ROB_KGE = 256.0
KGE_MM2 = 1.54e-4
E_PER_BYTE_PER_HOP_PJ = 0.15
V_NOM = 0.8
VC_ENERGY_FACTOR = 0.05

# spec defaults a grid point does not set
DEFAULTS = {"n_channels": 3, "n_vcs": 1, "ni_order": "robless", "streams": 1,
            "seed": 7}


def point_workload(fab: Fabric, point: dict) -> dict:
    """Workload arrays of one point: every tile issues ``n_txns`` reads of
    ``transfer_kb`` kB on each stream, all to its pattern destination."""
    p = {**DEFAULTS, **point}
    dst = gen.pattern_dst(fab, p["workload"], p["seed"])
    return gen.workload(fab, streams=p["streams"],
                        dst=np.repeat(dst[:, None], p["streams"], axis=1),
                        burst_kb=p["transfer_kb"], txns=p["n_txns"])


def cycles_budget(w: dict) -> int:
    """Cycles a point asks for: from its busiest endpoint's wide beats."""
    per_ep = np.maximum(np.asarray(w["dma_txns"]), 0).sum(axis=1) * int(w["dma_beats"])
    return CYCLES_BASE + CYCLES_PER_BEAT * int(per_ep.max())


def traffic_pairs(fab: Fabric, pattern: str, seed: int) -> list[tuple[int, int]]:
    """(source, destination) endpoint pairs the pattern can exercise."""
    nt = fab.n_tiles
    if pattern == "uniform":
        return [(s, d) for s in range(nt) for d in range(nt) if s != d]
    dst = gen.pattern_dst(fab, pattern, seed)
    return [(s, int(dst[s])) for s in range(nt) if int(dst[s]) != s]


def mean_hops(fab: Fabric, pairs) -> float:
    """Mean routers traversed over the pairs, ejection router included."""
    pe = fab.port_ep
    if len(pairs) > 4096:
        pairs = pairs[:: len(pairs) // 2048]
    total = 0
    for s, d in pairs:
        cur = int(fab.ep_attach[s][0])
        n = 0
        while True:
            n += 1
            op = int(fab.route[cur, d])
            if pe[cur, op] == d:
                break
            cur = int(fab.link_to[cur, op, 0])
        total += n
    return total / max(len(pairs), 1)


def router_area_mm2(radix: int, n_channels: int, n_vcs: int) -> float:
    """A router's area scaled from the paper's radix-5, 3-channel router:
    buffers with channels x VCs x ports, crossbar with channels x ports^2."""
    a0 = NOC_TILE_FRACTION * TILE_AREA_MM2
    c = n_channels / ROUTER_REF_CHANNELS
    r = radix / ROUTER_REF_RADIX
    buffers = ROUTER_BUFFER_FRACTION * a0 * c * n_vcs * r
    logic = (1.0 - ROUTER_BUFFER_FRACTION) * a0 * c * r * r
    return buffers + logic


def fabric_area_mm2(fab: Fabric, n_channels: int, n_vcs: int, ni_order: str) -> float:
    """Every router at its live radix (wired links + endpoints) plus one
    network interface per endpoint."""
    radix = np.asarray((fab.link_to[..., 0] >= 0).sum(axis=1))
    for r, _ in fab.ep_attach:
        radix[r] += 1
    area = sum(router_area_mm2(int(k), n_channels, n_vcs) for k in radix)
    ni_kge = NI_ROBLESS_KGE + (ROB_KGE if ni_order == "rob" else 0.0)
    area += fab.n_endpoints * ni_kge * KGE_MM2
    return float(area)


def pj_per_byte(hops: float, n_vcs: int) -> float:
    """Energy per payload byte at ``hops`` router traversals, with 5% more
    per extra virtual channel."""
    per_hop = E_PER_BYTE_PER_HOP_PJ * (V_NOM / V_NOM) ** 2
    return per_hop * hops * (1.0 + VC_ENERGY_FACTOR * (n_vcs - 1))


def _models(fab: Fabric, point: dict) -> tuple[float, float, float]:
    """Mean hops, area and pJ per byte of a point, unrounded."""
    p = {**DEFAULTS, **point}
    hops = mean_hops(fab, traffic_pairs(fab, p["workload"], p["seed"]))
    return (hops, fabric_area_mm2(fab, p["n_channels"], p["n_vcs"], p["ni_order"]),
            pj_per_byte(hops, p["n_vcs"]))


def static_fields(fab: Fabric, point: dict, budget: int) -> dict:
    """The row fields that need no simulation, rounded as the row keeps
    them."""
    hops, area, pj_b = _models(fab, point)
    return {"n_cycles_run": budget, "mean_hops": round(hops, 4),
            "area_mm2": round(area, 6), "pj_per_byte": round(pj_b, 6)}


def row(fab: Fabric, point: dict, w: dict, stats: dict, budget: int) -> dict:
    """The frontier row of a simulated point (``stats`` from
    ``bench.reference.sim.stats``)."""
    hops, area, pj_b = _models(fab, point)
    cycles = int(stats["last_rx"].max())
    done = int(stats["dma_done"].sum())
    expect = int(np.maximum(np.asarray(w["dma_txns"]), 0).sum())
    bytes_moved = int(stats["beats_rcvd"].sum()) * BEAT_BYTES
    gbps = bytes_moved / max(cycles, 1) * FREQ_GHZ
    return {
        "n_cycles_run": budget,
        "cycles": cycles,
        "delivered": bool(done == expect),
        "bytes": bytes_moved,
        "wide_util": round(float(stats["wide_util"]), 6),
        "mean_hops": round(hops, 4),
        "area_mm2": round(area, 6),
        "pj_per_byte": round(pj_b, 6),
        "energy_uj": round(pj_b * bytes_moved * 1e-6, 6),
        "gbps": round(gbps, 3),
        "gbps_per_mm2": round(gbps / area, 3),
    }
