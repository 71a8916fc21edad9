"""Pallas backend for the FlooNoC router cycle: K-router tiles, fused cycles.

One simulated cycle of the channel-batched fabric is two ``pallas_call``s,
each with ``grid=(n_channels, n_routers / K)`` — one program per (channel,
K-router block) — with the link stage between them in XLA:

1. **request lookup** (XLA) — ``ref.request_ports`` maps every input head
   to the output slot it requests (the routing-table gather, plus the VC
   expansion when ``n_vcs > 1``).
2. **arb** (kernel) — every program runs round-robin output arbitration
   for its router block from the cycle-start snapshot (its own requests,
   heads, occupancy and wormhole locks) and emits the decisions: pop/grant
   masks, the chosen flits, updated rr/wormhole state, and whether each
   input FIFO has space after its pops (``in_space``).
3. **link** (XLA) — ``ref.link_stage`` resolves every link traversal and
   endpoint delivery from the fabric-wide snapshot plus ``in_space``.
   Link acceptance depends on the *downstream* router's arbitration pops,
   so ``in_space`` of every router must be known before any link decision;
   that arb -> link barrier is the only per-cycle synchronization. The
   link stage gathers across routers, which Mosaic cannot lower and which
   would make every program read the whole fabric.
4. **apply** (kernel) — every program applies the FIFO pops/pushes of its
   block (``ref.apply_cycle``).

``K`` (``NocParams.router_tile``) amortizes program dispatch. It is the
second-to-last axis of the ``[C, R, P]`` counter blocks, which the TPU
tiles by 8 sublanes, so ``effective_tile`` only picks multiples of 8 that
divide R, or the whole fabric.

``router_cycles_fused_pallas`` runs N simulated cycles in one kernel per
channel: a ``fori_loop`` whose carry (the whole channel's fabric state plus
the endpoint egress queues) stays resident in kernel memory, with
``input_output_aliases`` donating the state buffers in place. Its body
(``ref.fused_cycle_body``) gathers across routers inside the kernel, so it
runs in interpret mode only; on a TPU ``ops.router_cycles_fused`` refuses
it (see ``FUSED_TPU_REFUSAL``).

All decision math is imported from ``repro.kernels.noc_router.ref`` — the
functions are rank-generic over the leading router axis, so the Pallas
programs (R-blocks of K) execute the very same code as the vmapped jnp
reference (full R), making the backends bit-identical by construction.
The per-cycle kernels compile for the TPU (checked against a described
v5e in ``tests/test_tpu_compile.py``); off the TPU they run in interpret
mode. Use ``repro.kernels.noc_router.ops`` for the backend-dispatching
entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.noc_router import ref
from repro.kernels.noc_router.ref import NF, NRED

# what the TPU compiler (Mosaic) refuses in the fused multi-cycle kernel,
# which therefore runs in interpret mode only; ops.py raises this on a TPU
FUSED_TPU_REFUSAL = (
    "the fused multi-cycle Pallas kernel does not compile for TPU: Mosaic "
    "refuses its per-channel (1, E) blocks (the last two block dims must be "
    "multiples of (8, 128) or the whole array) and has no lowering for the "
    "gathers of ref.fused_cycle_body; use fused_cycles=1 or backend='jnp'")


def effective_tile(router_tile: int, n_routers: int) -> int:
    """Routers per program: the largest multiple of 8 that divides
    ``n_routers`` and is <= ``router_tile``; the whole fabric when there
    is none or ``router_tile`` is 0 or >= ``n_routers``.

    Dividing keeps every block full (no padding programs, no masked
    lanes); the multiple of 8 is what the TPU accepts as the
    second-to-last block dim of the ``[C, R, P]`` counters.
    """
    if 0 < router_tile < n_routers:
        for k in range(router_tile - router_tile % 8, 0, -8):
            if n_routers % k == 0:
                return k
    return n_routers


def _arb_kernel(heads_ref, req_ref, in_cnt_ref, out_cnt_ref, rr_ref, wh_ref,
                *out_refs, depth_in: int, depth_out: int):
    """Arbitration decisions for one (channel, K-router block) program."""
    arb = ref.arbitrate(req_ref[0], heads_ref[0], in_cnt_ref[0],
                        out_cnt_ref[0], rr_ref[0], wh_ref[0],
                        depth_in=depth_in, depth_out=depth_out)
    for out_ref, val in zip(out_refs, arb):
        out_ref[...] = val[None]


def _arb_kernel_offload(heads_ref, req_ref, in_cnt_ref, out_cnt_ref, rr_ref,
                        wh_ref, fork_ref, rparent_ref, rneed_ref, racc_ref,
                        rgot_ref, *out_refs, depth_in: int, depth_out: int,
                        n_endpoints: int):
    """Collective-offload arbitration: fork table + reduction ALU.

    Mirrors ``ref.offload_decisions`` for one (channel, K-router block)
    program; the per-(router, group) reduction accumulator/contribution
    state rides as two extra channel-batched operands and comes back as two
    extra outputs. The apply kernel is shared unchanged: fork copies and
    emitted reduction flits arrive through the merged grant/chosen
    decisions.
    """
    arb, racc2, rgot2 = ref.offload_decisions(
        req_ref[0], heads_ref[0], in_cnt_ref[0], out_cnt_ref[0], rr_ref[0],
        wh_ref[0], depth_in=depth_in, depth_out=depth_out,
        fork_out=fork_ref[...],  # [K, NG, P]
        red_parent=rparent_ref[...],  # [K, NG]
        red_need=rneed_ref[...],  # [K, NG]
        red_acc=racc_ref[0],  # [K, NG, NRED]
        red_got=rgot_ref[0],  # [K, NG, P]
        n_endpoints=n_endpoints)
    for out_ref, val in zip(out_refs, (*arb, racc2, rgot2)):
        out_ref[...] = val[None]


def _apply_kernel(in_buf_ref, in_cnt_ref, out_buf_ref, out_cnt_ref,
                  arb_pop_ref, granted_ref, chosen_ref, accept_ref,
                  up_head_ref, sent_ref, *out_refs, fused: bool):
    """FIFO pops/pushes for one (channel, K-block) program."""
    new = ref.apply_cycle(
        in_buf_ref[0], in_cnt_ref[0], out_buf_ref[0], out_cnt_ref[0],
        arb_pop_ref[0], granted_ref[0], chosen_ref[0], accept_ref[0],
        up_head_ref[0], sent_ref[0], fused=fused)
    for out_ref, val in zip(out_refs, new):
        out_ref[...] = val[None]


def router_cycle_pallas(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
                        route, link_src, link_dst, port_ep, ep_attach,
                        ep_space, *, router_tile: int = 1,
                        fused_fifo: bool = False, interpret: bool = False,
                        vc_out=None, n_vcs: int = 1,
                        fork_out=None, red_parent=None, red_need=None,
                        red_acc=None, red_got=None, n_endpoints: int = 0):
    """One fabric cycle on the Pallas backend.

    State is channel-batched (``in_buf`` [C, R, P, Din, NF], counters
    [C, R, P]); tables are shared across channels (``route`` [R, E],
    ``link_src``/``link_dst`` [R, Pp, 2], ``port_ep`` [R, P], ``ep_attach``
    [E, 2]); ``ep_space`` [C, E] is the per-channel endpoint ingress-space
    mask. ``router_tile`` blocks K routers per program (grid
    ``(C, R / K)``, see ``effective_tile``); ``fused_fifo`` selects the
    fused FIFO datapath (must match the jnp side being compared against).
    With ``n_vcs > 1`` the state P axis is slot-level (physical ports
    Pp = P / n_vcs; link tables stay physical) and the request lookup
    expands through ``vc_out`` [R, P, Pp]; the kernels are the same.
    Returns the updated state plus the endpoint deliveries
    ``(ep_flit [C, E, NF], ep_valid [C, E])`` — identical, bit for bit, to
    ``ref.router_cycle_reference`` vmapped over channels with the same
    ``fused`` flag.

    With ``fork_out`` set (collective offload), arbitration runs the
    ``_arb_kernel_offload`` variant: the multicast fork / reduction-tree
    tables ride as extra block-sliced operands, the channel-batched
    reduction state ``red_acc`` [C, R, NG, NRED] / ``red_got``
    [C, R, NG, P] is consumed and re-emitted, and the return tuple extends
    to ``(..., ep_flit, ep_valid, red_acc', red_got')`` — bit-identical to
    ``ref.router_cycle_offload_reference`` vmapped over channels.
    """
    C, R, P = in_cnt.shape
    Din = in_buf.shape[-2]
    Dout = out_buf.shape[-2]
    i32 = jnp.int32
    K = effective_tile(router_tile, R)
    G = R // K

    state_spec = lambda *tail: pl.BlockSpec(
        (1, K, *tail), lambda c, r: (c, r) + (0,) * len(tail))
    router_spec = lambda *tail: pl.BlockSpec(
        (K, *tail), lambda c, r: (r,) + (0,) * len(tail))
    mask = jax.ShapeDtypeStruct((C, R, P), jnp.bool_)

    in_heads = in_buf[..., 0, :]  # [C, R, P, NF]
    req_port = jax.vmap(functools.partial(
        ref.request_ports, route=route, vc_out=vc_out, n_vcs=n_vcs))(
            in_heads, in_cnt)

    offload = fork_out is not None
    if offload:
        NG = red_need.shape[-1]
        arb_fn = functools.partial(_arb_kernel_offload, depth_in=Din,
                                   depth_out=Dout, n_endpoints=n_endpoints)
        arb_extra = [fork_out, red_parent, red_need, red_acc, red_got]
        arb_extra_specs = [router_spec(NG, P), router_spec(NG),
                           router_spec(NG), state_spec(NG, NRED),
                           state_spec(NG, P)]
        extra_out_specs = [state_spec(NG, NRED), state_spec(NG, P)]
        extra_out_shapes = [
            jax.ShapeDtypeStruct((C, R, NG, NRED), i32),
            jax.ShapeDtypeStruct((C, R, NG, P), jnp.bool_),
        ]
    else:
        arb_fn = functools.partial(_arb_kernel, depth_in=Din, depth_out=Dout)
        arb_extra, arb_extra_specs = [], []
        extra_out_specs, extra_out_shapes = [], []
    arb_pop, granted, chosen, rr2, wh2, in_space, *red_new = pl.pallas_call(
        arb_fn,
        grid=(C, G),
        in_specs=[
            state_spec(P, NF),  # input heads
            state_spec(P),  # requested output slot
            state_spec(P),  # in_cnt
            state_spec(P),  # out_cnt
            state_spec(P),  # rr_ptr
            state_spec(P),  # wh_lock
            *arb_extra_specs,  # offload tables + reduction state
        ],
        out_specs=[
            state_spec(P),  # arb_pop
            state_spec(P),  # granted
            state_spec(P, NF),  # chosen
            state_spec(P),  # rr_ptr'
            state_spec(P),  # wh_lock'
            state_spec(P),  # in_space
            *extra_out_specs,  # red_acc' / red_got' (offload only)
        ],
        out_shape=[
            mask,
            mask,
            jax.ShapeDtypeStruct((C, R, P, NF), i32),
            jax.ShapeDtypeStruct((C, R, P), i32),
            jax.ShapeDtypeStruct((C, R, P), i32),
            mask,
            *extra_out_shapes,
        ],
        interpret=interpret,
    )(in_heads, req_port, in_cnt, out_cnt, rr_ptr, wh_lock, *arb_extra)

    up_head, link_accept, sent, ep_flit, ep_valid = jax.vmap(
        lambda ob, oc, sp, es: ref.link_stage(
            ob, oc, sp, link_src, link_dst, port_ep, ep_attach, es,
            n_vcs=n_vcs))(out_buf, out_cnt, in_space, ep_space)

    in2, in_cnt2, out2, out_cnt2 = pl.pallas_call(
        functools.partial(_apply_kernel, fused=fused_fifo),
        grid=(C, G),
        in_specs=[
            state_spec(P, Din, NF),  # in_buf
            state_spec(P),  # in_cnt
            state_spec(P, Dout, NF),  # out_buf
            state_spec(P),  # out_cnt
            state_spec(P),  # arb_pop
            state_spec(P),  # granted
            state_spec(P, NF),  # chosen
            state_spec(P),  # link_accept
            state_spec(P, NF),  # up_head
            state_spec(P),  # sent
        ],
        out_specs=[
            state_spec(P, Din, NF),
            state_spec(P),
            state_spec(P, Dout, NF),
            state_spec(P),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, R, P, Din, NF), i32),
            jax.ShapeDtypeStruct((C, R, P), i32),
            jax.ShapeDtypeStruct((C, R, P, Dout, NF), i32),
            jax.ShapeDtypeStruct((C, R, P), i32),
        ],
        interpret=interpret,
    )(in_buf, in_cnt, out_buf, out_cnt, arb_pop, granted, chosen,
      link_accept, up_head, sent)

    if offload:
        return (in2, in_cnt2, out2, out_cnt2, rr2, wh2, ep_flit, ep_valid,
                red_new[0], red_new[1])
    return in2, in_cnt2, out2, out_cnt2, rr2, wh2, ep_flit, ep_valid


def _fused_impl(in_buf_ref, in_cnt_ref, out_buf_ref, out_cnt_ref, rr_ref,
                wh_ref, eg_ref, eg_ready_ref, eg_head_ref, eg_cnt_ref,
                route_ref, link_src_ref, link_dst_ref, port_ep_ref,
                ep_attach_ref, ep_space_ref, cycle0_ref,
                nin_buf_ref, nin_cnt_ref, nout_buf_ref, nout_cnt_ref,
                nrr_ref, nwh_ref, neg_ref, neg_ready_ref, neg_head_ref,
                neg_cnt_ref, deliver_f_ref, deliver_v_ref, waiting_ref,
                vc_out, n_cycles: int, n_vcs: int):
    """N fused fabric cycles for one channel, state resident in the loop.

    The carry (fabric state + this channel's circular egress queue) lives
    in kernel values across the ``fori_loop`` — VMEM on TPU — touching the
    output refs only once at the end; per-cycle deliveries and waiting
    masks are streamed out at their cycle index. Shared body of the
    default and VC kernels (``vc_out=None, n_vcs=1`` traces exactly the
    historical kernel).
    """
    carry = (in_buf_ref[0], in_cnt_ref[0], out_buf_ref[0], out_cnt_ref[0],
             rr_ref[0], wh_ref[0], eg_ref[0], eg_ready_ref[0],
             eg_head_ref[0], eg_cnt_ref[0])
    route = route_ref[...]
    link_src = link_src_ref[...]
    link_dst = link_dst_ref[...]
    port_ep = port_ep_ref[...]
    ep_attach = ep_attach_ref[...]
    ep_space = ep_space_ref[0]
    cycle0 = cycle0_ref[0]

    def body(i, carry):
        carry, (ep_flit, ep_valid, waiting) = ref.fused_cycle_body(
            i, carry, route, link_src, link_dst, port_ep, ep_attach,
            ep_space, cycle0, n_cycles, vc_out=vc_out, n_vcs=n_vcs)
        at = (slice(0, 1), pl.ds(i, 1))
        deliver_f_ref[at] = ep_flit[None, None]
        deliver_v_ref[at] = ep_valid[None, None]
        waiting_ref[at] = waiting[None, None]
        return carry

    carry = jax.lax.fori_loop(0, n_cycles, body, carry)
    for out_ref, val in zip(
            (nin_buf_ref, nin_cnt_ref, nout_buf_ref, nout_cnt_ref, nrr_ref,
             nwh_ref, neg_ref, neg_ready_ref, neg_head_ref, neg_cnt_ref),
            carry):
        out_ref[...] = val[None]


def _fused_kernel(*refs, n_cycles: int):
    """Default (VC-less) fused kernel: the historical operand list."""
    _fused_impl(*refs, vc_out=None, n_cycles=n_cycles, n_vcs=1)


def _fused_kernel_vc(in_buf_ref, in_cnt_ref, out_buf_ref, out_cnt_ref,
                     rr_ref, wh_ref, eg_ref, eg_ready_ref, eg_head_ref,
                     eg_cnt_ref, route_ref, vc_out_ref, *rest,
                     n_cycles: int, n_vcs: int):
    """VC fused kernel: ``vc_out`` rides as one extra table operand after
    ``route``; everything else is the shared body."""
    _fused_impl(in_buf_ref, in_cnt_ref, out_buf_ref, out_cnt_ref, rr_ref,
                wh_ref, eg_ref, eg_ready_ref, eg_head_ref, eg_cnt_ref,
                route_ref, *rest, vc_out=vc_out_ref[...], n_cycles=n_cycles,
                n_vcs=n_vcs)


def router_cycles_fused_pallas(in_buf, in_cnt, out_buf, out_cnt, rr_ptr,
                               wh_lock, eg, eg_ready, eg_head, eg_cnt,
                               route, link_src, link_dst, port_ep, ep_attach,
                               ep_space, cycle0, n_cycles: int, *,
                               interpret: bool = False, vc_out=None,
                               n_vcs: int = 1):
    """``n_cycles`` fused fabric cycles, one program per channel.

    Inputs are channel-batched state (+ the circular egress queues ``eg``
    [C, E, Q, NF] / ``eg_ready`` [C, E, Q] / ``eg_head``/``eg_cnt``
    [C, E]); ``cycle0`` is the window's first cycle number (traced scalar).
    The state inputs are aliased onto the outputs (donated in place).
    With ``n_vcs > 1`` the P axis is slot-level and ``vc_out`` [R, P, Pp]
    rides along as one extra shared table. Returns ``(state'..., eg'...,
    ep_flit [C, N, E, NF], ep_valid [C, N, E], req_waiting [C, N, E])`` —
    identical, bit for bit, to ``ref.router_cycles_scan`` vmapped over
    channels.
    """
    C, R, P = in_cnt.shape
    Din = in_buf.shape[-2]
    Dout = out_buf.shape[-2]
    E, Q = eg_ready.shape[-2:]
    Pp = P // n_vcs  # physical ports (== P when n_vcs == 1)
    i32 = jnp.int32
    N = n_cycles

    chan_spec = lambda *tail: pl.BlockSpec(
        (1, *tail), lambda c: (c,) + (0,) * len(tail))
    full_spec = lambda *shape: pl.BlockSpec(shape, lambda c: (0,) * len(shape))

    state_shapes = [
        jax.ShapeDtypeStruct((C, R, P, Din, NF), i32),  # in_buf
        jax.ShapeDtypeStruct((C, R, P), i32),  # in_cnt
        jax.ShapeDtypeStruct((C, R, P, Dout, NF), i32),  # out_buf
        jax.ShapeDtypeStruct((C, R, P), i32),  # out_cnt
        jax.ShapeDtypeStruct((C, R, P), i32),  # rr_ptr
        jax.ShapeDtypeStruct((C, R, P), i32),  # wh_lock
        jax.ShapeDtypeStruct((C, E, Q, NF), i32),  # eg
        jax.ShapeDtypeStruct((C, E, Q), i32),  # eg_ready
        jax.ShapeDtypeStruct((C, E), i32),  # eg_head
        jax.ShapeDtypeStruct((C, E), i32),  # eg_cnt
    ]
    state_specs = [
        chan_spec(R, P, Din, NF),
        chan_spec(R, P),
        chan_spec(R, P, Dout, NF),
        chan_spec(R, P),
        chan_spec(R, P),
        chan_spec(R, P),
        chan_spec(E, Q, NF),
        chan_spec(E, Q),
        chan_spec(E),
        chan_spec(E),
    ]

    if n_vcs == 1:
        kern = functools.partial(_fused_kernel, n_cycles=N)
        vc_tables, vc_specs = [], []
    else:
        kern = functools.partial(_fused_kernel_vc, n_cycles=N, n_vcs=n_vcs)
        vc_tables, vc_specs = [vc_out], [full_spec(R, P, Pp)]
    outs = pl.pallas_call(
        kern,
        grid=(C,),
        in_specs=state_specs + [
            full_spec(R, E),  # route
            *vc_specs,  # vc_out (V > 1 only)
            full_spec(R, Pp, 2),  # link_src (physical ports)
            full_spec(R, Pp, 2),  # link_dst
            full_spec(R, P),  # port_ep (slot-level)
            full_spec(E, 2),  # ep_attach
            chan_spec(E),  # ep_space
            full_spec(1),  # cycle0
        ],
        out_specs=state_specs + [
            chan_spec(N, E, NF),  # deliveries
            chan_spec(N, E),  # delivery valid
            chan_spec(N, E),  # req_waiting
        ],
        out_shape=state_shapes + [
            jax.ShapeDtypeStruct((C, N, E, NF), i32),
            jax.ShapeDtypeStruct((C, N, E), jnp.bool_),
            jax.ShapeDtypeStruct((C, N, E), jnp.bool_),
        ],
        input_output_aliases={i: i for i in range(len(state_specs))},
        interpret=interpret,
    )(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
      eg, eg_ready, eg_head, eg_cnt,
      route, *vc_tables, link_src, link_dst, port_ep, ep_attach, ep_space,
      jnp.reshape(jnp.asarray(cycle0, i32), (1,)))
    return outs
