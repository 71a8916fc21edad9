"""Pallas backend for the FlooNoC router cycle: K-router tiles.

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
    ``link_src``/``link_dst`` [R, P, 2], ``port_ep`` [R, P], ``ep_attach``
    [E, 2]); ``ep_space`` [C, E] is the per-channel endpoint ingress-space
    mask. ``router_tile`` blocks K routers per program (grid
    ``(C, R / K)``, see ``effective_tile``); ``fused_fifo`` selects the
    fused FIFO datapath (must match the jnp side being compared against).
    With ``n_vcs > 1`` the state P axis is slot-level (physical ports
    Pp = P / n_vcs; route stays physical) and the request lookup
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

