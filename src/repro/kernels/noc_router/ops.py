"""Public entry points: router-fabric cycles, backend-dispatched.

``router_cycle(..., backend="jnp" | "pallas")`` runs one cycle of the
channel-batched fabric on raw arrays. ``"jnp"`` vmaps the reference
implementation over the channel axis (the engine's historical hot path);
``"pallas"`` launches the (C, R/K)-gridded kernels (``router_tile``
routers per program): compiled on a TPU, interpreted elsewhere, so CPU CI
exercises the exact kernel dataflow. Both backends execute the same
decision functions from ``ref.py`` and are bit-identical — pinned by
``tests/test_noc_backend.py``. ``fused_fifo`` selects the fused FIFO
datapath on both backends (identical live contents either way; the flag
must simply match across a bit-exact comparison).

``router_cycles_fused(...)`` advances the fabric N cycles per call with
endpoint egress injection threaded through (the multi-cycle super-step):
``"jnp"`` scans ``ref.fused_cycle_body``, ``"pallas"`` runs the same body
inside one kernel per channel with the state resident across the loop.

On a TPU the per-cycle kernels compile for every datapath: default, VC
and collective offload (``tests/test_tpu_compile.py`` compiles them for a
described v5e). The fused multi-cycle kernel does not: with ``interpret``
False it raises ``NotImplementedError`` naming what the TPU compiler
refuses. Nothing falls back to interpret mode or to the jnp path on a
TPU.

This module is deliberately free of ``repro.core.noc`` imports: the engine
layers on top of it, not the other way around.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.noc_router.noc_router import (
    FUSED_TPU_REFUSAL,
    router_cycle_pallas,
    router_cycles_fused_pallas,
)
from repro.kernels.noc_router.ref import (
    router_cycle_offload_reference,
    router_cycle_reference,
    router_cycles_scan,
)

BACKENDS = ("jnp", "pallas")

# vmap the single-channel reference over the leading channel axis of the
# state and the per-channel endpoint ingress space; tables are shared.
_cycle_jnp = jax.vmap(
    router_cycle_reference,
    in_axes=(0, 0, 0, 0, 0, 0, None, None, None, None, None, 0),
)
_cycle_jnp_fused = jax.vmap(
    functools.partial(router_cycle_reference, fused=True),
    in_axes=(0, 0, 0, 0, 0, 0, None, None, None, None, None, 0),
)


def _interp(interpret):
    """Interpret mode off the TPU only (``None`` = decide from the device)."""
    return (jax.default_backend() != "tpu") if interpret is None else interpret


def router_cycle(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
                 route, link_src, link_dst, port_ep, ep_attach, ep_space,
                 *, backend: str = "jnp", interpret=None,
                 router_tile: int = 1, fused_fifo: bool = False,
                 vc_out=None, n_vcs: int = 1,
                 fork_out=None, red_parent=None, red_need=None,
                 red_acc=None, red_got=None, n_endpoints: int = 0):
    """One cycle of every channel at once on the selected backend.

    State arrays are channel-batched ([C, R, P, ...]); tables are shared
    ([R, ...] / [E, 2]); ``ep_space`` [C, E]. Returns
    ``(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
    ep_flit [C, E, NF], ep_valid [C, E])``. ``n_vcs > 1`` selects the
    virtual-channel datapath (state P axis = physical ports * n_vcs,
    ``vc_out`` [R, P, P_phys] the dateline VC-switch table shared across
    channels); the default leaves every historical call bit-identical.

    Passing ``fork_out`` (with the other collective-offload tables and the
    channel-batched reduction state ``red_acc`` [C, R, G, NRED] /
    ``red_got`` [C, R, G, P]) selects the offload datapath on both
    backends and extends the return tuple to ``(..., red_acc', red_got')``.
    """
    offload = fork_out is not None
    if backend == "jnp":
        if offload:
            fn = jax.vmap(
                functools.partial(router_cycle_offload_reference,
                                  n_endpoints=n_endpoints, fused=fused_fifo,
                                  vc_out=vc_out, n_vcs=n_vcs),
                in_axes=(0,) * 8 + (None,) * 8 + (0,),
            )
            return fn(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
                      red_acc, red_got, route, link_src, link_dst, port_ep,
                      ep_attach, fork_out, red_parent, red_need, ep_space)
        if n_vcs > 1:
            fn = jax.vmap(
                functools.partial(router_cycle_reference, fused=fused_fifo,
                                  vc_out=vc_out, n_vcs=n_vcs),
                in_axes=(0, 0, 0, 0, 0, 0, None, None, None, None, None, 0),
            )
        else:
            fn = _cycle_jnp_fused if fused_fifo else _cycle_jnp
        return fn(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
                  route, link_src, link_dst, port_ep, ep_attach, ep_space)
    if backend == "pallas":
        return router_cycle_pallas(in_buf, in_cnt, out_buf, out_cnt, rr_ptr,
                                   wh_lock, route, link_src, link_dst,
                                   port_ep, ep_attach, ep_space,
                                   router_tile=router_tile,
                                   fused_fifo=fused_fifo,
                                   interpret=_interp(interpret),
                                   vc_out=vc_out, n_vcs=n_vcs,
                                   fork_out=fork_out, red_parent=red_parent,
                                   red_need=red_need, red_acc=red_acc,
                                   red_got=red_got, n_endpoints=n_endpoints)
    raise ValueError(f"unknown router backend {backend!r}; expected one of {BACKENDS}")


# vmap the single-channel fused scan over the channel axis: state + egress
# queues and ep_space are per-channel, tables and the cycle base are shared.
# out_axes puts the per-cycle outputs at [C, N, ...] like the kernel.
_cycles_scan_jnp = jax.vmap(
    router_cycles_scan,
    in_axes=(0,) * 10 + (None,) * 5 + (0, None, None),
    out_axes=(0, 0),
)


def router_cycles_fused(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
                        eg, eg_ready, eg_head, eg_cnt,
                        route, link_src, link_dst, port_ep, ep_attach,
                        ep_space, cycle0, n_cycles: int, *,
                        backend: str = "jnp", interpret=None,
                        vc_out=None, n_vcs: int = 1):
    """``n_cycles`` fused fabric cycles with egress injection threaded in.

    Same array contract as :func:`router_cycle` plus this channel-batched
    circular egress queue (``eg`` [C, E, Q, NF], ``eg_ready`` [C, E, Q],
    ``eg_head``/``eg_cnt`` [C, E]) and the window's first cycle number
    ``cycle0``. ``ep_space`` is sampled once and held for the window (the
    k=1 window is bit-identical to per-cycle stepping; see
    ``sim.Sim.step_super`` for the k>1 contract). Returns the 10 updated
    state arrays plus ``(ep_flit [C, N, E, NF], ep_valid [C, N, E],
    req_waiting [C, N, E])``. Backends are bit-identical (same
    ``ref.fused_cycle_body``).
    """
    if backend == "jnp":
        if n_vcs > 1:
            scan_fn = jax.vmap(
                functools.partial(router_cycles_scan, vc_out=vc_out,
                                  n_vcs=n_vcs),
                in_axes=(0,) * 10 + (None,) * 5 + (0, None, None),
                out_axes=(0, 0),
            )
        else:
            scan_fn = _cycles_scan_jnp
        carry, (ep_flit, ep_valid, waiting) = scan_fn(
            in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
            eg, eg_ready, eg_head, eg_cnt,
            route, link_src, link_dst, port_ep, ep_attach,
            ep_space, cycle0, n_cycles)
        return (*carry, ep_flit, ep_valid, waiting)
    if backend == "pallas":
        if not _interp(interpret):
            raise NotImplementedError(FUSED_TPU_REFUSAL)
        return router_cycles_fused_pallas(
            in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
            eg, eg_ready, eg_head, eg_cnt,
            route, link_src, link_dst, port_ep, ep_attach,
            ep_space, cycle0, n_cycles, interpret=True,
            vc_out=vc_out, n_vcs=n_vcs)
    raise ValueError(f"unknown router backend {backend!r}; expected one of {BACKENDS}")
