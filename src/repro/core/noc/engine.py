"""Vectorized cycle-accurate router fabric in JAX, batched over physical
channels, with a selectable per-cycle compute backend.

One FabricState carries *all* physical channels of the NoC (the paper
instantiates three separate routers per tile: req / rsp / wide; PATRONoC-style
configurations add more). State is a packed array over
[C channels, R routers, P ports, DEPTH fifo slots, NF flit fields].

The per-cycle router datapath itself — cycle-start snapshot, round-robin
arbitration, wormhole-lock updates, FIFO push/pop — lives in
``repro.kernels.noc_router``:

* ``ref.py`` is the reference implementation (the logic that used to be
  inlined here as ``_cycle_one``); ``backend="jnp"`` vmaps it over the
  leading channel axis, so the lax.scan step body contains no Python channel
  loop and the traced op count is independent of the channel count.
* ``noc_router.py`` holds the Pallas kernels, gridded over (C, R / K) — one
  program per (channel, K-router block) — selected with
  ``backend="pallas"``: compiled on a TPU, interpreted elsewhere. Both
  backends run the same decision functions and are bit-identical
  (tests/test_noc_backend.py).

Flits are a single int32 array with a trailing field axis (see FLIT_FIELDS /
F_* indices) instead of a dict of seven arrays: every push/pop/gather is one
jnp.where instead of seven.

Cycle semantics: arbitration and link decisions are both computed from the
cycle-start snapshot, then applied. A flit therefore spends >= 1 cycle in the
input buffer and >= 1 cycle in the output buffer: 2 cycles per router hop at
zero load, matching the paper's Fig. 7.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.noc.topology import Topology, route_vcs
from repro.kernels.noc_router import ops as router_ops
from repro.kernels.noc_router import ref as router_ops_ref
from repro.kernels.noc_router.ref import (  # noqa: F401  (re-exported API)
    F_DST,
    F_KIND,
    F_LAST,
    F_META,
    F_SRC,
    F_TS,
    F_TXN,
    FLIT_FIELDS,
    NF,
    NRED,
    empty_flits,
    fifo_pop,
    fifo_push,
    heads,
    pack_flit,
    router_cycle_offload_reference,
    router_cycle_reference,
)


@jax.tree_util.register_dataclass
@dataclass
class FabricState:
    """Channel-batched router-fabric state (one pytree for all channels).

    ``red_acc``/``red_got`` are the per-(router, group) reduction-ALU
    state of the collective offload path; they stay ``None`` (empty
    subtrees, zero trace cost) unless the fabric was built with collective
    groups."""

    in_buf: jnp.ndarray  # [C, R, P, Din, NF]
    in_cnt: jnp.ndarray  # [C, R, P]
    out_buf: jnp.ndarray  # [C, R, P, Dout, NF]
    out_cnt: jnp.ndarray  # [C, R, P]
    rr_ptr: jnp.ndarray  # [C, R, P] round-robin pointer per *output* port
    wh_lock: jnp.ndarray  # [C, R, P] wormhole: locked input port (-1 = free)
    red_acc: jnp.ndarray | None = None  # [C, R, G, NRED] reduction ALU slots
    red_got: jnp.ndarray | None = None  # [C, R, G, P] per-beat contributions


def init_fabric(
    topo: Topology, depth_in: int, depth_out: int, n_channels: int,
    n_vcs: int = 1, n_groups: int = 0,
) -> FabricState:
    """Empty fabric state for ``n_channels`` physical channels of ``topo``.

    With ``n_vcs > 1`` the port axis folds the VC axis in: slot
    ``p * n_vcs + v`` is (physical port p, virtual channel v), so every
    (port, VC) pair gets its own input FIFO, output buffer, round-robin
    pointer, and wormhole lock. ``n_vcs=1`` is exactly the historical
    per-port layout. ``n_groups > 0`` sizes the collective-offload
    reduction state (all-zero = empty ALU slots)."""
    C, R, P = n_channels, topo.n_routers, topo.n_ports * n_vcs
    return FabricState(
        in_buf=empty_flits((C, R, P, depth_in)),
        in_cnt=jnp.zeros((C, R, P), jnp.int32),
        out_buf=empty_flits((C, R, P, depth_out)),
        out_cnt=jnp.zeros((C, R, P), jnp.int32),
        rr_ptr=jnp.zeros((C, R, P), jnp.int32),
        wh_lock=jnp.full((C, R, P), -1, jnp.int32),
        red_acc=(jnp.zeros((C, R, n_groups, NRED), jnp.int32)
                 if n_groups else None),
        red_got=(jnp.zeros((C, R, n_groups, P), bool)
                 if n_groups else None),
    )


@dataclass(frozen=True)
class FabricTables:
    """Static routing/wiring tables shared by every physical channel.

    With ``n_vcs > 1``, ``port_ep``/``ep_attach`` are *slot*-level (slot =
    physical port * n_vcs + vc; endpoints always attach at VC0 of their
    port), and so are ``link_src``/``link_dst`` (a flit keeps its VC
    across the wire: slot (p, v) links to the far slot (p2, v)), while
    ``route`` stays physical — arbitration expands a physical out-port to
    an output slot via ``vc_out``, and the link stage lets the V slots of
    one physical wire move one flit a cycle between them. ``n_vcs=1``
    keeps ``vc_out=None`` and every table bit-identical to the historical
    fabric."""

    route: jnp.ndarray  # [R, E] physical out port
    link_src: jnp.ndarray  # [R, P, 2] upstream (router, port) feeding my in port
    link_dst: jnp.ndarray  # [R, P, 2]; both slot-level if V > 1
    port_ep: jnp.ndarray  # [R, P] endpoint attached (-1); slot-level if V > 1
    ep_attach: jnp.ndarray  # [E, 2] (router, port-or-slot)
    # output VC for (router, input slot, physical out port); None when V == 1
    vc_out: jnp.ndarray | None = None  # [R, P*V, Pp]
    n_vcs: int = 1
    # collective-offload trees (None unless built with groups): multicast
    # fork out-slots per group, reduction parent out-slot (-1 off-tree) and
    # per-beat child-contribution count per (router, group)
    fork_out: jnp.ndarray | None = None  # [R, G, P] bool
    red_parent: jnp.ndarray | None = None  # [R, G] int32
    red_need: jnp.ndarray | None = None  # [R, G] int32
    n_groups: int = 0


def _route_walk(topo: Topology, src_ep: int, dst_ep: int):
    """(router, physical out port) hops of the deterministic src->dst route,
    ejection link included (the last hop's port attaches ``dst_ep``)."""
    r = int(topo.ep_attach[src_ep, 0])
    links = []
    for _ in range(topo.n_routers + 2):
        p = int(topo.route[r, dst_ep])
        links.append((r, p))
        if int(topo.port_ep[r, p]) == dst_ep:
            return links
        r = int(topo.link_to[r, p][0])
    raise ValueError(
        f"routing walk {src_ep}->{dst_ep} did not terminate")


def _collective_trees(topo: Topology, groups, n_vcs: int):
    """Derive multicast fork / reduction trees from the routing tables.

    ``groups`` is a sequence of dicts: ``{"root": ep, "members": [ep, ...]}``
    for a multicast tree (root -> every member along the deterministic
    routes, ejection slots included) plus optionally ``"reduce":
    [ep, ...]`` for a reduction tree (every contributor's route to the
    root; converging hops become ALU child slots, the root's ejection slot
    is the final parent). Multicast slots carry the same dateline VCs as
    ``route_vcs``; reduction hops are store-and-forward per router and
    always travel VC0. Raises if the union of a group's multicast routes
    is not a tree (two copies would reach one router) or if reduction
    routes disagree on a parent port — both are impossible for the
    deterministic dimension-ordered tables the topology zoo emits, but a
    custom route table could violate them.
    """
    V = n_vcs
    R, Pp = topo.n_routers, topo.n_ports
    G = len(groups)
    fork = np.zeros((R, G, Pp * V), bool)
    red_parent = np.full((R, G), -1, np.int32)
    red_need = np.zeros((R, G), np.int32)
    for g, grp in enumerate(groups):
        root = int(grp["root"])
        members = [int(m) for m in grp.get("members", ())]
        in_ports: dict[int, set[int]] = {}
        for m in members:
            if m == root:
                continue
            links = _route_walk(topo, root, m)
            vcs = route_vcs(topo, links) if V > 1 else [0] * len(links)
            for (r, p), v in zip(links, vcs):
                fork[r, g, p * V + v] = True
                r2, p2 = (int(x) for x in topo.link_to[r, p])
                if r2 >= 0:
                    in_ports.setdefault(r2, set()).add(p2)
        if any(len(s) > 1 for s in in_ports.values()):
            raise ValueError(
                f"multicast routes of group {g} do not form a tree")
        child_slots: dict[int, set[int]] = {}
        for m in (int(c) for c in grp.get("reduce", ())):
            ar = int(topo.ep_attach[m, 0])
            child_slots.setdefault(ar, set()).add(
                int(topo.ep_attach[m, 1]) * V)
            for r, p in _route_walk(topo, m, root):
                slot = p * V  # reduction hops always travel VC0
                if red_parent[r, g] not in (-1, slot):
                    raise ValueError(
                        f"reduction routes of group {g} disagree at router {r}")
                red_parent[r, g] = slot
                if int(topo.port_ep[r, p]) != root:
                    r2, p2 = (int(x) for x in topo.link_to[r, p])
                    child_slots.setdefault(r2, set()).add(p2 * V)
        for r, slots in child_slots.items():
            red_need[r, g] = len(slots)
    return fork, red_parent, red_need


def make_tables(topo: Topology, n_vcs: int = 1, groups=None) -> FabricTables:
    """Device-resident FabricTables derived from a Topology's numpy tables.

    ``groups`` (optional) derives the collective-offload multicast fork /
    reduction trees from the same routing tables (see
    ``_collective_trees``); ``None`` keeps every table bit-identical to
    the historical fabric."""
    R, P = topo.n_routers, topo.n_ports
    link_src = np.full((R, P, 2), -1, np.int32)
    for r in range(R):
        for p in range(P):
            r2, p2 = topo.link_to[r, p]
            if r2 >= 0:
                link_src[r2, p2] = (r, p)
    offload = {}
    if groups is not None:
        fork, red_parent, red_need = _collective_trees(topo, groups, n_vcs)
        offload = dict(fork_out=jnp.asarray(fork),
                       red_parent=jnp.asarray(red_parent),
                       red_need=jnp.asarray(red_need),
                       n_groups=len(groups))
    if n_vcs == 1:
        return FabricTables(
            route=jnp.asarray(topo.route),
            link_src=jnp.asarray(link_src),
            link_dst=jnp.asarray(topo.link_to),
            port_ep=jnp.asarray(topo.port_ep),
            ep_attach=jnp.asarray(topo.ep_attach),
            **offload,
        )
    V = n_vcs
    # slot-level wiring: a flit keeps its VC across the wire, so slot
    # (p, v) links to slot (p2, v) of the far end; no link stays -1
    vc = np.tile(np.arange(V, dtype=np.int32), P)

    def slot_links(link):
        out = np.repeat(link, V, axis=1)
        out[..., 1] = np.where(out[..., 0] >= 0, out[..., 1] * V + vc, -1)
        return out

    # slot-level endpoint tables: endpoints live on VC0 of their port
    port_ep = np.full((R, P * V), -1, np.int32)
    port_ep[:, ::V] = topo.port_ep
    ep_attach = topo.ep_attach.copy()
    ep_attach[:, 1] *= V
    # dateline VC-switching table: a flit arriving on input slot
    # (pin, vin) and routed out physical port pout departs on
    #   1            if dateline[r, pout]  (crossing the ring's dateline)
    #   vin          if port_dim[r, pout] == port_dim[r, pin]  (same ring)
    #   0            otherwise  (dimension turn / ejection resets the VC)
    # Topologies without VC tables keep everything on VC0 (docs/ROUTING.md).
    vc_out = np.zeros((R, P * V, P), np.int32)
    if topo.port_dim is not None and topo.dateline is not None:
        for pin in range(P):
            for vin in range(V):
                s = pin * V + vin
                same = topo.port_dim[:, :] == topo.port_dim[:, pin:pin + 1]
                vout = np.where(same, vin, 0)
                vout = np.where(topo.dateline, np.minimum(1, V - 1), vout)
                vc_out[:, s, :] = vout
    return FabricTables(
        route=jnp.asarray(topo.route),
        link_src=jnp.asarray(slot_links(link_src)),
        link_dst=jnp.asarray(slot_links(topo.link_to)),
        port_ep=jnp.asarray(port_ep),
        ep_attach=jnp.asarray(ep_attach),
        vc_out=jnp.asarray(vc_out),
        n_vcs=V,
        **offload,
    )


def _cycle_one(st: FabricState, tb: FabricTables, ep_ingress_space: jnp.ndarray):
    """One cycle of a single channel (reference path; state [R, P, ...])."""
    if tb.fork_out is not None:
        (in2, in_cnt2, out2, out_cnt2, rr, wh, ep_flit, ep_valid,
         racc2, rgot2) = router_cycle_offload_reference(
            st.in_buf, st.in_cnt, st.out_buf, st.out_cnt, st.rr_ptr,
            st.wh_lock, st.red_acc, st.red_got, tb.route, tb.link_src,
            tb.link_dst, tb.port_ep, tb.ep_attach, tb.fork_out,
            tb.red_parent, tb.red_need, ep_ingress_space,
            n_endpoints=int(tb.ep_attach.shape[0]), vc_out=tb.vc_out,
            n_vcs=tb.n_vcs)
        return (FabricState(in2, in_cnt2, out2, out_cnt2, rr, wh,
                            racc2, rgot2), ep_flit, ep_valid)
    (in2, in_cnt2, out2, out_cnt2, rr, wh, ep_flit, ep_valid) = (
        router_cycle_reference(
            st.in_buf, st.in_cnt, st.out_buf, st.out_cnt, st.rr_ptr,
            st.wh_lock, tb.route, tb.link_src, tb.link_dst, tb.port_ep,
            tb.ep_attach, ep_ingress_space, vc_out=tb.vc_out,
            n_vcs=tb.n_vcs))
    return FabricState(in2, in_cnt2, out2, out_cnt2, rr, wh), ep_flit, ep_valid


def _inject_one(st: FabricState, tb: FabricTables, flit: jnp.ndarray, want: jnp.ndarray):
    """Single-channel endpoint injection: flit [E, NF]; want [E]."""
    Din = st.in_buf.shape[-2]
    R, P = st.in_cnt.shape
    er, ep_p = tb.ep_attach[:, 0], tb.ep_attach[:, 1]
    space = st.in_cnt[er, ep_p] < Din
    accepted = want & space
    push_mask = jnp.zeros((R, P), bool).at[er, ep_p].set(accepted)
    flit_rp = jnp.zeros((R, P, NF), jnp.int32).at[er, ep_p].set(flit)
    in_buf, in_cnt = fifo_push(st.in_buf, st.in_cnt, push_mask, flit_rp)
    return replace(st, in_buf=in_buf, in_cnt=in_cnt), accepted


# channel-batched entry points: vmap the single-channel logic over the leading
# channel axis of FabricState (tables are shared; ingress space is per-channel
# so an endpoint can backpressure one channel — e.g. hold narrow requests
# while its rsp egress queue is full — without stalling the others).
_cycle_all = jax.vmap(_cycle_one, in_axes=(0, None, 0))
_inject_all = jax.vmap(_inject_one, in_axes=(0, None, 0, 0))
# gather-based injection (the fast path): each attach port pulls its
# endpoint's flit (unique attach => expressible as a gather + one-hot
# select, much faster than a scattered write on CPU). Bit-identical to
# _inject_all (untouched slots keep their contents either way).
_inject_scatter = jax.vmap(router_ops_ref.inject_endpoints,
                           in_axes=(0, 0, None, None, None, 0, 0))


def fabric_cycle(st: FabricState, tb: FabricTables, ep_ingress_space: jnp.ndarray,
                 backend: str = "jnp", interpret=None, *,
                 router_tile: int = 1, fused_fifo: bool = False):
    """One cycle of every channel at once.

    ep_ingress_space: [C, E] bool — endpoint can accept one flit on that
    channel this cycle (a refused flit stays in the router's output buffer:
    memory-server-style backpressure into the fabric).
    ``backend`` selects the per-cycle compute path: ``"jnp"`` (vmapped
    reference) or ``"pallas"`` ((C, R/K)-gridded kernels with
    ``router_tile`` routers per program; ``interpret=None`` compiles them
    on a TPU and interprets them elsewhere). ``fused_fifo`` applies each
    FIFO's pop+push as one fused select per slot on either backend (same
    live contents; the naive reference path keeps it off). The backends
    are bit-identical for any fixed ``fused_fifo``. Returns (state',
    ep_flit [C, E, NF], ep_valid [C, E])."""
    if backend == "jnp" and not fused_fifo:
        return _cycle_all(st, tb, ep_ingress_space)
    if tb.fork_out is not None:
        (in2, in_cnt2, out2, out_cnt2, rr, wh, ep_flit, ep_valid,
         racc2, rgot2) = router_ops.router_cycle(
            st.in_buf, st.in_cnt, st.out_buf, st.out_cnt, st.rr_ptr,
            st.wh_lock, tb.route, tb.link_src, tb.link_dst, tb.port_ep,
            tb.ep_attach, ep_ingress_space, backend=backend,
            interpret=interpret, router_tile=router_tile,
            fused_fifo=fused_fifo, vc_out=tb.vc_out, n_vcs=tb.n_vcs,
            fork_out=tb.fork_out, red_parent=tb.red_parent,
            red_need=tb.red_need, red_acc=st.red_acc, red_got=st.red_got,
            n_endpoints=int(tb.ep_attach.shape[0]))
        return (FabricState(in2, in_cnt2, out2, out_cnt2, rr, wh,
                            racc2, rgot2), ep_flit, ep_valid)
    (in2, in_cnt2, out2, out_cnt2, rr, wh, ep_flit, ep_valid) = (
        router_ops.router_cycle(
            st.in_buf, st.in_cnt, st.out_buf, st.out_cnt, st.rr_ptr,
            st.wh_lock, tb.route, tb.link_src, tb.link_dst, tb.port_ep,
            tb.ep_attach, ep_ingress_space, backend=backend,
            interpret=interpret, router_tile=router_tile,
            fused_fifo=fused_fifo, vc_out=tb.vc_out, n_vcs=tb.n_vcs))
    return FabricState(in2, in_cnt2, out2, out_cnt2, rr, wh), ep_flit, ep_valid


def fabric_cycles_fused(st: FabricState, tb: FabricTables,
                        ep_ingress_space: jnp.ndarray,
                        eg, eg_ready, eg_head, eg_cnt, cycle0,
                        n_cycles: int, backend: str = "jnp", interpret=None):
    """``n_cycles`` fused fabric cycles with egress injection threaded in.

    The multi-cycle super-step core: the fabric advances ``n_cycles`` with
    ``ep_ingress_space`` held and each endpoint's ready circular-egress
    head injected per cycle (except the window's last — the caller injects
    after the endpoint phases, making a 1-cycle window bit-identical to
    ``fabric_cycle`` + ``inject``). On the Pallas backend the whole window
    runs inside one kernel per channel with state resident across the
    loop. Returns ``(state', eg, eg_ready, eg_head, eg_cnt,
    ep_flit [C, N, E, NF], ep_valid [C, N, E], req_waiting [C, N, E])``.
    Collective offload is per-cycle only (``fused_cycles == 1``).
    """
    if tb.fork_out is not None:
        raise ValueError(
            "collective offload does not support fused multi-cycle windows")
    (in2, in_cnt2, out2, out_cnt2, rr, wh, eg, eg_ready, eg_head, eg_cnt,
     ep_flit, ep_valid, waiting) = router_ops.router_cycles_fused(
        st.in_buf, st.in_cnt, st.out_buf, st.out_cnt, st.rr_ptr, st.wh_lock,
        eg, eg_ready, eg_head, eg_cnt,
        tb.route, tb.link_src, tb.link_dst, tb.port_ep, tb.ep_attach,
        ep_ingress_space, cycle0, n_cycles, backend=backend,
        interpret=interpret, vc_out=tb.vc_out, n_vcs=tb.n_vcs)
    return (FabricState(in2, in_cnt2, out2, out_cnt2, rr, wh),
            eg, eg_ready, eg_head, eg_cnt, ep_flit, ep_valid, waiting)


def inject(st: FabricState, tb: FabricTables, flit: jnp.ndarray,
           want: jnp.ndarray, scatter: bool = False):
    """Endpoints push one flit per channel into their attached port's in_buf
    (seen by the arbiter next cycle). flit [C, E, NF]; want [C, E].
    ``scatter`` selects the O(E) scattered-write fast path (bit-identical).
    Returns (state, accepted [C, E])."""
    if scatter:
        er, ep_p = tb.ep_attach[:, 0], tb.ep_attach[:, 1]
        in_buf, in_cnt, accepted = _inject_scatter(
            st.in_buf, st.in_cnt, er, ep_p, tb.port_ep, flit, want)
        return replace(st, in_buf=in_buf, in_cnt=in_cnt), accepted
    return _inject_all(st, tb, flit, want)
