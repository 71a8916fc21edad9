"""Reference implementation of one FlooNoC router cycle (single channel).

This is the bit-exact specification of the per-cycle router datapath that
used to live inline in ``repro.core.noc.engine._cycle_one``: cycle-start
snapshot semantics, round-robin output arbitration, wormhole-lock updates,
and FIFO push/pop over packed ``[R, P, D, NF]`` flit state.

The decision functions are written **rank-generically over the leading
router axis**: every operation addresses the port/fifo/field axes by their
position relative to that leading axis, so the same code runs on

* the full fabric (``R`` = all routers) — the ``backend="jnp"`` engine path,
  vmapped over channels by ``repro.core.noc.engine``; and
* a K-router block — inside the Pallas kernels
  (``repro.kernels.noc_router.noc_router``), gridded over ``(C, R / K)``.
  The kernels run the router-local stages (``arbitrate`` /
  ``offload_decisions`` and ``apply_cycle``); the lookups that gather
  across routers or tables (``request_ports``, ``link_stage``) run in XLA
  between them.

Because both backends execute these exact functions on the same integer
state, they are bit-identical by construction; the golden-pin tests in
``tests/test_noc_backend.py`` verify it end to end. The kernel-side
functions use only what the TPU compiler (Mosaic) lowers: selects over
small static axes instead of gathers, and masks reshaped as int32.

Cycle semantics contract: arbitration and link decisions are both computed
from the cycle-start snapshot, then applied. A flit therefore spends >= 1
cycle in the input buffer and >= 1 cycle in the output buffer: 2 cycles per
router hop at zero load, matching the paper's Fig. 7.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# packed flit layout: trailing axis of NF int32 fields
FLIT_FIELDS = ("dst", "src", "kind", "txn", "last", "ts", "meta")
NF = len(FLIT_FIELDS)
F_DST, F_SRC, F_KIND, F_TXN, F_LAST, F_TS, F_META = range(NF)

# collective-offload flit kinds (must match repro.core.noc.params.WIDE_MC /
# WIDE_RED; the kernel package deliberately does not import core.noc, so the
# pairing is pinned by tests/test_noc_offload.py). MC/RED flits are
# group-addressed: F_DST = n_endpoints + group id.
KIND_MC = 6
KIND_RED = 7

# per-(router, group) reduction-ALU accumulator layout: trailing axis of
# NRED int32 fields. "nlast" accumulates max(1 - F_LAST) so the all-zero
# reset state emits last=1 single-beat semantics by default and clearing an
# emitted slot is a uniform zero-fill.
RED_FIELDS = ("val", "cnt", "nlast", "txn", "ts", "src")
NRED = len(RED_FIELDS)
A_VAL, A_CNT, A_NLAST, A_TXN, A_TS, A_SRC = range(NRED)


def empty_flits(shape) -> jnp.ndarray:
    """Zeroed packed flit array of shape [*shape, NF]."""
    return jnp.zeros((*tuple(shape), NF), jnp.int32)


def pack_flit(dst, src, kind, txn, last, ts, meta) -> jnp.ndarray:
    """Pack per-field values (broadcast against dst's shape) into [..., NF]."""
    ref = jnp.asarray(dst, jnp.int32)
    parts = [
        jnp.broadcast_to(jnp.asarray(v, jnp.int32), ref.shape)
        for v in (ref, src, kind, txn, last, ts, meta)
    ]
    return jnp.stack(parts, axis=-1)


def _expand(mask, axis: int = -1):
    """``jnp.expand_dims(mask, axis)``, expanded as int32: Mosaic reshapes
    32-bit vectors but not boolean ones."""
    return jnp.expand_dims(mask.astype(jnp.int32), axis) > 0


def fifo_pop(buf: jnp.ndarray, cnt, pop_mask):
    """Drop the head slot of every FIFO selected by ``pop_mask`` [..., P]."""
    D = buf.shape[-2]
    pop = _expand(pop_mask)
    newbuf = jnp.stack([jnp.where(pop, buf[..., (d + 1) % D, :], buf[..., d, :])
                        for d in range(D)], axis=-2)
    return newbuf, cnt - pop_mask.astype(jnp.int32)


def fifo_push(buf: jnp.ndarray, cnt, push_mask, flit: jnp.ndarray):
    """Append ``flit`` [..., P, NF] at the tail where ``push_mask`` [..., P]."""
    D = buf.shape[-2]
    idx = jnp.clip(cnt, 0, D - 1)
    newbuf = jnp.stack([jnp.where(_expand(push_mask & (idx == d)), flit,
                                  buf[..., d, :]) for d in range(D)], axis=-2)
    return newbuf, cnt + push_mask.astype(jnp.int32)


def fifo_update(buf: jnp.ndarray, cnt, pop_mask, push_mask, flit: jnp.ndarray):
    """Fused pop-then-push: each slot is written once by two selects
    instead of a roll, a one-hot and two full-buffer writes.

    Identical to ``fifo_pop`` followed by ``fifo_push`` on every *live* slot
    (index < count); dead slots may hold different garbage than the two-step
    pair leaves behind, which is why the ``step_impl="naive"`` reference path
    keeps the two-step functions and equivalence is compared through
    ``sim.canonical_state``. Never pushes past the last slot: callers
    guarantee space (``link_accept`` requires ``in_space``; ``granted``
    requires output-buffer room). Slots are unrolled over the (static,
    small) depth axis and every select is at most 3-D, which is what
    Mosaic lowers: no gather and no 4-D broadcast.
    """
    D = buf.shape[-2]
    cnt1 = cnt - pop_mask.astype(jnp.int32)
    tail = jnp.clip(cnt1, 0, D - 1)
    pop = _expand(pop_mask)
    slots = []
    for d in range(D):
        s = jnp.where(pop, buf[..., min(d + 1, D - 1), :], buf[..., d, :])
        slots.append(jnp.where(_expand(push_mask & (tail == d)), flit, s))
    return jnp.stack(slots, axis=-2), cnt1 + push_mask.astype(jnp.int32)


def heads(buf: jnp.ndarray) -> jnp.ndarray:
    """Head flit of every FIFO: [..., D, NF] -> [..., NF]."""
    return buf[..., 0, :]


class ArbDecisions(NamedTuple):
    """Per-output-port arbitration results, all computed from the snapshot.

    All leaves carry the [R, P] leading shape of the inputs (R may be a
    K-router Pallas block).
    """

    arb_pop: jnp.ndarray  # [R, P_in] bool: head popped by some output port
    granted: jnp.ndarray  # [R, P_out] bool: output port granted a flit
    chosen: jnp.ndarray  # [R, P_out, NF] flit the output port latches
    rr_ptr: jnp.ndarray  # [R, P_out] updated round-robin pointer
    wh_lock: jnp.ndarray  # [R, P_out] updated wormhole lock (-1 = free)
    in_space: jnp.ndarray  # [R, P_in] bool: input FIFO has a free slot after pops


def request_ports(h, in_cnt, route, vc_out=None, n_vcs: int = 1):
    """Output slot each input head requests (-1 = no valid head).

    ``h`` [R, P, NF] are the input heads, ``in_cnt`` [R, P] their FIFO
    counts, ``route`` [R, E] the physical out port per destination. This
    is the only table lookup of arbitration, a gather over the destination
    axis; the Pallas backend runs it in XLA ahead of its arbitration
    kernel (Mosaic has no general gather). Destinations clip into
    ``[0, E)``: group-addressed collective heads (``F_DST >= E``) never
    request a unicast port, their arbitration masks them out.

    With ``n_vcs > 1`` the port axis P is *slot*-level (physical port *
    n_vcs + vc) and ``vc_out`` [R, P, P_phys] assigns the departing VC:
    the routing table still yields a physical out port, which expands to
    output slot ``phys * n_vcs + vc_out[r, slot_in, phys]`` (dateline
    VC-switching).
    """
    P = in_cnt.shape[-1]
    E = route.shape[-1]
    req_port = jnp.take_along_axis(route, jnp.clip(h[..., F_DST], 0, E - 1),
                                   axis=1)
    if n_vcs > 1:
        with jax.named_scope("noc.vc"):
            Pp = P // n_vcs
            vout = jnp.take_along_axis(
                vc_out, jnp.clip(req_port, 0, Pp - 1)[..., None], axis=-1)[..., 0]
            req_port = req_port * n_vcs + vout
    return jnp.where(in_cnt > 0, req_port, -1)  # [R, P_in]


def arb_decisions(in_buf, in_cnt, out_cnt, rr_ptr, wh_lock, route,
                  depth_out: int, vc_out=None, n_vcs: int = 1) -> ArbDecisions:
    """Round-robin output arbitration from the cycle-start snapshot.

    Inputs are single-channel: ``in_buf`` [R, P, Din, NF], counters and
    pointers [R, P], ``route`` [R, E], ``depth_out`` the output-buffer
    depth. The route lookup (``request_ports``, with the VC expansion for
    ``n_vcs > 1``) feeds ``arbitrate``; each output slot has its own
    round-robin pointer and wormhole lock, so wormholes on different VCs
    of one physical link interleave safely.
    """
    h = heads(in_buf)  # [R, P, NF]
    req_port = request_ports(h, in_cnt, route, vc_out=vc_out, n_vcs=n_vcs)
    return arbitrate(req_port, h, in_cnt, out_cnt, rr_ptr, wh_lock,
                     depth_in=in_buf.shape[-2], depth_out=depth_out)


def _round_robin(elig, rr_ptr, h):
    """Round-robin pick of every output port from ``elig`` [R, P_in, P_out].

    Each output port takes the eligible input with the lowest round-robin
    distance from ``rr_ptr`` [R, P_out]. The first-min selection is
    unrolled over the (static, small) input-port axis: the same winner as
    ``jnp.argmin`` but ~2x faster on XLA CPU, and the winner's head flit
    from ``h`` [R, P_in, NF] is selected alongside (no gather). Returns
    ``(granted [R, P_out], winner [R, P_out], win_onehot [R, P_in, P_out],
    chosen [R, P_out, NF])``.
    """
    P = rr_ptr.shape[-1]
    pin = jnp.arange(P)[None, :, None]
    score = (pin - rr_ptr[:, None, :]) % P
    score = jnp.where(elig, score, P + 1)
    best = score[:, 0, :]
    winner = jnp.zeros_like(best)
    chosen = jnp.broadcast_to(h[:, 0:1, :], h.shape)
    for i in range(1, P):
        si = score[:, i, :]
        better = si < best
        best = jnp.where(better, si, best)
        winner = jnp.where(better, i, winner)
        chosen = jnp.where(_expand(better), h[:, i:i + 1, :], chosen)
    win_onehot = (winner[:, None, :] == pin) & (best[:, None, :] <= P)
    return best <= P, winner, win_onehot, chosen


def arbitrate(req_port, h, in_cnt, out_cnt, rr_ptr, wh_lock, depth_in: int,
              depth_out: int) -> ArbDecisions:
    """Arbitration of each router from its own snapshot, no table lookup.

    ``req_port`` [R, P_in] is the output slot each input head requests
    (``request_ports``), ``h`` [R, P_in, NF] the heads. Each output port
    picks the lowest-scoring eligible input head (round-robin distance
    from ``rr_ptr``); eligibility requires a head routed to that port, a
    free or matching wormhole lock, and output-buffer space (no same-cycle
    fall-through). A granted tail flit releases the wormhole lock; a
    granted body flit locks the output to its input port. Everything here
    is elementwise or a select over the (static, small) port axis, which
    is what the Pallas arbitration kernel compiles for the TPU.
    """
    P = in_cnt.shape[-1]
    pin = jnp.arange(P)[None, :, None]
    elig = req_port[:, :, None] == jnp.arange(P)[None, None, :]
    locked = wh_lock[:, None, :]
    elig &= (locked < 0) | (locked == pin)
    elig &= out_cnt[:, None, :] < depth_out  # no same-cycle fall-through
    granted, winner, win_onehot, chosen = _round_robin(elig, rr_ptr, h)
    arb_pop = jnp.any(win_onehot, axis=2)  # [R, P_in]

    rr = jnp.where(granted, (winner + 1) % P, rr_ptr)
    is_tail = chosen[..., F_LAST] > 0
    wh = jnp.where(granted & ~is_tail, winner, wh_lock)
    wh = jnp.where(granted & is_tail, -1, wh)

    # space after this cycle's arb pops (slot freed same cycle is reusable)
    in_space = (in_cnt - arb_pop.astype(jnp.int32)) < depth_in
    return ArbDecisions(arb_pop, granted, chosen, rr, wh, in_space)


def offload_decisions(req_port, h, in_cnt, out_cnt, rr_ptr, wh_lock,
                      depth_in: int, depth_out: int, fork_out, red_parent,
                      red_need, red_acc, red_got, n_endpoints: int):
    """Arbitration with tree-multicast fork + in-fabric reduction ALU.

    The ``collective_offload=True`` counterpart of ``arbitrate`` (which
    stays untouched so the default path carries no extra operands), fed
    the same ``request_ports`` lookup and heads ``h``. Single-channel,
    rank-generic over the leading router axis like every decision function
    here. Extra inputs:

    * ``fork_out`` [R, G, P] bool — multicast tree out-slots per group: a
      head with ``F_KIND == KIND_MC`` and ``F_DST == n_endpoints + g``
      requests *every* marked slot and pops only when it wins all of them
      in the same cycle (credit-checked on all branches before the pop;
      wormhole locks are taken branch-wise so multi-beat bursts stay
      atomic). A partial win cancels the won branches for this cycle —
      round-robin pointers do not advance on cancelled ports, so the
      multicast head keeps its claim and converges as contended branches
      rotate toward it.
    * ``red_parent`` [R, G] int32 / ``red_need`` [R, G] int32 — reduction
      tree: the out-slot toward the root (ejection slot at the root's
      router, -1 off-tree) and the number of distinct child slots that
      must contribute per beat.
    * ``red_acc`` [R, G, NRED] / ``red_got`` [R, G, P] — the ALU slot: a
      ``KIND_RED`` head at an un-contributed child slot is consumed into
      the accumulator (``val`` += F_META, ``cnt`` += 1, max-merged
      metadata) when the slot can take it; once ``cnt == red_need`` the
      combined flit is emitted into the parent out-slot (lowest group id
      wins a shared port, reduction emission pre-empts normal arbitration
      on that port) and the slot zero-clears, accepting the next beat the
      same cycle — one beat per cycle per router of pipelined throughput,
      store-and-forward per hop.

    Returns ``(ArbDecisions, red_acc', red_got')``. The link/apply phases
    consume the merged ``ArbDecisions`` unchanged, which is how the Pallas
    backend mirrors the fork and reduce paths without touching its apply
    kernel.
    """
    P = in_cnt.shape[-1]
    G = red_need.shape[-1]

    h_valid = in_cnt > 0
    kind = h[..., F_KIND]
    dst = h[..., F_DST]
    is_mc = h_valid & (kind == KIND_MC)
    is_red = h_valid & (kind == KIND_RED)
    g_of = jnp.clip(dst - n_endpoints, 0, G - 1)  # [R, P]

    # one-hot group of every head [R, G, P]: every per-group lookup below
    # is a select over this small axis (Mosaic has no gather)
    gsel = g_of[:, None, :] == jnp.arange(G)[None, :, None]

    # ---- reduction ALU (all decisions from the cycle-start snapshot) ----
    on_tree = red_need > 0  # [R, G]
    full = on_tree & (red_acc[..., A_CNT] >= red_need)
    parent = jnp.clip(red_parent, 0, P - 1)  # [R, G]
    psel = parent[..., None] == jnp.arange(P)  # [R, G, P_out]
    parent_free = jnp.any(psel & (out_cnt[:, None, :] < depth_out), axis=-1)
    parent_unlocked = jnp.any(psel & (wh_lock[:, None, :] < 0), axis=-1)
    can_emit = full & (red_parent >= 0) & parent_free & parent_unlocked
    # lowest group id wins a shared parent port (rows sliced as int32:
    # Mosaic cannot slice boolean vectors along the group axis)
    emit_i = (psel & _expand(can_emit)).astype(jnp.int32)
    rows = [emit_i[:, 0, :] > 0]
    taken = rows[0]
    for g in range(1, G):
        rows.append((emit_i[:, g, :] > 0) & ~taken)
        taken |= rows[-1]
    emit_port = taken  # [R, P_out]
    emitting = jnp.stack([r.astype(jnp.int32) for r in rows],
                         axis=1).max(-1) > 0  # [R, G]
    g_sel = jnp.zeros(emit_port.shape, jnp.int32)  # [R, P_out]
    acc_sel = jnp.zeros((*emit_port.shape, NRED), jnp.int32)
    for g, row in enumerate(rows):
        g_sel = jnp.where(row, g, g_sel)
        acc_sel = jnp.where(_expand(row), red_acc[:, g:g + 1, :], acc_sel)
    red_flit = pack_flit(  # stays group-addressed for the next hop
        n_endpoints + g_sel, acc_sel[..., A_SRC], KIND_RED,
        acc_sel[..., A_TXN], 1 - acc_sel[..., A_NLAST],
        acc_sel[..., A_TS], acc_sel[..., A_VAL])

    # consume RED heads whose group slot takes a contribution this cycle:
    # not yet contributed to the current beat, and the slot is either not
    # full or flushing its snapshot this same cycle (pipelined refill).
    accept_g = on_tree & (~full | emitting)  # [R, G]
    accept_at = jnp.any(gsel & _expand(accept_g), axis=1)  # [R, P]
    got_at = jnp.any(gsel & red_got, axis=1)
    red_pop = is_red & ~got_at & accept_at  # [R, P_in]

    gmask = _expand(red_pop, 1) & gsel  # [R, G, P]
    base_acc = jnp.where(_expand(emitting), 0, red_acc)
    base_got = red_got & ~_expand(emitting)
    gm = gmask.astype(jnp.int32)

    def _contrib(f, combine):
        """Merge field ``f`` of this cycle's contributing heads per group."""
        v = h[..., f][:, None, :]  # [R, 1, P]
        if combine == "sum":
            return (gm * v).sum(-1)
        return jnp.where(gmask, v, 0).max(-1)

    red_acc2 = jnp.stack([
        base_acc[..., A_VAL] + _contrib(F_META, "sum"),
        base_acc[..., A_CNT] + gm.sum(-1),
        jnp.maximum(base_acc[..., A_NLAST],
                    jnp.where(gmask, 1 - h[..., F_LAST][:, None, :], 0).max(-1)),
        jnp.maximum(base_acc[..., A_TXN], _contrib(F_TXN, "max")),
        jnp.maximum(base_acc[..., A_TS], _contrib(F_TS, "max")),
        jnp.maximum(base_acc[..., A_SRC], _contrib(F_SRC, "max")),
    ], axis=-1)
    red_got2 = base_got | gmask

    # ---- arbitration with multicast fork requests -----------------------
    uni = h_valid & ~is_mc & ~is_red
    req_port = jnp.where(uni, req_port, -1)

    pin = jnp.arange(P)[None, :, None]
    fork_i = fork_out.astype(jnp.int32)
    fork_at = jnp.zeros((*g_of.shape, P), jnp.int32)  # [R, P_in, P_out]
    for g in range(G):
        fork_at = jnp.where(_expand(g_of == g), fork_i[:, g:g + 1, :], fork_at)
    req = ((req_port[:, :, None] == jnp.arange(P)[None, None, :])
           | (_expand(is_mc) & (fork_at > 0)))  # [R, P_in, P_out]
    elig = req
    locked = wh_lock[:, None, :]
    elig &= (locked < 0) | (locked == pin)
    elig &= out_cnt[:, None, :] < depth_out
    elig &= ~_expand(emit_port, 1)  # reduction emission owns the port
    granted0, winner, win_onehot, chosen = _round_robin(elig, rr_ptr, h)

    # a multicast head fires only when it wins EVERY requested branch
    fire_mc = is_mc & jnp.any(req, axis=2) & ~jnp.any(req & ~win_onehot,
                                                      axis=2)
    pop_uni = jnp.any(win_onehot & _expand(uni), axis=2)
    arb_pop = pop_uni | fire_mc | red_pop

    # cancel grants whose winner is a multicast head that did not fire
    win_at = winner[:, None, :] == pin  # [R, P_in, P_out]
    w_is_mc = jnp.any(win_at & _expand(is_mc), axis=1)
    w_fired = jnp.any(win_at & _expand(fire_mc), axis=1)
    granted = granted0 & (~w_is_mc | w_fired)

    rr = jnp.where(granted, (winner + 1) % P, rr_ptr)
    is_tail = chosen[..., F_LAST] > 0
    wh = jnp.where(granted & ~is_tail, winner, wh_lock)
    wh = jnp.where(granted & is_tail, -1, wh)

    # merge reduction emissions (their ports were excluded from arb)
    granted_all = granted | emit_port
    chosen_all = jnp.where(_expand(emit_port), red_flit, chosen)

    in_space = (in_cnt - arb_pop.astype(jnp.int32)) < depth_in
    return (ArbDecisions(arb_pop, granted_all, chosen_all, rr, wh, in_space),
            red_acc2, red_got2)


def _lowest_vc(elig, n_vcs: int):
    """Of each physical port's ``n_vcs`` slots in ``elig`` [..., Pp * n_vcs],
    keep only the lowest eligible one: slot p*V+v survives iff no slot
    p*V+v' with v' < v is eligible. Elementwise over shifted slices,
    unrolled over the static V: no argmax, no reduce, no trailing VC axis.
    """
    vc = np.arange(elig.shape[-1]) % n_vcs
    pad = [(0, 0)] * (elig.ndim - 1)
    blocked = jnp.zeros_like(elig)
    for k in range(1, n_vcs):
        lower = jnp.pad(elig[..., :-k], [*pad, (k, 0)])  # slot i - k
        blocked |= lower & (vc >= k)
    return elig & ~blocked


def _upstream(out_heads_all, out_valid_all, link_src):
    """The head [R, P, NF] and valid bit [R, P] of the output port (or
    slot) that ``link_src`` [R, P, 2] wires to each input; no link reads
    invalid."""
    R_all, P = out_valid_all.shape
    src_r, src_p = link_src[..., 0], link_src[..., 1]
    have_up = src_r >= 0
    sr = jnp.clip(src_r, 0, R_all - 1)
    sp = jnp.clip(src_p, 0, P - 1)
    return out_heads_all[sr, sp], out_valid_all[sr, sp] & have_up


def link_inputs(out_heads_all, out_valid_all, link_src, in_space,
                n_vcs: int = 1):
    """Link-traversal decisions for this router's *input* side.

    ``out_heads_all`` [R_all, P, NF] / ``out_valid_all`` [R_all, P] are the
    full-fabric snapshot (every router's output heads); ``link_src`` [R, P, 2]
    and ``in_space`` [R, P] describe this router block. Returns
    ``(up_head [R, P, NF], link_accept [R, P])``: the upstream head feeding
    each input port and whether it is accepted this cycle.

    With ``n_vcs > 1`` the physical wire still moves one flit per cycle:
    each in-link folds the V upstream output slots onto it and accepts the
    *lowest eligible VC first* (eligible = upstream head valid and this
    VC's input FIFO has space). A flit stays on its VC across the wire —
    VC switching happens only at arbitration — so slot (p, v) can only
    receive from upstream output slot (src_p, v). Fixed-priority among
    eligible candidates always moves some flit, so sharing cannot deadlock
    the wire. ``link_src`` is slot-level (``engine.make_tables``), so the
    gathers read straight into the slot axis with no trailing VC axis, and
    the lowest-VC choice is elementwise over V (``_lowest_vc``).
    """
    if n_vcs == 1:
        up_head, up_valid = _upstream(out_heads_all, out_valid_all, link_src)
        return up_head, up_valid & in_space
    with jax.named_scope("noc.vc"):
        up_head, up_valid = _upstream(out_heads_all, out_valid_all, link_src)
        return up_head, _lowest_vc(up_valid & in_space, n_vcs)


def sent_mask(out_valid, link_dst, port_ep, in_space_all, ep_space,
              n_vcs: int = 1, link_accept_all=None):
    """Which of this router's output heads leave the buffer this cycle.

    A head is sent either over a live link — iff the downstream input FIFO
    has space after its own arbitration pops (``in_space_all`` [R_all, P]) —
    or into an attached endpoint (``port_ep`` [R, P], id or -1) iff the
    endpoint signalled ingress space (``ep_space`` [E]). Both legs reproduce
    the reference gather/scatter exactly: for a live link (r, p) ->
    (dst_r, dst_p), downstream ``link_accept`` is
    ``out_valid[r, p] & in_space_all[dst_r, dst_p]`` because this port *is*
    the upstream of that input.

    With ``n_vcs > 1`` the link leg reads that decision back instead of
    re-deriving it: a slot is sent iff the downstream ``link_accept_all``
    [R_all, P] (``link_inputs``'s lowest-eligible-VC choice, same cycle)
    took it at the paired slot. The wiring is one-to-one (``link_src`` is
    ``link_dst``'s inverse), so exactly the accepted slot's head is popped.
    Endpoint slots are VC0-only (slot-level ``port_ep``), so the ep leg is
    unchanged.
    """
    E = ep_space.shape[0]
    dst_r, dst_p = link_dst[..., 0], link_dst[..., 1]
    to_router = dst_r >= 0
    if n_vcs == 1:
        R_all, P = in_space_all.shape
        down_space = in_space_all[jnp.clip(dst_r, 0, R_all - 1),
                                  jnp.clip(dst_p, 0, P - 1)]
        sent_link = to_router & out_valid & down_space
    else:
        with jax.named_scope("noc.vc"):
            R_all, P = link_accept_all.shape
            sent_link = to_router & link_accept_all[
                jnp.clip(dst_r, 0, R_all - 1), jnp.clip(dst_p, 0, P - 1)]
    has_ep = port_ep >= 0
    ep_ok = ep_space[jnp.clip(port_ep, 0, E - 1)]
    sent_ep = has_ep & out_valid & ep_ok
    return sent_link | sent_ep


def link_stage(out_buf, out_cnt, in_space, link_src, link_dst, port_ep,
               ep_attach, ep_space, n_vcs: int = 1):
    """Every cross-router decision of the cycle for one channel.

    From the cycle-start output buffers ``out_buf`` [R, P, Dout, NF] /
    ``out_cnt`` [R, P] and the post-pop input space ``in_space`` [R, P] of
    every router, returns ``(up_head [R, P, NF], link_accept [R, P],
    sent [R, P], ep_flit [E, NF], ep_valid [E])``: what each input port
    takes off its link, which output heads leave, and the endpoint
    deliveries. These are the gathers over the router axis that the link
    wiring implies; the Pallas backend runs this stage in XLA between its
    arbitration and apply kernels. With ``n_vcs > 1`` the wire's VC is
    chosen once, on the receiving side (elementwise over V), and ``sent``
    reads that ``link_accept`` back through ``link_dst``.
    """
    out_heads = heads(out_buf)
    out_valid = out_cnt > 0
    up_head, link_accept = link_inputs(out_heads, out_valid, link_src,
                                       in_space, n_vcs=n_vcs)
    sent = sent_mask(out_valid, link_dst, port_ep, in_space, ep_space,
                     n_vcs=n_vcs, link_accept_all=link_accept)
    er, ep_p = ep_attach[:, 0], ep_attach[:, 1]
    ep_flit = out_heads[er, ep_p]  # [E, NF]
    ep_valid = out_valid[er, ep_p] & ep_space
    return up_head, link_accept, sent, ep_flit, ep_valid


def apply_cycle(in_buf, in_cnt, out_buf, out_cnt, arb_pop, granted, chosen,
                link_accept, up_head, sent, fused: bool = False):
    """Apply the snapshot decisions: FIFO pops then pushes, per side.

    ``fused=True`` applies each side's pop+push as one ``fifo_update``
    (same live contents, different dead-slot garbage)."""
    if fused:
        in2, in_cnt2 = fifo_update(in_buf, in_cnt, arb_pop, link_accept, up_head)
        out2, out_cnt2 = fifo_update(out_buf, out_cnt, sent, granted, chosen)
        return in2, in_cnt2, out2, out_cnt2
    in1, in_cnt1 = fifo_pop(in_buf, in_cnt, arb_pop)
    in2, in_cnt2 = fifo_push(in1, in_cnt1, link_accept, up_head)
    out1, out_cnt1 = fifo_pop(out_buf, out_cnt, sent)
    out2, out_cnt2 = fifo_push(out1, out_cnt1, granted, chosen)
    return in2, in_cnt2, out2, out_cnt2


def router_cycle_reference(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
                           route, link_src, link_dst, port_ep, ep_attach,
                           ep_space, fused: bool = False, vc_out=None,
                           n_vcs: int = 1):
    """One cycle of a single channel over the full fabric (reference).

    All state is single-channel ([R, P, ...]); ``ep_space`` [E] is the
    endpoint ingress-space mask for this channel. Returns
    ``(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock, ep_flit [E, NF],
    ep_valid [E])``. This is the extracted body of the original
    ``engine._cycle_one`` and the bit-exact specification the Pallas
    backend is tested against. ``fused`` selects the fused FIFO datapath
    (the fast/Pallas default; identical on live slots). ``n_vcs > 1``
    selects the virtual-channel datapath (folded slot axis P = phys *
    n_vcs, ``vc_out`` the dateline table); endpoint delivery/injection is
    slot-level already (endpoints attach at VC0), so it needs no branch.
    """
    arb = arb_decisions(in_buf, in_cnt, out_cnt, rr_ptr, wh_lock, route,
                        depth_out=out_buf.shape[-2], vc_out=vc_out,
                        n_vcs=n_vcs)
    up_head, link_accept, sent, ep_flit, ep_valid = link_stage(
        out_buf, out_cnt, arb.in_space, link_src, link_dst, port_ep,
        ep_attach, ep_space, n_vcs=n_vcs)
    in2, in_cnt2, out2, out_cnt2 = apply_cycle(
        in_buf, in_cnt, out_buf, out_cnt, arb.arb_pop, arb.granted, arb.chosen,
        link_accept, up_head, sent, fused=fused)
    return in2, in_cnt2, out2, out_cnt2, arb.rr_ptr, arb.wh_lock, ep_flit, ep_valid


def router_cycle_offload_reference(in_buf, in_cnt, out_buf, out_cnt, rr_ptr,
                                   wh_lock, red_acc, red_got, route, link_src,
                                   link_dst, port_ep, ep_attach, fork_out,
                                   red_parent, red_need, ep_space,
                                   n_endpoints: int, fused: bool = False,
                                   vc_out=None, n_vcs: int = 1):
    """One cycle with collective offload enabled (single channel, reference).

    Identical to ``router_cycle_reference`` except that arbitration runs
    through ``offload_decisions`` (fork table + reduction ALU) and the
    per-(router, group) reduction state rides along. Returns the
    ``router_cycle_reference`` tuple extended with ``(red_acc', red_got')``.
    The link-traversal and apply phases are byte-for-byte shared: the
    offload path only changes *which* flits are popped and latched.
    """
    h = heads(in_buf)
    arb, red_acc2, red_got2 = offload_decisions(
        request_ports(h, in_cnt, route, vc_out=vc_out, n_vcs=n_vcs), h,
        in_cnt, out_cnt, rr_ptr, wh_lock, depth_in=in_buf.shape[-2],
        depth_out=out_buf.shape[-2], fork_out=fork_out,
        red_parent=red_parent, red_need=red_need, red_acc=red_acc,
        red_got=red_got, n_endpoints=n_endpoints)
    up_head, link_accept, sent, ep_flit, ep_valid = link_stage(
        out_buf, out_cnt, arb.in_space, link_src, link_dst, port_ep,
        ep_attach, ep_space, n_vcs=n_vcs)
    in2, in_cnt2, out2, out_cnt2 = apply_cycle(
        in_buf, in_cnt, out_buf, out_cnt, arb.arb_pop, arb.granted, arb.chosen,
        link_accept, up_head, sent, fused=fused)
    return (in2, in_cnt2, out2, out_cnt2, arb.rr_ptr, arb.wh_lock,
            ep_flit, ep_valid, red_acc2, red_got2)


def inject_endpoints(in_buf, in_cnt, er, ep_p, port_ep, flit, want):
    """Gather-push one flit per endpoint into its attached input FIFO.

    Single channel: ``in_buf`` [R, P, Din, NF], ``in_cnt`` [R, P],
    ``er``/``ep_p`` [E] the attach (router, port) of every endpoint,
    ``port_ep`` [R, P] the inverse map (endpoint at that port, -1), ``flit``
    [E, NF], ``want`` [E]. Returns ``(in_buf, in_cnt, accepted [E])``.
    Because attach ports are unique, the push is expressible as a *gather*
    per (router, port) — each port pulls its endpoint's flit and writes
    slot ``cnt`` via a one-hot select — which XLA CPU runs much faster than
    a scattered write. Bit-identical to the one-hot ``fifo_push`` path
    (untouched slots keep their garbage either way).
    """
    Din = in_buf.shape[-2]
    pe = jnp.clip(port_ep, 0, None)  # [R, P]
    want_rp = want[pe] & (port_ep >= 0)
    acc_rp = want_rp & (in_cnt < Din)
    flit_rp = flit[pe]  # [R, P, NF]
    at = acc_rp[..., None] & (jnp.arange(Din) == in_cnt[..., None])
    in_buf = jnp.where(at[..., None], flit_rp[..., None, :], in_buf)
    in_cnt = in_cnt + acc_rp.astype(jnp.int32)
    accepted = acc_rp[er, ep_p]  # [E]
    return in_buf, in_cnt, accepted


def fused_cycle_body(i, carry, route, link_src, link_dst, port_ep, ep_attach,
                     ep_space, cycle0, n_cycles: int, vc_out=None,
                     n_vcs: int = 1):
    """One cycle of the fused multi-cycle window (single channel).

    ``carry`` holds the fabric state plus this channel's endpoint egress
    queue (circular: buf [E, Q, NF], ready [E, Q], head [E], cnt [E]).
    Cycle ``i`` of the window: capture ``req_waiting`` (output head pending
    at an attach port, pre-cycle), run the router cycle against the frozen
    ``ep_space``, then inject each endpoint's ready egress head — except on
    the window's last cycle, where the caller injects after running the
    endpoint phases (so a window of 1 is bit-identical to per-cycle
    stepping). Returns ``(carry', (ep_flit [E, NF], ep_valid [E],
    req_waiting [E]))``.

    This body is the single source of truth for both fused backends: the
    jnp path scans it, the Pallas kernel runs it inside ``fori_loop`` with
    the carry resident in kernel memory.
    """
    (in_buf, in_cnt, out_buf, out_cnt, rr, wh,
     eg, eg_ready, eg_head, eg_cnt) = carry
    er, ep_p = ep_attach[:, 0], ep_attach[:, 1]
    req_waiting = out_cnt[er, ep_p] > 0

    (in_buf, in_cnt, out_buf, out_cnt, rr, wh, ep_flit, ep_valid) = (
        router_cycle_reference(in_buf, in_cnt, out_buf, out_cnt, rr, wh,
                               route, link_src, link_dst, port_ep, ep_attach,
                               ep_space, fused=True, vc_out=vc_out,
                               n_vcs=n_vcs))

    Q = eg_ready.shape[-1]
    head_flit = jnp.take_along_axis(eg, eg_head[:, None, None], axis=1)[:, 0]
    head_ready = jnp.take_along_axis(eg_ready, eg_head[:, None], axis=1)[:, 0]
    want = (eg_cnt > 0) & (head_ready <= cycle0 + i) & (i < n_cycles - 1)
    in_buf, in_cnt, accepted = inject_endpoints(in_buf, in_cnt, er, ep_p,
                                                port_ep, head_flit, want)
    eg_head = (eg_head + accepted.astype(jnp.int32)) % Q
    eg_cnt = eg_cnt - accepted.astype(jnp.int32)

    carry = (in_buf, in_cnt, out_buf, out_cnt, rr, wh,
             eg, eg_ready, eg_head, eg_cnt)
    return carry, (ep_flit, ep_valid, req_waiting)


def router_cycles_scan(in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
                       eg, eg_ready, eg_head, eg_cnt,
                       route, link_src, link_dst, port_ep, ep_attach,
                       ep_space, cycle0, n_cycles: int, vc_out=None,
                       n_vcs: int = 1):
    """``n_cycles`` of ``fused_cycle_body`` as a lax.scan (single channel).

    The jnp reference for the fused Pallas kernel: same body, same order.
    Returns ``(carry', (ep_flit [N, E, NF], ep_valid [N, E],
    req_waiting [N, E]))``.
    """
    carry0 = (in_buf, in_cnt, out_buf, out_cnt, rr_ptr, wh_lock,
              eg, eg_ready, eg_head, eg_cnt)

    def body(carry, i):
        return fused_cycle_body(i, carry, route, link_src, link_dst, port_ep,
                                ep_attach, ep_space, cycle0, n_cycles,
                                vc_out=vc_out, n_vcs=n_vcs)

    return jax.lax.scan(body, carry0, jnp.arange(n_cycles))
