"""The plain reference simulator: one FlooNoC fabric cycle after another.

A straightforward statement of the simulated semantics, kept with the
benchmark and importing nothing of the program. It follows the simulator's
``step_impl="naive"`` reference datapath as it stood when the benchmark was
defined (head-at-0 queues shifted on every pop, one-hot FIFO writes, the
first-minimum round-robin pick) and models what the benchmark's cells drive:
read DMA over one or more streams, narrow reads, RoB-less NIs, the memory
server and HBM token bucket, any channel count, dateline VCs. Write DMA,
RoB ordering, scheduled (collective) DMA and in-fabric collectives are not
modelled; a workload that asks for them is refused.

State is a flat dict of arrays keyed ``fabric.<leaf>``, ``eps.<leaf>`` and
``cycle``, the names of the program's ``SimState`` leaves, so that
``bench.lib.check`` can compare the two leaf by leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.topology import Fabric

# paper defaults (FlooNoC Sec. III-V; calibrated to Fig. 7 and the HBM2E
# channel of Fig. 11)
DEPTH_IN = 2
DEPTH_OUT = 2
N_TXN_IDS = 8
MAX_OUTSTANDING = 32
CLUSTER_REQ_LAT = 4
CLUSTER_RSP_LAT = 4
MEM_LAT = 3
NI_REQ_LAT = 1
NI_RSP_LAT = 1
HBM_RATE = 57.6 / 80.6
HBM_EFF = 0.97
EGRESS_DEPTH = 8
MEMQ_DEPTH = 256

# flit fields and kinds
NF = 7
F_DST, F_SRC, F_KIND, F_TXN, F_LAST, F_TS, F_META = range(NF)
NARROW_REQ, NARROW_RSP, WIDE_AR, WIDE_R, WIDE_AW_W, WIDE_B = range(6)
CH_REQ, CH_RSP, CH_WIDE = 0, 1, 2
# memory-queue entry fields
NMQ = 6
MQ_SRC, MQ_TXN, MQ_BEATS, MQ_KIND, MQ_TS, MQ_META = range(NMQ)


def pack(dst, src, kind, txn, last, ts, meta):
    """Flit [..., NF] from its fields, broadcast against ``dst``."""
    ref = jnp.asarray(dst, jnp.int32)
    return jnp.stack([jnp.broadcast_to(jnp.asarray(v, jnp.int32), ref.shape)
                      for v in (ref, src, kind, txn, last, ts, meta)], -1)


# ---------------------------------------------------------------- tables
def tables(fab: Fabric, n_vcs: int) -> dict:
    """Routing tables, with the port axis folded to (port, VC) slots."""
    R, P, V = fab.n_routers, fab.n_ports, n_vcs
    link_src = np.full((R, P, 2), -1, np.int32)
    for r in range(R):
        for p in range(P):
            r2, p2 = fab.link_to[r, p]
            if r2 >= 0:
                link_src[r2, p2] = (r, p)
    port_ep = np.full((R, P * V), -1, np.int32)
    port_ep[:, ::V] = fab.port_ep  # endpoints attach at VC0 of their port
    ep_attach = fab.ep_attach.copy()
    ep_attach[:, 1] *= V
    vc_out = np.zeros((R, P * V, P), np.int32)
    if V > 1 and fab.port_dim is not None:
        for pin in range(P):
            for vin in range(V):
                same = fab.port_dim == fab.port_dim[:, pin:pin + 1]
                v = np.where(same, vin, 0)  # a turn resets the VC
                vc_out[:, pin * V + vin] = np.where(fab.dateline, min(1, V - 1), v)
    return dict(route=fab.route, link_src=link_src, link_dst=fab.link_to,
                port_ep=port_ep, ep_attach=ep_attach, vc_out=vc_out)


# ---------------------------------------------------------------- FIFOs
def fifo_pop(buf, cnt, pop):
    """Shift out the head of every FIFO where ``pop``."""
    return (jnp.where(pop[..., None, None], jnp.roll(buf, -1, axis=-2), buf),
            cnt - pop.astype(jnp.int32))


def fifo_push(buf, cnt, push, flit):
    """Write ``flit`` at the tail of every FIFO where ``push``."""
    D = buf.shape[-2]
    at = (jnp.arange(D) == jnp.clip(cnt, 0, D - 1)[..., None]) & push[..., None]
    return (jnp.where(at[..., None], flit[..., None, :], buf),
            cnt + push.astype(jnp.int32))


# ---------------------------------------------------------------- routers
def router_cycle(f: dict, t: dict, ep_space, V: int):
    """One cycle of one channel. Arbitration and link traversal both read
    the cycle-start snapshot; pops apply before pushes on each side."""
    in_buf, in_cnt = f["in_buf"], f["in_cnt"]
    out_buf, out_cnt = f["out_buf"], f["out_cnt"]
    rr, wh = f["rr_ptr"], f["wh_lock"]
    R, P = in_cnt.shape
    Pp = P // V
    E = t["route"].shape[1]
    Din, Dout = in_buf.shape[-2], out_buf.shape[-2]
    r_ix = jnp.arange(R)[:, None]

    # requests: each valid input head asks for one output slot
    h = in_buf[:, :, 0]
    phys = t["route"][r_ix, jnp.clip(h[..., F_DST], 0, E - 1)]  # [R, P]
    vc = jnp.take_along_axis(t["vc_out"], jnp.clip(phys, 0, Pp - 1)[..., None],
                             axis=-1)[..., 0]
    req = jnp.where(in_cnt > 0, phys * V + vc, -1)

    # round-robin arbitration per output slot, wormhole locks
    pin = jnp.arange(P)[None, :, None]
    elig = req[:, :, None] == jnp.arange(P)[None, None, :]  # [R, Pin, Pout]
    elig &= (wh[:, None, :] < 0) | (wh[:, None, :] == pin)
    elig &= out_cnt[:, None, :] < Dout
    score = jnp.where(elig, (pin - rr[:, None, :]) % P, P + 1)
    winner = jnp.argmin(score, axis=1)  # [R, Pout] first minimum
    granted = jnp.min(score, axis=1) <= P
    chosen = jnp.take_along_axis(h, winner[..., None], axis=1)  # [R, Pout, NF]
    arb_pop = jnp.any((winner[:, None, :] == pin) & granted[:, None, :], axis=2)
    rr2 = jnp.where(granted, (winner + 1) % P, rr)
    tail = chosen[..., F_LAST] > 0
    wh2 = jnp.where(granted & ~tail, winner, wh)
    wh2 = jnp.where(granted & tail, -1, wh2)
    in_space = (in_cnt - arb_pop.astype(jnp.int32)) < Din

    # link traversal: one flit per physical wire per cycle, lowest
    # eligible VC first; ejection into endpoints with ingress space
    out_h, out_v = out_buf[:, :, 0], out_cnt > 0
    src_r = jnp.clip(t["link_src"][..., 0], 0, R - 1)[..., None]
    src_s = jnp.clip(t["link_src"][..., 1], 0, Pp - 1)[..., None] * V + jnp.arange(V)
    up_head = out_h[src_r, src_s].reshape(R, P, NF)
    up_ok = out_v[src_r, src_s] & (t["link_src"][..., :1] >= 0)
    cand = up_ok & in_space.reshape(R, Pp, V)
    first = jnp.argmax(cand, axis=-1)[..., None] == jnp.arange(V)
    accept = (cand & first).reshape(R, P)

    dst_r = jnp.clip(t["link_dst"][..., 0], 0, R - 1)[..., None]
    dst_s = jnp.clip(t["link_dst"][..., 1], 0, Pp - 1)[..., None] * V + jnp.arange(V)
    down = (out_v.reshape(R, Pp, V) & in_space[dst_r, dst_s]
            & (t["link_dst"][..., :1] >= 0))
    first = jnp.argmax(down, axis=-1)[..., None] == jnp.arange(V)
    sent = (down & first).reshape(R, P)
    pe = t["port_ep"]
    sent |= (pe >= 0) & out_v & ep_space[jnp.clip(pe, 0, E - 1)]
    er, ep_p = t["ep_attach"][:, 0], t["ep_attach"][:, 1]
    ep_flit = out_h[er, ep_p]
    ep_valid = out_v[er, ep_p] & ep_space

    in_buf, in_cnt = fifo_pop(in_buf, in_cnt, arb_pop)
    in_buf, in_cnt = fifo_push(in_buf, in_cnt, accept, up_head)
    out_buf, out_cnt = fifo_pop(out_buf, out_cnt, sent)
    out_buf, out_cnt = fifo_push(out_buf, out_cnt, granted, chosen)
    new = dict(in_buf=in_buf, in_cnt=in_cnt, out_buf=out_buf, out_cnt=out_cnt,
               rr_ptr=rr2, wh_lock=wh2)
    return new, ep_flit, ep_valid


def inject(f: dict, t: dict, flit, want):
    """Endpoints push one flit each into their attach port's input FIFO."""
    R, P = f["in_cnt"].shape
    er, ep_p = t["ep_attach"][:, 0], t["ep_attach"][:, 1]
    accepted = want & (f["in_cnt"][er, ep_p] < f["in_buf"].shape[-2])
    push = jnp.zeros((R, P), bool).at[er, ep_p].set(accepted)
    flit_rp = jnp.zeros((R, P, NF), jnp.int32).at[er, ep_p].set(flit)
    in_buf, in_cnt = fifo_push(f["in_buf"], f["in_cnt"], push, flit_rp)
    return dict(f, in_buf=in_buf, in_cnt=in_cnt), accepted


# ---------------------------------------------------------------- endpoints
def _hash(a, b, c):
    u = jnp.uint32
    a, b, c = (jnp.asarray(x).astype(u) for x in (a, b, c))
    h = a * u(2654435761) + b * u(40503) + c * u(69069) + u(12345)
    h = (h ^ (h >> u(13))) * u(1274126177)
    h = h ^ (h >> u(16))
    return (h & u(0x7FFFFFFF)).astype(jnp.int32)


def uniform_dst(e, seq, n_tiles: int):
    """Per-message uniform destination: any tile but the sender."""
    other = _hash(e, seq, 0) % max(n_tiles - 1, 1)
    return ((e + 1 + other) % n_tiles).astype(jnp.int32)


def col_add(x, idx, delta):
    """``x[e, idx[..., e]] += delta[..., e]`` (leading axes accumulate)."""
    eidx = jnp.broadcast_to(jnp.arange(x.shape[0]), jnp.shape(idx))
    return x.at[eidx, idx].add(delta)


def eg_push(eg, eg_ready, eg_cnt, ch, mask, flit, ready):
    """Append ``flit`` [E, NF] to the egress queue of channel ``ch``
    (static or per endpoint) where ``mask``."""
    C, E, Q = eg_ready.shape
    ch = jnp.broadcast_to(jnp.asarray(ch, jnp.int32), (E,))
    ch_oh = jnp.arange(C)[:, None] == ch[None, :]  # [C, E]
    cnt = jnp.take_along_axis(eg_cnt, ch[None], axis=0)[0]
    at = ch_oh[..., None] & (jnp.arange(Q) == jnp.clip(cnt, 0, Q - 1)[:, None])
    at &= mask[None, :, None]
    eg = jnp.where(at[..., None], flit[None, :, None, :], eg)
    eg_ready = jnp.where(at, ready[None, :, None], eg_ready)
    return eg, eg_ready, eg_cnt + (ch_oh & mask[None]).astype(jnp.int32)


def mq_push(mq, mq_cnt, mask, vals):
    """Append one request [..., E, NMQ] per endpoint and leading index where
    ``mask`` [..., E]; pushes of one cycle land in leading-index order."""
    Q = mq.shape[1]
    vals = vals.reshape((-1,) + vals.shape[-2:])
    mask = mask.reshape((-1, mask.shape[-1]))
    for k in range(mask.shape[0]):
        at = (jnp.arange(Q) == jnp.clip(mq_cnt, 0, Q - 1)[:, None]) & mask[k][:, None]
        mq = jnp.where(at[..., None], vals[k][:, None, :], mq)
        mq_cnt = mq_cnt + mask[k].astype(jnp.int32)
    return mq, mq_cnt


def ni_ok(ni_cnt, ni_dst, txn, dst):
    """RoB-less ordering: a TxnID with outstanding transactions may only
    issue to the destination they went to."""
    e = jnp.arange(ni_cnt.shape[0]).reshape((-1,) + (1,) * (jnp.ndim(txn) - 1))
    return (ni_cnt[e, txn] == 0) | (ni_dst[e, txn] == dst)


def ni_issue(ni_cnt, ni_dst, mask, txn, dst):
    e = jnp.arange(ni_cnt.shape[0])
    ni_cnt = col_add(ni_cnt, txn, mask.astype(jnp.int32))
    ni_dst = ni_dst.at[e, txn].set(jnp.where(mask, dst, ni_dst[e, txn]))
    return ni_cnt, ni_dst


def ingest(s: dict, flits, valid, cycle):
    """Deliveries [C, E, NF] / [C, E] reach their endpoints."""
    E = valid.shape[1]
    e = jnp.arange(E)
    S = s["d_outst"].shape[1]
    kind = flits[..., F_KIND]
    f, v = flits[CH_REQ], valid[CH_REQ]
    # narrow read at the target: fixed-latency response via rsp egress
    is_nreq = v & (f[:, F_KIND] == NARROW_REQ)
    rsp = pack(f[:, F_SRC], e, NARROW_RSP, f[:, F_TXN], 1, f[:, F_TS], 1)
    ready = jnp.broadcast_to(cycle + NI_RSP_LAT + MEM_LAT + NI_REQ_LAT, (E,))
    s["eg"], s["eg_ready"], s["eg_cnt"] = eg_push(
        s["eg"], s["eg_ready"], s["eg_cnt"], CH_RSP, is_nreq, rsp,
        ready.astype(jnp.int32))
    # wide read request at the target: queue it for the memory server
    is_war = v & (f[:, F_KIND] == WIDE_AR)
    req = jnp.stack([f[:, F_SRC], f[:, F_TXN], f[:, F_META],
                     jnp.full((E,), WIDE_R, jnp.int32), f[:, F_TS],
                     f[:, F_META]], -1)
    s["mq"], s["mq_cnt"] = mq_push(s["mq"], s["mq_cnt"], is_war, req)
    # read data back at the issuer, write data at the target
    stream = jnp.clip(flits[..., F_TXN], 0, S - 1)
    is_r = valid & (kind == WIDE_R)
    s["d_beats_got"] = col_add(s["d_beats_got"], stream, is_r.astype(jnp.int32))
    r_done = is_r & (flits[..., F_LAST] > 0)
    s["d_outst"] = col_add(s["d_outst"], stream, -r_done.astype(jnp.int32))
    s["d_done"] = col_add(s["d_done"], stream, r_done.astype(jnp.int32))
    s["ni_cnt"] = col_add(s["ni_cnt"], flits[..., F_TXN], -r_done.astype(jnp.int32))
    is_w = valid & (kind == WIDE_AW_W)
    rcvd = is_r | is_w
    s["beats_rcvd"] = s["beats_rcvd"] + rcvd.sum(0)
    any_beat = rcvd.any(0)
    c = jnp.broadcast_to(cycle, (E,)).astype(jnp.int32)
    s["first_rx"] = jnp.where(any_beat & (s["first_rx"] < 0), c, s["first_rx"])
    s["last_rx"] = jnp.where(any_beat, c, s["last_rx"])
    w_tail = is_w & (flits[..., F_LAST] > 0)
    C = valid.shape[0]
    wreq = jnp.stack([jnp.broadcast_to(x, (C, E)) for x in (
        flits[..., F_SRC], flits[..., F_TXN], 1, WIDE_B, flits[..., F_TS],
        flits[..., F_META])], -1)
    s["mq"], s["mq_cnt"] = mq_push(s["mq"], s["mq_cnt"], w_tail, wreq)
    s["rx_bursts"] = col_add(s["rx_bursts"], stream, w_tail.astype(jnp.int32))
    # responses at the issuer
    f, v = flits[CH_RSP], valid[CH_RSP]
    is_nrsp = v & (f[:, F_KIND] == NARROW_RSP)
    s["lat_sum"] = s["lat_sum"] + jnp.where(
        is_nrsp, (cycle - f[:, F_TS] + CLUSTER_RSP_LAT).astype(jnp.float32), 0.0)
    s["lat_cnt"] = s["lat_cnt"] + is_nrsp.astype(jnp.int32)
    is_b = v & (f[:, F_KIND] == WIDE_B)
    sb = jnp.clip(f[:, F_TXN], 0, S - 1)
    s["d_outst"] = col_add(s["d_outst"], sb, -is_b.astype(jnp.int32))
    s["d_done"] = col_add(s["d_done"], sb, is_b.astype(jnp.int32))
    s["ni_cnt"] = col_add(s["ni_cnt"], f[:, F_TXN], -is_nrsp.astype(jnp.int32))
    s["ni_cnt"] = col_add(s["ni_cnt"], f[:, F_TXN], -is_b.astype(jnp.int32))
    return s


def generate(s: dict, wl: dict, cycle, n_tiles: int):
    """Narrow requests, then one DMA stream per endpoint, into egress."""
    E = s["n_acc"].shape[0]
    e = jnp.arange(E)
    Q = s["eg_ready"].shape[-1]
    delay = CLUSTER_REQ_LAT + NI_REQ_LAT
    at = jnp.broadcast_to(cycle + delay, (E,)).astype(jnp.int32)

    n_acc = s["n_acc"] + wl["narrow_rate"]
    want = (n_acc >= 1.0) & (wl["narrow_dst"] != -1)
    dst = jnp.where(wl["narrow_dst"] == -2, uniform_dst(e, s["n_seq"], n_tiles),
                    wl["narrow_dst"]).astype(jnp.int32)
    txn = s["n_seq"] % N_TXN_IDS
    ok = ni_ok(s["ni_cnt"], s["ni_dst"], txn, dst)
    fire = want & ok & (s["eg_cnt"][CH_REQ] < Q)
    stall_n = want & ~ok
    s["eg"], s["eg_ready"], s["eg_cnt"] = eg_push(
        s["eg"], s["eg_ready"], s["eg_cnt"], CH_REQ, fire,
        pack(dst, e, NARROW_REQ, txn, 1, cycle, 1), at)
    s["ni_cnt"], s["ni_dst"] = ni_issue(s["ni_cnt"], s["ni_dst"], fire, txn, dst)
    s["n_acc"] = jnp.where(fire, n_acc - 1.0, jnp.minimum(n_acc, 4.0))
    s["n_seq"] = s["n_seq"] + fire.astype(jnp.int32)
    s["n_sent"] = s["n_sent"] + fire.astype(jnp.int32)

    S = s["d_outst"].shape[1]
    txn_s = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None] % N_TXN_IDS, (E, S))
    odd = (s["d_seq"] % 2) == 1
    dst_s = jnp.where((wl["dma_alt_dst"] >= 0) & odd, wl["dma_alt_dst"], wl["dma_dst"])
    dst_s = jnp.where(wl["dma_dst"] == -2,
                      uniform_dst(e[:, None], s["d_seq"] * S + jnp.arange(S)[None],
                                  n_tiles), dst_s).astype(jnp.int32)
    ok = ni_ok(s["ni_cnt"], s["ni_dst"], txn_s, dst_s)
    want = ((s["d_txns_left"] > 0) & (s["d_outst"] < MAX_OUTSTANDING)
            & (wl["dma_dst"] != -1))
    rot = (jnp.arange(S)[None] - (cycle + e[:, None])) % S  # rotating priority
    score = jnp.where(want & ok, rot, S + 1)
    pick = jnp.argmin(score, axis=1)
    any_pick = jnp.min(score, axis=1) <= S
    stall_d = jnp.any(want & ~ok, axis=1) & ~any_pick
    p_dst, p_txn = dst_s[e, pick], txn_s[e, pick]
    fire = any_pick & (s["eg_cnt"][CH_REQ] < Q)
    s["eg"], s["eg_ready"], s["eg_cnt"] = eg_push(
        s["eg"], s["eg_ready"], s["eg_cnt"], CH_REQ, fire,
        pack(p_dst, e, WIDE_AR, p_txn, 1, cycle, wl["dma_beats"]), at)
    s["ni_cnt"], s["ni_dst"] = ni_issue(s["ni_cnt"], s["ni_dst"], fire, p_txn, p_dst)
    n = fire.astype(jnp.int32)
    s["d_txns_left"] = col_add(s["d_txns_left"], pick, -n)
    s["d_outst"] = col_add(s["d_outst"], pick, n)
    s["d_seq"] = col_add(s["d_seq"], pick, n)
    s["ni_stall"] = (s["ni_stall"] + stall_n.astype(jnp.int32)
                     + stall_d.astype(jnp.int32))
    return s


def memory(s: dict, cycle, is_hbm, C: int):
    """Memory server: take one request when idle, answer after the access
    latency with one beat per cycle (HBM endpoints at the channel's rate)."""
    E = s["m_busy"].shape[0]
    e = jnp.arange(E)
    Q = s["eg_ready"].shape[-1]
    tok = jnp.where(is_hbm, jnp.minimum(s["hbm_tok"] + HBM_RATE * HBM_EFF, 8.0),
                    jnp.asarray(1.0, jnp.float32))
    busy = jnp.maximum(s["m_busy"] - 1, 0)
    pop = ~s["m_active"] & (s["mq_cnt"] > 0)
    head = s["mq"][:, 0]
    s["mq"] = jnp.where(pop[:, None, None], jnp.roll(s["mq"], -1, axis=1), s["mq"])
    s["mq_cnt"] = s["mq_cnt"] - pop.astype(jnp.int32)
    active = s["m_active"] | pop
    busy = jnp.where(pop, MEM_LAT + NI_RSP_LAT, busy)
    beats = jnp.where(pop, head[:, MQ_BEATS], s["m_beats"])
    flit = jnp.where(pop[:, None], pack(head[:, MQ_SRC], e, head[:, MQ_KIND],
                                        head[:, MQ_TXN], 0, head[:, MQ_TS],
                                        head[:, MQ_META]), s["m_flit"])
    wide_r = flit[:, F_KIND] == WIDE_R
    wide_ch = CH_WIDE + jnp.clip(flit[:, F_TXN], 0, None) % (C - CH_WIDE)
    ch = jnp.where(wide_r, wide_ch, CH_RSP)
    tok_ok = jnp.where(is_hbm & wide_r, tok >= 1.0, True)
    space = jnp.take_along_axis(s["eg_cnt"], ch[None], axis=0)[0] < Q
    emit = active & (busy == 0) & tok_ok & space & (beats > 0)
    out = flit.at[:, F_LAST].set((beats == 1).astype(jnp.int32))
    ready = jnp.broadcast_to(cycle + NI_REQ_LAT, (E,)).astype(jnp.int32)
    s["eg"], s["eg_ready"], s["eg_cnt"] = eg_push(
        s["eg"], s["eg_ready"], s["eg_cnt"], ch, emit, out, ready)
    served = emit & is_hbm & wide_r
    s["hbm_tok"] = jnp.where(served, tok - 1.0, tok)
    s["hbm_served"] = s["hbm_served"] + served.astype(jnp.int32)
    beats = jnp.where(emit, beats - 1, beats)
    s["m_active"] = active & ~(emit & (beats == 0))
    s["m_busy"], s["m_beats"], s["m_flit"] = busy, beats, flit
    return s


# ---------------------------------------------------------------- the cycle
def step(state: dict, t: dict, wl: dict, is_hbm, n_tiles: int, V: int):
    """One simulated cycle of the whole system."""
    cycle = state["cycle"]
    fab = {k[7:]: v for k, v in state.items() if k.startswith("fabric.")}
    s = {k[4:]: v for k, v in state.items() if k.startswith("eps.")}
    C, E = s["eg_cnt"].shape
    Q = s["eg_ready"].shape[-1]
    er, ep_p = t["ep_attach"][:, 0], t["ep_attach"][:, 1]
    # a delivered narrow request answers into rsp egress, so the req channel
    # only delivers while that queue has room
    rsp_free = s["eg_cnt"][CH_RSP] < Q
    space = jnp.ones((C, E), bool).at[CH_REQ].set(rsp_free)
    waiting = fab["out_cnt"][CH_REQ][er, ep_p] > 0
    fab, ep_flit, ep_valid = jax.vmap(
        functools.partial(router_cycle, V=V), in_axes=(0, None, 0))(fab, t, space)
    s = ingest(s, ep_flit, ep_valid, cycle)
    s["eg_overflow"] = s["eg_overflow"] + (waiting & ~rsp_free).astype(jnp.int32)
    s = generate(s, wl, cycle, n_tiles)
    s = memory(s, cycle, is_hbm, C)
    ready = (s["eg_cnt"] > 0) & (s["eg_ready"][:, :, 0] <= cycle)
    fab, accepted = jax.vmap(inject, in_axes=(0, None, 0, 0))(
        fab, t, s["eg"][:, :, 0], ready)
    pop = accepted
    s["eg"] = jnp.where(pop[..., None, None], jnp.roll(s["eg"], -1, axis=2), s["eg"])
    s["eg_ready"] = jnp.where(pop[..., None], jnp.roll(s["eg_ready"], -1, axis=2),
                              s["eg_ready"])
    s["eg_cnt"] = s["eg_cnt"] - pop.astype(jnp.int32)
    out = {f"fabric.{k}": v for k, v in fab.items()}
    out.update({f"eps.{k}": v for k, v in s.items()})
    out["cycle"] = cycle + 1
    return out


class Reference:
    """A fabric, its channel and VC counts and a workload, simulated plainly
    on whatever device is the default when :meth:`run` is called."""

    def __init__(self, fab: Fabric, n_channels: int, n_vcs: int, wl: dict):
        if wl.get("dma_write") or wl.get("scheduled"):
            raise NotImplementedError("the reference models read DMA only")
        self.fab, self.C, self.V = fab, n_channels, n_vcs
        self.t = {k: jnp.asarray(v) for k, v in tables(fab, n_vcs).items()}
        self.wl = {k: jnp.asarray(wl[k]) for k in (
            "narrow_rate", "narrow_dst", "dma_dst", "dma_alt_dst", "dma_txns")}
        self.wl["dma_beats"] = int(wl["dma_beats"])
        hbm = np.zeros(fab.n_endpoints, bool)
        if fab.n_hbm:
            hbm[fab.n_endpoints - fab.n_hbm:] = True
        self.is_hbm = jnp.asarray(hbm)

    def init_state(self) -> dict:
        """All queues and FIFOs empty at cycle 0."""
        C, R, P, E = self.C, self.fab.n_routers, self.fab.n_ports * self.V, self.fab.n_endpoints
        S = self.wl["dma_dst"].shape[1]
        z = lambda *sh: np.zeros(sh, np.int32)
        st = {
            "fabric.in_buf": z(C, R, P, DEPTH_IN, NF), "fabric.in_cnt": z(C, R, P),
            "fabric.out_buf": z(C, R, P, DEPTH_OUT, NF), "fabric.out_cnt": z(C, R, P),
            "fabric.rr_ptr": z(C, R, P), "fabric.wh_lock": np.full((C, R, P), -1, np.int32),
            "eps.ni_cnt": z(E, N_TXN_IDS), "eps.ni_dst": np.full((E, N_TXN_IDS), -1, np.int32),
            "eps.rob_credit": np.full((E,), 128, np.int32),
            "eps.n_acc": np.zeros(E, np.float32), "eps.n_seq": z(E),
            "eps.d_txns_left": np.asarray(self.wl["dma_txns"], np.int32),
            "eps.d_outst": z(E, S), "eps.d_seq": z(E, S), "eps.d_beats_got": z(E, S),
            "eps.rx_bursts": z(E, S), "eps.w_stream": np.full((E,), -1, np.int32),
            "eps.w_left": z(E), "eps.w_beats": z(E), "eps.w_dst": z(E), "eps.w_txn": z(E),
            "eps.w_ts": z(E), "eps.t_aww_left": z(E), "eps.t_aww_src": z(E),
            "eps.t_aww_txn": z(E), "eps.mq": z(E, MEMQ_DEPTH, NMQ), "eps.mq_head": z(E),
            "eps.mq_cnt": z(E), "eps.m_busy": z(E), "eps.m_beats": z(E),
            "eps.m_flit": z(E, NF), "eps.m_active": np.zeros(E, bool),
            "eps.hbm_tok": np.zeros(E, np.float32), "eps.eg": z(C, E, EGRESS_DEPTH, NF),
            "eps.eg_ready": z(C, E, EGRESS_DEPTH), "eps.eg_head": z(C, E),
            "eps.eg_cnt": z(C, E), "eps.lat_sum": np.zeros(E, np.float32),
            "eps.lat_cnt": z(E), "eps.beats_rcvd": z(E), "eps.beats_sent": z(E),
            "eps.ni_stall": z(E), "eps.eg_overflow": z(E), "eps.hbm_served": z(E),
            "eps.n_sent": z(E), "eps.d_done": z(E, S), "eps.last_rx": z(E),
            "eps.first_rx": np.full((E,), -1, np.int32), "cycle": np.int32(0),
        }
        return st

    def run(self, state: dict, n_cycles: int) -> dict:
        """``state`` advanced by ``n_cycles``, as numpy arrays."""
        out = _scan(self.fab.n_tiles, self.V, n_cycles)(
            {k: jnp.asarray(v) for k, v in state.items()}, self.t, self.wl,
            self.is_hbm)
        return {k: np.asarray(v) for k, v in out.items()}


@functools.lru_cache(maxsize=None)
def _scan(n_tiles: int, V: int, n_cycles: int):
    @jax.jit
    def run(state, t, wl, is_hbm):
        body = lambda s, _: (step(s, t, wl, is_hbm, n_tiles, V), None)
        return jax.lax.scan(body, state, None, length=n_cycles)[0]
    return run


def stats(state: dict, n_tiles: int, n_hbm: int) -> dict:
    """The summary a user reads off a finished simulation."""
    g = lambda k: np.asarray(state[f"eps.{k}"])
    cyc = int(state["cycle"])
    beats = g("beats_rcvd")
    return {
        "cycles": cyc,
        "narrow_lat_mean": (g("lat_sum") / np.maximum(g("lat_cnt"), 1))[:n_tiles],
        "narrow_lat_cnt": g("lat_cnt")[:n_tiles],
        "beats_rcvd": beats,
        "beats_sent": g("beats_sent"),
        "hbm_served": g("hbm_served"),
        "ni_stalls": g("ni_stall"),
        "eg_overflow": g("eg_overflow"),
        "dma_done": g("d_done"),
        "rx_bursts": g("rx_bursts"),
        "last_rx": g("last_rx"),
        "first_rx": g("first_rx"),
        "mq_max": int(g("mq_cnt").max()),
        "wide_util": beats[:n_tiles].sum() / max(cyc * n_tiles, 1),
        "hbm_util": g("hbm_served").sum() / max(cyc * max(n_hbm, 1), 1) / HBM_RATE,
    }
