"""Full-system FlooNoC simulator: a channel-batched fabric (req/rsp/wide plus
optional extra wide channels, see NocParams.n_channels) + vectorized
endpoints, stepped with jax.lax.scan (jit-compiled, cycle-accurate).

The scan step body contains no Python loop over channels: the fabric is
vmapped over a leading channel axis and the endpoint egress/ingest paths carry
the same axis, so trace size and compile time are independent of the channel
count.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.noc import endpoints as epm
from repro.core.noc import engine as eng
from repro.core.noc.engine import (
    F_DST,
    F_KIND,
    F_LAST,
    F_META,
    F_SRC,
    F_TS,
    F_TXN,
)
from repro.core.noc.params import (
    CH_REQ,
    CH_RSP,
    CH_WIDE,
    NARROW_REQ,
    NARROW_RSP,
    WIDE_AR,
    WIDE_AW_W,
    WIDE_B,
    WIDE_MC,
    WIDE_R,
    WIDE_RED,
    NocParams,
    wide_channel_of,
)
from repro.core.noc.topology import Topology

# host spans of the drivers (run, run_sweep) on the profiler's host plane;
# they record nothing unless a profile is being captured
_span = jax.profiler.TraceAnnotation


@jax.tree_util.register_dataclass
@dataclass
class SimState:
    """Full simulator state: fabric + endpoints + the cycle counter."""

    fabric: eng.FabricState  # channel-batched [C, ...]
    eps: epm.EndpointState
    cycle: jnp.ndarray


def _ingest(st: epm.EndpointState, flits, valid, cycle, params: NocParams, wl):
    """Process delivered flits on all channels at once.

    flits: [C, E, NF]; valid: [C, E]. Narrow requests / responses ride their
    role channels (CH_REQ / CH_RSP); wide kinds are recognized by kind on any
    wide channel, so counters are scatter-summed over the channel axis."""
    E = st.lat_sum.shape[0]
    circ = params.step_impl == "fast"
    eidx = jnp.arange(E)
    ni_cnt, ni_dst, rob = st.ni_cnt, st.ni_dst, st.rob_credit
    kind = flits[..., F_KIND]  # [C, E]

    # ---- req channel: we are the target ----
    f = flits[CH_REQ]
    v = valid[CH_REQ]
    is_nreq = v & (f[:, F_KIND] == NARROW_REQ)
    is_war = v & (f[:, F_KIND] == WIDE_AR)
    # narrow reads: the multi-banked L1 SPM is fully pipelined (1 req/cycle
    # throughput); model as a fixed-latency response through the egress delay
    # queue. Wide bursts go through the serializing memory server below.
    rsp_flit = eng.pack_flit(f[:, F_SRC], eidx, NARROW_RSP, f[:, F_TXN], 1,
                             f[:, F_TS], 1)
    rsp_ready = jnp.broadcast_to(
        cycle + params.ni_rsp_lat + params.mem_lat + params.ni_req_lat,
        (E,)).astype(jnp.int32)
    # the req-channel delivery is gated on rsp-egress space upstream (see
    # Sim.step), so this push can never overflow the queue
    eg, eg_ready, eg_cnt = epm._eg_push(st.eg, st.eg_ready, st.eg_head,
                                        st.eg_cnt, CH_RSP, is_nreq, rsp_flit,
                                        rsp_ready, circular=circ)
    mq, mq_cnt = epm._mq_push(st.mq, st.mq_head, st.mq_cnt, is_war,
                              f[:, F_SRC], f[:, F_TXN], f[:, F_META], WIDE_R,
                              f[:, F_TS], f[:, F_META], circular=circ)

    # ---- wide kinds (any channel) ----
    S = st.d_outst.shape[1]  # streams
    stream = jnp.clip(flits[..., F_TXN], 0, S - 1)
    # read data beats coming back to us (we are the issuer)
    is_r = valid & (kind == WIDE_R)
    d_beats_got = epm._col_add(st.d_beats_got, stream,
                               is_r.astype(jnp.int32), circ)
    r_done = is_r & (flits[..., F_LAST] > 0)
    d_outst = epm._col_add(st.d_outst, stream, -r_done.astype(jnp.int32), circ)
    d_done = epm._col_add(st.d_done, stream, r_done.astype(jnp.int32), circ)
    # retire exactly the beats that transfer issued (response F_META carries
    # the original burst size) — NOT the scalar wl.dma_beats, which over- or
    # under-frees RoB credits on variable-size scheduled (collective) DMA
    if not circ:
        ni_cnt, ni_dst, rob = epm._ni_retire(ni_cnt, ni_dst, rob, r_done,
                                             flits[..., F_TXN],
                                             flits[..., F_META], params)
    # write bursts arriving (we are the target); wormhole => no interleave
    is_w = valid & (kind == WIDE_AW_W)
    if params.collective_offload:
        # in-fabric collective payloads (tree-forked multicast beats and
        # combined reduction partials) are posted writes: they count as
        # received beats / complete bursts but neither enqueue a memory
        # response nor touch the issuer-side NI (nothing to retire). The
        # branch is static, so offload=False traces stay bit-identical.
        is_off = valid & ((kind == WIDE_MC) | (kind == WIDE_RED))
        rcvd = is_r | is_w | is_off
        off_tail = is_off & (flits[..., F_LAST] > 0)
    else:
        rcvd = is_r | is_w
    beats_rcvd = st.beats_rcvd + rcvd.sum(axis=0)
    any_beat = rcvd.any(axis=0)
    cyc_e = jnp.broadcast_to(cycle, (E,)).astype(jnp.int32)
    last_rx = jnp.where(any_beat, cyc_e, st.last_rx)
    first_rx = jnp.where(any_beat & (st.first_rx < 0), cyc_e, st.first_rx)
    w_tail = is_w & (flits[..., F_LAST] > 0)
    if circ and params.n_channels == 3:
        # single wide channel: AW_W beats only ever ride CH_WIDE (req/rsp
        # carry narrow/AR/B kinds), so the per-channel push collapses to a
        # single-channel push — one third of the scattered rows, same cells
        fw = flits[CH_WIDE]
        mq, mq_cnt = epm._mq_push(mq, st.mq_head, mq_cnt, w_tail[CH_WIDE],
                                  fw[:, F_SRC], fw[:, F_TXN], 1, WIDE_B,
                                  fw[:, F_TS], fw[:, F_META], circular=True)
    else:
        mq, mq_cnt = epm._mq_push_multi(mq, st.mq_head, mq_cnt, w_tail,
                                        flits[..., F_SRC], flits[..., F_TXN],
                                        1, WIDE_B, flits[..., F_TS],
                                        flits[..., F_META], circular=circ)
    # completed write bursts per stream: the data-dependency signal the
    # scheduled (collective) DMA gates on. Offloaded collective tails count
    # too (a root gates its multicast on the in-fabric reduction arriving).
    burst_tail = w_tail
    if params.collective_offload:
        burst_tail = w_tail | off_tail
    rx_bursts = epm._col_add(st.rx_bursts, stream, burst_tail.astype(jnp.int32),
                             circ)

    # ---- rsp channel ----
    f = flits[CH_RSP]
    v = valid[CH_RSP]
    is_nrsp = v & (f[:, F_KIND] == NARROW_RSP)
    rx_const = params.cluster_rsp_lat
    lat_sum = st.lat_sum + jnp.where(
        is_nrsp, (cycle - f[:, F_TS] + rx_const).astype(jnp.float32), 0.0)
    lat_cnt = st.lat_cnt + is_nrsp.astype(jnp.int32)
    is_b = v & (f[:, F_KIND] == WIDE_B)
    stream_b = jnp.clip(f[:, F_TXN], 0, S - 1)
    d_outst = epm._col_add(d_outst, stream_b, -is_b.astype(jnp.int32), circ)
    d_done = epm._col_add(d_done, stream_b, is_b.astype(jnp.int32), circ)
    # B responses carry the written burst's beat count in F_META: retire
    # what was actually issued (exact RoB credits for mixed-size schedules)
    if not circ:
        ni_cnt, ni_dst, rob = epm._ni_retire(ni_cnt, ni_dst, rob, is_nrsp,
                                             f[:, F_TXN], 1, params)
        ni_cnt, ni_dst, rob = epm._ni_retire(ni_cnt, ni_dst, rob, is_b,
                                             f[:, F_TXN], f[:, F_META], params)
    else:
        # fast path: the three retirements (wide-R tails on any channel,
        # narrow responses and B responses on CH_RSP) have disjoint masks
        # — a delivered flit has exactly one kind — and only add into
        # ni_cnt / rob_credit, so one combined retire is bit-identical to
        # the three sequential calls the naive path makes
        rsp_row = jnp.arange(params.n_channels)[:, None] == CH_RSP
        m_all = r_done | (rsp_row & (is_nrsp | is_b)[None])
        beats_all = jnp.where(r_done, flits[..., F_META], 0) + jnp.where(
            rsp_row & is_nrsp[None], 1, 0) + jnp.where(
            rsp_row & is_b[None], f[None, :, F_META], 0)
        ni_cnt, ni_dst, rob = epm._ni_retire(ni_cnt, ni_dst, rob, m_all,
                                             flits[..., F_TXN], beats_all,
                                             params)

    return dataclasses.replace(
        st, ni_cnt=ni_cnt, ni_dst=ni_dst, rob_credit=rob, mq=mq, mq_cnt=mq_cnt,
        d_beats_got=d_beats_got, rx_bursts=rx_bursts, beats_rcvd=beats_rcvd,
        d_outst=d_outst, d_done=d_done, lat_sum=lat_sum, lat_cnt=lat_cnt,
        last_rx=last_rx, first_rx=first_rx, eg=eg, eg_ready=eg_ready,
        eg_cnt=eg_cnt,
    )


def _generators(st: epm.EndpointState, cycle, params: NocParams, wl, n_tiles):
    """Narrow + DMA request generation into egress queues."""
    E = st.lat_sum.shape[0]
    circ = params.step_impl == "fast"
    eidx = jnp.arange(E)
    eg, eg_ready, eg_cnt = st.eg, st.eg_ready, st.eg_cnt
    ni_cnt, ni_dst, rob = st.ni_cnt, st.ni_dst, st.rob_credit
    EQ = eg_ready.shape[-1]
    T = ni_cnt.shape[1]
    src_delay = params.cluster_req_lat + params.ni_req_lat

    narrow_rate = jnp.asarray(wl.narrow_rate)
    narrow_dst = jnp.asarray(wl.narrow_dst)

    # ---- narrow generator ----
    n_acc = st.n_acc + narrow_rate
    want_n = (n_acc >= 1.0) & (narrow_dst != -1)
    dst_n = jnp.where(
        narrow_dst == -2,
        _uniform_dst(eidx, st.n_seq, cycle, n_tiles),
        narrow_dst,
    ).astype(jnp.int32)
    txn_n = st.n_seq % T
    ok_n = epm._ni_check(
        dataclasses.replace(st, ni_cnt=ni_cnt, ni_dst=ni_dst, rob_credit=rob),
        txn_n, dst_n, params, jnp.ones((E,), jnp.int32))
    space_n = eg_cnt[CH_REQ] < EQ
    fire_n = want_n & ok_n & space_n
    stall_n = want_n & ~ok_n
    flit_n = eng.pack_flit(dst_n, eidx, NARROW_REQ, txn_n, 1, cycle, 1)
    eg, eg_ready, eg_cnt = epm._eg_push(
        eg, eg_ready, st.eg_head, eg_cnt, CH_REQ, fire_n, flit_n,
        jnp.broadcast_to(cycle + src_delay, (E,)).astype(jnp.int32),
        circular=circ)
    ni_cnt, ni_dst, rob = epm._ni_issue(
        dataclasses.replace(st, ni_cnt=ni_cnt, ni_dst=ni_dst, rob_credit=rob),
        fire_n, txn_n, dst_n, jnp.ones((E,), jnp.int32), params)
    n_acc = jnp.where(fire_n, n_acc - 1.0, jnp.minimum(n_acc, 4.0))
    n_seq = st.n_seq + fire_n.astype(jnp.int32)
    n_sent = st.n_sent + fire_n.astype(jnp.int32)

    # ---- DMA: pick one eligible stream per endpoint (rotating priority) ----
    S = st.d_outst.shape[1]
    dma_dst_t = jnp.asarray(wl.dma_dst)  # [E, S]
    dma_alt_t = jnp.asarray(wl.dma_alt_dst)
    txn_of_stream = (
        jnp.arange(S, dtype=jnp.int32)[None, :] % T
        if wl.unique_txn_per_stream
        else jnp.zeros((1, S), jnp.int32)
    )
    txn_of_stream = jnp.broadcast_to(txn_of_stream, (E, S))
    if wl.dma_dst_seq is not None:
        # scheduled multi-phase DMA (collective lowering): destination,
        # beats and receive-gate are looked up per issue index; a transfer
        # only becomes eligible once the stream has received its gate count
        # of complete write bursts (ring-step data dependency). Its own
        # scope, traced only with a schedule (noc/README.md).
        with jax.named_scope("noc.sched"):
            k = jnp.clip(st.d_seq, 0, wl.dma_dst_seq.shape[-1] - 1)[:, :, None]
            at_k = lambda a: jnp.take_along_axis(jnp.asarray(a), k, axis=2)[..., 0]
            dst_es = at_k(wl.dma_dst_seq).astype(jnp.int32)
            beats = at_k(wl.dma_beats_seq)
            gate_ok = st.rx_bursts >= at_k(wl.dma_gate)
            enabled = dst_es != -1
    else:
        # per-(e, s) desired destination for the *next* transfer
        odd = (st.d_seq % 2) == 1
        dst_es = jnp.where((dma_alt_t >= 0) & odd, dma_alt_t, dma_dst_t)
        dst_es = jnp.where(
            dma_dst_t == -2,
            _uniform_dst(eidx[:, None], st.d_seq * S + jnp.arange(S)[None, :], cycle, n_tiles),
            dst_es,
        ).astype(jnp.int32)
        beats = jnp.full((E, S), wl.dma_beats, jnp.int32)
        gate_ok = jnp.ones((E, S), bool)
        enabled = dma_dst_t != -1
    st_tmp = dataclasses.replace(st, ni_cnt=ni_cnt, ni_dst=ni_dst, rob_credit=rob)
    ok_es = epm._ni_check(st_tmp, txn_of_stream, dst_es, params, beats)
    n_off = wl.n_groups
    if n_off:
        # group-addressed transfers (dst >= E: offloaded multicast in
        # [E, E+G), reduction contributions in [E+G, E+2G)) are posted
        # writes — no response returns, so they bypass the NI/RoB check
        ok_es = ok_es | (dst_es >= E)
    want_es = (st.d_txns_left > 0) & (st.d_outst < params.max_outstanding) & enabled & gate_ok
    elig = want_es & ok_es
    # rotating pick — except under collective offload, where the pick is a
    # static lowest-stream-first priority: in-fabric reduction consumes the
    # streams' bursts beat-aligned per group, so contributors must drain
    # their streams in one globally consistent order or the per-beat child
    # alignment and the shared write serializer close a circular wait
    # (endpoint A's stream-1 burst backpressured behind a reduction waiting
    # on endpoint B's stream-1, which B cannot start before its stream-0
    # burst drains through a tree waiting on A's stream-0)
    rot = (jnp.arange(S)[None, :] - (cycle + eidx[:, None])) % S
    if n_off:
        score = jnp.where(elig, jnp.arange(S)[None, :], S + 1)
    else:
        score = jnp.where(elig, rot, S + 1)
    pick = jnp.argmin(score, axis=1)
    any_pick = jnp.take_along_axis(score, pick[:, None], axis=1)[:, 0] <= S
    stall_d = jnp.any(want_es & ~ok_es, axis=1) & ~any_pick

    pick_dst = dst_es[eidx, pick]
    pick_txn = txn_of_stream[eidx, pick]
    pick_beats = beats[eidx, pick]

    if not wl.dma_write:
        space_r = eg_cnt[CH_REQ] < EQ
        fire_d = any_pick & space_r
        flit_ar = eng.pack_flit(pick_dst, eidx, WIDE_AR, pick_txn, 1, cycle,
                                pick_beats)
        eg, eg_ready, eg_cnt = epm._eg_push(
            eg, eg_ready, st.eg_head, eg_cnt, CH_REQ, fire_d, flit_ar,
            jnp.broadcast_to(cycle + src_delay, (E,)).astype(jnp.int32),
            circular=circ)
        w_stream, w_left, w_beats, w_dst, w_txn, w_ts = (
            st.w_stream, st.w_left, st.w_beats, st.w_dst, st.w_txn, st.w_ts)
    else:
        # claim the write serializer
        fire_d = any_pick & (st.w_stream < 0)
        w_stream = jnp.where(fire_d, pick, st.w_stream)
        w_left = jnp.where(fire_d, pick_beats, st.w_left)
        w_beats = jnp.where(fire_d, pick_beats, st.w_beats)
        w_dst = jnp.where(fire_d, pick_dst, st.w_dst)
        w_txn = jnp.where(fire_d, pick_txn, st.w_txn)
        w_ts = jnp.where(fire_d, jnp.broadcast_to(cycle, (E,)).astype(jnp.int32), st.w_ts)

    d_done = st.d_done
    if n_off:
        # posted group-addressed transfers hold no NI slot and are never
        # outstanding (nothing retires them); they count done at issue
        pick_off = fire_d & (pick_dst >= E)
        fire_ni = fire_d & ~pick_off
        d_done = epm._col_add(d_done, pick, pick_off.astype(jnp.int32), circ)
    else:
        fire_ni = fire_d
    ni_cnt, ni_dst, rob = epm._ni_issue(
        dataclasses.replace(st, ni_cnt=ni_cnt, ni_dst=ni_dst, rob_credit=rob),
        fire_ni, pick_txn, pick_dst, pick_beats, params)
    d_txns_left = epm._col_add(st.d_txns_left, pick,
                               -fire_d.astype(jnp.int32), circ)
    d_outst = epm._col_add(st.d_outst, pick, fire_ni.astype(jnp.int32), circ)
    d_seq = epm._col_add(st.d_seq, pick, fire_d.astype(jnp.int32), circ)

    # ---- write burst serializer: one AW_W beat per cycle ----
    beats_sent = st.beats_sent
    if wl.dma_write:
        active = w_stream >= 0
        if circ and params.n_channels == 3:
            # single wide channel: wide_channel_of is constant, so the
            # serializer push can take _eg_push's static-channel slice path
            wch = CH_WIDE
            space_w = eg_cnt[CH_WIDE] < EQ
        else:
            wch = wide_channel_of(jnp.clip(w_txn, 0, None), params.n_channels)
            space_w = jnp.take_along_axis(eg_cnt, wch[None, :], axis=0)[0] < EQ
        emit = active & space_w
        last = jnp.where(emit, (w_left == 1).astype(jnp.int32), 0)
        # META carries the burst's TOTAL beats so the target can echo it in
        # the B response (exact retirement credit at the issuer)
        if n_off:
            # decode the group-address range at emission: reduction
            # contributions rewrite dst to the group address [E, E+G) the
            # in-fabric ALU emits toward the root; multicast beats keep it
            is_red_w = w_dst >= E + n_off
            kind_w = jnp.where(is_red_w, WIDE_RED,
                               jnp.where(w_dst >= E, WIDE_MC, WIDE_AW_W))
            flit_w = eng.pack_flit(jnp.where(is_red_w, w_dst - n_off, w_dst),
                                   eidx, kind_w, w_txn, last, w_ts, w_beats)
        else:
            flit_w = eng.pack_flit(w_dst, eidx, WIDE_AW_W, w_txn, last, w_ts,
                                   w_beats)
        eg, eg_ready, eg_cnt = epm._eg_push(
            eg, eg_ready, st.eg_head, eg_cnt, wch, emit, flit_w,
            jnp.broadcast_to(cycle + 1, (E,)).astype(jnp.int32),
            circular=circ)
        beats_sent = beats_sent + emit.astype(jnp.int32)
        w_left = jnp.where(emit, w_left - 1, w_left)
        done_w = emit & (w_left == 0)
        w_stream = jnp.where(done_w, -1, w_stream)

    ni_stall = st.ni_stall + stall_n.astype(jnp.int32) + stall_d.astype(jnp.int32)
    return dataclasses.replace(
        st, eg=eg, eg_ready=eg_ready, eg_cnt=eg_cnt, ni_cnt=ni_cnt, ni_dst=ni_dst,
        rob_credit=rob, n_acc=n_acc, n_seq=n_seq, n_sent=n_sent,
        d_txns_left=d_txns_left, d_outst=d_outst, d_seq=d_seq, d_done=d_done,
        w_stream=w_stream, w_left=w_left, w_beats=w_beats, w_dst=w_dst,
        w_txn=w_txn, w_ts=w_ts, beats_sent=beats_sent, ni_stall=ni_stall,
    )


def _uniform_dst(e, seq, cycle, n_tiles):
    h = epm._hash(e, seq, 0)
    other = h % jnp.maximum(n_tiles - 1, 1)
    return ((e + 1 + other) % n_tiles).astype(jnp.int32)


def _memory(st: epm.EndpointState, cycle, params: NocParams, is_hbm, is_mem):
    """Memory server: pop requests, serve after latency, emit response beats."""
    E = st.lat_sum.shape[0]
    circ = params.step_impl == "fast"
    eidx = jnp.arange(E)
    EQ = st.eg_ready.shape[-1]

    hbm_tok = jnp.where(
        is_hbm, jnp.minimum(st.hbm_tok + params.hbm_rate * params.hbm_eff, 8.0),
        jnp.asarray(1.0, jnp.float32))

    m_busy = jnp.maximum(st.m_busy - 1, 0)
    # pop next request when idle
    can_pop = ~st.m_active & (st.mq_cnt > 0) & is_mem
    head, mq, mq_head, mq_cnt = epm._mq_pop(st.mq, st.mq_head, st.mq_cnt,
                                            can_pop, circular=circ)
    m_active = st.m_active | can_pop
    m_busy = jnp.where(can_pop, params.mem_lat + params.ni_rsp_lat, m_busy)
    m_beats = jnp.where(can_pop, head[:, epm.MQ_BEATS], st.m_beats)
    # response template META = the original transfer size (MQ_META), kept
    # constant over the burst so the issuer retires exactly what it issued
    new_flit = eng.pack_flit(head[:, epm.MQ_SRC], eidx, head[:, epm.MQ_KIND],
                             head[:, epm.MQ_TXN], 0, head[:, epm.MQ_TS],
                             head[:, epm.MQ_META])
    m_flit = jnp.where(can_pop[:, None], new_flit, st.m_flit)

    # emit a beat when serving (channel picked per endpoint: wide reads stripe
    # over the wide channels by TxnID, B responses ride rsp)
    is_wide_r = m_flit[:, F_KIND] == WIDE_R
    wch = wide_channel_of(jnp.clip(m_flit[:, F_TXN], 0, None), params.n_channels)
    ch_of_kind = jnp.where(is_wide_r, wch, CH_RSP)
    tok_ok = jnp.where(is_hbm & is_wide_r, hbm_tok >= 1.0, True)
    space = jnp.take_along_axis(st.eg_cnt, ch_of_kind[None, :], axis=0)[0] < EQ
    emit = m_active & (m_busy == 0) & tok_ok & space & (m_beats > 0)
    out = m_flit.at[:, F_LAST].set((m_beats == 1).astype(jnp.int32))
    ready = jnp.broadcast_to(cycle + params.ni_req_lat, (E,)).astype(jnp.int32)

    if circ:
        # fast path: split the dynamic-channel push into its two legs (wide
        # read beats / B responses on CH_RSP) — the masks are disjoint per
        # endpoint so the writes commute, and a static channel lets
        # ``_eg_push`` slice-update instead of one-hot the whole buffer.
        # With the default 3 channels the wide leg is static too.
        wide_ch = CH_WIDE if params.n_channels == 3 else wch
        eg, eg_ready_, eg_cnt = epm._eg_push(
            st.eg, st.eg_ready, st.eg_head, st.eg_cnt, wide_ch,
            emit & is_wide_r, out, ready, circular=True)
        eg, eg_ready_, eg_cnt = epm._eg_push(
            eg, eg_ready_, st.eg_head, eg_cnt, CH_RSP,
            emit & ~is_wide_r, out, ready, circular=True)
    else:
        eg, eg_ready_, eg_cnt = epm._eg_push(st.eg, st.eg_ready, st.eg_head,
                                             st.eg_cnt, ch_of_kind, emit, out,
                                             ready, circular=circ)

    hbm_tok = jnp.where(is_hbm & emit & is_wide_r, hbm_tok - 1.0, hbm_tok)
    hbm_served = st.hbm_served + (emit & is_hbm & is_wide_r).astype(jnp.int32)
    m_beats = jnp.where(emit, m_beats - 1, m_beats)
    m_active = m_active & ~(emit & (m_beats == 0))

    return dataclasses.replace(
        st, mq=mq, mq_head=mq_head, mq_cnt=mq_cnt, m_busy=m_busy,
        m_beats=m_beats, m_flit=m_flit,
        m_active=m_active, hbm_tok=hbm_tok, hbm_served=hbm_served,
        eg=eg, eg_ready=eg_ready_, eg_cnt=eg_cnt,
    )


@dataclass
class Sim:
    """A built simulator: topology + params + workload + derived tables.

    Step with :meth:`step`, or use the module-level ``run`` / ``run_trace``
    / ``run_sweep`` drivers, which share one jit-cached scan body per
    ``(n_cycles, trace)`` key. The router compute backend is selected by
    ``params.backend`` ("jnp" | "pallas", bit-identical).
    """

    topo: Topology
    params: NocParams
    wl: epm.Workload
    tables: eng.FabricTables
    is_hbm: jnp.ndarray
    is_mem: jnp.ndarray
    _jit_cache: dict = field(default_factory=dict, repr=False, compare=False)

    def init_state(self, wl: epm.Workload | None = None) -> SimState:
        """Fresh SimState at cycle 0 (``wl`` overrides the built workload)."""
        wl = self.wl if wl is None else wl
        fabric = eng.init_fabric(self.topo, self.params.depth_in,
                                 self.params.depth_out, self.params.n_channels,
                                 self.params.n_vcs,
                                 n_groups=self.tables.n_groups)
        eps = epm.init_endpoints(self.topo.n_endpoints, self.params, wl.n_streams)
        eps = dataclasses.replace(eps, d_txns_left=jnp.asarray(wl.dma_txns))
        return SimState(fabric=fabric, eps=eps, cycle=jnp.zeros((), jnp.int32))

    def step(self, st: SimState, wl: epm.Workload | None = None):
        """One simulated cycle. Returns (state', (ep_flit [C, E, NF],
        ep_valid [C, E])) — the per-channel endpoint deliveries. ``wl``
        overrides the baked-in workload (sweep engine: traced arrays)."""
        wl = self.wl if wl is None else wl
        fast = self.params.step_impl == "fast"
        cycle = st.cycle
        E = self.topo.n_endpoints
        C = self.params.n_channels
        EQ = st.eps.eg_ready.shape[-1]
        # 1) fabric cycle, all channels at once. Ingest is combinational on
        #    delivery except for one queue: a delivered narrow request pushes
        #    its response into the CH_RSP egress queue, so req-channel
        #    delivery is held (memory-server-style stall into the fabric)
        #    while that queue is full — previously the push silently
        #    overwrote the newest entry, corrupting a flit.
        # Each phase runs in a named scope (noc/README.md, "Profiling a
        # run"): the names reach the compiled program's op metadata, so a
        # device profile splits by layer. Scopes change metadata, not ops.
        with jax.named_scope("noc.router"):
            rsp_free = st.eps.eg_cnt[CH_RSP] < EQ
            space = jnp.ones((C, E), bool).at[CH_REQ].set(rsp_free)
            er, ep_p = self.tables.ep_attach[:, 0], self.tables.ep_attach[:, 1]
            req_waiting = st.fabric.out_cnt[CH_REQ, er, ep_p] > 0
            fabric, ep_flit, ep_valid = eng.fabric_cycle(
                st.fabric, self.tables, space, backend=self.params.backend,
                router_tile=self.params.router_tile, fused_fifo=fast)
        # 2) endpoint processing
        with jax.named_scope("noc.ingest"):
            eps = _ingest(st.eps, ep_flit, ep_valid, cycle, self.params, wl)
            eps = dataclasses.replace(
                eps, eg_overflow=eps.eg_overflow
                + (req_waiting & ~rsp_free).astype(jnp.int32))
        with jax.named_scope("noc.generators"):
            eps = _generators(eps, cycle, self.params, wl, wl.n_tiles)
        with jax.named_scope("noc.memory"):
            eps = _memory(eps, cycle, self.params, self.is_hbm, self.is_mem)
        # 3) egress -> injection: every channel's head whose ready time came
        with jax.named_scope("noc.inject"):
            head, ready_ts = epm._eg_peek(eps.eg, eps.eg_ready, eps.eg_head,
                                          circular=fast)
            ready = (eps.eg_cnt > 0) & (ready_ts <= cycle)  # [C, E]
            fabric, accepted = eng.inject(fabric, self.tables, head, ready,
                                          scatter=fast)
            eg, eg_ready, eg_head, eg_cnt = epm._eg_pop(
                eps.eg, eps.eg_ready, eps.eg_head, eps.eg_cnt, accepted,
                circular=fast)
            eps = dataclasses.replace(eps, eg=eg, eg_ready=eg_ready,
                                      eg_head=eg_head, eg_cnt=eg_cnt)
        return SimState(fabric=fabric, eps=eps, cycle=cycle + 1), (ep_flit, ep_valid)

    def step_super(self, st: SimState, wl: epm.Workload | None = None):
        """One super-step: ``params.fused_cycles`` cycles per fabric call.

        The fabric advances k cycles through ``eng.fabric_cycles_fused``
        (a ``lax.scan`` of the jnp router cycle; ``backend="pallas"`` is
        refused at k>1 by ``NocParams``), recording per-cycle deliveries; the
        endpoint phases then replay those k cycles in order against their
        true cycle numbers, and the final egress injection closes the
        window. Requires ``step_impl="fast"`` (circular egress queues are
        threaded through the fused window).

        A k=1 super-step is bit-identical to :meth:`step`. For k>1 the
        endpoint interaction is quantized to the window: the req-channel
        backpressure mask and delivery gating are sampled at the window
        start and held, and an egress flit *pushed during* the window
        becomes injectable only at the window close (entries already queued
        inject per cycle inside the window, at their exact ready times,
        since every push's ready stamp is >= push-cycle + 1). Use k=1
        whenever exact per-cycle semantics matter; larger k trades that
        fidelity for fewer host round trips. Returns
        ``(state', (ep_flit [k, C, E, NF], ep_valid [k, C, E]))``.
        """
        wl = self.wl if wl is None else wl
        k = self.params.fused_cycles
        if self.params.step_impl != "fast":
            raise ValueError("step_super requires step_impl='fast'")
        cycle = st.cycle
        E = self.topo.n_endpoints
        C = self.params.n_channels
        EQ = st.eps.eg_ready.shape[-1]
        with jax.named_scope("noc.router"):  # scopes as in step
            rsp_free = st.eps.eg_cnt[CH_RSP] < EQ
            space = jnp.ones((C, E), bool).at[CH_REQ].set(rsp_free)
            (fabric, eg, eg_ready, eg_head, eg_cnt, dF, dV, dW) = (
                eng.fabric_cycles_fused(
                    st.fabric, self.tables, space, st.eps.eg, st.eps.eg_ready,
                    st.eps.eg_head, st.eps.eg_cnt, cycle, k))
            eps = dataclasses.replace(st.eps, eg=eg, eg_ready=eg_ready,
                                      eg_head=eg_head, eg_cnt=eg_cnt)
            # [C, k, ...] -> [k, C, ...] for the per-cycle endpoint replay
            dF, dV, dW = (jnp.moveaxis(x, 1, 0) for x in (dF, dV, dW))

        def ep_body(carry, xs):
            """Endpoint phases of one window cycle (ingest/gen/memory)."""
            eps, cyc = carry
            flits, valids, waiting = xs
            with jax.named_scope("noc.ingest"):
                eps = _ingest(eps, flits, valids, cyc, self.params, wl)
                eps = dataclasses.replace(
                    eps, eg_overflow=eps.eg_overflow
                    + (waiting[CH_REQ] & ~rsp_free).astype(jnp.int32))
            with jax.named_scope("noc.generators"):
                eps = _generators(eps, cyc, self.params, wl, wl.n_tiles)
            with jax.named_scope("noc.memory"):
                eps = _memory(eps, cyc, self.params, self.is_hbm, self.is_mem)
            return (eps, cyc + 1), None

        (eps, _), _ = jax.lax.scan(ep_body, (eps, cycle), (dF, dV, dW))

        with jax.named_scope("noc.inject"):
            head, ready_ts = epm._eg_peek(eps.eg, eps.eg_ready, eps.eg_head,
                                          circular=True)
            ready = (eps.eg_cnt > 0) & (ready_ts <= cycle + (k - 1))
            fabric, accepted = eng.inject(fabric, self.tables, head, ready,
                                          scatter=True)
            eg, eg_ready, eg_head, eg_cnt = epm._eg_pop(
                eps.eg, eps.eg_ready, eps.eg_head, eps.eg_cnt, accepted,
                circular=True)
            eps = dataclasses.replace(eps, eg=eg, eg_ready=eg_ready,
                                      eg_head=eg_head, eg_cnt=eg_cnt)
        return SimState(fabric=fabric, eps=eps, cycle=cycle + k), (dF, dV)

    def _scan_fn(self, n_cycles: int, with_trace: bool,
                 fields: tuple = ("deliver",)):
        """One jitted scan over the step body, cached per (length, trace,
        fields). The incoming SimState is consumed — callers must not reuse
        the state they pass in (run()/run_trace() delete its large buffers
        after the scan, see ``_consume_state``)."""
        k = self.params.fused_cycles
        key = (n_cycles, with_trace, fields, k)
        fn = self._jit_cache.get(key)
        if fn is None:
            if n_cycles % max(k, 1):
                raise ValueError(
                    f"n_cycles={n_cycles} not a multiple of "
                    f"fused_cycles={k}")

            @jax.jit
            def scan(st):
                """Scan ``step`` for n_cycles (closure-jitted; its program
                is ``jit_scan`` in a profile)."""
                def body(s, _):
                    """One scan step: advance a (super-)cycle, maybe trace."""
                    if k > 1:
                        s2, deliver = self.step_super(s)
                    else:
                        s2, deliver = self.step(s)
                    if not with_trace:
                        return s2, None
                    return s2, _trace_slice(s2, deliver, fields)

                return jax.lax.scan(body, st, None, length=n_cycles // max(k, 1))

            fn = self._jit_cache[key] = scan
        return fn

    def _sweep_fn(self, n_cycles: int, fields: tuple):
        """One jitted vmapped scan over N workload configs at once: the
        workload arrays become traced inputs instead of baked-in constants,
        so the whole sweep compiles exactly once per batch size. The same
        program splits the batched final state, so one call takes the
        stacked fields in and returns a list of N SimStates, each in
        buffers of its own."""
        key = ("sweep", n_cycles, fields)
        fn = self._jit_cache.get(key)
        if fn is None:
            @jax.jit
            def sweep(batch):
                """Vmapped scan over the batched workload arrays (its
                program is ``jit_sweep`` in a profile)."""
                def one(values):
                    """Scan one workload configuration to its final state."""
                    wl = dataclasses.replace(self.wl, **dict(zip(fields, values)))
                    def body(s, _):
                        """One scan step under the traced workload."""
                        s2, _ = self.step(s, wl)
                        return s2, None
                    s, _ = jax.lax.scan(body, self.init_state(wl), None,
                                        length=n_cycles)
                    return s
                final = jax.vmap(one)(batch)
                n = len(batch[0])
                return [jax.tree.map(lambda x, i=i: x[i], final)
                        for i in range(n)]

            fn = self._jit_cache[key] = sweep
        return fn


def _consume_state(st: SimState) -> None:
    """Free the large buffers of a consumed input SimState.

    ``run``/``run_trace`` consume the state they are given: the scan result
    is a fresh pytree, so the input's big buffers (FIFO contents, memory and
    egress queues) are deleted here to release their memory immediately.
    This intentionally replaces jit donation (``donate_argnums``): declaring
    input/output aliasing on the scan makes XLA's CPU while-loop copy the
    carry every iteration (~25% of the whole step cost at 32x32), while an
    explicit post-call delete frees the same memory without constraining
    the loop. Only buffers the step always rewrites are deleted, so a
    pass-through leaf can never be invalidated.
    """
    for buf in (st.fabric.in_buf, st.fabric.out_buf, st.eps.mq, st.eps.eg,
                st.eps.eg_ready):
        buf.delete()


# selectable per-cycle trace fields for run_trace. The default traces only
# the delivered flits (+ validity): O(T*C*E) — safe at 32x32/64x64 scale.
# "counters" adds small per-cycle occupancy/progress counters; "fabric"
# snapshots the whole FabricState every cycle, which is O(T*C*R*P*D*NF) and
# will exhaust memory on large meshes — opt in deliberately.
TRACE_FIELDS = ("deliver", "counters", "fabric")


def _trace_slice(st: SimState, deliver, fields: tuple):
    """Per-cycle trace pytree for the selected fields (scan-stacked)."""
    out = {}
    for f in fields:
        if f == "deliver":
            out[f] = deliver
        elif f == "counters":
            out[f] = {
                "eg_cnt": st.eps.eg_cnt,
                "mq_cnt": st.eps.mq_cnt,
                "in_flight": st.fabric.in_cnt.sum(axis=(1, 2))
                + st.fabric.out_cnt.sum(axis=(1, 2)),
                "beats_rcvd": st.eps.beats_rcvd,
                "n_sent": st.eps.n_sent,
            }
        else:  # "fabric" (validated in run_trace)
            out[f] = st.fabric
    if fields == ("deliver",):
        return deliver  # back-compat: bare (flits, valid) tuple
    return out


def build_sim(topo: Topology, params: NocParams, wl: epm.Workload,
              groups: list[dict] | None = None) -> Sim:
    """Assemble a Sim: fabric tables + HBM/memory maps for ``topo``.

    ``groups`` (requires ``params.collective_offload``) declares the
    in-fabric collective groups — ``{"root": ep, "members": [...]}`` dicts,
    optionally with ``"reduce": [...]`` contributors — whose multicast fork
    and reduction trees are baked into the fabric tables; group ``g`` is
    then addressed by workloads as destination ``E + g`` (multicast) or
    ``E + G + g`` (reduction contribution).
    """
    E = topo.n_endpoints
    if groups is not None and not params.collective_offload:
        raise ValueError("collective groups require NocParams(collective_offload=True)")
    if wl.n_groups and (groups is None or len(groups) != wl.n_groups):
        raise ValueError(
            f"workload addresses {wl.n_groups} collective group(s) but the "
            f"fabric was built with {0 if groups is None else len(groups)}")
    is_hbm = np.zeros((E,), bool)
    n_hbm = topo.meta.get("n_hbm", 0)
    if n_hbm:
        is_hbm[E - n_hbm :] = True
    is_mem = np.ones((E,), bool)  # every endpoint can serve (tiles: SPM)
    return Sim(
        topo=topo, params=params, wl=wl,
        tables=eng.make_tables(topo, params.n_vcs, groups=groups),
        is_hbm=jnp.asarray(is_hbm), is_mem=jnp.asarray(is_mem),
    )


def run(sim: Sim, n_cycles: int, state: SimState | None = None) -> SimState:
    """Advance ``sim`` by ``n_cycles`` through one jit-compiled scan.

    ``params.fused_cycles`` > 1 advances in fused super-steps (n_cycles
    must be a multiple). The incoming ``state`` is consumed — do not reuse
    it after this call (re-init or use the returned state).
    """
    with _span("noc.run"):
        st = state if state is not None else sim.init_state()
        with _span("noc.run.scan"):
            s, _ = sim._scan_fn(n_cycles, with_trace=False)(st)
        with _span("noc.run.consume"):
            _consume_state(st)
    return s


def run_trace(sim: Sim, n_cycles: int, state: SimState | None = None,
              fields: tuple = ("deliver",)):
    """Like run(), but also returns a per-cycle trace.

    With the default ``fields=("deliver",)`` the trace is the endpoint
    deliveries ``(flits [T, C, E, NF], valid [T, C, E])`` — the only
    per-cycle record that stays affordable at 32x32+ scale. Other
    ``TRACE_FIELDS`` ("counters", "fabric") come back in a dict keyed by
    field name; "fabric" snapshots the full FabricState per cycle and is
    intentionally opt-in (it is what OOMs on big meshes). ``state`` is
    consumed, as in :func:`run`.
    """
    fields = tuple(fields)
    for f in fields:
        if f not in TRACE_FIELDS:
            raise ValueError(
                f"unknown trace field {f!r}; expected one of {TRACE_FIELDS}")
    st = state if state is not None else sim.init_state()
    s, trace = sim._scan_fn(n_cycles, with_trace=True, fields=fields)(st)
    _consume_state(st)
    k = sim.params.fused_cycles
    if k > 1:
        # deliveries come back [T/k, k, C, ...] from the super-step scan;
        # flatten to per-cycle [T, C, ...] ("counters"/"fabric" stay
        # per-super-step: they sample state at window boundaries)
        flat = lambda x: x.reshape((-1,) + x.shape[2:])
        if fields == ("deliver",):
            trace = jax.tree.map(flat, trace)
        elif "deliver" in trace:
            trace["deliver"] = jax.tree.map(flat, trace["deliver"])
    return s, trace


def canonical_state(sim: Sim, st: SimState, scrub: bool = False) -> SimState:
    """SimState with implementation-defined garbage masked out.

    The fast and naive step paths are behaviorally identical but leave
    different garbage where no live data is stored: dead FIFO slots
    (index >= count) after fused vs two-step updates, and rotated vs
    head-at-0 circular queues. This rotates every circular queue to head 0
    and zeroes all dead queue/FIFO slots, so
    ``canonical_state(sim_fast, st_fast) == canonical_state(sim_naive,
    st_naive)`` leaf-for-leaf iff the simulations agree on all live state.

    ``scrub=True`` additionally neutralizes the endpoint scratch registers
    that retain their last burst after going idle (the memory server's
    response template ``m_flit``, the write serializer's ``w_*`` registers,
    and NI destination slots with zero outstanding count). Differential
    harnesses should compare scrubbed states: without the scrub, two
    behaviorally identical runs can compare unequal on a stale tail flit —
    and the workaround of excluding those whole leaves from the comparison
    would let real divergences in their *live* values pass by accident.
    """
    f, eps = st.fabric, st.eps

    def mask_fifo(buf, cnt):
        """Zero slots at or past the FIFO count (buf [..., D, NF])."""
        D = buf.shape[-2]
        live = jnp.arange(D) < cnt[..., None]
        return jnp.where(live[..., None], buf, 0)

    fabric = dataclasses.replace(
        f, in_buf=mask_fifo(f.in_buf, f.in_cnt),
        out_buf=mask_fifo(f.out_buf, f.out_cnt))

    Q = eps.mq.shape[1]
    rot = (eps.mq_head[:, None] + jnp.arange(Q)[None]) % Q  # [E, Q]
    mq = jnp.take_along_axis(eps.mq, rot[..., None], axis=1)
    mq = jnp.where((jnp.arange(Q)[None] < eps.mq_cnt[:, None])[..., None],
                   mq, 0)

    EQ = eps.eg_ready.shape[-1]
    rote = (eps.eg_head[..., None] + jnp.arange(EQ)) % EQ  # [C, E, EQ]
    live = jnp.arange(EQ) < eps.eg_cnt[..., None]
    eg = jnp.where(live[..., None],
                   jnp.take_along_axis(eps.eg, rote[..., None], axis=2), 0)
    eg_ready = jnp.where(live, jnp.take_along_axis(eps.eg_ready, rote, axis=2),
                         0)
    eps = dataclasses.replace(
        eps, mq=mq, mq_head=jnp.zeros_like(eps.mq_head),
        eg=eg, eg_ready=eg_ready, eg_head=jnp.zeros_like(eps.eg_head))
    if scrub:
        w_idle = eps.w_stream < 0
        z = jnp.zeros_like(eps.w_left)
        eps = dataclasses.replace(
            eps,
            m_flit=jnp.where(eps.m_active[:, None], eps.m_flit, 0),
            w_left=jnp.where(w_idle, z, eps.w_left),
            w_beats=jnp.where(w_idle, z, eps.w_beats),
            w_dst=jnp.where(w_idle, z, eps.w_dst),
            w_txn=jnp.where(w_idle, z, eps.w_txn),
            w_ts=jnp.where(w_idle, z, eps.w_ts),
            ni_dst=jnp.where(eps.ni_cnt == 0, -1, eps.ni_dst),
        )
    return SimState(fabric=fabric, eps=eps, cycle=st.cycle)


# workload fields that may vary across a sweep batch (they become traced
# inputs); everything else (dma_write, unique_txn_per_stream, n_tiles,
# stream count, schedule presence/length) is compile-time static and must
# match across the batch.
SWEEP_FIELDS = ("narrow_rate", "narrow_dst", "dma_dst", "dma_alt_dst",
                "dma_txns", "dma_beats", "dma_dst_seq", "dma_gate",
                "dma_beats_seq")


def run_sweep(sim: Sim, wls: list[epm.Workload], n_cycles: int) -> list[SimState]:
    """Run N workload configurations through ONE jit-compiled vmapped scan.

    All workloads must share ``sim.topo`` / ``sim.params`` and every static
    workload attribute (read/write mode, stream count, n_tiles, schedule
    shape); the array-valued fields are batched into traced inputs, so the
    scan body compiles exactly once for the whole sweep instead of once per
    configuration (each ``build_sim`` + ``run`` bakes its workload in as
    constants and recompiles). Returns one final SimState per workload,
    split from the batch inside that same program: a call is one program
    launch, with the fields stacked on the host beforehand.
    """
    ref = sim.wl
    for w in wls:
        if (w.dma_write != ref.dma_write
                or w.unique_txn_per_stream != ref.unique_txn_per_stream
                or w.n_tiles != ref.n_tiles or w.n_streams != ref.n_streams
                or w.n_groups != ref.n_groups):
            raise ValueError("sweep workloads must share static workload attributes")
        # the swept-field list is derived from the REFERENCE workload, so a
        # field the reference leaves unset would be silently dropped for the
        # whole batch (the config would run with the wrong traffic): require
        # presence agreement for every sweepable field, not just the
        # schedule triple
        for f in SWEEP_FIELDS:
            if (getattr(w, f) is None) != (getattr(ref, f) is None):
                raise ValueError(
                    f"sweep workloads must agree on {f} presence (swept "
                    "fields are taken from the reference sim.wl, so a field "
                    "only some workloads set would be silently ignored)")
    fields = tuple(f for f in SWEEP_FIELDS if getattr(ref, f) is not None)
    with _span("noc.sweep"):
        with _span("noc.sweep.stack"):
            batch = tuple(np.stack([np.asarray(getattr(w, f)) for w in wls])
                          for f in fields)
        with _span("noc.sweep.scan"):
            return sim._sweep_fn(n_cycles, fields)(batch)


def stats(sim: Sim, st: SimState) -> dict:
    """Summarize a final SimState: latency, beats, utilization, stalls."""
    eps = st.eps
    cyc = int(st.cycle)
    n_tiles = sim.wl.n_tiles
    lat = np.asarray(eps.lat_sum) / np.maximum(np.asarray(eps.lat_cnt), 1)
    out = {
        "cycles": cyc,
        "narrow_lat_mean": lat[:n_tiles],
        "narrow_lat_cnt": np.asarray(eps.lat_cnt)[:n_tiles],
        "beats_rcvd": np.asarray(eps.beats_rcvd),
        "beats_sent": np.asarray(eps.beats_sent),
        "hbm_served": np.asarray(eps.hbm_served),
        "ni_stalls": np.asarray(eps.ni_stall),
        "eg_overflow": np.asarray(eps.eg_overflow),
        "dma_done": np.asarray(eps.d_done),
        "rx_bursts": np.asarray(eps.rx_bursts),
        "last_rx": np.asarray(eps.last_rx),
        "first_rx": np.asarray(eps.first_rx),
        "mq_max": int(np.asarray(eps.mq_cnt).max()),
        "wide_util": np.asarray(eps.beats_rcvd)[:n_tiles].sum() / max(cyc * n_tiles, 1),
        "hbm_util": (
            np.asarray(eps.hbm_served).sum()
            / max(cyc * max(int(np.asarray(sim.is_hbm).sum()), 1), 1)
            / sim.params.hbm_rate
        ),
    }
    return out
