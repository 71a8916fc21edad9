"""Plain reference of the direct-rotation all-to-all: scheduled write DMA.

``bench/reference/sim.py`` models read DMA only. This module adds what an
all-to-all drives, over that module's router, FIFOs, dateline tables,
ingest, memory server and injection, which it imports unchanged:

- the schedule: a frozen copy of the direct rotation (:func:`schedule`).
  Ring position ``i`` of ``order`` sends its ``k``-th chunk to position
  ``i + k + 1`` (mod n), gated on ``k`` complete write bursts received on
  the same stream;
- scheduled DMA: each stream's next destination, burst length and receive
  gate are looked up at its issue index;
- write DMA: a picked transfer claims the endpoint's one write serializer,
  which emits one AW+W beat per cycle into the wide channel's egress queue
  (ready the next cycle); the target queues one B response per burst tail,
  which the memory server returns on the rsp channel;
- the RoB-less NI: a stream whose next step goes to another destination
  waits while its TxnID has writes outstanding (the retarget stall), and
  counts a stall cycle when no other stream can issue.

It follows the simulator's ``step_impl="naive"`` semantics as they stood
when the cell was defined. Departures from the program, none of which the
cell's traffic reaches: the schedule is the only traffic, so there is no
narrow generator (the program's is inert at rate 0); no read DMA, RoB
ordering, HBM endpoint or in-fabric collective (group-addressed)
destination; the schedule is the direct rotation only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import sim as RS

BEAT_BYTES = 64  # one 512-bit wide beat


def schedule(order, n_endpoints: int, streams: int, data_kb: float) -> dict:
    """The direct-rotation all-to-all over the tiles of ``order``:
    ``dst_seq``, ``gate`` and ``beats_seq`` [E, S, K] (-1 / 0 where no step),
    ``txns`` and ``expect_rx`` [E, S]."""
    order = np.asarray(order, np.int32)
    n, E, S = len(order), n_endpoints, streams
    K = max(n - 1, 0)
    beats = max(int(np.ceil(data_kb * 1024 / BEAT_BYTES / (n * S))), 1)
    dst = np.full((E, S, max(K, 1)), -1, np.int32)
    gate = np.zeros((E, S, max(K, 1)), np.int32)
    bts = np.zeros((E, S, max(K, 1)), np.int32)
    txns = np.zeros((E, S), np.int32)
    for i, tile in enumerate(order):
        for k in range(K):
            dst[tile, :, k] = order[(i + 1 + k) % n]
            gate[tile, :, k] = k
            bts[tile, :, k] = beats
        txns[tile] = K
    return dict(dst_seq=dst, gate=gate, beats_seq=bts, txns=txns,
                expect_rx=txns.copy())


def generate(s: dict, sch: dict, cycle, C: int):
    """Scheduled write DMA: one stream per endpoint claims the write
    serializer, which then emits one beat per cycle into wide egress."""
    E, S = s["d_outst"].shape
    e = jnp.arange(E)
    Q = s["eg_ready"].shape[-1]
    K = sch["dst_seq"].shape[-1]
    txn_s = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None] % RS.N_TXN_IDS, (E, S))
    k = jnp.clip(s["d_seq"], 0, K - 1)[..., None]
    at_k = lambda a: jnp.take_along_axis(a, k, axis=2)[..., 0]
    dst_s = at_k(sch["dst_seq"])
    beats = at_k(sch["beats_seq"])
    gate_ok = s["rx_bursts"] >= at_k(sch["gate"])
    ok = RS.ni_ok(s["ni_cnt"], s["ni_dst"], txn_s, dst_s)
    want = ((s["d_txns_left"] > 0) & (s["d_outst"] < RS.MAX_OUTSTANDING)
            & (dst_s != -1) & gate_ok)
    rot = (jnp.arange(S)[None] - (cycle + e[:, None])) % S  # rotating priority
    score = jnp.where(want & ok, rot, S + 1)
    pick = jnp.argmin(score, axis=1)
    any_pick = jnp.min(score, axis=1) <= S
    stall_d = jnp.any(want & ~ok, axis=1) & ~any_pick
    p_dst, p_txn, p_beats = dst_s[e, pick], txn_s[e, pick], beats[e, pick]
    fire = any_pick & (s["w_stream"] < 0)  # the serializer is free
    c = jnp.broadcast_to(cycle, (E,)).astype(jnp.int32)
    for key, v in (("w_stream", pick), ("w_left", p_beats), ("w_beats", p_beats),
                   ("w_dst", p_dst), ("w_txn", p_txn), ("w_ts", c)):
        s[key] = jnp.where(fire, v, s[key]).astype(jnp.int32)
    s["ni_cnt"], s["ni_dst"] = RS.ni_issue(s["ni_cnt"], s["ni_dst"], fire, p_txn, p_dst)
    n = fire.astype(jnp.int32)
    s["d_txns_left"] = RS.col_add(s["d_txns_left"], pick, -n)
    s["d_outst"] = RS.col_add(s["d_outst"], pick, n)
    s["d_seq"] = RS.col_add(s["d_seq"], pick, n)

    # the serializer: one AW+W beat per cycle while the egress queue has room
    ch = RS.CH_WIDE + jnp.clip(s["w_txn"], 0, None) % (C - RS.CH_WIDE)
    space = jnp.take_along_axis(s["eg_cnt"], ch[None], axis=0)[0] < Q
    emit = (s["w_stream"] >= 0) & space
    flit = RS.pack(s["w_dst"], e, RS.WIDE_AW_W, s["w_txn"],
                   (s["w_left"] == 1).astype(jnp.int32), s["w_ts"], s["w_beats"])
    s["eg"], s["eg_ready"], s["eg_cnt"] = RS.eg_push(
        s["eg"], s["eg_ready"], s["eg_cnt"], ch, emit, flit, c + 1)
    s["beats_sent"] = s["beats_sent"] + emit.astype(jnp.int32)
    s["w_left"] = jnp.where(emit, s["w_left"] - 1, s["w_left"])
    s["w_stream"] = jnp.where(emit & (s["w_left"] == 0), -1, s["w_stream"])
    s["ni_stall"] = s["ni_stall"] + stall_d.astype(jnp.int32)
    return s


def step(state: dict, t: dict, sch: dict, is_hbm, V: int):
    """One simulated cycle: ``sim.step`` with the scheduled write
    generator in place of the read generator."""
    cycle = state["cycle"]
    fab = {k[7:]: v for k, v in state.items() if k.startswith("fabric.")}
    s = {k[4:]: v for k, v in state.items() if k.startswith("eps.")}
    C, E = s["eg_cnt"].shape
    Q = s["eg_ready"].shape[-1]
    er, ep_p = t["ep_attach"][:, 0], t["ep_attach"][:, 1]
    rsp_free = s["eg_cnt"][RS.CH_RSP] < Q
    space = jnp.ones((C, E), bool).at[RS.CH_REQ].set(rsp_free)
    waiting = fab["out_cnt"][RS.CH_REQ][er, ep_p] > 0
    fab, ep_flit, ep_valid = jax.vmap(
        functools.partial(RS.router_cycle, V=V), in_axes=(0, None, 0))(fab, t, space)
    s = RS.ingest(s, ep_flit, ep_valid, cycle)
    s["eg_overflow"] = s["eg_overflow"] + (waiting & ~rsp_free).astype(jnp.int32)
    s = generate(s, sch, cycle, C)
    s = RS.memory(s, cycle, is_hbm, C)
    ready = (s["eg_cnt"] > 0) & (s["eg_ready"][:, :, 0] <= cycle)
    fab, accepted = jax.vmap(RS.inject, in_axes=(0, None, 0, 0))(
        fab, t, s["eg"][:, :, 0], ready)
    s["eg"] = jnp.where(accepted[..., None, None], jnp.roll(s["eg"], -1, axis=2), s["eg"])
    s["eg_ready"] = jnp.where(accepted[..., None], jnp.roll(s["eg_ready"], -1, axis=2),
                              s["eg_ready"])
    s["eg_cnt"] = s["eg_cnt"] - accepted.astype(jnp.int32)
    out = {f"fabric.{k}": v for k, v in fab.items()}
    out.update({f"eps.{k}": v for k, v in s.items()})
    out["cycle"] = cycle + 1
    return out


class Reference(RS.Reference):
    """A fabric, its channel and VC counts and a :func:`schedule`,
    simulated plainly (``init_state`` as in ``sim.Reference``)."""

    def __init__(self, fab, n_channels: int, n_vcs: int, sch: dict):
        if fab.n_hbm:
            raise NotImplementedError("the all-to-all reference has no HBM endpoints")
        self.fab, self.C, self.V = fab, n_channels, n_vcs
        self.t = {k: jnp.asarray(v) for k, v in RS.tables(fab, n_vcs).items()}
        E, S = sch["txns"].shape
        self.sch = {k: jnp.asarray(sch[k]) for k in ("dst_seq", "gate", "beats_seq")}
        self.wl = {"dma_dst": np.zeros((E, S), np.int32), "dma_txns": sch["txns"]}
        self.is_hbm = jnp.zeros((E,), bool)

    def run(self, state: dict, n_cycles: int) -> dict:
        """``state`` advanced by ``n_cycles``, as numpy arrays."""
        out = _scan(self.V, n_cycles)({k: jnp.asarray(v) for k, v in state.items()},
                                      self.t, self.sch, self.is_hbm)
        return {k: np.asarray(v) for k, v in out.items()}


@functools.lru_cache(maxsize=None)
def _scan(V: int, n_cycles: int):
    @jax.jit
    def run(state, t, sch, is_hbm):
        body = lambda s, _: (step(s, t, sch, is_hbm, V), None)
        return jax.lax.scan(body, state, None, length=n_cycles)[0]
    return run
