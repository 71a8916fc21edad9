"""Collectives on the cycle-level fabric: schedule correctness (deadlock
freedom + exactly-once delivery), cycle-accurate runs vs the simulator-
calibrated analytical model (repro.core.collectives.FabricCollectiveModel),
a golden-stats pin, and the vmapped multi-config sweep engine."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.collectives import FabricCollectiveModel
from repro.core.noc import collective_traffic as CT
from repro.core.noc import engine as eng
from repro.core.noc import sim as S
from repro.core.noc import traffic as T
from repro.core.noc.params import WIDE_AW_W, NocParams
from repro.core.noc.topology import build_mesh


# ----------------------------------------------------------------------
# schedule level (no simulator): replay gates, count deliveries
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name,kw", [
    ("all-gather", dict(data_kb=8)),
    ("reduce-scatter", dict(data_kb=8)),
    ("all-reduce", dict(data_kb=8)),
    ("all-reduce", dict(data_kb=8, streams=2)),
    ("all-reduce-2d", dict(data_kb=8)),
    ("multicast", dict(data_kb=2)),
    ("multicast", dict(data_kb=2, streams=4)),
    ("barrier", {}),
])
def test_schedules_deadlock_free_and_exactly_once(name, kw):
    topo = build_mesh(nx=4, ny=4)
    sched = CT.build(topo, name, **kw)
    CT.check_schedule(sched)  # asserts all transfers fire + rx == expect_rx


def test_snake_order_is_hamiltonian_with_unit_hops():
    topo = build_mesh(nx=4, ny=4)
    order = CT.snake_order(topo)
    assert sorted(order.tolist()) == list(range(16))
    hops = CT._ring_hops(topo, order)
    # every edge is a mesh neighbour (2 router traversals) except the wrap
    assert (np.sort(hops)[:-1] == 2).all()
    assert hops[-1] == topo.meta["ny"] - 1 + 1  # wrap runs down column 0


# ----------------------------------------------------------------------
# fabric level
# ----------------------------------------------------------------------
def _run_collective(topo, sched, n_cycles):
    wl = CT.to_workload(topo, sched)
    sim = S.build_sim(topo, NocParams(), wl)
    st = S.run(sim, n_cycles)
    return sim, st, S.stats(sim, st)


def test_ring_all_reduce_delivers_every_chunk_exactly_once():
    """4x4 ring all-reduce: every tile receives exactly 2(N-1) write bursts
    per stream, every one from its ring predecessor, and the fabric drains."""
    topo = build_mesh(nx=4, ny=4)
    sched = CT.build(topo, "all-reduce", data_kb=4)
    wl = CT.to_workload(topo, sched)
    sim = S.build_sim(topo, NocParams(), wl)
    st, (flits, valid) = S.run_trace(sim, 800)
    flits, valid = np.asarray(flits), np.asarray(valid)
    order = sched.meta["order"]
    pred = np.empty_like(order)
    pred[np.roll(order, -1)] = order  # pred[tile] = ring predecessor
    n = topo.meta["n_tiles"]
    tails = valid & (flits[..., eng.F_KIND] == WIDE_AW_W) \
        & (flits[..., eng.F_LAST] > 0)
    for e in range(n):
        t, c = np.nonzero(tails[:, :, e])
        srcs = flits[t, c, e, eng.F_SRC]
        assert len(srcs) == 2 * (n - 1), f"tile {e}: {len(srcs)} bursts"
        assert (srcs == pred[e]).all(), f"tile {e} heard from non-predecessor"
    # exactly-once at counter level too, and nothing left in flight
    np.testing.assert_array_equal(np.asarray(st.eps.rx_bursts), sched.expect_rx)
    assert int(np.asarray(st.eps.d_txns_left).sum()) == 0
    assert int(np.asarray(st.fabric.in_cnt).sum()) == 0
    assert int(np.asarray(st.fabric.out_cnt).sum()) == 0


@pytest.mark.parametrize("name,kw,n_cycles", [
    ("all-gather", dict(data_kb=16), 700),
    ("all-reduce", dict(data_kb=16), 1000),
    ("all-reduce", dict(data_kb=16, streams=2), 800),
    ("all-reduce-2d", dict(data_kb=16), 1200),
    ("barrier", {}, 300),
])
def test_measured_cycles_match_calibrated_model(name, kw, n_cycles):
    """Completion cycle within 15% of the simulator-calibrated analytical
    model on the 4x4 mesh (the ISSUE acceptance bar; most cases are exact)."""
    topo = build_mesh(nx=4, ny=4)
    sched = CT.build(topo, name, **kw)
    _, st, out = _run_collective(topo, sched, n_cycles)
    np.testing.assert_array_equal(out["rx_bursts"], sched.expect_rx)
    meas = CT.measured_cycles(out, topo)
    est = CT.analytical_cycles(sched, NocParams())
    assert abs(est - meas) <= 0.15 * meas, f"{name}: measured {meas} vs model {est}"


def test_ring_all_reduce_golden_stats_pin():
    """Bit-exact pin of a fixed configuration (4x4, 4 kB, 2 streams): guards
    the scheduled-DMA datapath against silent behaviour drift."""
    topo = build_mesh(nx=4, ny=4)
    sched = CT.build(topo, "all-reduce", data_kb=4, streams=2)
    _, st, out = _run_collective(topo, sched, 900)
    nt = topo.meta["n_tiles"]
    assert CT.measured_cycles(out, topo) == 190
    np.testing.assert_array_equal(out["beats_rcvd"][:nt], [120] * 16)
    np.testing.assert_array_equal(out["beats_sent"][:nt], [120] * 16)
    np.testing.assert_array_equal(
        out["last_rx"][:nt],
        [190, 190, 190, 190, 190, 190, 190, 190, 190, 190, 190, 190,
         186, 186, 190, 190])
    np.testing.assert_array_equal(
        out["first_rx"][:nt],
        [9, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5])
    assert out["ni_stalls"][:nt].sum() == 0
    assert int(out["rx_bursts"].sum()) == 960


def test_2d_all_reduce_respects_dimension_order():
    """Trace-level check of the gate semantics for the 2-D schedule: at
    every tile the whole row phase (bursts from the row predecessor) is
    delivered before the first column burst arrives, so the receive-count
    gates coincide with the true dimension-ordered dependencies."""
    topo = build_mesh(nx=4, ny=4)
    sched = CT.build(topo, "all-reduce-2d", data_kb=8)
    sim = S.build_sim(topo, NocParams(), CT.to_workload(topo, sched))
    st, (flits, valid) = S.run_trace(sim, 1200)
    flits, valid = np.asarray(flits), np.asarray(valid)
    nx, ny = topo.meta["nx"], topo.meta["ny"]
    tails = valid & (flits[..., eng.F_KIND] == WIDE_AW_W) \
        & (flits[..., eng.F_LAST] > 0)
    for e in range(topo.meta["n_tiles"]):
        x, y = e % nx, e // nx
        row_pred = y * nx + (x - 1) % nx
        col_pred = ((y - 1) % ny) * nx + x
        t, c = np.nonzero(tails[:, :, e])
        src = flits[t, c, e, eng.F_SRC]
        row_t, col_t = t[src == row_pred], t[src == col_pred]
        assert len(row_t) == sched.meta["k_row"]
        assert len(col_t) == sched.meta["k_col"]
        assert row_t.max() < col_t.min(), \
            f"tile {e}: column burst delivered before its row phase finished"


def test_multicast_multistream_removes_rt_serialization():
    """One stream: the RoB-less NI serializes destination changes over full
    round trips. Four TxnIDs pipeline them (paper Sec. III/IV)."""
    topo = build_mesh(nx=4, ny=4)
    done = {}
    for streams in (1, 4):
        sched = CT.build(topo, "multicast", data_kb=2, streams=streams)
        _, st, out = _run_collective(topo, sched, 1500)
        np.testing.assert_array_equal(out["rx_bursts"], sched.expect_rx)
        done[streams] = CT.measured_cycles(out, topo)
    assert done[4] < done[1], done


# ----------------------------------------------------------------------
# analytical model units
# ----------------------------------------------------------------------
def test_model_terms_from_params():
    m = FabricCollectiveModel.from_noc_params(NocParams())
    assert m.hop_cycles == 2.0  # per router traversal: in-buf + out-buf stage
    # latency-bound edge: beats + 2/router; serializer-bound: streams * beats
    assert m.edge_cycles(beats=8, hops=2) == 8 + 4
    assert m.edge_cycles(beats=8, hops=2, streams=4) == 32


def test_analytical_scales_with_mesh_and_streams():
    p = NocParams()
    t44, t48 = build_mesh(nx=4, ny=4), build_mesh(nx=4, ny=8)
    e44 = CT.analytical_cycles(CT.build(t44, "all-reduce", data_kb=16), p)
    e48 = CT.analytical_cycles(CT.build(t48, "all-reduce", data_kb=16), p)
    assert e48 > e44  # more steps, longer ring
    s1 = CT.analytical_cycles(CT.build(t44, "all-reduce", data_kb=16), p)
    s2 = CT.analytical_cycles(CT.build(t44, "all-reduce", data_kb=16, streams=2), p)
    assert s2 < s1  # chunk parallelism wins while latency-bound


# ----------------------------------------------------------------------
# vmapped sweep engine
# ----------------------------------------------------------------------
def test_run_sweep_matches_sequential_runs():
    """The sweep engine is a pure batching transform: per-config results are
    bit-identical to building and running each Sim separately."""
    topo = build_mesh(nx=4, ny=2)
    params = NocParams()
    wls = [T.dma_workload(topo, p, transfer_kb=1, n_txns=2)
           for p in ("uniform", "neighbor", "bit-complement")]
    sim0 = S.build_sim(topo, params, wls[0])
    swept = S.run_sweep(sim0, wls, 400)
    assert len(swept) == len(wls)
    for wl, st in zip(wls, swept):
        sim = S.build_sim(topo, params, wl)
        ref = S.stats(sim, S.run(sim, 400))
        got = S.stats(sim0, st)
        for k in ("beats_rcvd", "dma_done", "last_rx", "first_rx",
                  "ni_stalls", "narrow_lat_cnt"):
            np.testing.assert_array_equal(got[k], ref[k], err_msg=k)


def test_run_sweep_compiles_once():
    topo = build_mesh(nx=4, ny=2)
    wls = [T.dma_workload(topo, p, transfer_kb=1, n_txns=2)
           for p in ("uniform", "neighbor")]
    sim = S.build_sim(topo, NocParams(), wls[0])
    S.run_sweep(sim, wls, 50)
    keys = [k for k in sim._jit_cache if k[0] == "sweep"]
    assert len(keys) == 1
    # same shape signature => cache hit, still one entry
    S.run_sweep(sim, list(reversed(wls)), 50)
    assert len([k for k in sim._jit_cache if k[0] == "sweep"]) == 1


def test_run_sweep_states_own_their_buffers():
    """Each returned state owns its buffers: continuing one fabric, which
    deletes the consumed state's large buffers, leaves every other fabric
    whole, even one with the same values (fabrics 0 and 2 here)."""
    topo = build_mesh(nx=4, ny=2)
    wls = [T.dma_workload(topo, p, transfer_kb=1, n_txns=2)
           for p in ("uniform", "neighbor", "uniform")]
    sim = S.build_sim(topo, NocParams(), wls[0])
    finals = S.run_sweep(sim, wls, 200)
    S.run(sim, 100, finals[0])
    assert finals[0].fabric.in_buf.is_deleted()
    fresh = S.run_sweep(sim, wls, 200)
    for k in (1, 2):
        for got, want in zip(jax.tree.leaves(finals[k]), jax.tree.leaves(fresh[k])):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_run_sweep_rejects_static_mismatch():
    topo = build_mesh(nx=4, ny=2)
    r = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2)
    w = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2, write=True)
    sim = S.build_sim(topo, NocParams(), r)
    with pytest.raises(ValueError):
        S.run_sweep(sim, [r, w], 50)
    sched = CT.build(topo, "barrier")
    with pytest.raises(ValueError):
        S.run_sweep(sim, [r, dataclasses.replace(
            CT.to_workload(topo, sched), dma_write=False)], 50)


def test_sweep_batches_collective_schedules():
    """Shape-compatible collective schedules sweep through one compile and
    reproduce the calibrated cycle counts."""
    topo = build_mesh(nx=4, ny=2)
    params = NocParams()
    scheds = [CT.build(topo, "all-gather", data_kb=kb) for kb in (2, 4)]
    wls = [CT.to_workload(topo, sc) for sc in scheds]
    sim = S.build_sim(topo, params, wls[0])
    for sc, st in zip(scheds, S.run_sweep(sim, wls, 500)):
        out = S.stats(sim, st)
        np.testing.assert_array_equal(out["rx_bursts"], sc.expect_rx)
        meas = CT.measured_cycles(out, topo)
        est = CT.analytical_cycles(sc, params)
        assert abs(est - meas) <= 0.15 * meas
