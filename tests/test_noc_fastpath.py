"""Fast-path semantics: step_impl fast-vs-naive equivalence (canonical
states), fused multi-cycle super-steps (k=1 bitwise, k>1 drain), input
state consumption by the jitted scan, the run_trace field filter, and the
new NocParams knob validation.

The fast path (circular queues, fused FIFO updates, scattered injection)
is identical to the naive roll-based reference on every live queue slot
but leaves different garbage in dead slots; sim.canonical_state rotates
circular queues to head 0 and zeroes dead slots so equality stays a
strict bitwise check.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.noc import collective_traffic as CT
from repro.core.noc import sim as S
from repro.core.noc import traffic as T
from repro.core.noc.params import NocParams
from repro.core.noc.topology import build_topology


def _assert_states_equal(a, b, tag=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=tag)


def _sim(params=None, write=True, name="torus", **kw):
    topo = build_topology(name, **(kw or dict(nx=4, ny=2)))
    wl = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2,
                        write=write)
    return S.build_sim(topo, params or NocParams(), wl)


@pytest.mark.parametrize("name,kw", [
    ("mesh", dict(nx=4, ny=2)),
    ("torus", dict(nx=4, ny=2)),
    ("multi_die", dict(n_dies=2, nx=2, ny=2, d2d=2)),
    ("mesh", dict(nx=8, ny=4)),  # the paper's 8x4 mesh
])
def test_fast_matches_naive_canonical(name, kw):
    """step_impl='fast' and 'naive' agree on the canonical SimState (live
    queue contents, counters, stats) across the zoo."""
    simf = _sim(NocParams(step_impl="fast"), name=name, **kw)
    simn = _sim(NocParams(step_impl="naive"), name=name, **kw)
    stf = S.run(simf, 300)
    stn = S.run(simn, 300)
    _assert_states_equal(S.canonical_state(simf, stf),
                         S.canonical_state(simn, stn), f"{name} fast/naive")
    outf, outn = S.stats(simf, stf), S.stats(simn, stn)
    for k in outf:
        np.testing.assert_array_equal(np.asarray(outf[k]),
                                      np.asarray(outn[k]), err_msg=k)


def test_fused_k1_bitwise_equals_per_cycle():
    """A 1-cycle super-step is bit-identical to plain per-cycle stepping
    (same SimState leaf-for-leaf, no canonicalization needed)."""
    st1 = S.run(_sim(), 200)
    stk = S.run(_sim(NocParams(fused_cycles=1)), 200)
    # fused_cycles=1 routes through step_super when forced; run() uses
    # plain step at k=1, so drive step_super directly too.
    simk = _sim(NocParams(fused_cycles=1))
    st = simk.init_state()
    step = jax.jit(simk.step_super)
    for _ in range(200):
        st, _ = step(st)
    _assert_states_equal(st1, stk, "k=1 via run")
    _assert_states_equal(st1, st, "k=1 via step_super")


def test_fused_k4_drains_same_traffic():
    """k=4 super-steps deliver the same traffic to completion: identical
    beats received, txns retired, and memory counters after full drain."""
    sim1, sim4 = _sim(), _sim(NocParams(fused_cycles=4))
    st1, st4 = S.run(sim1, 2000), S.run(sim4, 2000)
    np.testing.assert_array_equal(np.asarray(st1.eps.beats_rcvd),
                                  np.asarray(st4.eps.beats_rcvd))
    np.testing.assert_array_equal(np.asarray(st1.eps.rx_bursts),
                                  np.asarray(st4.eps.rx_bursts))
    assert int(np.asarray(st4.eps.d_txns_left).sum()) == 0
    assert int(np.asarray(st4.eps.mq_cnt).sum()) == 0


def test_fused_collective_replay_drains():
    """A gated ring all-reduce completes under k=4 super-steps with the
    exact same delivered-flit multiset per endpoint."""
    topo = build_topology("torus", nx=4, ny=2)
    sched = CT.build(topo, "all-reduce", data_kb=1)
    wl = CT.to_workload(topo, sched)
    st4 = S.run(S.build_sim(topo, NocParams(fused_cycles=4), wl), 500)
    np.testing.assert_array_equal(np.asarray(st4.eps.rx_bursts),
                                  sched.expect_rx)
    assert int(np.asarray(st4.eps.d_txns_left).sum()) == 0


def test_run_consumes_state_buffers():
    """run() consumes its SimState argument: the caller's input buffers
    are deleted after the scan (no second fabric-sized copy stays live).
    Done by explicit post-scan deletion, not donate_argnums — aliasing the
    scan carry makes XLA CPU copy it every iteration."""
    sim = _sim()
    st0 = sim.init_state()
    st0 = jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x, st0)
    _ = S.run(sim, 50, state=st0)
    assert st0.fabric.in_buf.is_deleted()
    assert st0.eps.mq.is_deleted()


def test_run_trace_field_filter():
    """fields=('deliver',) keeps the legacy (flits, valid) tuple;
    'counters' adds per-cycle occupancy/progress series; k>1 traces
    flatten back to one entry per simulated cycle."""
    sim = _sim()
    st, (f1, v1) = S.run_trace(sim, 100)
    st2, tr = S.run_trace(_sim(NocParams()), 100,
                          fields=("deliver", "counters"))
    f2, v2 = tr["deliver"]
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    for key in ("eg_cnt", "mq_cnt", "in_flight", "beats_rcvd", "n_sent"):
        assert tr["counters"][key].shape[0] == 100
    # fused trace flattens [T/k, k, ...] -> [T, ...] and delivers the
    # same beats overall
    st4, (f4, v4) = S.run_trace(_sim(NocParams(fused_cycles=4)), 100)
    assert f4.shape == f1.shape and v4.shape == v1.shape
    with pytest.raises(ValueError):
        S.run_trace(sim, 100, fields=("deliver", "nope"))


def test_params_validation():
    with pytest.raises(ValueError):
        NocParams(step_impl="fancy")
    with pytest.raises(ValueError):
        NocParams(router_tile=-1)
    with pytest.raises(ValueError):
        NocParams(fused_cycles=0)
    # run length must tile into super-steps
    with pytest.raises(ValueError):
        S.run(_sim(NocParams(fused_cycles=4)), 101)


def test_canonical_state_idempotent_preserves_live():
    """Guards the normalizer itself: canonicalizing twice is a no-op (heads
    land at 0, dead slots at 0) and live state — counters, queue counts,
    cycle — is untouched, on both step implementations. (Both paths leave
    garbage in dead slots: the naive roll-based pops shift stale flits into
    the tail slot rather than zero-filling, so canonicalization is *not* an
    identity on either impl.)"""
    for impl in ("fast", "naive"):
        sim = _sim(NocParams(step_impl=impl))
        st = S.run(sim, 150)
        c1 = S.canonical_state(sim, st)
        c2 = S.canonical_state(sim, c1)
        _assert_states_equal(c1, c2, f"{impl} idempotent")
        for name in ("beats_rcvd", "rx_bursts", "mq_cnt", "eg_cnt",
                     "d_txns_left"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st.eps, name)),
                np.asarray(getattr(c1.eps, name)), err_msg=f"{impl} {name}")
        np.testing.assert_array_equal(np.asarray(st.fabric.in_cnt),
                                      np.asarray(c1.fabric.in_cnt))
        assert int(np.asarray(c1.cycle)) == int(np.asarray(st.cycle))
