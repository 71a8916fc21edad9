"""CPU tests of the ``a2a`` traffic kind at a small size (4x4 torus, 16 kB
per tile): the program equals the plain reference ``bench/reference/a2a.py``
exactly, the planted faults and the VC-less control read ``correct``
false, and the harness finds the kind as a file.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_a2a.py
"""
from __future__ import annotations

import time

import pytest

from bench.kinds import a2a as kind
from bench.lib import check, harness
from bench.reference import a2a as RA
from bench.reference import sim as RS
from bench.reference import topology as RT
from bench.tests.a2a_control import CELL, loaded, planted

SEEDS = [2**31 + 12345, 2**31 + 777]
SMALL = {"data_kb": 16, "cycles_per_call": 1000}
# an order whose schedule wedges the VC-less torus at this size
DEADLOCK_SEED = 2**31 + 3
# the slowest orders found at the cell's own size (last payload beat at
# cycle 5,004 and 5,312 of 6,401 seeded orders)
SLOW_SEEDS = [12066292, 788167051]


def small(n_vcs: int | None = None) -> tuple:
    return loaded(n_vcs, **SMALL)


def run(files: tuple, seed: int, trace: bool = False) -> dict:
    return harness.run_cell(CELL, seed, 1.0, trace, t_start=time.perf_counter(),
                            loaded=files, require_tpu=False)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_equals_reference(seed):
    """The cell's check at a small size: every number reads 0."""
    out = run(small(), seed)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["unchecked"]["value"] == 0
    assert set(out["metrics"]) == {"fabric_cycles_per_s", "setup_s"}


def test_reference_matches_a_run_in_flight():
    """Mid-exchange, with bursts on the wires and gates closed, the
    program's state still equals the reference's leaf by leaf."""
    from repro.core.noc import collective_traffic as CT
    from repro.core.noc import sim as S
    from repro.core.noc.spec import FabricSpec

    config = harness.load_cell(CELL)[2]
    topo, params = FabricSpec(**config["fabric"]).lower()
    fab = RT.build(config["fabric"])
    order = kind.rotation_order(SEEDS[1], fab.n_tiles)
    sched = CT.all_to_all(topo, data_kb=16, streams=2, order=order, algo="direct",
                          n_vcs=params.n_vcs)
    sim = S.build_sim(topo, params, CT.to_workload(topo, sched))
    st = S.run(sim, 300)
    ref = RA.Reference(fab, params.n_channels, params.n_vcs,
                       RA.schedule(order, fab.n_endpoints, 2, 16))
    out = ref.run(ref.init_state(), 300)
    assert check.state_mismatch(check.flat_state(st), out) == 0
    assert check.stats_mismatch(S.stats(sim, st), RS.stats(out, fab.n_tiles, 0)) == 0
    assert 0 < int(out["eps.rx_bursts"].sum()) < int(sched.expect_rx.sum())
    assert int(out["eps.ni_stall"].sum()) > 0  # retargets waited on B responses


@pytest.mark.parametrize("fault,reads", [
    ("gate", ("schedule_mismatch", "state_mismatch")),
    ("burst", ("schedule_mismatch", "state_mismatch")),
    ("half", ("cycle_gap", "incomplete", "state_mismatch")),
])
def test_planted_fault_is_caught(fault, reads):
    with planted(fault):
        out = run(small(), SEEDS[0])
    assert not out["correct"], out["checks"]
    assert out["failed"] <= out["attempted"]
    for k in reads:
        assert out["checks"][k]["value"] > 0, (k, out["checks"])


def test_failed_counts_the_window_calls_only():
    """Where every call falls short, the traced call included, ``failed``
    is the window's calls and no more; the traced call still reads in the
    checks."""
    with planted("half"):
        out = run(small(), SEEDS[1], trace=True)
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"] > 0
    assert out["checks"]["cycle_gap"]["value"] == 500 * (out["attempted"] + 1)


def test_vcless_control_is_not_correct():
    """The same schedule on the torus without the dateline VCs wedges:
    the reference wedges alike, so the receipts give it away."""
    out = run(small(n_vcs=1), DEADLOCK_SEED)
    assert not out["correct"], out["checks"]
    assert out["checks"]["incomplete"]["value"] > 0
    assert out["failed"] > 0


def test_traced_run_is_correct():
    """Off the chip the trace holds no device op: the per-layer metrics
    are left out rather than read as 0."""
    out = run(small(), SEEDS[1], trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"] == {}


def test_slowest_orders_complete_within_a_call():
    """At the cell's own size, the slowest orders found still deliver every
    burst before the call's last cycle."""
    import numpy as np

    from repro.core.noc import collective_traffic as CT
    from repro.core.noc import sim as S
    from repro.core.noc.spec import FabricSpec

    _, _, config, traffic = harness.load_cell(CELL)
    topo, params = FabricSpec(**config["fabric"]).lower()
    scheds = [CT.all_to_all(topo, data_kb=traffic["data_kb"], streams=traffic["streams"],
                            order=kind.rotation_order(seed, topo.n_endpoints),
                            algo=traffic["algo"], n_vcs=params.n_vcs)
              for seed in SLOW_SEEDS]
    wls = [CT.to_workload(topo, sc) for sc in scheds]
    sts = S.run_sweep(S.build_sim(topo, params, wls[0]), wls, traffic["cycles_per_call"])
    for sc, st in zip(scheds, sts):
        assert np.array_equal(np.asarray(st.eps.rx_bursts), sc.expect_rx)
        assert 5000 < int(np.asarray(st.eps.last_rx).max()) < traffic["cycles_per_call"]


def test_kind_is_found_as_a_file():
    cls = harness.driver_class("a2a")
    assert cls.__name__ == "Driver" and cls.__module__ == "bench_kind_a2a"


def test_nested_scopes_see_through_transforms():
    name = "jit(scan)/while/body/closed_call/noc.router/vmap(noc.vc)/eq"
    assert kind.nested_scopes(name) == {"noc.router", "noc.vc"}
    assert kind.nested_scopes("jit(scan)/while/body/add") == set()


def test_scope_split_of_a_synthetic_trace():
    """Nested scopes and layers from a trace laid out as the chip's: the
    first op to cover an instant takes it, ops of other programs and
    outside the window count nowhere."""
    from types import SimpleNamespace as NS

    ev = lambda name, s, d: NS(name=name, start_ns=s, duration_ns=d)
    host = NS(name="/host:CPU", lines=[NS(name="py", events=[ev("bench.window", 100, 1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_scan(1)", 100, 800), ev("jit_other(2)", 950, 100)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = s32[4] fusion(%a)", 100, 200),  # noc.vc
            ev("%fusion.2 = s32[4] fusion(%b)", 250, 100),  # noc.sched, 50 ns overlapped
            ev("%fusion.3 = s32[4] fusion(%c)", 400, 300),  # noc.router only
            ev("%fusion.4 = s32[4] fusion(%d)", 960, 50),  # another program
        ])])
    ops = {"fusion.1": "jit(scan)/while/body/noc.router/vmap(noc.vc)/gather",
           "fusion.2": "jit(scan)/while/body/noc.generators/noc.sched/gather",
           "fusion.3": "jit(scan)/while/body/noc.router/select"}
    got = kind.scope_split(NS(planes=[host, dev]), "jit_scan", ops)
    want = {"noc.vc": 200e-9, "noc.sched": 50e-9, "noc.router": 500e-9,
            "noc.generators": 50e-9}
    assert got.keys() == want.keys()
    assert all(abs(got[k] - v) < 1e-15 for k, v in want.items())
    assert kind.scope_split(NS(planes=[host, dev]), "jit_scan",
                            {"fusion.3": ops["fusion.3"]}) == {}
