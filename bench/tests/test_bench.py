"""CPU tests of the benchmark: the reference against the program, every
traffic kind end to end at a small size, the controls and planted faults
that ``correct`` has to catch, and the trace reduction.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.lib import check, gen, trace
from bench.reference import sim as RS
from bench.reference import topology as RT
from bench.tests import cells
from bench.lib.harness import ROOT

SEED = 2**31 + 12345  # seeds are larger than 32 signed bits


@pytest.mark.parametrize("fabric", [
    {"topology": "mesh", "nx": 4, "ny": 4, "n_channels": 4, "express": 2},
    {"topology": "torus", "nx": 4, "ny": 4, "n_vcs": 2},
    {"topology": "multi_die", "n_dies": 2, "nx": 2, "ny": 4},
])
def test_reference_matches_program(fabric):
    """Fresh and mid-run (teacher-forced) states of the program's default
    step equal the reference's, leaf by leaf, with narrow and DMA load."""
    from repro.core.noc import sim as S
    from repro.core.noc.spec import FabricSpec

    from bench.lib.drivers import program_workload

    fab = RT.build(fabric)
    w = gen.run_workload(fab, {"pattern_seed": SEED, "streams": 2, "burst_kb": 1,
                               "txns_per_stream": 64, "narrow_rate": 0.05,
                               "narrow_dst": "uniform"})
    topo, params = FabricSpec(**fabric).lower()
    sim = S.build_sim(topo, params, program_workload(w))
    mid = S.run(sim, 300)
    mid_flat = check.flat_state(mid)
    end = S.run(sim, 300, state=mid)
    ref = RS.Reference(fab, params.n_channels, params.n_vcs, w)
    assert check.state_mismatch(check.flat_state(end), ref.run(ref.init_state(), 600)) == 0
    out = ref.run(check.canonical(mid_flat), 300)
    assert check.state_mismatch(check.flat_state(end), out) == 0
    assert check.stats_mismatch(S.stats(sim, end), RS.stats(out, fab.n_tiles, fab.n_hbm)) == 0
    assert int(out["eps.beats_rcvd"].sum()) > 0


@pytest.mark.parametrize("name", sorted(cells.SMALL))
def test_cell_is_correct(name):
    out = cells.run(cells.small(name), SEED, require_tpu=False)
    assert out["correct"], cells.dumps(out)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"setup_s"}
    assert list(out)[-1] == "checks"


FAULTS = [("floonoc8x4.perm4_dma", f) for f in ("unchanged", "altered")] + [
    ("floonoc8x4.fig8_sweep", f) for f in ("unchanged", "half", "altered")] + [
    ("dse_grid.default", f) for f in ("unchanged", "half", "altered", "area")]


@pytest.mark.parametrize("name,fault", FAULTS)
def test_fault_is_caught(name, fault):
    loaded = cells.small(name)
    with cells.fault(fault):
        out = cells.run(loaded, SEED, require_tpu=False)
    assert not out["correct"], cells.dumps(out)


CONTROLS = [("floonoc8x4.perm4_dma", "fused8"), ("floonoc8x4.perm4_dma", "wormhole"),
            ("floonoc8x4.fig8_sweep", "wormhole"), ("dse_grid.default", "wormhole")]


@pytest.mark.parametrize("name,kind", CONTROLS)
def test_control_is_not_correct(name, kind):
    with cells.control(kind):
        out = cells.run(cells.with_control(cells.small(name), kind), SEED,
                        require_tpu=False)
    assert not out["correct"], cells.dumps(out)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "floonoc8x4.perm4_dma", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_union_and_gaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    spans = [("bench.block", 0, 10), ("bench.dispatch", 2, 4)]
    assert trace._host_activity(spans, 3) == "bench.dispatch"
    assert trace._host_activity(spans, 5) == "bench.block"
    assert trace._host_activity(spans, 11) == "host"


@pytest.mark.parametrize("name", ["floonoc8x4.perm4_dma", "dse_grid.default"])
def test_traced_run_is_correct(name):
    """A traced run checks the same; off the chip no device op is found,
    so the per-layer metrics are left out rather than read as 0."""
    loaded = cells.small(name)
    out = cells.harness.run_cell(loaded[1]["name"], SEED, 1.0, True, t_start=0.0,
                                 loaded=loaded, require_tpu=False)
    assert out["correct"], cells.dumps(out)
    assert out["metrics"] == {}


SMALL_TRACE = ROOT / "bench/tests/data/small.xplane.pb"


def test_reduction_of_a_recorded_chip_trace():
    """Two 16-cycle chunks of the 8x4 cell traced on a TPU v5e: the
    reduction's busy time is the union of the op intervals in the window,
    recomputed here by brute force on a 1 us grid."""
    pd = trace._load(str(SMALL_TRACE))
    r = trace.reduce(pd)
    (w0, w1), = [(s, e) for n, s, e in trace.host_spans(pd) if n == trace.WINDOW]
    ivs = [(max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
           for plane in pd.planes if plane.name == "/device:TPU:0"
           for line in plane.lines if line.name == trace.OP_LINE
           for ev in line.events if not trace.is_container(trace.op_name(ev.name))]
    ivs = [(s, e) for s, e in ivs if e > s]
    grid = np.zeros(int((w1 - w0) / 1000) + 1, bool)
    for s, e in ivs:
        grid[int((s - w0) / 1000):int(np.ceil((e - w0) / 1000))] = True
    assert list(r["devices"]) == ["/device:TPU:0"]
    assert r["devices"]["/device:TPU:0"]["n_ops"] == len(ivs) > 0
    assert abs(r["busy_s"] - grid.sum() * 1e-6) <= len(ivs) * 2e-6
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert names and not any(trace.is_container(n) for n in names)
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
