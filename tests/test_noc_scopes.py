"""The simulator names its layers inside the program: the five named scopes
of the scan step reach the compiled program's op metadata, and ``run`` /
``run_sweep`` open nested host spans in a profile, without changing what
they compute (the golden stat pins hold under the profiler)."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.noc import sim as S
from test_noc_channels import GOLDEN, _golden_sim

SCOPES = ("noc.router", "noc.ingest", "noc.generators", "noc.memory", "noc.inject")


def _scan_text(sim, n_cycles):
    state = jax.eval_shape(sim.init_state)
    return sim._scan_fn(n_cycles, with_trace=False).lower(state).compile().as_text()


def _sweep_text(sim, wls, n_cycles):
    fields = tuple(f for f in S.SWEEP_FIELDS if getattr(wls[0], f) is not None)
    batch = tuple(np.stack([np.asarray(getattr(w, f)) for w in wls]) for f in fields)
    return sim._sweep_fn(n_cycles, fields).lower(batch).compile().as_text()


@pytest.mark.parametrize("program", ["fast", "naive", "fused2", "sweep"])
def test_compiled_program_carries_every_scope(program):
    sim = _golden_sim()
    if program == "sweep":
        text = _sweep_text(sim, [sim.wl, sim.wl], 8)
    else:
        params = dataclasses.replace(
            sim.params, step_impl="naive" if program == "naive" else "fast",
            fused_cycles=2 if program == "fused2" else 1)
        text = _scan_text(S.build_sim(sim.topo, params, sim.wl), 8)
    for scope in SCOPES:
        assert f'/{scope}/' in text, f"{program}: no op carries {scope}"


def _profiled(tmp_path, fn, prefix="noc."):
    """``fn()``'s result and the host-plane events of its profile whose
    names start with ``prefix``, as ``(name, start_ns, end_ns, line)``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, line.name)
              for plane in pd.planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith(prefix)]
    return out, events


def _assert_nested(spans, outer, inner):
    """One ``outer`` span, holding the ``inner`` spans once each, in order,
    on its own thread."""
    (_, s0, e0, line), = [s for s in spans if s[0] == outer]
    kids = sorted((s, e, n, ln) for n, s, e, ln in spans if n != outer)
    assert [n for _, _, n, _ in kids] == list(inner)
    for s, e, _, ln in kids:
        assert s0 <= s <= e <= e0 and ln == line
    assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


def _assert_golden(sim, st):
    out = S.stats(sim, st)
    np.testing.assert_array_equal(out["beats_rcvd"], GOLDEN["beats_rcvd"])
    np.testing.assert_array_equal(out["dma_done"].sum(axis=-1), GOLDEN["dma_done"])
    np.testing.assert_array_equal(out["narrow_lat_cnt"], GOLDEN["narrow_lat_cnt"])
    np.testing.assert_array_equal(np.asarray(st.eps.lat_sum), GOLDEN["narrow_lat_sum"])
    np.testing.assert_array_equal(out["ni_stalls"], GOLDEN["ni_stalls"])
    np.testing.assert_array_equal(out["last_rx"], GOLDEN["last_rx"])
    np.testing.assert_array_equal(out["first_rx"], GOLDEN["first_rx"])


def test_run_spans_nest_and_keep_the_golden_state(tmp_path):
    sim = _golden_sim()
    st, spans = _profiled(tmp_path, lambda: S.run(sim, 1200))
    _assert_nested(spans, "noc.run", ("noc.run.scan", "noc.run.consume"))
    _assert_golden(sim, st)


def test_sweep_spans_nest_and_keep_the_golden_state(tmp_path):
    sim = _golden_sim()
    finals, spans = _profiled(tmp_path, lambda: S.run_sweep(sim, [sim.wl, sim.wl], 1200))
    _assert_nested(spans, "noc.sweep", ("noc.sweep.stack", "noc.sweep.scan"))
    for st in finals:
        _assert_golden(sim, st)


def test_warm_sweep_call_launches_one_program(tmp_path):
    """A warm ``run_sweep`` call launches ``jit_sweep`` alone: the stacked
    batch goes in and one state per fabric comes out, with no eager
    slicing, squeezing, broadcasting or stacking programs around it."""
    sim = _golden_sim()
    wls = [sim.wl, sim.wl, sim.wl]
    jax.block_until_ready(S.run_sweep(sim, wls, 40))
    finals, events = _profiled(tmp_path, lambda: S.run_sweep(sim, wls, 40), prefix="")
    (_, s0, e0, _), = [ev for ev in events if ev[0] == "noc.sweep"]
    inside = [n for n, s, e, _ in events if s0 <= s and e <= e0]
    assert {n for n in inside if n.startswith("PjitFunction(")} == {"PjitFunction(sweep)"}
    assert inside.count("PjRtCpuExecutable::Execute") == 1
    assert len(finals) == len(wls)
