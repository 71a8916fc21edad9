"""CPU tests of the layer split of a trace (``bench/lib/scopes.py``), on
recorded TPU v5e traces: ``scoped.xplane.pb`` (two 16-cycle chunks of the
8x4 cell and one 4-cycle sweep of 2 fabrics, with the programs' HLO texts
beside it; ``bench/tests/layers.py --record``) and the older
``small.xplane.pb`` (two 16-cycle chunks of a program with no scopes).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_scopes.py
"""
from __future__ import annotations

import gzip
import re
from types import SimpleNamespace

import pytest

from bench.lib import harness, scopes, trace
from bench.lib.harness import ROOT

DATA = ROOT / "bench/tests/data"
SCOPED = DATA / "scoped.xplane.pb"
SMALL = DATA / "small.xplane.pb"


def _texts() -> list[str]:
    out = []
    for path in sorted(DATA.glob("scoped.*.hlo.txt.gz")):
        with gzip.open(path, "rt") as f:
            out.append(f.read())
    return out


def _programs() -> dict:
    return dict(scopes.op_names(t) for t in _texts())


def _ev(name, start, end):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=end - start)


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[SimpleNamespace(name=k.replace("_", " "), events=v)
                                             for k, v in lines.items()])


def test_reduction_of_a_synthetic_trace():
    """Ops go to their program and scope, overlapping time to the op that
    started first, idle gaps to the innermost span at their start."""
    host = _plane(trace.HOST_PLANE, python=[
        _ev("bench.window", 0, 1000), _ev("bench.dispatch", 10, 300),
        _ev("noc.sweep", 20, 290), _ev("noc.sweep.stack", 20, 100),
        _ev("noc.sweep.scan", 100, 130), _ev("noc.sweep.delete", 130, 200),
        _ev("noc.sweep.unstack", 200, 290), _ev("bench.block", 300, 900)])
    dev = _plane("/device:TPU:0", XLA_Modules=[
        _ev("jit_sweep(1)", 140, 600), _ev("jit_slice(2)", 620, 630)], XLA_Ops=[
        _ev("%fusion.9 = s32[2] fusion()", 50, 60),
        _ev("%while.1 = (s32[]) while()", 140, 600),
        _ev("%fusion.1 = s32[2] fusion()", 140, 300),
        _ev("%fusion.2 = s32[2] fusion()", 250, 400),
        _ev("%copy.3 = s32[2] copy()", 450, 500),
        _ev("%slice.1 = s32[1] slice()", 620, 630)])
    ops = {"jit_sweep": {"fusion.1": "jit(sweep)/while/body/noc.router/gather",
                         "fusion.2": "jit(sweep)/while/body/noc.memory/jit(x)/add",
                         "copy.3": "jit(sweep)/while"}}
    r = scopes.reduce(SimpleNamespace(planes=[host, dev]), ops)
    assert r["busy_s"] == pytest.approx(330e-9) and r["window_s"] == pytest.approx(1000e-9)
    assert r["layers"] == pytest.approx({"noc.router": 160e-9, "noc.memory": 100e-9,
                                         scopes.UNSCOPED: 60e-9, "jit_slice": 10e-9})
    assert r["idle"] == pytest.approx({"host": 50e-9, "noc.sweep.stack": 80e-9,
                                       "bench.block": 540e-9})
    assert r["call_s"] == pytest.approx([270e-9])
    assert dict(r["breakdown"]["device_ops"]) == pytest.approx({
        "noc.router:fusion.1": 160e-9, "noc.memory:fusion.2": 100e-9,
        "unscoped:copy.3": 50e-9, "unscoped:fusion.9": 10e-9, "jit_slice:slice.1": 10e-9})
    m = scopes.per_layer(r, 10)
    assert m["router.device_us_per_fabric_cycle"] == pytest.approx(0.016)
    assert m["endpoints.ingest_us_per_fabric_cycle"] == 0.0
    assert m["scan.unscoped_share"] == pytest.approx(100 * 70 / 330)
    assert m["device.idle_share.noc_host"] == pytest.approx(8.0)
    assert m["noc.host_ms_per_call"] == pytest.approx(270e-6)


@pytest.fixture(scope="module")
def scoped():
    pd = trace._load(str(SCOPED))
    return pd, scopes.reduce(pd, _programs())


def test_op_names_and_scopes_of_an_hlo_text():
    text = ("HloModule jit_scan, is_scheduled=true\n\n"
            "ENTRY %main {\n"
            '  %fusion.7 = s32[4]{0} fusion(%p), kind=kLoop, metadata={op_name='
            '"jit(scan)/while/body/closed_call/noc.memory/jit(take)/gather" '
            "stack_frame_id=3}\n"
            '  ROOT %copy.2 = s32[4]{0} copy(%fusion.7), metadata={op_name="jit(scan)/while"}\n'
            "  %constant.1 = s32[] constant(0)\n}\n")
    module, ops = scopes.op_names(text)
    assert module == "jit_scan"
    assert ops == {"fusion.7": "jit(scan)/while/body/closed_call/noc.memory/jit(take)/gather",
                   "copy.2": "jit(scan)/while"}
    assert [scopes.scope_of(v) for v in ops.values()] == ["noc.memory", scopes.UNSCOPED]
    assert scopes.module_name("jit_scan(15633506474664818365)") == "jit_scan"


def test_layers_add_up_to_the_busy_time(scoped):
    """Every instant of busy time goes to one layer: the five scopes, the
    program's unscoped ops and other programs add up to the busy time of
    ``trace.reduce``, and every op of the traced programs is found in their
    texts."""
    pd, r = scoped
    old = trace.reduce(pd)
    assert r["busy_s"] == old["busy_s"] and r["window_s"] == old["window_s"]
    assert set(scopes.LAYERS) <= set(r["layers"])
    assert sum(r["layers"].values()) == pytest.approx(r["busy_s"], rel=1e-9)
    m = scopes.per_layer(r, 1000)
    scoped_s = sum(m[name] for name in scopes.LAYERS.values()) * 1000 * 1e-6
    unscoped_s = m["scan.unscoped_share"] / 100 * r["busy_s"]
    assert scoped_s + unscoped_s == pytest.approx(r["busy_s"], rel=1e-9)
    instr = {scopes.op_names(t)[0]: set(re.findall(r"^\s*(?:ROOT )?%(\S+) = ", t, re.M))
             for t in _texts()}
    assert set(instr) == {"jit_scan", "jit_sweep"}
    plane, = [p for p in pd.planes if p.name == "/device:TPU:0"]
    evs = scopes.op_events(plane, 0, float("inf"))
    ours = [(mod, name) for _, _, mod, name, _ in evs if mod in instr]
    assert ours and all(name in instr[mod] for mod, name in ours)
    for key, _ in r["breakdown"]["device_ops"]:
        layer = key.split(":", 1)[0]
        assert layer in scopes.LAYERS or layer == scopes.UNSCOPED or layer.startswith("jit_")


def test_every_idle_gap_is_named_by_its_innermost_span(scoped):
    """Each idle gap, recomputed here from the op events, goes to the
    shortest ``bench.*`` or ``noc.*`` span open where it starts ("host"
    where none is); the program's own spans name some of them."""
    pd, r = scoped
    sp = scopes.spans(pd)
    (w0, w1), = [(s, e) for n, s, e in sp if n == trace.WINDOW]
    plane, = [p for p in pd.planes if p.name == "/device:TPU:0"]
    idle, t = {}, w0
    for s, e, *_ in sorted(scopes.op_events(plane, w0, w1)) + [(w1, w1)]:
        if s > t:
            open_ = [(b - a, n) for n, a, b in sp if n != trace.WINDOW and a <= t < b]
            name = min(open_)[1] if open_ else "host"
            idle[name] = idle.get(name, 0.0) + (s - t) * 1e-9
        t = max(t, e)
    assert r["idle"] == pytest.approx(idle, rel=1e-9)
    assert any(n.startswith("noc.") for n in r["idle"])
    assert sum(r["idle"].values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_the_older_trace_reads_as_before():
    """On the trace recorded before the program had scopes, the three
    existing metrics read what they read when it was recorded, and the
    split names every op by its program."""
    pd = trace._load(str(SMALL))
    old = trace.reduce(pd)
    ctx = {"trace": old, "window": {"cycles_per_call": 16, "fabrics": 1},
           "traced_calls": 2}
    read = {m: harness.reader(m)(ctx) for m in
            ("device.idle_share.sim", "scan.device_us_per_fabric_cycle", "scan.ops_per_step")}
    assert read == pytest.approx({"device.idle_share.sim": 83.08455711027808,
                                  "scan.device_us_per_fabric_cycle": 51.2721875,
                                  "scan.ops_per_step": 248.1875}, rel=1e-12)
    r = scopes.reduce(pd, {})
    assert r["busy_s"] == old["busy_s"] and r["window_s"] == old["window_s"]
    assert r["layers"] == pytest.approx({"jit_fn": old["busy_s"]})
    assert r["idle"] == pytest.approx(dict(old["breakdown"]["idle_gaps"]))
