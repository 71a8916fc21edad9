"""Split a profiler trace of the measured window by the simulator's own
layer names.

The program names its layers twice (``src/repro/core/noc/README.md``,
"Profiling a run"):

- on the device, each phase of the scan step runs in a ``jax.named_scope``
  (``noc.router``, ``noc.ingest``, ``noc.generators``, ``noc.memory``,
  ``noc.inject``). The scope is kept in the ``op_name`` metadata of each
  instruction of the compiled program, which its optimized HLO text shows
  and the trace's op events do not: an op event is named by its
  instruction (``fusion.250``, see :func:`bench.lib.trace.op_name`);
- on the host, ``sim.run`` and ``sim.run_sweep`` open
  ``jax.profiler.TraceAnnotation`` spans: ``noc.run`` around
  ``noc.run.scan`` and ``noc.run.consume``; ``noc.sweep`` around
  ``noc.sweep.stack``, ``noc.sweep.scan``, ``noc.sweep.delete`` and
  ``noc.sweep.unstack``. They sit on the host plane beside the harness's
  ``bench.*`` spans, on the device trace's clock.

:func:`reduce` adds to :func:`bench.lib.trace.reduce`, over the same
``bench.window`` span, and leaves its numbers as they are: every op event
is given the program (the ``XLA Modules`` event that contains it in time)
and, for a program whose HLO text is known, the first ``noc.*`` component
of its instruction's ``op_name`` (else ``unscoped``); ops of programs
whose text is not known are named by their module (``jit_foo``). Each
instant of device busy time goes to exactly one op, the first one that
covers it, so the layers' times add up to the busy time. Each idle gap is
named by the innermost span, ``bench.*`` or ``noc.*``, open at its start.

The texts come from :func:`op_names` of ``Compiled.as_text()``, taken
after the trace by lowering and compiling the jitted function the traced
calls ran (a cache hit).
"""
from __future__ import annotations

import bisect
import collections
import re
import statistics

from bench.lib import trace as tr

MODULE_LINE = "XLA Modules"
SCOPE_PREFIX = "noc."
UNSCOPED = "unscoped"
# the outer span of one call of each program driver
CALL_SPANS = ("noc.run", "noc.sweep")
# device layers in step order, and the per-layer metric that reads each
LAYERS = {
    "noc.router": "router.device_us_per_fabric_cycle",
    "noc.ingest": "endpoints.ingest_us_per_fabric_cycle",
    "noc.generators": "endpoints.generators_us_per_fabric_cycle",
    "noc.memory": "endpoints.memory_us_per_fabric_cycle",
    "noc.inject": "inject.device_us_per_fabric_cycle",
}

_INSTR = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"', re.M)


def op_names(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module, {instruction: op_name})`` of an HLO module's text."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    return module, dict(_INSTR.findall(hlo_text))


def scope_of(op_name: str) -> str:
    """The first ``noc.*`` component of a name stack, else ``unscoped``."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def module_name(event_name: str) -> str:
    """``jit_scan(15633506474664818365)`` -> ``jit_scan``."""
    return event_name.split("(", 1)[0]


def spans(pd) -> list[tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every ``bench.*`` and ``noc.*`` span
    on the host plane."""
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name == tr.HOST_PLANE
            for line in plane.lines for ev in line.events
            if ev.name.startswith(("bench.", SCOPE_PREFIX))]


def op_events(plane, w0: float, w1: float) -> list[tuple]:
    """``(start_ns, end_ns, module, op, event_name)`` of a device plane's op
    events, clipped to ``[w0, w1)``, containers left out; ``module`` is the
    name of the ``XLA Modules`` event the op starts in, or ``None``."""
    modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                      module_name(ev.name))
                     for line in plane.lines if line.name == MODULE_LINE
                     for ev in line.events)
    starts = [m[0] for m in modules]
    out = []
    for line in plane.lines:
        if line.name != tr.OP_LINE:
            continue
        for ev in line.events:
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            name = tr.op_name(ev.name)
            if e <= s or tr.is_container(name):
                continue
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = modules[k][2] if k >= 0 and ev.start_ns < modules[k][1] else None
            out.append((s, e, mod, name, ev.name))
    return out


def _layer_of(ops: dict, module: str | None, op: str) -> str:
    if module is None:
        return UNSCOPED
    if module not in ops:
        return module
    return scope_of(ops[module].get(op, ""))


def _device(plane, w0, w1, ops, spans_in):
    """Per-layer busy time, per-op time and named idle of one device."""
    evs = [(s, e, _layer_of(ops, mod, name), name)
           for s, e, mod, name, _ in op_events(plane, w0, w1)]
    layers, per_op = collections.Counter(), collections.Counter()
    covered = w0
    for s, e, layer, name in sorted(evs):
        own = max(e, covered) - max(s, covered)  # the part no earlier op held
        covered = max(covered, e)
        layers[layer] += own * 1e-9
        per_op[f"{layer}:{name}"] += own * 1e-9
    busy = tr._union((s, e) for s, e, _, _ in evs)
    idle = collections.Counter()
    for a, b in zip([[w0, w0]] + busy, busy + [[w1, w1]]):
        if b[0] > a[1]:
            idle[tr._host_activity(spans_in, a[1])] += (b[0] - a[1]) * 1e-9
    return {"busy_s": sum(e - s for s, e in busy) * 1e-9, "layers": layers,
            "per_op": per_op, "idle": idle}


def reduce(pd, ops: dict[str, dict[str, str]]) -> dict | None:
    """Layer split of the ``bench.window`` span of a trace, or ``None`` if
    the trace holds no window or no device op. ``ops`` maps a module name
    to its :func:`op_names` table."""
    sp = spans(pd)
    win = [s for s in sp if s[0] == tr.WINDOW]
    if not win:
        return None
    _, w0, w1 = win[0]
    inner = [s for s in sp if s[0] != tr.WINDOW]
    devs = [_device(p, w0, w1, ops, inner) for p in pd.planes
            if p.name.startswith(tr.DEVICE_PREFIX)
            and p.name[len(tr.DEVICE_PREFIX):].isdigit()]
    if not any(d["per_op"] for d in devs):
        return None
    layers, per_op, idle = (collections.Counter() for _ in range(3))
    for d in devs:
        for key, c in (("layers", layers), ("per_op", per_op), ("idle", idle)):
            c.update({k: v / len(devs) for k, v in d[key].items()})
    calls = [(e - s) * 1e-9 for n, s, e in inner if n in CALL_SPANS and w0 <= s < w1]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": statistics.fmean(d["busy_s"] for d in devs),
        "layers": dict(layers),
        "idle": dict(idle),
        "call_s": calls,
        "breakdown": {
            "device_ops": [[k, v] for k, v in per_op.most_common(10)],
            "idle_gaps": [[k, v] for k, v in idle.most_common(10)],
        },
    }


def per_layer(r: dict, fabric_cycles: int) -> dict[str, float]:
    """The per-layer metrics of a :func:`reduce` result whose traced calls
    simulated ``fabric_cycles`` fabric-cycles in all (every fabric counted)."""
    out = {m: 1e6 * r["layers"].get(s, 0.0) / fabric_cycles
           for s, m in LAYERS.items()}
    unscoped = sum(v for k, v in r["layers"].items() if k not in LAYERS)
    out["scan.unscoped_share"] = 100.0 * unscoped / r["busy_s"]
    noc_idle = sum(v for k, v in r["idle"].items() if k.startswith(SCOPE_PREFIX))
    out["device.idle_share.noc_host"] = 100.0 * noc_idle / r["window_s"]
    if r["call_s"]:
        out["noc.host_ms_per_call"] = 1e3 * statistics.fmean(r["call_s"])
    return out
