"""Reduce a profiler trace of the measured window to per-device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each TPU chip is a plane ``/device:TPU:<n>``; the line ``XLA Ops``
holds one event per executed device operation, with its start and length
in nanoseconds on the same clock as the host planes. The harness's own
``TraceAnnotation`` spans (``bench.window``, ``bench.dispatch``,
``bench.block``, ``bench.pass``) sit on the host plane ``/host:CPU``.

For the traced window (the ``bench.window`` span) this gives, per chip: the
time some operation ran (the union of the op intervals), the number of op
executions, the time per op name, and the idle gaps between ops, each
named after the harness span the host was in when the gap began.

Run ``python -m bench.lib.trace <file.xplane.pb>`` to print a trace's
planes, lines and busiest event names.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
# ops that only contain other ops (a loop's event spans every iteration of
# its body); counting them would make the whole loop look busy
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.250 = s32[480] fusion(...)`` -> ``fusion.250``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_container(name: str) -> bool:
    return re.sub(r"\.\d+$", "", name) in CONTAINERS


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def host_spans(pd) -> list[tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every ``bench.*`` host span."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def reduce(pd) -> dict | None:
    """Per-device busy time, op counts and idle gaps over the window, or
    ``None`` if the trace holds no window or no device operation."""
    spans = host_spans(pd)
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        return None
    _, w0, w1 = win[0]
    inner = [s for s in spans if s[0] != WINDOW]
    devices = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX) or not plane.name[len(DEVICE_PREFIX):].isdigit():
            continue
        ivs, n_ops, per_op = [], 0, collections.Counter()
        for line in plane.lines:
            if line.name != OP_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                name = op_name(ev.name)
                if e <= s or is_container(name):
                    continue
                ivs.append((s, e))
                n_ops += 1
                per_op[name] += (e - s) * 1e-9
        busy = _union(ivs)
        gaps = [(a[1], b[0]) for a, b in zip([[w0, w0]] + busy, busy + [[w1, w1]])
                if b[0] > a[1]]
        idle = collections.Counter()
        for g0, g1 in gaps:
            idle[_host_activity(inner, g0)] += (g1 - g0) * 1e-9
        devices[plane.name] = {"busy_s": sum(e - s for s, e in busy) * 1e-9,
                               "n_ops": n_ops, "per_op": per_op, "idle": idle}
    if not any(d["n_ops"] for d in devices.values()):
        return None
    window_s = (w1 - w0) * 1e-9
    ops, idle = collections.Counter(), collections.Counter()
    for d in devices.values():
        ops.update(d["per_op"])
        idle.update(d["idle"])
    busy = [d["busy_s"] for d in devices.values()]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "devices": {k: {"busy_s": d["busy_s"], "n_ops": d["n_ops"]}
                    for k, d in sorted(devices.items())},
        "breakdown": {
            "device_ops": [[k, v] for k, v in ops.most_common(10)],
            "idle_gaps": [[k, v] for k, v in idle.most_common(10)],
        },
    }


def _host_activity(spans, t: float) -> str:
    """The innermost harness span open at ``t`` ("host" if none)."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host"


def reduce_dir(log_dir: str) -> dict | None:
    """:func:`reduce` of the one trace the profiler wrote under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return reduce(_load(files[0])) if files else None


def describe(path: str) -> None:
    """Print the planes and lines of a trace and their busiest events."""
    pd = _load(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            t = sum(e.duration_ns for e in evs) * 1e-9
            span = (max(e.start_ns + e.duration_ns for e in evs)
                    - min(e.start_ns for e in evs)) * 1e-9 if evs else 0
            print(f"  line {line.name!r}: {len(evs)} events, {t:.6f} s summed, "
                  f"{span:.6f} s spanned; top {names.most_common(8)}")


if __name__ == "__main__":
    describe(sys.argv[1])
