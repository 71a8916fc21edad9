"""Whether a cell's traced calls kept every device op event.

    python3 bench/tests/trace_cover.py --workload <cell> --seed <n>

Sets up as ``bench/run.py`` does, makes one untraced call and the cell's
traced calls as a ``--trace 1`` run makes them, and keeps the trace. Prints
one JSON line: the seconds the profiler took to stop and the reduction
took, the trace's size, and per chip the events of each line and, over the
program executions on its ``XLA Modules`` line, the longest time from an
execution's start to its first op and from its last op to its end. A
trace that lost the ops at the end of a long execution, as one that hits
the profiler's event cap does, shows a long tail there. Needs the cell's
chips.
"""
import argparse
import bisect
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MODULES = "XLA Modules"


def cover(pd) -> dict:
    """Per device plane: events per line, and the executions' head and tail."""
    from bench.lib import trace as tr

    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        ops = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in lines.get(tr.OP_LINE, ())
                     if not tr.is_container(tr.op_name(ev.name)))
        starts = [s for s, _ in ops]
        head = tail = 0.0
        for ev in lines.get(MODULES, ()):
            m0, m1 = ev.start_ns, ev.start_ns + ev.duration_ns
            inside = ops[bisect.bisect_left(starts, m0):bisect.bisect_right(starts, m1)]
            if inside:
                head = max(head, (inside[0][0] - m0) * 1e-9)
                tail = max(tail, (m1 - max(e for _, e in inside)) * 1e-9)
        out[plane.name] = {"events": {k: len(v) for k, v in lines.items()},
                           "executions": len(lines.get(MODULES, ())),
                           "max_head_s": head, "max_tail_s": tail}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from bench.lib import drivers, harness, trace as tr

    _, cell, config, traffic = harness.load_cell(args.workload)
    harness.devices(cell["chips"], True)
    driver = harness.driver_class(traffic["kind"])(config, traffic, args.seed)
    driver.setup()
    log_dir = tempfile.mkdtemp(prefix="bench_cover_")
    try:
        t0 = time.perf_counter()
        driver.window(1e-3, drivers.Tracer(log_dir, traffic.get("trace_calls", 1)))
        window_s = time.perf_counter() - t0
        path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        t0 = time.perf_counter()
        pd = tr._load(path)
        reduced = tr.reduce(pd)
        reduce_s = time.perf_counter() - t0
        out = {"untraced_and_traced_calls_s": window_s, "reduce_s": reduce_s,
               "trace_bytes": os.path.getsize(path),
               "reduced_n_ops": {k: d["n_ops"] for k, d in reduced["devices"].items()},
               "planes": cover(pd)}
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
