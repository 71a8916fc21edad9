"""One run of one cell: find the cell's files by name, check the device,
set up, measure, check against the reference, and print the result line.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own (``bench/configs``, ``bench/traffic``,
``bench/metrics``) and is found through ``BENCHMARK.json``, so a cell or a
metric is added by adding files. A traffic kind that ``drivers.KINDS``
lacks brings its driver as ``bench/kinds/<kind>.py``, which exposes
``Driver``.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoDevice(Exception):
    """The machine lacks the chips the cell asks for."""


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, config, traffic)`` for the cell ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _module(path: Path, name: str):
    """The Python file ``path``, loaded as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return _module(BENCH / "metrics" / f"{metric}.py", f"bench_metric_{metric}").read


def driver_class(kind: str):
    """The driver of a traffic kind: ``drivers.KINDS[kind]``, or else the
    ``Driver`` of ``bench/kinds/<kind>.py``."""
    from bench.lib import drivers

    if kind in drivers.KINDS:
        return drivers.KINDS[kind]
    path = BENCH / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise KeyError(f"no driver for traffic kind {kind!r}: not in drivers.KINDS "
                       f"and no file {path.name} in {path.parent}")
    return _module(path, f"bench_kind_{kind}").Driver


def devices(chips: int, require_tpu: bool):
    """The accelerator devices; raise ``NoDevice`` if there are too few."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"cell needs {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def device_info(devs) -> dict:
    """The device as JAX reports it, with the peak memory of the fullest."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             loaded: tuple | None = None) -> dict:
    """Run the cell once and return its result dict (the printed line).
    ``loaded`` replaces :func:`load_cell`'s files (tests at small sizes)."""
    from bench.lib import drivers, trace as tr

    bench, cell, config, traffic = loaded or load_cell(name)
    devs = devices(cell["chips"], require_tpu)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = driver_class(traffic["kind"])(config, traffic, seed)
    driver.phase["start"] = time.perf_counter() - t_start
    driver.setup()
    setup_s = time.perf_counter() - t_start

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    traced_calls = traffic.get("trace_calls", 1)
    tracer = drivers.Tracer(log_dir, traced_calls)
    try:
        with driver.phase("window"):
            window = driver.window(seconds, tracer)
        info = device_info(devs)
        with driver.phase("trace_reduce"):
            reduced = tr.reduce_dir(log_dir) if trace else None
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    driver.release()
    with driver.phase("reference"):
        checks, failed = driver.check()
    for k, v in driver.phase.items():
        print(f"bench: {k} {v:.3f} s", file=sys.stderr)

    ctx = {"setup_s": setup_s, "window": window, "trace": reduced,
           "traced_calls": traced_calls}
    metrics = {}
    for m in metrics_for(bench, name, trace):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if reduced:
        for plane, d in reduced["devices"].items():
            print(f"bench: {plane} busy {d['busy_s']:.6f} s, {d['n_ops']} op events "
                  f"in a {reduced['window_s']:.6f} s window", file=sys.stderr)
        info["busy_s"] = reduced["busy_s"]
        info["window_s"] = reduced["window_s"]
    out = {"correct": all(v <= lim for v, lim in checks.values()),
           "attempted": window["attempted"], "failed": failed,
           "metrics": metrics, "device": info}
    if reduced:
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


def main(args, t_start: float) -> int:
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
