"""Split a cell's traced calls by the simulator's layer names on the chip,
or record the small scoped trace that ``bench/tests/test_scopes.py`` reads.

    python3 bench/tests/layers.py --workload <cell> --seed <n> --untraced 8 --traces 3
    python3 bench/tests/layers.py --record bench/tests/data

A cell run sets up as ``bench/run.py`` does, makes ``--untraced`` calls
with the profiler off, then ``--traces`` profiles of ``trace_calls`` calls
each, every one in a ``bench.window`` span as in a ``--trace 1`` run of
the benchmark. It prints one JSON line: the per-call medians with the
profiler off and on; for the first profile, :func:`bench.lib.scopes.per_layer`
with its breakdown, and the numbers of ``bench/lib/trace.py``'s reduction;
and the check of the program text the split rests on (``op_name`` kept,
every traced op of the program found in it, with its opcode).

``--record`` traces two 16-cycle chunks of the 8x4 cell and one 4-cycle
sweep of 2 fabrics in one window, and writes ``scoped.xplane.pb`` and the
text of each traced program (``scoped.<module>.hlo.txt.gz``) to the
directory. Both need the chip.
"""
import argparse
import copy
import glob
import gzip
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 4242


def program_text(driver) -> tuple[str, float]:
    """Optimized HLO text of the program the driver's calls run, and the
    seconds its compile (or cache load) took."""
    import jax.numpy as jnp

    from bench.lib import drivers
    from repro.core.noc import sim as S

    if isinstance(driver, drivers.RunDriver):
        fn, args = driver.sim._scan_fn(driver.chunk, with_trace=False), (driver.st,)
    else:
        fields = tuple(f for f in S.SWEEP_FIELDS
                       if getattr(driver.wls[0], f) is not None)
        fn = driver.sim._sweep_fn(driver.cycles, fields)
        args = (tuple(jnp.stack([jnp.asarray(getattr(w, f)) for w in driver.wls])
                      for f in fields),)
    t0 = time.perf_counter()
    text = fn.lower(*args).compile().as_text()
    return text, time.perf_counter() - t0


def _opcode(text: str) -> str:
    m = re.search(r"\s([a-z][a-z0-9_-]*)\(", text.split(" = ", 1)[1])
    return m.group(1) if m else ""


def text_check(pd, module: str, text: str) -> dict:
    """How the trace's ops of ``module`` match the program text."""
    from bench.lib import scopes, trace as tr

    instr = {m.group(1): m.group(0) for m in
             re.finditer(r"^\s*(?:ROOT )?%(\S+) = [^\n]*", text, re.M)}
    found = missing = other_opcode = 0
    for plane in pd.planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        for _, _, mod, name, ev_name in scopes.op_events(plane, 0, float("inf")):
            if mod != module:
                continue
            if name not in instr:
                missing += 1
            elif _opcode(instr[name]) != _opcode(ev_name):
                other_opcode += 1
            else:
                found += 1
    return {"module": module, "op_name_kept": "op_name=" in text,
            "scopes": sorted(set(re.findall(r"/(noc\.[a-z]+)/", text))),
            "ops_found": found, "ops_missing": missing,
            "ops_other_opcode": other_opcode}


def _xplane(log_dir: str) -> str:
    return glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]


def profile_cell(name: str, seed: int, untraced: int, traces: int) -> dict:
    from bench.lib import drivers, harness, scopes, trace as tr

    bench, cell, config, traffic = harness.load_cell(name)
    harness.devices(cell["chips"], True)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    d = drivers.KINDS[traffic["kind"]](config, traffic, seed)
    d.setup()
    d.samples = []  # no check copies: every call is a plain call
    calls = traffic.get("trace_calls", 1)
    off = []
    for i in range(untraced):
        t0 = time.perf_counter()
        d.call(i)
        off.append(time.perf_counter() - t0)
    on, dirs = [], []
    try:
        for _ in range(traces):
            dirs.append(tempfile.mkdtemp(prefix="bench_layers_"))
            with drivers.Tracer(dirs[-1], calls).tracing():
                for _ in range(calls):
                    t0 = time.perf_counter()
                    d.call(0)
                    on.append(time.perf_counter() - t0)
        text, compile_s = program_text(d)
        module, ops = scopes.op_names(text)
        pd = tr._load(_xplane(dirs[0]))
        old, new = tr.reduce(pd), scopes.reduce(pd, {module: ops})
        if new is None:
            raise SystemExit("layers: the trace holds no device op")
        check = text_check(pd, module, text)
    finally:
        for p in dirs:
            shutil.rmtree(p, ignore_errors=True)
    work = d.work(calls)
    ctx = {"trace": old, "window": work, "traced_calls": calls}
    existing = {m: harness.reader(m)(ctx) for m in
                ("device.idle_share.sim", "scan.device_us_per_fabric_cycle",
                 "scan.ops_per_step")}
    return {
        "workload": name, "seed": seed, "device": harness.device_info(harness.devices(1, True)),
        "per_call_s": {"profiler_off": off, "profiler_on": on,
                       "median_off": statistics.median(off), "median_on": statistics.median(on)},
        "existing": {**existing, "breakdown": old["breakdown"]},
        "per_layer": scopes.per_layer(new, work["fabric_cycles"]),
        "layers_s": new["layers"], "idle_s": new["idle"], "call_s": new["call_s"],
        "busy_s": new["busy_s"], "window_s": new["window_s"],
        "breakdown": new["breakdown"],
        "program": {**check, "compile_s": compile_s},
    }


def record(out: Path) -> dict:
    """The small scoped trace and its programs' texts (see the docstring)."""
    from bench.lib import drivers, harness, scopes

    _, _, config, traffic = harness.load_cell("floonoc8x4.perm4_dma")
    run_traffic = dict(copy.deepcopy(traffic),
                       chunk_cycles=[{"max_routers": 4096, "cycles": 16}])
    _, _, sconfig, straffic = harness.load_cell("floonoc8x4.fig8_sweep")
    sweep_traffic = dict(copy.deepcopy(straffic), patterns=straffic["patterns"][:1],
                         cycles_per_call=4)
    ds = [drivers.RunDriver(config, run_traffic, SEED),
          drivers.SweepDriver(sconfig, sweep_traffic, SEED)]
    for d in ds:
        d.setup()
        d.samples = []
    log_dir = tempfile.mkdtemp(prefix="bench_layers_")
    try:
        with drivers.Tracer(log_dir, 0).tracing():
            ds[0].call(0)
            ds[0].call(1)
            ds[1].call(0)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(_xplane(log_dir), out / "scoped.xplane.pb")
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    written = {"scoped.xplane.pb": (out / "scoped.xplane.pb").stat().st_size}
    for d in ds:
        text, _ = program_text(d)
        module, _ = scopes.op_names(text)
        path = out / f"scoped.{module}.hlo.txt.gz"
        with gzip.open(path, "wt") as f:
            f.write(text)
        written[path.name] = path.stat().st_size
    return written


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--untraced", type=int, default=8)
    ap.add_argument("--traces", type=int, default=3)
    ap.add_argument("--record", type=Path)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    platforms = os.environ.get("JAX_PLATFORMS")  # the drivers keep a CPU device
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    if args.record:
        out = record(args.record)
    else:
        out = profile_cell(args.workload, args.seed, args.untraced, args.traces)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
