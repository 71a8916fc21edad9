"""Simulator engine microbenchmark: scan-body compile time and simulated
cycles/second of the channel-batched fabric on the paper's 8x4 mesh, plus
the vmapped multi-config sweep engine vs a sequential build+run loop.

Pre-refactor baseline (per-channel FabricState list, dict-of-arrays flits,
same host): compile+first-run 5.5 s, steady state ~1400 cycles/s.

The ``--backend`` axis compares the per-cycle router compute backends
(``jnp`` vmapped reference vs the ``pallas`` (C, R/K)-gridded kernels,
compiled on a TPU and interpreted elsewhere) on the same workload:
cycles/s for both, plus a bit-equivalence check on the delivered-beat
counters. Every run prints the device its timings come from first.

The ``--scaling`` axis grows the mesh (8x4 -> 16x16 -> 32x32, --full adds
64x64) and reports a routers x cycles/s curve for the naive per-cycle jnp
scan (``step_impl="naive"``, the pre-fast-path reference datapath) vs the
fast path (circular queues + fused FIFOs) vs fused k-cycle super-steps,
pinning fast-vs-naive canonical-state equality at every point. The curve
is written into the ``--json`` artifact under ``"scaling"`` (the CI
bench-smoke job uploads it). Standalone usage::

    PYTHONPATH=src python -m benchmarks.sim_throughput --smoke --backend pallas
    PYTHONPATH=src python -m benchmarks.sim_throughput --scaling --json curve.json

Note ``S.run`` consumes the passed-in state (its large buffers are
deleted after the scan), so every timed repetition below re-inits its
state outside the timed region instead of re-feeding one ``st0``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import row
from repro.core.noc import sim as S
from repro.core.noc import traffic as T
from repro.core.noc.spec import FabricSpec, preset

BASELINE_CYC_PER_S = 1400  # seed engine, steady state, 8x4 mesh / 2000 cycles
SWEEP_SPEEDUP_TARGET = 3.0  # vmapped sweep vs sequential per-config compiles
# fast path vs naive per-cycle scan at 32x32 — regression floor, not the
# measured value. Measured on the 1-core CI host: ~5.3x per-cycle (naive
# ~11.5 ms/cyc vs fast ~2.2 ms/cyc; 7.3x at 64x64 — see
# benchmarks/results/scaling_curve.json). The original 10x goal is not
# reachable there: past the decision logic (~0.35 ms/cyc) the step is
# dominated by the 4 full-FIFO-buffer rewrites per cycle (~0.7 ms/cyc of
# pure memory traffic on 2x 860 KB buffers), i.e. bandwidth-bound; see
# docs/ARCHITECTURE.md "Scaling methodology". The naive datapath's FIFO
# pop/push became per-slot selects (the form the TPU compiler lowers),
# which sped naive up by ~20-30% at 32x32 on an 8-core host CPU while the
# fast path moved ~3%: fast/naive fell from ~5.0x to 3.9-4.3x there, and
# the floor keeps its ~25% margin below that.
SCALING_SPEEDUP_TARGET = 3.0

# the --scaling mesh ladder: (nx, ny, timed cycles, fused super-step k).
# 64x64 (4096 routers) only runs under --full.
SCALING_MESHES = [
    (8, 4, 2000, 8),
    (16, 16, 600, 8),
    (32, 32, 200, 8),
]
SCALING_MESHES_FULL = SCALING_MESHES + [(64, 64, 64, 8)]

# the --topology axis: every shape the engine must keep simulating, as
# declarative FabricSpecs (smoke runs one torus and one multi-die config;
# --full also times them)
SMOKE_TOPOLOGIES = [
    ("torus", FabricSpec(topology="torus", nx=4, ny=2)),
    ("multi_die", FabricSpec(topology="multi_die", n_dies=2, nx=2, ny=2, d2d=2)),
]
FULL_TOPOLOGIES = [
    ("torus", FabricSpec(topology="torus", nx=4, ny=8)),
    ("multi_die", FabricSpec(topology="multi_die", n_dies=2, nx=2, ny=8, d2d=3)),
]


def _measure(spec: FabricSpec, streams: int, n_cycles: int, iters: int):
    topo, params = spec.lower()
    wl = T.dma_workload(topo, "uniform", transfer_kb=8, n_txns=4, streams=streams)
    sim = S.build_sim(topo, params, wl)
    t0 = time.perf_counter()
    r = S.run(sim, n_cycles, state=sim.init_state())
    jax.block_until_ready(r.cycle)
    compile_s = time.perf_counter() - t0
    steady = float("inf")
    for _ in range(iters):
        st0 = sim.init_state()  # re-init: run() consumes its input state
        jax.block_until_ready(st0.cycle)
        t0 = time.perf_counter()
        r = S.run(sim, n_cycles, state=st0)
        jax.block_until_ready(r.cycle)
        steady = min(steady, time.perf_counter() - t0)
    return compile_s, n_cycles / steady


def _sweep_speedup(n_configs: int, n_cycles: int):
    """Wall-clock of N pattern x size configs: sequential per-config Sims
    (one compile each) vs one vmapped run_sweep (compiles once)."""
    topo, params = preset("mesh").lower()
    pats = ["uniform", "shuffle", "bit-complement", "transpose", "neighbor",
            "tiled-matmul"]
    wls = [T.dma_workload(topo, p, transfer_kb=kb, n_txns=4)
           for p in pats for kb in (1, 2)][:n_configs]
    t0 = time.perf_counter()
    for wl in wls:
        sim = S.build_sim(topo, params, wl)
        jax.block_until_ready(S.run(sim, n_cycles).cycle)
    t_seq = time.perf_counter() - t0
    sim0 = S.build_sim(topo, params, wls[0])
    t0 = time.perf_counter()
    sts = S.run_sweep(sim0, wls, n_cycles)
    jax.block_until_ready(sts[0].cycle)
    t_sweep = time.perf_counter() - t0
    return t_seq, t_sweep, len(wls)


def _backend_rows(n_cycles: int) -> list[dict]:
    """cycles/s of both router backends on one workload + bit-equivalence.

    Small 4x2 mesh: the pallas backend runs interpret-mode off TPU (the
    grid becomes a scanned loop), so it trades simulated throughput for
    exercising the exact kernel dataflow — CI pins its equivalence here.
    """
    topo = FabricSpec(topology="mesh", nx=4, ny=2).build_topology()
    wl = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2)
    rows, done = [], {}
    for backend in ("jnp", "pallas"):
        params = FabricSpec(topology="mesh", nx=4, ny=2,
                            backend=backend).params()
        sim = S.build_sim(topo, params, wl)
        t0 = time.perf_counter()
        r = S.run(sim, n_cycles, state=sim.init_state())
        jax.block_until_ready(r.cycle)
        compile_s = time.perf_counter() - t0
        st0 = sim.init_state()  # re-init: run() consumes its input state
        jax.block_until_ready(st0.cycle)
        t0 = time.perf_counter()
        r = S.run(sim, n_cycles, state=st0)
        jax.block_until_ready(r.cycle)
        cps = n_cycles / (time.perf_counter() - t0)
        out = S.stats(sim, r)
        done[backend] = (out["beats_rcvd"].tolist(), out["dma_done"].tolist())
        rows.append(row(f"sim_throughput/backend_{backend}/compile_s",
                        compile_s * 1e6, round(compile_s, 2)))
        rows.append(row(f"sim_throughput/backend_{backend}/cycles_per_s", 0.0,
                        round(cps)))
    rows.append(row("sim_throughput/backend_equiv", 0.0,
                    int(done["jnp"] == done["pallas"]), target=1, cmp="ge"))
    return rows


def _scaling_point(nx: int, ny: int, n_cycles: int, k: int,
                   iters: int = 2) -> tuple[list[dict], dict]:
    """One mesh point of the scaling curve: cycles/s for the naive
    per-cycle jnp scan vs the fast path vs fused k-cycle super-steps,
    plus the fast-vs-naive canonical-SimState bit-identity pin (the fast
    path must be a pure speedup over the reference datapath)."""
    base = FabricSpec(topology="mesh", nx=nx, ny=ny)
    topo = base.build_topology()
    wl = T.dma_workload(topo, "uniform", transfer_kb=8, n_txns=4)
    tag = f"sim_throughput/scaling_{nx}x{ny}"
    rows: list[dict] = []
    cps, finals = {}, {}
    for impl, params in (
            ("naive", dataclasses.replace(base, step_impl="naive").params()),
            ("fast", base.params()),
            (f"fused{k}", dataclasses.replace(base, fused_cycles=k).params())):
        sim = S.build_sim(topo, params, wl)
        r = S.run(sim, n_cycles, state=sim.init_state())  # compile + warmup
        jax.block_until_ready(r.cycle)
        finals[impl] = r
        steady = float("inf")
        for _ in range(iters):
            st0 = sim.init_state()  # run() consumes its input state
            jax.block_until_ready(st0.cycle)
            t0 = time.perf_counter()
            r2 = S.run(sim, n_cycles, state=st0)
            jax.block_until_ready(r2.cycle)
            steady = min(steady, time.perf_counter() - t0)
        cps[impl] = n_cycles / steady
        rows.append(row(f"{tag}/{impl}_cycles_per_s", steady * 1e6 / n_cycles,
                        round(cps[impl], 1)))
        finals[impl + "_sim"] = sim
    equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(S.canonical_state(finals["naive_sim"],
                                              finals["naive"])),
            jax.tree.leaves(S.canonical_state(finals["fast_sim"],
                                              finals["fast"]))))
    rows.append(row(f"{tag}/fast_equals_naive", 0.0, int(equal),
                    target=1, cmp="ge"))
    speedup = cps["fast"] / cps["naive"]
    target = SCALING_SPEEDUP_TARGET if (nx, ny) == (32, 32) else None
    rows.append(row(f"{tag}/fast_speedup_x", 0.0, round(speedup, 2),
                    target=target, cmp="ge"))
    point = {"mesh": f"{nx}x{ny}", "routers": topo.n_routers,
             "n_cycles": n_cycles, "fused_k": k, "equal": bool(equal),
             "speedup_fast_vs_naive": round(speedup, 2),
             "cycles_per_s": {i: round(v, 1) for i, v in cps.items()}}
    return rows, point


def scaling_rows(full: bool = False, smoke: bool = False
                 ) -> tuple[list[dict], list[dict]]:
    """The routers x cycles/s curve. Returns (rows, curve-json-points).
    Smoke trims to the two smallest meshes and fewer cycles so the CI
    bench-smoke lane can upload a curve artifact cheaply."""
    meshes = SCALING_MESHES_FULL if full else SCALING_MESHES
    if smoke:
        meshes = [(nx, ny, min(nc, 200), k)
                  for nx, ny, nc, k in meshes[:2]]
    rows, curve = [], []
    for nx, ny, nc, k in meshes:
        r, point = _scaling_point(nx, ny, nc, k, iters=1 if smoke else 2)
        rows += r
        curve.append(point)
    return rows, curve


def bench(full: bool = False, smoke: bool = False,
          backend: str | None = None) -> list[dict]:
    n_cycles = 4000 if full else 2000
    iters = 3 if full else 2
    rows = []
    if smoke:
        # toy scale: exercise every path (compile, run, sweep) cheaply
        t_seq, t_sweep, n = _sweep_speedup(n_configs=3, n_cycles=100)
        rows.append(row(f"sim_throughput/sweep{n}_smoke_speedup_x",
                        t_sweep * 1e6, round(t_seq / t_sweep, 2)))
        compile_s, cps = _measure(preset("mesh", big=True), streams=1,
                                  n_cycles=400, iters=1)
        rows.append(row("sim_throughput/8x4_smoke/compile_s", compile_s * 1e6,
                        round(compile_s, 2)))
        # cycles/s floor: the fast path must stay above the pre-refactor
        # seed engine's steady state even at smoke scale (CI gate)
        rows.append(row("sim_throughput/8x4_smoke/cycles_per_s", 0.0,
                        round(cps), target=BASELINE_CYC_PER_S, cmp="ge"))
        # topology axis: one torus and one multi-die config must stay green
        # (on the selected backend, so the pallas CI lane replays the zoo)
        for tname, sp in SMOKE_TOPOLOGIES:
            sp = dataclasses.replace(sp, backend=backend or "jnp")
            topo, params = sp.lower()
            wl = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2)
            sim = S.build_sim(topo, params, wl)
            out = S.stats(sim, S.run(sim, 300))
            nt = topo.meta["n_tiles"]
            rows.append(row(f"sim_throughput/{tname}_smoke/dma_done", 0.0,
                            int(out["dma_done"][:nt].sum()), target=nt * 2,
                            rel_tol=0.01))
        if backend:
            rows += _backend_rows(n_cycles=150)
        return rows
    compile_s, cps = _measure(preset("mesh", big=True), streams=1,
                              n_cycles=n_cycles, iters=iters)
    rows.append(row("sim_throughput/8x4/compile_s", compile_s * 1e6,
                    round(compile_s, 2)))
    rows.append(row("sim_throughput/8x4/cycles_per_s", 0.0, round(cps),
                    target=BASELINE_CYC_PER_S, cmp="ge"))
    # channel scaling: trace size is channel-count independent, so extra wide
    # channels must not blow up compile time (runtime grows with state size)
    c4, cps4 = _measure(preset("mesh", big=True, n_channels=4), streams=2,
                        n_cycles=n_cycles, iters=iters)
    rows.append(row("sim_throughput/8x4_c4/compile_s", c4 * 1e6, round(c4, 2),
                    target=round(3 * max(compile_s, 0.1), 2), cmp="le"))
    rows.append(row("sim_throughput/8x4_c4/cycles_per_s", 0.0, round(cps4)))
    # topology axis: simulated throughput of the zoo shapes (same engine,
    # different tables/router counts — multi_die carries repeater routers)
    for tname, sp in FULL_TOPOLOGIES:
        ct, cpst = _measure(sp, streams=1, n_cycles=n_cycles, iters=iters)
        rows.append(row(f"sim_throughput/{tname}/cycles_per_s", 0.0,
                        round(cpst)))
    # vmapped multi-config sweep: N configs through one jit-compiled scan
    # body vs the sequential loop's N per-Sim compiles
    t_seq, t_sweep, n = _sweep_speedup(n_configs=12, n_cycles=600)
    rows.append(row(f"sim_throughput/sweep{n}_sequential_s", t_seq * 1e6,
                    round(t_seq, 2)))
    rows.append(row(f"sim_throughput/sweep{n}_vmapped_s", t_sweep * 1e6,
                    round(t_sweep, 2)))
    rows.append(row(f"sim_throughput/sweep{n}_speedup_x", 0.0,
                    round(t_seq / t_sweep, 2), target=SWEEP_SPEEDUP_TARGET,
                    cmp="ge"))
    if backend:
        rows += _backend_rows(n_cycles=400 if full else 200)
    return rows


if __name__ == "__main__":
    import argparse
    import json

    from benchmarks import common
    from repro.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", default=None, choices=("jnp", "pallas"),
                    help="run the topology smoke on this router backend and "
                         "report cycles/s for BOTH backends")
    ap.add_argument("--scaling", action="store_true",
                    help="mesh-scaling curve: naive vs fast vs fused "
                         "cycles/s per mesh size (8x4 .. 32x32; --full "
                         "adds 64x64; --smoke trims to the 2 smallest)")
    ap.add_argument("--json", default=None,
                    help="write rows (and the scaling curve) to this file")
    args = ap.parse_args()
    enable_compile_cache()
    dev = common.device()
    print(common.device_line(dev))
    print(common.CSV_HEADER)
    all_rows, curve, bad = [], [], []

    def _emit(r):
        all_rows.append(r)
        print(common.csv_line(r), flush=True)
        if r["ok"] is False:
            bad.append(r["name"])

    if not args.scaling or args.smoke:
        for r in bench(full=args.full, smoke=args.smoke,
                       backend=args.backend):
            _emit(r)
    if args.scaling:
        srows, curve = scaling_rows(full=args.full, smoke=args.smoke)
        for r in srows:
            _emit(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "full": args.full,
                       "device": dev, "scaling": curve, "rows": all_rows},
                      f, indent=1, default=str, sort_keys=True)
    if bad:
        raise SystemExit("failed targets: " + ", ".join(bad))
