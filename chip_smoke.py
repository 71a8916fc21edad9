"""Smoke check of the FlooNoC simulator on one TPU chip.

Drives the simulator through its user entry points
(``FabricSpec.lower`` -> ``sim.build_sim`` -> ``sim.run`` -> ``sim.stats``)
on the chip and holds every result to the same simulation run on the host
CPU in this process, bit for bit:

* ``mesh8x4``: the paper's 32-tile mesh (``preset("mesh", big=True)``)
  under the uniform 8 kB x 4 DMA workload, 2000 cycles, default params
  (jnp backend, fast step). Run twice from fresh states: the two chip runs
  must agree as well.
* ``mesh32x32``: the same workload on a 32x32 mesh, 200 cycles.
* ``torus4x4_vc2``: a 4x4 torus with two virtual channels, 1000 cycles.
* ``pallas8x4``: ``mesh8x4`` on the compiled Pallas router kernels
  (``backend="pallas"``), against the jnp chip run.

Compile seconds and steady cycles/s are printed per phase, labelled with
the device; they are informational, not a benchmark. The last line of
standard output is ``{"ok": true, "device": {...}}`` only when every
phase matched; otherwise the script exits non-zero without it. With no
TPU it exits non-zero at once.

``--four-chips`` runs only the multi-device path: ``dse.run_dse``, which
round-robins its compile groups over all four devices of a host, against
each point swept alone on device 0, and checks that each group's final
state lives on the device it was given.

Usage::

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DMA = dict(transfer_kb=8, n_txns=4)  # the paper-scale uniform DMA load


def _equal_trees(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _equal_stats(a: dict, b: dict) -> bool:
    import numpy as np

    return a.keys() == b.keys() and all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


def _build(spec, **param_overrides):
    from repro.core.noc import sim as S
    from repro.core.noc import traffic as T

    topo, params = spec.lower()
    params = dataclasses.replace(params, **param_overrides)
    wl = T.dma_workload(topo, "uniform", **DMA)
    return S.build_sim(topo, params, wl)


def _timed_run(sim, n_cycles: int):
    import jax

    from repro.core.noc import sim as S

    st0 = sim.init_state()
    jax.block_until_ready(st0)
    t0 = time.perf_counter()
    st = S.run(sim, n_cycles, state=st0)
    jax.block_until_ready(st)
    return st, time.perf_counter() - t0


def _on_device(st, dev) -> bool:
    import jax

    return all(leaf.devices() == {dev} for leaf in jax.tree.leaves(st))


def _report(name: str, n_cycles: int, kind: str, t_first: float,
            t_steady: float, checks: dict) -> None:
    """Print a phase's informational timings and its checks; raise if any
    check failed."""
    print(f"phase {name}: cycles={n_cycles} device_kind={kind!r} "
          f"first_run_s={t_first:.3f} steady_s={t_steady:.3f} "
          f"steady_cycles_per_s={n_cycles / t_steady:.1f} (informational)",
          flush=True)
    print(f"phase {name}: " + " ".join(f"{k}={v}" for k, v in checks.items()),
          flush=True)
    failed = [k for k, v in checks.items() if not v]
    if failed:
        raise AssertionError(f"{name}: failed checks {failed}")


def run_phase(name: str, spec, n_cycles: int, chip, cpu, **overrides):
    """Run ``spec`` on ``chip`` twice and on ``cpu`` once; return the chip
    sim and final state after checking that all three agree."""
    import jax

    from repro.core.noc import sim as S

    with jax.default_device(chip):
        sim = _build(spec, **overrides)
        st, t_first = _timed_run(sim, n_cycles)
        st2, t_steady = _timed_run(sim, n_cycles)  # same jit, fresh state
    with jax.default_device(cpu):
        sim_c = _build(spec, **overrides)
        st_c = S.run(sim_c, n_cycles)
    checks = {
        "on_chip": _on_device(st, chip),
        "on_cpu": _on_device(st_c, cpu),
        "rerun_equal": _equal_trees(S.canonical_state(sim, st, scrub=True),
                                    S.canonical_state(sim, st2, scrub=True)),
        "state_equals_cpu": _equal_trees(
            S.canonical_state(sim, st, scrub=True),
            S.canonical_state(sim_c, st_c, scrub=True)),
        "stats_equal_cpu": _equal_stats(S.stats(sim, st), S.stats(sim_c, st_c)),
        "beats_delivered": int(S.stats(sim, st)["beats_rcvd"].sum()) > 0,
    }
    _report(name, n_cycles, chip.device_kind, t_first, t_steady, checks)
    return sim, st


def one_chip(chip, cpu) -> None:
    """The four single-chip phases; raises on the first mismatch."""
    import jax

    from repro.core.noc import sim as S
    from repro.core.noc.spec import FabricSpec, preset

    mesh8x4 = preset("mesh", big=True)
    sim_j, st_j = run_phase("mesh8x4", mesh8x4, 2000, chip, cpu)
    run_phase("mesh32x32", FabricSpec(topology="mesh", nx=32, ny=32), 200,
              chip, cpu)
    run_phase("torus4x4_vc2",
              FabricSpec(topology="torus", nx=4, ny=4, n_vcs=2), 1000,
              chip, cpu)

    with jax.default_device(chip):
        sim_p = _build(mesh8x4, backend="pallas")
        hlo = sim_p._scan_fn(2000, with_trace=False).lower(
            sim_p.init_state()).as_text()
        st_p, t_first = _timed_run(sim_p, 2000)
        _, t_steady = _timed_run(sim_p, 2000)
    checks = {
        "compiled_kernel": "tpu_custom_call" in hlo,
        "state_equals_jnp": _equal_trees(
            S.canonical_state(sim_p, st_p, scrub=True),
            S.canonical_state(sim_j, st_j, scrub=True)),
        "stats_equal_jnp": _equal_stats(S.stats(sim_p, st_p),
                                        S.stats(sim_j, st_j)),
    }
    _report("pallas8x4", 2000, chip.device_kind, t_first, t_steady, checks)


def four_chip_specs():
    """One point per fabric variant of the stock DSE grid: four compile
    groups, one per device of a four-chip host."""
    from repro.core.noc import dse

    seen, specs = set(), []
    for sp in dse.default_grid():
        if sp.group_key() not in seen and sp.workload == "uniform":
            seen.add(sp.group_key())
            specs.append(dataclasses.replace(sp, transfer_kb=1, n_txns=2))
    return specs[:4]


def four_chips(devices) -> None:
    """``run_dse``, which round-robins its compile groups over
    ``jax.devices()`` (here ``devices``), against each point swept alone
    on ``devices[0]``: identical final states, each group on the device
    it was given."""
    import jax

    from repro.core.noc import dse
    from repro.core.noc import sim as S

    specs = four_chip_specs()
    jobs = dse.build_jobs(specs)
    t0 = time.perf_counter()
    multi = dse.run_dse(specs, workers=1, return_states=True)
    t_multi = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.default_device(devices[0]):
        single = []
        for sp, res in zip(specs, multi):
            topo, params = sp.lower()
            wl = sp.build_workload(topo)
            sim = S.build_sim(topo, params, wl)
            single.append(S.run_sweep(sim, [wl], res["n_cycles_run"])[0])
    t_single = time.perf_counter() - t0
    placed, equal = [], []
    for j, (_, _, members) in enumerate(jobs):
        dev = devices[j % len(devices)]
        for i, _, _ in members:
            placed.append(_on_device(multi[i]["state"], dev))
            equal.append(_equal_trees(multi[i]["state"], single[i])
                         and _on_device(single[i], devices[0]))
    delivered = all(r["delivered"] for r in multi)
    print(f"four-chips: {len(specs)} points in {len(jobs)} groups over "
          f"{len(devices)} devices; run_s={t_multi:.3f} vs each point alone "
          f"on device 0 {t_single:.3f} (informational)", flush=True)
    print(f"four-chips: per_point_equal={equal} on_assigned_device={placed} "
          f"delivered={delivered}", flush=True)
    if not (all(equal) and all(placed) and delivered):
        raise AssertionError("four-chips: run_dse over several devices "
                             "disagrees with one device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the multi-device run_dse check (4 devices)")
    args = ap.parse_args()

    # the reference runs need the host CPU backend next to the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    from benchmarks import common
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    chip = devices[0]
    device = common.device()
    print(common.device_line(device), flush=True)
    if chip.platform != "tpu":
        print("chip_smoke: no TPU; this check runs on the chip only",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({n_cached} entries at start)", flush=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(devices)
        else:
            one_chip(chip, jax.devices("cpu")[0])
    except Exception:
        traceback.print_exc()
        return 1
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"total_s={time.perf_counter() - t0:.1f} compile cache entries at "
          f"end: {n_cached}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
