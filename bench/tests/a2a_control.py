"""Read the numbers that decide ``correct`` for the ``a2a`` cell over many
seeds in one process, with the program as it is or broken on purpose.

    python3 bench/tests/a2a_control.py --control none|vc1|gate|burst|half \\
        --seconds 3 --seeds 11 22 33

``vc1`` runs the cell's schedule on the torus without its dateline VCs
(the reference too), which wedges on most rank orders; ``gate``,
``burst`` and ``half`` are the faults of :func:`planted`. Each seed prints
one line with its checks; a control has to read ``correct`` false. On the
chip it needs one chip; ``--cpu`` runs on the host instead.
"""
import argparse
import contextlib
import copy
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "torus4x4_vc2.a2a"


@contextlib.contextmanager
def planted(fault: str):
    """Break the program underneath the kind.

    ``gate``: one step of the first rank's first stream waits for one more
    received burst; ``burst``: that step's burst is one beat longer;
    ``half``: every call simulates half its cycles; ``none``: nothing."""
    from repro.core.noc import collective_traffic as CT
    from repro.core.noc import sim as S

    a2a, run = CT.all_to_all, S.run

    def bad_a2a(topo, **kw):
        sched = a2a(topo, **kw)
        key = {"gate": "gate", "burst": "beats_seq"}[fault]
        arr = getattr(sched, key).copy()
        arr[int(kw["order"][0]), 0, 5] += 1
        return dataclasses.replace(sched, **{key: arr})

    def half(sim, n_cycles, state=None):
        return run(sim, n_cycles // 2, state=state)

    if fault == "half":
        S.run = half
    elif fault in ("gate", "burst"):
        CT.all_to_all = bad_a2a
    try:
        yield
    finally:
        CT.all_to_all, S.run = a2a, run


def loaded(n_vcs: int | None = None, **traffic) -> tuple:
    """The cell's files, with the fabric's VC count and traffic fields
    replaced where given."""
    from bench.lib import harness

    bench, cell, config, traffic_ = harness.load_cell(CELL)
    config = copy.deepcopy(config)
    if n_vcs is not None:
        config["fabric"]["n_vcs"] = n_vcs
    return bench, cell, config, dict(traffic_, **traffic)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", choices=("none", "vc1", "gate", "burst", "half"),
                    default="none")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    platforms = os.environ.get("JAX_PLATFORMS")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from bench.lib import harness

    files = loaded(1 if args.control == "vc1" else None)
    for seed in args.seeds:
        with planted(args.control):
            out = harness.run_cell(CELL, seed, args.seconds, False,
                                   t_start=time.perf_counter(), loaded=files,
                                   require_tpu=not args.cpu)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": out["correct"], "failed": out["failed"],
                          "checks": {k: v["value"] for k, v in out["checks"].items()},
                          "metrics": out["metrics"], "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
