"""Read the numbers that decide ``correct`` over many seeds in one process:
the program as it is, or with a control that breaks a guarantee.

    python3 bench/tests/control.py --workload <cell> --control none|fused8|wormhole \\
        --seconds 3 --seeds 11 22 33

``fused8`` runs the program's own super-step path (endpoint interaction
sampled every 8 cycles) and applies to cells of the ``run`` kind;
``wormhole`` makes every router forget its wormhole locks each cycle. Each
seed prints one line with its checks; a control has to read ``correct``
false. On the chip it needs the cell's chips; ``--cpu`` runs the small
sizes of ``bench/tests/cells.py`` on the host instead.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", choices=("none", "fused8", "wormhole"), default="none")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from bench.lib import harness
    from bench.tests import cells

    loaded = cells.small(args.workload) if args.cpu else harness.load_cell(args.workload)
    loaded = cells.with_control(loaded, args.control)
    for seed in args.seeds:
        with cells.control(args.control):
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t_start=time.perf_counter(), loaded=loaded,
                                   require_tpu=not args.cpu)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": out["correct"], "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
