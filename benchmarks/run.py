"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus target/ok columns) and a
validation summary against the paper's published numbers.

Usage: PYTHONPATH=src python -m benchmarks.run [--full] [--smoke]
                                               [--json out.json]

``--smoke`` runs every benchmark at toy scale (tiny meshes, few cycles,
modules that support it via a ``smoke`` parameter) and fails only on
exceptions, not on missed paper targets — the CI bench-smoke gate.
``--json`` additionally writes all rows to a JSON file (CI artifact).
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger sweeps")
    ap.add_argument("--smoke", action="store_true",
                    help="toy scale, fail on exceptions only")
    ap.add_argument("--json", default=None, help="write rows to this JSON file")
    ap.add_argument("--only", default=None, help="substring filter on module name")
    args = ap.parse_args()

    from benchmarks import (
        collective_bench,
        fig7_latency,
        fig8_traffic,
        fig9_area_power,
        fig10_rob,
        fig11_hbm,
        table1_links,
        table2_occamy,
        table3_soa,
    )

    modules = [
        ("table1_links", table1_links),
        ("fig7_latency", fig7_latency),
        ("fig8_traffic", fig8_traffic),
        ("fig9_area_power", fig9_area_power),
        ("fig10_rob", fig10_rob),
        ("fig11_hbm", fig11_hbm),
        ("table2_occamy", table2_occamy),
        ("table3_soa", table3_soa),
        ("collective_bench", collective_bench),
    ]

    from benchmarks import common
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = common.device()
    print(common.device_line(dev))
    print(common.CSV_HEADER)
    n_checked = n_ok = 0
    failed = []
    all_rows = []
    for name, mod in modules:
        if args.only and args.only not in name:
            continue
        kwargs = {"full": args.full}
        params = inspect.signature(mod.bench).parameters
        if args.smoke and "smoke" in params:
            kwargs["smoke"] = True
        for r in mod.bench(**kwargs):
            all_rows.append({"module": name, **r})
            print(common.csv_line(r), flush=True)
            if r["ok"] is not None:
                n_checked += 1
                n_ok += bool(r["ok"])
                if not r["ok"]:
                    failed.append(r["name"])
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "full": args.full, "device": dev,
                       "rows": all_rows}, f, indent=1, default=str,
                      sort_keys=True)
    print(f"\n# paper-validation: {n_ok}/{n_checked} targets matched", flush=True)
    if failed:
        print("# failed targets:", ", ".join(failed))
        if not args.smoke:
            sys.exit(1)


if __name__ == "__main__":
    main()
