import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)
# ^^ must run before ANY other import (jax locks the device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct stand-ins (no allocation), record memory/cost analysis and
roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --shape long_500k
  DRYRUN_XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m repro.launch.dryrun --reduced ...   # CI-scale
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, get_config, list_archs, shape_applicable
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh, make_reduced_mesh
from repro.launch.specs import (
    decode_input_schema,
    serve_needs_2d,
    train_input_schema,
)
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models import model as M
from repro.models.spec import struct_tree
from repro.optim.adamw import opt_state_schema
from repro.runtime import Runtime
from repro.sharding.partition import cache_rules, serve_rules, sharding_tree, train_rules

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def build_cell(cfg, shape, mesh, rt, variant: str = "baseline"):
    """Returns (fn, arg_structs: tuple, in_shardings: tuple, donate_argnums)."""
    psch = M.param_schema(cfg)
    if shape.kind == "train":
        rules = train_rules(mesh, variant if variant == "fsdp2d" else "baseline")
        p_sh = sharding_tree(psch, mesh, rules)
        osch = opt_state_schema(psch)
        o_sh = sharding_tree(osch, mesh, rules)
        bsch = train_input_schema(cfg, shape)
        b_sh = sharding_tree(bsch, mesh, rules)
        fn = make_train_step(cfg, rt, param_shardings=p_sh)
        args = (struct_tree(psch), struct_tree(osch), struct_tree(bsch))
        return fn, args, (p_sh, o_sh, b_sh), (0, 1), rules
    if shape.kind == "prefill":
        rules = serve_rules(mesh, shard_params_data=serve_needs_2d(cfg, mesh.shape["model"]))
        p_sh = sharding_tree(psch, mesh, rules)
        bsch = train_input_schema(cfg, shape)
        # prefill inputs: no targets needed, but extra args are harmless
        bsch = {k: v for k, v in bsch.items() if k not in ("targets", "loss_mask")}
        b_sh = sharding_tree(bsch, mesh, cache_rules(mesh))
        fn = make_prefill_step(cfg, rt)
        return fn, (struct_tree(psch), struct_tree(bsch)), (p_sh, b_sh), (), rules
    # decode
    seq_axes = ("data", "model") if shape.name == "long_500k" else "model"
    rules = serve_rules(
        mesh,
        shard_params_data=serve_needs_2d(cfg, mesh.shape["model"]) or variant == "serve2d",
    )
    crules = cache_rules(mesh, seq_axes=seq_axes)
    p_sh = sharding_tree(psch, mesh, rules)
    isch = decode_input_schema(cfg, shape, seq_shard=True,
                               quant=variant == "cache_int8")
    c_sh = sharding_tree(isch["cache"], mesh, crules)
    t_sh = sharding_tree(isch["tokens"], mesh, crules)
    fn = make_serve_step(cfg, rt)
    args = (struct_tree(psch), struct_tree(isch["cache"]), struct_tree(isch["tokens"]))
    rules.fallbacks.extend(crules.fallbacks)
    return fn, args, (p_sh, c_sh, t_sh), (1,), rules


def run_cell(arch: str, shape_name: str, multi_pod: bool, reduced: bool = False,
             rt_overrides: dict | None = None, variant: str = "baseline"):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    shape = SHAPES[shape_name]
    overrides = dict(rt_overrides or {})
    if variant == "fsdp2d" and shape.kind == "train":
        overrides.setdefault("batch_over_model", True)
        overrides.setdefault("gather_weights", True)
        if cfg.family == "moe":
            overrides.setdefault("moe_impl", "a2a")
    if variant == "a2a" and cfg.family == "moe":
        overrides.setdefault("moe_impl", "a2a")
    rt_overrides = overrides
    if reduced:
        import dataclasses

        shape = dataclasses.replace(
            shape, seq_len=min(shape.seq_len, 512),
            global_batch=max(min(shape.global_batch, 8), 8),
        )
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single", "status": "skipped",
                "reason": why}
    mesh = (make_reduced_mesh if reduced else make_production_mesh)(multi_pod=multi_pod)
    rt = Runtime(mesh=mesh, attn_impl="flash", remat=True,
                 **(rt_overrides or {}))
    n_dev = mesh.size
    t0 = time.time()
    fn, args, shardings, donate, rules = build_cell(cfg, shape, mesh, rt, variant)
    res = {
        "arch": arch,
        "shape": shape_name,
        "variant": variant,
        "mesh": "x".join(str(s) for s in mesh.devices.shape)
        + ("(pod,data,model)" if multi_pod else "(data,model)"),
        "n_devices": n_dev,
        "status": "ok",
    }
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
            lowered = jitted.lower(*args)
            res["t_lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            res["t_compile_s"] = round(time.time() - t1, 2)
        ma = compiled.memory_analysis()
        res["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_per_device_gb": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 1e9, 3),
        }
        roof = RL.analyze(compiled, cfg, shape, n_dev,
                          cf=rt.moe_capacity_factor or 2.0,
                          cache_quant=variant == "cache_int8")
        res["roofline"] = roof.to_dict()
        res["sharding_fallbacks"] = rules.fallbacks
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        res["status"] = "error"
        res["error"] = f"{type(e).__name__}: {e}"
        res["traceback"] = traceback.format_exc(limit=16)
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all", choices=["all", *SHAPES])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--reduced", action="store_true",
                    help="reduced configs + small mesh (CI)")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "fsdp2d", "a2a", "cache_int8", "serve2d"],
                    help="perf-hillclimb variant (EXPERIMENTS.md §Perf)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    out_dir = Path(args.out) if args.out else ART_DIR
    out_dir.mkdir(parents=True, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                r = run_cell(arch, shape, mp, reduced=args.reduced,
                             variant=args.variant)
                results.append(r)
                tag = f"{arch} x {shape} x {'multi' if mp else 'single'}"
                if args.variant != "baseline":
                    tag += f" [{args.variant}]"
                if r["status"] == "ok":
                    roof = r["roofline"]
                    print(
                        f"OK    {tag:60s} compile={r['t_compile_s']:7.1f}s "
                        f"hbm={r['memory']['peak_hbm_per_device_gb']:8.2f}GB "
                        f"bottleneck={roof['bottleneck']:10s} "
                        f"frac={roof['roofline_fraction']:.3f}",
                        flush=True,
                    )
                elif r["status"] == "skipped":
                    print(f"SKIP  {tag:60s} {r['reason'][:80]}", flush=True)
                else:
                    print(f"ERROR {tag:60s} {r['error'][:140]}", flush=True)
                suffix = "" if args.variant == "baseline" else f"__{args.variant}"
                fname = out_dir / f"{arch}__{shape}__{'multi' if mp else 'single'}{suffix}.json"
                fname.write_text(json.dumps(r, indent=2, default=str))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_err} errors of {len(results)} cells")
    sname = "summary.json" if args.variant == "baseline" else f"summary__{args.variant}.json"
    (out_dir / sname).write_text(json.dumps(results, indent=2, default=str))
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
