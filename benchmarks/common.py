"""Shared benchmark plumbing: timing + row construction + paper targets."""
from __future__ import annotations

import time


def timed(fn, *args, warmup: int = 1, iters: int = 3):
    for _ in range(warmup):
        out = fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    dt = (time.perf_counter() - t0) / iters
    return out, dt * 1e6  # us


def row(name: str, us: float, derived, target=None, rel_tol: float = 0.15,
        cmp: str = "approx") -> dict:
    ok = None
    if target is not None and isinstance(derived, (int, float)):
        if cmp == "approx":
            ok = abs(derived - target) <= rel_tol * abs(target)
        elif cmp == "ge":
            ok = derived >= target
        elif cmp == "le":
            ok = derived <= target
    return {"name": name, "us_per_call": round(us, 1), "derived": derived,
            "target": target, "ok": ok}


CSV_HEADER = "name,us_per_call,derived,target,ok"


def device() -> dict:
    """The device the timings ran on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_line(dev: dict) -> str:
    """Comment line that labels a run's timing rows with their device."""
    return (f"# device: platform={dev['platform']} kind={dev['kind']} "
            f"count={dev['count']}")


def csv_line(r: dict) -> str:
    """One CSV line per row dict (blank target/ok when unset) — the shared
    print format of benchmarks.run and the standalone CLIs."""
    tgt = "" if r["target"] is None else r["target"]
    ok = "" if r["ok"] is None else r["ok"]
    return f"{r['name']},{r['us_per_call']},{r['derived']},{tgt},{ok}"
