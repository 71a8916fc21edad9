"""Small cells of every traffic kind, sized for a CPU test run; the
controls that break one guarantee of the simulated fabric; and the faults
planted in the timed path that ``correct`` has to catch."""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import json
import time

from bench.lib import harness

SMALL = {
    "floonoc8x4.perm4_dma": ({"fabric": {"topology": "mesh", "nx": 4, "ny": 4}},
                             {"chunk_cycles": [{"max_routers": 4096, "cycles": 400}],
                              "txns_per_stream": 4096}),
    "floonoc8x4.fig8_sweep": ({"fabric": {"topology": "mesh", "nx": 4, "ny": 2}},
                              {"patterns": ["uniform", "neighbor", "tiled-matmul"],
                               "burst_kb": [1, 4], "cycles_per_call": 640}),
    # the shape of dse.default_grid(smoke=True): 2 fabrics x 2 patterns x 1
    # size; one worker process, since the CPU backend would otherwise fan
    # the groups out over a pool that cannot return states
    "dse_grid.default": ({"grid": [
        {"fabric": {"topology": "mesh", "nx": 4, "ny": 4}, "patterns": ["uniform", "neighbor"]},
        {"fabric": {"topology": "torus", "nx": 4, "ny": 4, "n_vcs": 2},
         "patterns": ["uniform", "neighbor"]}],
        "sizes": [[1, 2]], "run_dse": {"workers": 1, "n_cycles": None}}, {}),
}


def small(name: str) -> tuple:
    """The cell's files with the small sizes above merged in."""
    bench, cell, config, traffic = harness.load_cell(name)
    config.update(copy.deepcopy(SMALL[name][0]))
    traffic.update(copy.deepcopy(SMALL[name][1]))
    return bench, cell, config, traffic


def run(loaded: tuple, seed: int, seconds: float = 1.0, **kw) -> dict:
    return harness.run_cell(loaded[1]["name"], seed, seconds, False,
                            t_start=time.perf_counter(), loaded=loaded, **kw)


@contextlib.contextmanager
def control(kind: str):
    """Swap a guarantee of the simulated fabric out of the program.

    ``wormhole``: routers forget their wormhole locks every cycle, so the
    beats of bursts that share an output port may interleave. ``fused8``
    (applied to a configuration, see :func:`with_control`) is the program's
    own super-step path, which samples endpoint interaction every 8 cycles.
    """
    if kind != "wormhole":
        yield
        return
    from repro.core.noc import sim as S

    step = S.Sim.step

    def leaky(self, st, wl=None):
        free = dataclasses.replace(
            st.fabric, wh_lock=st.fabric.wh_lock * 0 - 1)
        return step(self, dataclasses.replace(st, fabric=free), wl)

    S.Sim.step = leaky
    try:
        yield
    finally:
        S.Sim.step = step


@contextlib.contextmanager
def fault(kind: str, point: int = 0):
    """Break the timed path underneath the harness.

    ``unchanged``: every simulation call returns its states as they came
    (a chained chunk its input, a sweep its fabrics' initial states).
    ``half``: a sweep leaves every other fabric of its batch at its
    initial state. ``altered``: one endpoint's received-beat count is off
    by one in every returned state. A design-space pass (``run_dse``)
    runs its groups through the sweep and so takes those three; besides,
    ``point``: the state it returns for point ``point`` is altered after
    scoring; ``area``: that point's row reads a larger area;
    ``one_device``: every group runs on the first device.
    """
    if kind in ("point", "area", "one_device"):
        with _dse_fault(kind, point):
            yield
        return
    from repro.core.noc import sim as S

    run, sweep = S.run, S.run_sweep

    def alter(st):
        eps = dataclasses.replace(st.eps, beats_rcvd=st.eps.beats_rcvd.at[0].add(1))
        return dataclasses.replace(st, eps=eps)

    def bad_run(sim, n_cycles, state=None):
        if kind == "unchanged":
            return state
        return alter(run(sim, n_cycles, state=state))

    def bad_sweep(sim, wls, n_cycles):
        if kind == "unchanged":
            return [sim.init_state(w) for w in wls]
        if kind == "half":
            done = iter(sweep(sim, wls[::2], n_cycles))
            return [next(done) if k % 2 == 0 else sim.init_state(w)
                    for k, w in enumerate(wls)]
        return [alter(st) for st in sweep(sim, wls, n_cycles)]

    S.run, S.run_sweep = bad_run, bad_sweep
    try:
        yield
    finally:
        S.run, S.run_sweep = run, sweep


@contextlib.contextmanager
def _dse_fault(kind: str, point: int):
    import jax

    from repro.core.noc import dse

    run_dse, devices = dse.run_dse, jax.devices

    def bad_run_dse(specs, **kw):
        if kind == "one_device":
            jax.devices = lambda *a: devices(*a)[:1]
            try:
                return run_dse(specs, **kw)
            finally:
                jax.devices = devices
        rows = run_dse(specs, **kw)
        if kind == "area":
            rows[point]["area_mm2"] += 1e-3
        else:
            st = rows[point]["state"]
            eps = dataclasses.replace(st.eps, beats_rcvd=st.eps.beats_rcvd.at[0].add(1))
            rows[point]["state"] = dataclasses.replace(st, eps=eps)
        return rows

    dse.run_dse = bad_run_dse
    try:
        yield
    finally:
        dse.run_dse = run_dse


def with_control(loaded: tuple, kind: str) -> tuple:
    """The cell's files with the control's configuration change."""
    if kind != "fused8":
        return loaded
    bench, cell, config, traffic = copy.deepcopy(loaded)
    config["fabric"]["fused_cycles"] = 8
    return bench, cell, config, traffic


def dumps(out: dict) -> str:
    return json.dumps(out["checks"])
