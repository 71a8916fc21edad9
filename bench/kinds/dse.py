"""``dse`` traffic: whole design-space passes, ``dse.run_dse`` over the
configuration's grid of fabrics, patterns and sizes, as ``noc_explore
--dse`` runs it for users.

One call is one pass: every point simulated and scored, all rows on the
host and every returned state ready. Set-up makes one warm pass. The grid
is the same for every seed; ``--seed`` draws the pass that is checked and,
in each compile group (one per fabric of the grid), the point held to the
plain reference. After the window the check holds:

- each checked point's final state and statistics to the reference
  simulator run for its group's budget, its workload to the frozen
  pattern lowering, and its whole row to the frozen scoring
  (``bench/reference/dse.py``);
- every point of every pass: its simulated cycle count to its group's
  budget, its state to the chip its group was sent to (group ``j`` on
  device ``j`` mod the device count), the row fields that need no
  simulation to the frozen models, and its row to the first pass's.

The window's clock stops while the check copies states and reads where
they lie. A traced run traces its passes with the profiler's Python tracer
off: a pass is mostly host Python (route walks, table builds), which that
tracer would slow and fill with events.
"""
from __future__ import annotations

import contextlib
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from bench.lib import check, drivers
from bench.reference import dse as RD
from bench.reference import sim as RS
from bench.reference import topology as RT

WORKLOAD_FIELDS = ("narrow_rate", "narrow_dst", "dma_dst", "dma_alt_dst", "dma_txns",
                   "dma_beats", "dma_write")


def grid(config: dict) -> list[tuple[int, dict]]:
    """``(group, spec fields)`` of every point, in the grid's order."""
    return [(g, {**entry["fabric"], "workload": p, "transfer_kb": kb, "n_txns": txns})
            for g, entry in enumerate(config["grid"])
            for p in entry["patterns"] for kb, txns in config["sizes"]]


def checked_points(config: dict, seed: int, per_group: int) -> list[int]:
    """Indices of the points checked against the reference: ``per_group``
    of each group, drawn from the seed."""
    points = grid(config)
    out = []
    for g in range(len(config["grid"])):
        members = [k for k, (h, _) in enumerate(points) if h == g]
        out += [members[j] for j in drivers.sample(seed, 10 + g, len(members), per_group)]
    return out


class Tracer(drivers.Tracer):
    """The harness's tracer, with no Python function events and no program
    protos in the trace."""

    @contextlib.contextmanager
    def tracing(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with drivers.span("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


class Driver(drivers.Driver):
    """Repeated ``run_dse`` passes over one grid of spec points."""

    def setup(self):
        from repro.core.noc.spec import FabricSpec

        with self.phase("lower"):
            self.points = grid(self.config)
            self.specs = [FabricSpec(**p) for _, p in self.points]
            self.fabs = [RT.build(e["fabric"]) for e in self.config["grid"]]
            self.ws = [RD.point_workload(self.fabs[g], p) for g, p in self.points]
            self.dse_kw = dict(self.config.get("run_dse", {}))
            fixed = self.dse_kw.get("n_cycles")
            self.budget = [fixed or max(RD.cycles_budget(w) for (h, _), w in
                                        zip(self.points, self.ws) if h == g)
                           for g in range(len(self.fabs))]
            self.checked = checked_points(self.config, self.seed,
                                          self.traffic["checked_per_group"])
        self.devs = jax.devices()
        self.rows, self.kept = [], {}
        self.misplaced = self.cycle_gap = 0
        # one pass compiles or loads all the groups' programs; a second read
        # like the window's passes, so it would only lengthen set-up
        with self.phase("first_call"):
            t0 = time.perf_counter()
            rows, states = self._pass()
            self.call_s = time.perf_counter() - t0
            self._keep(rows, states, False)

    def _pass(self):
        from repro.core.noc import dse

        with drivers.span("bench.pass"):
            rows = dse.run_dse(self.specs, return_states=True, **self.dse_kw)
            states = [r.pop("state") for r in rows]
            jax.block_until_ready(states)
        return rows, states

    def _keep(self, rows, states, sampled: bool):
        """What the check needs of one pass: its rows, where each state
        lies, its simulated cycles and, on the sampled pass, the checked
        points' states and statistics."""
        from repro.core.noc import sim as S

        self.rows.append(rows)
        cycles = jax.device_get([st.cycle for st in states])
        for (g, _), st, cyc in zip(self.points, states, cycles):
            want = self.devs[g % len(self.devs)]
            on = {d for leaf in jax.tree.leaves(st) for d in leaf.devices()}
            self.misplaced += on != {want}
            self.cycle_gap += abs(int(cyc) - self.budget[g])
        if not sampled:
            return
        for k in self.checked:
            if k >= len(states):
                continue
            topo, params = self.specs[k].lower()
            wl = self.specs[k].build_workload(topo)
            sim = S.build_sim(topo, params, wl)
            self.kept[k] = (check.flat_state(states[k]), S.stats(sim, states[k]), wl,
                            rows[k])

    def window(self, seconds, tracer):
        """The measured window; in a traced run, instead, the traced passes
        alone, each then checked as the window's are. A traced run prints
        only per-layer metrics, and stopping the profiler after a pass of
        some ten million device ops takes about three minutes."""
        if not tracer.dir:
            return super().window(seconds, tracer)
        self.samples, passes = [0], []
        t0 = time.perf_counter()
        with Tracer(tracer.dir, tracer.calls).tracing():
            for _ in range(tracer.calls):
                passes.append(self._pass())
            dt = time.perf_counter() - t0
        for i, (rows, states) in enumerate(passes):
            self._keep(rows, states, i in self.samples)
        self.calls = tracer.calls
        return {"seconds": dt, "attempted": self.calls, **self.work(self.calls)}

    def pick_samples(self, n):
        return drivers.sample(self.seed, 3, n, 1)

    def call(self, i) -> float:
        rows, states = self._pass()
        t0 = time.perf_counter()
        self._keep(rows, states, i in self.samples)
        return time.perf_counter() - t0

    def work(self, calls):
        cycles = sum(self.budget[g] for g, _ in self.points)
        return {"points": calls * len(self.points), "fabric_cycles": calls * cycles}

    def release(self):
        """Nothing of the program's stays on the device after a pass."""

    def _reference(self, k: int) -> dict:
        """Point ``k`` simulated by the reference on the host CPU for its
        group's budget (the points run in threads of their own)."""
        (g, p), fab = self.points[k], self.fabs[self.points[k][0]]
        q = {**RD.DEFAULTS, **p}
        with jax.default_device(self.cpu):
            ref = RS.Reference(fab, q["n_channels"], q["n_vcs"], self.ws[k])
            return ref.run(ref.init_state(), self.budget[g])

    def check(self) -> tuple[dict, int]:
        first = self.rows[0]
        missing = sum(abs(len(rows) - len(self.points)) for rows in self.rows)
        differ = sum(r != f for rows in self.rows[1:] for r, f in zip(rows, first))
        static = 0
        for (g, p), r in zip(self.points, first):
            want = RD.static_fields(self.fabs[g], p, self.budget[g])
            static += sum(r.get(f) != v for f, v in want.items())
        with ThreadPoolExecutor(max(1, len(self.kept))) as pool:
            refs = dict(zip(self.kept, pool.map(self._reference, self.kept)))
        bad = dict.fromkeys(("state", "stats", "row", "workload"), 0)
        failed = 0
        for k, (flat, st_prog, wl, row_prog) in self.kept.items():
            (g, p), w, out = self.points[k], self.ws[k], refs[k]
            fab = self.fabs[g]
            st_ref = RS.stats(out, fab.n_tiles, fab.n_hbm)
            row_ref = RD.row(fab, p, w, st_ref, self.budget[g])
            n = {"state": check.state_mismatch(flat, out),
                 "stats": check.stats_mismatch(st_prog, st_ref),
                 "row": sum(row_prog.get(f) != v for f, v in row_ref.items()),
                 "workload": sum(not np.array_equal(np.asarray(getattr(wl, f)), np.asarray(w[f]))
                                 for f in WORKLOAD_FIELDS)}
            for key, v in n.items():
                bad[key] += v
            failed += any(n.values())
        return {"unchecked": (len(self.checked) - len(self.kept), 0),
                **{f"{key}_mismatch": (v, 0) for key, v in bad.items()},
                "cycle_gap": (self.cycle_gap, 0),
                "placement_mismatch": (self.misplaced, 0),
                "static_row_mismatch": (static, 0),
                "pass_mismatch": (differ, 0),
                "rows_missing": (missing, 0)}, failed
