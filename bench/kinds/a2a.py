"""``a2a`` traffic: one whole all-to-all per call, the expert-parallel
dispatch of a mixture-of-experts layer with one rank per tile.

Set-up draws from ``--seed`` the permutation of the tiles that is the
rotation order (every seed gives the same sizes and lengths), builds the
program's schedule with ``collective_traffic.all_to_all``, lowers it with
``to_workload`` and warms the program. A call is ``sim.run`` of
``cycles_per_call`` cycles from the initial state: the whole exchange of
write bursts, each step gated on the previous step's receipt, and its
drain. It ends on ``block_until_ready`` of the whole state.

After the window the check holds:

- the program's schedule arrays to the frozen direct rotation of
  ``bench/reference/a2a.py``, element by element;
- a sampled call's final state and statistics to that reference simulated
  from reset for the same cycles;
- every call's receipts: each (tile, stream) ends with the bursts the
  schedule sends it, and each call simulates exactly its cycles.

The window's clock stops while the check copies those results. A traced
run also splits the traced call's device time by the scopes nested in the
router and generator layers (``noc.vc``, ``noc.sched``), which the
``vc.*`` and ``sched.*`` readers take from the window's ``scope_s``, and
by layer (``noc.router`` ... ``noc.inject``), printed to stderr.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys
import time

import jax
import numpy as np

from bench.lib import check, drivers, gen, scopes
from bench.lib import trace as tr
from bench.reference import a2a as RA
from bench.reference import sim as RS

ORDER_SALT = 16
SCHEDULE_FIELDS = (("dma_dst_seq", "dst_seq"), ("dma_gate", "gate"),
                   ("dma_beats_seq", "beats_seq"), ("dma_txns", "txns"))
# scopes nested under the step's layers that the traced run splits out
NESTED = ("noc.vc", "noc.sched")
_WRAP = re.compile(r"^[\w.]+\((.*)\)$")  # vmap(noc.vc) -> noc.vc


def rotation_order(seed: int, n_tiles: int) -> np.ndarray:
    """The tiles in the order the rotation visits them, drawn from the seed."""
    return gen.rng_of(seed, ORDER_SALT).permutation(n_tiles).astype(np.int32)


def nested_scopes(op_name: str) -> set[str]:
    """Every ``noc.*`` component of a name stack, transform wrappers
    (``vmap(...)``) taken off."""
    out = set()
    for part in op_name.split("/"):
        while (m := _WRAP.match(part)):
            part = m.group(1)
        if part.startswith(scopes.SCOPE_PREFIX):
            out.add(part)
    return out


def scope_split(pd, module: str, ops: dict[str, str]) -> dict[str, float]:
    """Device seconds of ``module``'s ops in the ``bench.window`` span,
    averaged over the chips: under each scope of ``NESTED``, and under
    each layer (the first ``noc.*`` component, ``scopes.scope_of``). Each
    instant of busy time goes to the first op that covers it, as in
    ``scopes.reduce``; a fused op goes by its root's ``op_name``. Nothing
    is returned where no instruction of the program carries a scope of
    ``NESTED``."""
    present = [s for s in NESTED if any(s in nested_scopes(n) for n in ops.values())]
    win = [s for s in scopes.spans(pd) if s[0] == tr.WINDOW]
    if not present or not win:
        return {}
    _, w0, w1 = win[0]
    per_dev = []
    for plane in pd.planes:
        if not (plane.name.startswith(tr.DEVICE_PREFIX)
                and plane.name[len(tr.DEVICE_PREFIX):].isdigit()):
            continue
        evs = sorted((s, e, name) for s, e, mod, name, _ in scopes.op_events(plane, w0, w1)
                     if mod == module)
        if not evs:
            continue
        got, covered = collections.Counter(), w0
        for s, e, name in evs:
            own = (max(e, covered) - max(s, covered)) * 1e-9
            covered = max(covered, e)
            op_name = ops.get(name, "")
            got[scopes.scope_of(op_name)] += own
            for sc in nested_scopes(op_name) & set(present):
                got[sc] += own
        per_dev.append(got)
    if not per_dev:
        return {}
    return {sc: sum(d[sc] for d in per_dev) / len(per_dev)
            for sc in set(present).union(*per_dev)}


class Driver(drivers.Driver):
    """Whole all-to-alls, each ``sim.run`` from the initial state."""

    def setup(self):
        from repro.core.noc import collective_traffic as CT
        from repro.core.noc import sim as S

        t = self.traffic
        self.cycles = t["cycles_per_call"]
        with self.phase("lower"):
            self._lower()
            order = rotation_order(self.seed, self.fab.n_tiles)
            sched = CT.all_to_all(self.topo, data_kb=t["data_kb"], streams=t["streams"],
                                  order=order, algo=t["algo"], n_vcs=self.params.n_vcs)
            self.wl = CT.to_workload(self.topo, sched)
            self.ref_sched = RA.schedule(order, self.fab.n_endpoints, t["streams"],
                                         t["data_kb"])
        with self.phase("build_sim"):
            self.sim = S.build_sim(self.topo, self.params, self.wl)
        self.kept, self.incomplete, self.cycle_gap, self.bad_calls = {}, 0, 0, set()
        for name in ("first_call", "second_call"):  # compile, then steady
            t0 = time.perf_counter()
            with self.phase(name):
                jax.block_until_ready(S.run(self.sim, self.cycles,
                                            state=self.sim.init_state()))
        self.call_s = time.perf_counter() - t0

    def pick_samples(self, n):
        return drivers.sample(self.seed, 3, n, 1)

    def call(self, i) -> float:
        from repro.core.noc import sim as S

        with drivers.span("bench.dispatch"):
            st = S.run(self.sim, self.cycles, state=self.sim.init_state())
        with drivers.span("bench.block"):
            jax.block_until_ready(st)
        t0 = time.perf_counter()
        rx, cycle = jax.device_get((st.eps.rx_bursts, st.cycle))
        missing = int(np.sum(rx != self.ref_sched["expect_rx"]))
        gap = abs(int(cycle) - self.cycles)
        self.incomplete += missing
        self.cycle_gap += gap
        if missing or gap:
            self.bad_calls.add(i)
        if i in self.samples:
            self.kept[i] = (check.flat_state(st), S.stats(self.sim, st))
        return time.perf_counter() - t0

    def window(self, seconds, tracer):
        out = super().window(seconds, tracer)
        self.attempted = out["attempted"]
        if tracer.dir:
            with self.phase("scope_split"):
                out["scope_s"] = self._scope_split(tracer.dir)
            cycles = tracer.calls * self.cycles
            for sc, t in sorted(out["scope_s"].items()):
                print(f"bench: scope {sc} {1e6 * t / cycles:.4f} us per cycle",
                      file=sys.stderr)
        return out

    def _scope_split(self, log_dir: str) -> dict[str, float]:
        files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            return {}
        fn = self.sim._scan_fn(self.cycles, with_trace=False)
        module, ops = scopes.op_names(fn.lower(self.sim.init_state()).compile().as_text())
        return scope_split(tr._load(files[0]), module, ops)

    def work(self, calls):
        return {"fabric_cycles": calls * self.cycles, "fabrics": 1,
                "cycles_per_call": self.cycles}

    def release(self):
        self.sim = None

    def check(self) -> tuple[dict, int]:
        sched_bad = 0
        for prog, ref in SCHEDULE_FIELDS:
            a, b = np.asarray(getattr(self.wl, prog)), self.ref_sched[ref]
            sched_bad += int(np.sum(a != b)) if a.shape == b.shape else b.size
        bad_state = bad_stats = 0
        bad = set(self.bad_calls)
        with jax.default_device(self.cpu):
            ref = RA.Reference(self.fab, self.params.n_channels, self.params.n_vcs,
                               self.ref_sched)
            out = ref.run(ref.init_state(), self.cycles) if self.kept else None
            for i, (flat, st_prog) in self.kept.items():
                b = check.state_mismatch(flat, out)
                s = check.stats_mismatch(st_prog, RS.stats(out, self.fab.n_tiles,
                                                           self.fab.n_hbm))
                bad_state, bad_stats = bad_state + b, bad_stats + s
                if b or s:
                    bad.add(i)
        # failed counts the window's calls; the checks count the traced ones too
        failed = sum(i < self.attempted for i in bad)
        return {"unchecked": (len(self.samples) - len(self.kept), 0),
                "schedule_mismatch": (sched_bad, 0),
                "state_mismatch": (bad_state, 0), "stats_mismatch": (bad_stats, 0),
                "incomplete": (self.incomplete, 0),
                "cycle_gap": (self.cycle_gap, 0)}, failed
