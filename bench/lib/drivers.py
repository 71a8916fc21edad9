"""The timed paths, one per traffic kind. Each driver builds its cell's
inputs from the seed, warms up every shape its window uses (set-up), runs
the measured window, and afterwards holds what the window produced to the
plain reference.

``run``: ``sim.run`` chunks chained from state to state.
``sweep``: repeated ``sim.run_sweep`` calls over a batch of fabrics.

The window's clock stops while the check copies a sampled call's states
to the host: the rate counts only the simulation calls and their waits.
"""
from __future__ import annotations

import contextlib
import statistics
import sys
import time

import jax

from bench.lib import check, gen
from bench.reference import sim as RS
from bench.reference import topology as RT

span = jax.profiler.TraceAnnotation


def program_workload(w: dict):
    """The program's ``Workload`` for generated workload arrays."""
    from repro.core.noc.endpoints import Workload

    return Workload(narrow_rate=w["narrow_rate"], narrow_dst=w["narrow_dst"],
                    dma_dst=w["dma_dst"], dma_alt_dst=w["dma_alt_dst"],
                    dma_txns=w["dma_txns"], dma_beats=w["dma_beats"],
                    dma_write=w["dma_write"], n_tiles=w["n_tiles"])


def chunk_cycles(traffic: dict, n_routers: int) -> int:
    """Cycles per simulation call from the traffic's size table."""
    for row in traffic["chunk_cycles"]:
        if n_routers <= row["max_routers"]:
            return row["cycles"]
    raise ValueError(f"no chunk length for a fabric of {n_routers} routers")


def sample(seed: int, salt: int, n: int, k: int) -> list[int]:
    """``k`` distinct indices of ``range(n)`` drawn from the seed."""
    rng = gen.rng_of(seed, salt)
    return sorted(int(i) for i in rng.choice(n, size=min(k, n), replace=False))


class Tracer:
    """Profiles calls made after the measured window has closed, inside one
    ``bench.window`` span, so that the window itself runs untraced."""

    def __init__(self, log_dir: str | None, calls: int):
        self.dir, self.calls = log_dir, calls

    @contextlib.contextmanager
    def tracing(self):
        jax.profiler.start_trace(self.dir)
        try:
            with span("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


class Phases(dict):
    """Seconds spent in each named phase, for the run's diagnostics."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        self[name] = self.get(name, 0.0) + time.perf_counter() - t0


class Driver:
    """Common shape of a driver; see the module docstring."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cpu = jax.devices("cpu")[0]
        self.phase = Phases()

    def _lower(self):
        from repro.core.noc.spec import FabricSpec

        self.spec = FabricSpec(**self.config["fabric"])
        self.topo, self.params = self.spec.lower()
        self.fab = RT.build(self.config["fabric"])
        if self.topo.n_endpoints != self.fab.n_endpoints:
            raise ValueError("the program's fabric and the reference's differ in "
                             f"endpoints: {self.topo.n_endpoints} != "
                             f"{self.fab.n_endpoints}")

    def reference(self, w: dict) -> RS.Reference:
        return RS.Reference(self.fab, self.params.n_channels, self.params.n_vcs, w)

    def window(self, seconds: float, tracer: Tracer) -> dict:
        """Calls until ``seconds`` have passed; then, with a tracer, the
        traced calls."""
        n_est = max(1, int(seconds / self.call_s))
        self.samples = self.pick_samples(max(1, int(0.8 * n_est)))
        i, paused, per_call = 0, 0.0, []
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            kept = self.call(i)  # seconds spent on the check's copies
            paused += kept
            per_call.append(time.perf_counter() - t - kept)
            i += 1
            if time.perf_counter() - t0 - paused >= seconds:
                break
        dt = time.perf_counter() - t0 - paused
        slowest = max(range(i), key=per_call.__getitem__)
        print(f"bench: {i} calls, per call median {statistics.median(per_call):.6f} s, "
              f"min {min(per_call):.6f} s, max {per_call[slowest]:.6f} s (call "
              f"{slowest}); check copies {paused:.3f} s off the clock", file=sys.stderr)
        attempted, work = i, self.work(i)
        if tracer.dir:
            with tracer.tracing():
                for _ in range(tracer.calls):
                    self.call(i)
                    i += 1
        self.calls = i
        return {"seconds": dt, "attempted": attempted, **work}


class RunDriver(Driver):
    """One long simulation, advanced in fixed-length ``sim.run`` chunks."""

    def setup(self):
        from repro.core.noc import sim as S

        with self.phase("lower"):
            self._lower()
            self.w = gen.run_workload(self.fab, self.traffic)
            self.start = gen.narrow_start(self.fab, self.seed)
        with self.phase("build_sim"):
            self.sim = S.build_sim(self.topo, self.params, program_workload(self.w))
            self.chunk = chunk_cycles(self.traffic, self.topo.n_routers)
            st = check.with_leaves(self.sim.init_state(), self.start)
            self.init = check.flat_state(st)
        for name in ("first_call", "second_call"):  # compile or load, then steady
            t0 = time.perf_counter()
            with self.phase(name):
                st = S.run(self.sim, self.chunk, state=st)
                jax.block_until_ready(st)
        self.call_s = time.perf_counter() - t0
        self.st, self.warm_cycles = st, 2 * self.chunk
        self.kept = {}

    def pick_samples(self, n):
        return sample(self.seed, 3, n, 2)

    def call(self, i) -> float:
        from repro.core.noc import sim as S

        t0 = time.perf_counter()
        keep = i in self.samples
        if keep:
            before = check.flat_state(self.st)
        t1 = time.perf_counter()
        with span("bench.dispatch"):
            self.st = S.run(self.sim, self.chunk, state=self.st)
        with span("bench.block"):
            jax.block_until_ready(self.st)
        if not keep:
            return t1 - t0
        t2 = time.perf_counter()
        self.kept[i] = (before, check.flat_state(self.st), S.stats(self.sim, self.st))
        return t1 - t0 + time.perf_counter() - t2

    def work(self, calls):
        return {"fabric_cycles": calls * self.chunk, "fabrics": 1,
                "cycles_per_call": self.chunk}

    def release(self):
        self.final_cycle = int(self.st.cycle)
        self.st = self.sim = None

    def check(self) -> tuple[dict, int]:
        expect = self.warm_cycles + self.calls * self.chunk
        ref = self.reference(self.w)
        bad_state = bad_stats = failed = 0
        with jax.default_device(self.cpu):
            bad_state += check.state_mismatch(self.init, {**ref.init_state(), **self.start})
            for before, after, st_prog in self.kept.values():
                out = ref.run(check.canonical(before), self.chunk)
                b = check.state_mismatch(after, out)
                s = check.stats_mismatch(st_prog, RS.stats(out, self.fab.n_tiles,
                                                           self.fab.n_hbm))
                bad_state, bad_stats, failed = bad_state + b, bad_stats + s, failed + bool(b or s)
        return {"unchecked": (len(self.samples) - len(self.kept), 0),
                "state_mismatch": (bad_state, 0), "stats_mismatch": (bad_stats, 0),
                "cycle_gap": (abs(self.final_cycle - expect), 0)}, failed


class SweepDriver(Driver):
    """A batch of fabric configurations advanced together by ``run_sweep``,
    each call from fresh states."""

    def setup(self):
        from repro.core.noc import sim as S

        with self.phase("lower"):
            self._lower()
            self.ws = gen.sweep_workloads(self.fab, self.traffic, self.seed)
            self.wls = [program_workload(w) for w in self.ws]
        with self.phase("build_sim"):
            self.sim = S.build_sim(self.topo, self.params, self.wls[0])
        self.cycles = self.traffic["cycles_per_call"]
        for name in ("first_call", "second_call"):
            t0 = time.perf_counter()
            with self.phase(name):
                jax.block_until_ready(S.run_sweep(self.sim, self.wls, self.cycles))
        self.call_s = time.perf_counter() - t0
        self.kept = {}

    def pick_samples(self, n):
        """One call drawn from the seed; in it the heaviest fabric (largest
        bursts, uniform destinations) and one other drawn from the seed."""
        heavy = max(range(len(self.ws)), key=lambda k: (
            self.ws[k]["dma_beats"], bool((self.ws[k]["dma_dst"] == -2).any())))
        others = [k for k in range(len(self.ws)) if k != heavy]
        self.fabrics = [heavy, others[sample(self.seed, 4, len(others), 1)[0]]]
        return sample(self.seed, 3, n, 1)

    def call(self, i) -> float:
        from repro.core.noc import sim as S

        with span("bench.dispatch"):
            finals = S.run_sweep(self.sim, self.wls, self.cycles)
        with span("bench.block"):
            jax.block_until_ready(finals)
        if i not in self.samples:
            return 0.0
        t0 = time.perf_counter()
        self.kept = {k: (check.flat_state(finals[k]), S.stats(self.sim, finals[k]))
                     for k in self.fabrics}
        return time.perf_counter() - t0

    def work(self, calls):
        n = len(self.ws)
        return {"fabric_cycles": calls * n * self.cycles, "fabrics": n,
                "cycles_per_call": self.cycles}

    def release(self):
        self.sim = None

    def check(self) -> tuple[dict, int]:
        bad_state = bad_stats = failed = 0
        with jax.default_device(self.cpu):
            for k, (prog, st_prog) in self.kept.items():
                ref = self.reference(self.ws[k])
                out = ref.run(ref.init_state(), self.cycles)
                b = check.state_mismatch(prog, out)
                s = check.stats_mismatch(st_prog, RS.stats(out, self.fab.n_tiles,
                                                           self.fab.n_hbm))
                bad_state, bad_stats, failed = bad_state + b, bad_stats + s, failed + bool(b or s)
        return {"unchecked": (len(self.fabrics) - len(self.kept), 0),
                "state_mismatch": (bad_state, 0), "stats_mismatch": (bad_stats, 0)}, failed


KINDS = {"run": RunDriver, "sweep": SweepDriver}
