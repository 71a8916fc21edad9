"""The one traffic generator: a traffic file's parameters plus ``--seed``
become the workload arrays that both the program and the reference get.

Every seed draws the same sizes, rates and lengths; the seed only changes
which tile talks to which and when, so the work per simulated cycle is the
same for every seed.

What the program compiles into its scan (``Sim`` bakes the workload arrays
into the program) never depends on the seed, so one compiled program
serves every seed. In ``run`` traffic the DMA derangements therefore come
from the traffic file's own ``pattern_seed``, and ``--seed`` enters through
the initial state: each tile's narrow-read sequence number (its uniform
destinations) and its narrow token bucket (its arrival phase). In
``sweep`` traffic the workloads are inputs of the compiled program, so the
seed draws them directly.
"""
from __future__ import annotations

import numpy as np

from bench.reference.topology import Fabric

BEAT_BYTES = 64  # one 512-bit wide beat
PATTERNS = ("uniform", "shuffle", "bit-complement", "transpose", "neighbor",
            "tiled-matmul")


def rng_of(seed: int, *salt: int) -> np.random.Generator:
    """Independent generator per (seed, salt); any integer seed."""
    return np.random.default_rng([seed % 2**63, *salt])


def derangement(rng: np.random.Generator, n: int) -> np.ndarray:
    """A uniform random permutation of ``range(n)`` with no fixed point."""
    while True:
        p = rng.permutation(n)
        if n < 2 or not np.any(p == np.arange(n)):
            return p.astype(np.int32)


def pattern_dst(fab: Fabric, pattern: str, shuffle_seed: int) -> np.ndarray:
    """Destination tile per tile under a Fig. 8 pattern (-2: a uniform
    random tile per message)."""
    nt, nx, ny = fab.n_tiles, fab.nx, fab.ny
    x, y = fab.tile_coord[:nt, 0], fab.tile_coord[:nt, 1]
    tid = lambda xx, yy: (yy % ny) * nx + (xx % nx)
    if pattern == "uniform":
        return np.full((nt,), -2, np.int32)
    if pattern == "neighbor":
        return tid(x + 1, y).astype(np.int32)
    if pattern == "bit-complement":
        return tid(nx - 1 - x, ny - 1 - y).astype(np.int32)
    if pattern == "transpose":
        n = int(np.ceil(np.sqrt(nt)))
        lin = y * nx + x
        return ((lin % n * n + lin // n) % nt).astype(np.int32)
    if pattern == "shuffle":
        perm = np.random.RandomState(shuffle_seed).permutation(nt)
        for i in range(nt):  # no tile sends to itself
            if perm[i] == i:
                j = (i + 1) % nt
                perm[i], perm[j] = perm[j], perm[i]
        return perm.astype(np.int32)
    if pattern == "tiled-matmul":  # every tile reads its row's HBM channel
        if not fab.n_hbm:
            raise ValueError("tiled-matmul needs HBM endpoints")
        return (nt + y).astype(np.int32)
    raise ValueError(f"unknown pattern {pattern!r}")


def workload(fab: Fabric, *, streams: int, dst: np.ndarray, burst_kb: int,
             txns: int, narrow_rate: float = 0.0,
             narrow_dst: int = -1) -> dict:
    """Read-DMA workload arrays: tile ``e`` stream ``s`` reads ``txns``
    bursts of ``burst_kb`` kB from ``dst[e, s]`` (-2: uniform random per
    burst); narrow reads at ``narrow_rate`` per tile per cycle."""
    E, nt = fab.n_endpoints, fab.n_tiles
    dd = np.full((E, streams), -1, np.int32)
    dd[:nt] = dst.reshape(nt, -1)
    dt = np.zeros((E, streams), np.int32)
    dt[:nt] = txns
    nr = np.zeros((E,), np.float32)
    nr[:nt] = narrow_rate
    nd = np.full((E,), -1, np.int32)
    if narrow_rate:
        nd[:nt] = narrow_dst
    return dict(narrow_rate=nr, narrow_dst=nd, dma_dst=dd,
                dma_alt_dst=np.full((E, streams), -1, np.int32),
                dma_txns=dt, dma_beats=burst_kb * 1024 // BEAT_BYTES,
                dma_write=False, n_tiles=nt)


def run_workload(fab: Fabric, traffic: dict) -> dict:
    """``run`` traffic: each tile's stream ``s`` reads from ``pi_s(tile)``,
    one derangement per stream drawn from ``pattern_seed`` (every tile's
    fan-in is one burst source per stream), plus uniform narrow reads."""
    rng = rng_of(traffic["pattern_seed"], 1)
    S = traffic["streams"]
    dst = np.stack([derangement(rng, fab.n_tiles) for _ in range(S)], axis=1)
    return workload(fab, streams=S, dst=dst, burst_kb=traffic["burst_kb"],
                    txns=traffic["txns_per_stream"],
                    narrow_rate=traffic["narrow_rate"],
                    narrow_dst=-2 if traffic["narrow_dst"] == "uniform" else -1)


def narrow_start(fab: Fabric, seed: int) -> dict:
    """Seeded initial narrow-generator state of the tiles: the sequence
    number each uniform destination is hashed from (``eps.n_seq``) and the
    token bucket that times each tile's reads (``eps.n_acc``, in [0, 1))."""
    rng = rng_of(seed, 7)
    E, nt = fab.n_endpoints, fab.n_tiles
    n_seq = np.zeros((E,), np.int32)
    n_seq[:nt] = rng.integers(0, 2**24, size=nt)
    n_acc = np.zeros((E,), np.float32)
    n_acc[:nt] = rng.random(nt, dtype=np.float32)
    return {"eps.n_seq": n_seq, "eps.n_acc": n_acc}


def sweep_workloads(fab: Fabric, traffic: dict, seed: int) -> list[dict]:
    """``sweep`` traffic: one fabric per (pattern, burst size)."""
    shuffle_seed = int(rng_of(seed, 2).integers(2**31))
    return [workload(fab, streams=1,
                     dst=pattern_dst(fab, p, shuffle_seed)[:, None],
                     burst_kb=kb, txns=traffic["txns_per_stream"])
            for p in traffic["patterns"] for kb in traffic["burst_kb"]]
