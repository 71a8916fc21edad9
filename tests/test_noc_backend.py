"""Backend equivalence: the Pallas router-cycle kernel (interpret mode on
CPU) must be bit-identical to the vmapped jnp reference — same final
SimState, same golden stat pins, same delivered traces — across the
topology zoo (mesh / torus / multi_die), n_channels in {3, 4}, and a
collective schedule replay.

Both backends execute the decision functions in
repro.kernels.noc_router.ref; these tests prove the (C, R)-gridded Pallas
dataflow (two-phase arb -> link/apply kernels) recomposes them without
drift."""
import dataclasses

import numpy as np
import pytest

from repro.core.noc import collective_traffic as CT
from repro.core.noc import sim as S
from repro.core.noc import traffic as T
from repro.core.noc.params import NocParams
from repro.core.noc.topology import build_topology
from repro.kernels.noc_router.noc_router import effective_tile
from test_noc_channels import GOLDEN, _golden_sim


def _leaves(st):
    import jax

    return jax.tree.leaves(st)


def _assert_states_equal(a, b, tag=""):
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=tag)


# zoo configs for the tiled kernel: (name, build kwargs, n_channels,
# streams, router_tile). The grid is (C, R / K) with
# K = effective_tile(router_tile, R): router_tile 8 splits the 16-, 24- and
# 32-router fabrics into 2, 3 and 4 blocks whose links cross block edges,
# router_tile 0 is the whole fabric per program (K = R).
ZOO = [
    ("mesh", dict(nx=4, ny=4), 3, 1, 8),
    ("mesh", dict(nx=8, ny=4), 4, 2, 8),
    ("torus", dict(nx=4, ny=2), 3, 1, 0),
    ("torus", dict(nx=4, ny=2), 4, 2, 0),
    ("multi_die", dict(n_dies=2, nx=2, ny=2, d2d=2), 3, 1, 0),
    ("multi_die", dict(n_dies=2, nx=2, ny=2, d2d=2), 4, 2, 0),
    ("torus", dict(nx=4, ny=4), 4, 2, 8),
    ("multi_die", dict(n_dies=2, nx=2, ny=4, d2d=2), 3, 1, 8),
]


@pytest.mark.parametrize("name,kw,channels,streams,tile", ZOO)
def test_pallas_matches_jnp_state_bitexact(name, kw, channels, streams, tile):
    """Full SimState after 300 cycles is identical leaf-for-leaf between
    the tiled kernel (K routers per program) and the jnp reference."""
    topo = build_topology(name, **kw)
    if tile:  # the grid really has several router blocks
        assert effective_tile(tile, topo.n_routers) < topo.n_routers
    wl = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2,
                        streams=streams)
    stj = S.run(S.build_sim(topo, NocParams(n_channels=channels), wl), 300)
    stp = S.run(S.build_sim(
        topo, NocParams(n_channels=channels, backend="pallas",
                        router_tile=tile), wl), 300)
    _assert_states_equal(stj, stp, f"{name} C={channels} K={tile}")


def test_pallas_reproduces_golden_stat_pins():
    """The Pallas backend hits the seed-commit golden stats directly (the
    same pins test_noc_channels holds the jnp engine to)."""
    simj = _golden_sim()
    simp = S.build_sim(simj.topo,
                       dataclasses.replace(simj.params, backend="pallas"),
                       simj.wl)
    st = S.run(simp, 1200)
    out = S.stats(simp, st)
    np.testing.assert_array_equal(out["beats_rcvd"], GOLDEN["beats_rcvd"])
    np.testing.assert_array_equal(out["dma_done"].sum(axis=-1), GOLDEN["dma_done"])
    np.testing.assert_array_equal(out["narrow_lat_cnt"], GOLDEN["narrow_lat_cnt"])
    np.testing.assert_array_equal(np.asarray(st.eps.lat_sum),
                                  GOLDEN["narrow_lat_sum"])
    np.testing.assert_array_equal(out["ni_stalls"], GOLDEN["ni_stalls"])
    np.testing.assert_array_equal(out["last_rx"], GOLDEN["last_rx"])
    np.testing.assert_array_equal(out["first_rx"], GOLDEN["first_rx"])


def test_pallas_collective_replay_trace_bitexact():
    """A scheduled ring all-reduce (gated multi-phase DMA) delivers the
    exact same per-cycle flit trace on both backends and completes."""
    topo = build_topology("torus", nx=4, ny=2)
    sched = CT.build(topo, "all-reduce", data_kb=1)
    wl = CT.to_workload(topo, sched)
    stj, (fj, vj) = S.run_trace(S.build_sim(topo, NocParams(), wl), 500)
    stp, (fp, vp) = S.run_trace(
        S.build_sim(topo, NocParams(backend="pallas"), wl), 500)
    np.testing.assert_array_equal(np.asarray(vj), np.asarray(vp))
    np.testing.assert_array_equal(np.asarray(fj), np.asarray(fp))
    _assert_states_equal(stj, stp, "collective replay")
    # the schedule actually finished (exactly-once receive counters)
    np.testing.assert_array_equal(np.asarray(stp.eps.rx_bursts),
                                  sched.expect_rx)
    assert int(np.asarray(stp.eps.d_txns_left).sum()) == 0


def test_pallas_run_sweep_matches_jnp():
    """The vmapped sweep engine batches over the Pallas kernel too (the
    pallas_call batching rule), still bit-identical to the jnp sweep."""
    topo = build_topology("mesh", nx=4, ny=2)
    wls = [T.dma_workload(topo, p, transfer_kb=1, n_txns=2)
           for p in ("uniform", "transpose")]
    stsj = S.run_sweep(S.build_sim(topo, NocParams(), wls[0]), wls, 150)
    stsp = S.run_sweep(
        S.build_sim(topo, NocParams(backend="pallas"), wls[0]), wls, 150)
    for a, b in zip(stsj, stsp):
        _assert_states_equal(a, b, "sweep config")


def test_backend_validation():
    with pytest.raises(ValueError):
        NocParams(backend="tpu")
    # the fused multi-cycle window runs on the jnp backend only
    with pytest.raises(ValueError, match="backend.*fused_cycles"):
        NocParams(backend="pallas", fused_cycles=4)
    from repro.kernels.noc_router import ops

    with pytest.raises(ValueError):
        ops.router_cycle(*([None] * 12), backend="nope")

