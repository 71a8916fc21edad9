"""Virtual channels + dateline routing (NocParams.n_vcs).

Pins the three contracts the VC datapath must honor:

- ``n_vcs=1`` is the historical fabric, bit-identical across backends and
  step implementations on the topology zoo (the golden pins in
  test_noc_channels/test_noc_backend hold independently; here the explicit
  field is exercised end to end).
- ``n_vcs=2`` breaks the Dally-Seitz wormhole cycle on torus wrap rings:
  a traffic pattern that deadlocks the VC-less fabric completes, the
  direct-rotation all-to-all replays exactly-once and beats the ring
  fallback, and the analytical model's per-VC serialization term tracks
  the measured grid within 10%.
- The ML traffic compiler converts its wrap-safety rejection into a VC
  requirement: a placement rejected at ``n_vcs=1`` compiles and delivers
  at ``n_vcs=2`` (``ml_traffic.required_vcs``).
"""
import dataclasses
import re

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.noc import collective_traffic as CT
from repro.core.noc import ml_traffic as ML
from repro.core.noc import sim as S
from repro.core.noc import traffic as T
from repro.core.noc.endpoints import idle_workload
from repro.core.noc.params import NocParams
from repro.core.noc.topology import build_mesh, build_topology, build_torus


def _assert_states_equal(a, b, tag=""):
    import jax

    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=tag)


def _run_sched(topo, sched, n_cycles, params):
    wl = CT.to_workload(topo, sched)
    sim = S.build_sim(topo, params, wl)
    st = S.run(sim, n_cycles)
    return st, S.stats(sim, st)


# ----------------------------------------------------------------------
# params + n_vcs=1 equivalence on the zoo
# ----------------------------------------------------------------------
def test_params_default_and_validation():
    assert NocParams().n_vcs == 1
    with pytest.raises(ValueError, match="n_vcs"):
        NocParams(n_vcs=0)


ZOO = [
    ("mesh", dict(nx=4, ny=2)),
    ("torus", dict(nx=4, ny=2)),
    ("multi_die", dict(n_dies=2, nx=2, ny=2, d2d=2)),
]


@pytest.mark.parametrize("name,kw", ZOO)
def test_explicit_single_vc_is_bit_identical(name, kw):
    """NocParams(n_vcs=1) takes the exact historical datapath: same final
    SimState as the default params, on both backends and both step
    implementations."""
    topo = build_topology(name, **kw)
    wl = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2)
    ref = S.run(S.build_sim(topo, NocParams(), wl), 300)
    for p in (NocParams(n_vcs=1),
              NocParams(n_vcs=1, backend="pallas"),
              NocParams(n_vcs=1, step_impl="naive")):
        sim = S.build_sim(topo, p, wl)
        st = S.run(sim, 300)
        if p.step_impl == "naive":
            simr = S.build_sim(topo, NocParams(), wl)
            _assert_states_equal(
                S.canonical_state(simr, ref), S.canonical_state(sim, st),
                f"{name} naive n_vcs=1")
        else:
            _assert_states_equal(ref, st, f"{name} {p.backend} n_vcs=1")


@pytest.mark.parametrize("name,kw", ZOO)
def test_two_vc_backends_and_steps_agree(name, kw):
    """With n_vcs=2 the jnp and Pallas backends stay bit-identical and the
    fast/naive step implementations agree on the canonical state — the
    equivalence pins extend to the folded port*VC state layout."""
    topo = build_topology(name, **kw)
    wl = T.dma_workload(topo, "uniform", transfer_kb=1, n_txns=2)
    simj = S.build_sim(topo, NocParams(n_vcs=2), wl)
    stj = S.run(simj, 300)
    stp = S.run(S.build_sim(topo, NocParams(n_vcs=2, backend="pallas"), wl),
                300)
    _assert_states_equal(stj, stp, f"{name} jnp/pallas n_vcs=2")
    simn = S.build_sim(topo, NocParams(n_vcs=2, step_impl="naive"), wl)
    stn = S.run(simn, 300)
    _assert_states_equal(S.canonical_state(simj, stj),
                         S.canonical_state(simn, stn),
                         f"{name} fast/naive n_vcs=2")
    # all three actually delivered the traffic (not an all-idle vacuous pass)
    assert int(np.asarray(stj.eps.d_txns_left).sum()) == 0


# ----------------------------------------------------------------------
# the deadlock itself: a 4-ring wormhole cycle
# ----------------------------------------------------------------------
def _ring_cycle_workload(topo, beats=64):
    """Every tile of an 8x1 torus sends one long write burst to the tile
    three hops east: the eight east links form a channel-waits-for cycle
    and every route holds links while waiting on the next — the textbook
    Dally-Seitz deadlock once bursts outrun the 2-deep FIFOs."""
    E = topo.n_endpoints
    wl = idle_workload(E, n_tiles=E)
    dst = np.array([[(x + 3) % E] for x in range(E)], np.int32)
    txns = np.ones((E, 1), np.int32)
    return dataclasses.replace(wl, dma_dst=dst, dma_txns=txns,
                               dma_beats=beats, dma_write=True)


def test_torus_ring_deadlocks_without_vcs_and_completes_with_two():
    """The regression the dateline VC-switch exists for: the wrap-ring
    wormhole cycle wedges the VC-less fabric forever (every burst is in
    flight, not one complete after 4000 cycles, zero progress in the last
    2000), while n_vcs=2 drains the identical workload to completion."""
    topo = build_torus(nx=8, ny=1)
    wl = _ring_cycle_workload(topo)
    sim1 = S.build_sim(topo, NocParams(), wl)
    st1 = S.run(sim1, 2000)
    mid = int(np.asarray(st1.eps.beats_rcvd).sum())
    st1 = S.run(sim1, 2000, st1)
    assert int(np.asarray(st1.eps.rx_bursts).sum()) == 0, \
        "expected the VC-less wrap ring to deadlock"
    assert int(np.asarray(st1.eps.beats_rcvd).sum()) == mid, \
        "deadlock must be a wedge, not slow progress"
    sim2 = S.build_sim(topo, NocParams(n_vcs=2), wl)
    st2 = S.run(sim2, 4000)
    assert int(np.asarray(st2.eps.rx_bursts).sum()) == topo.n_endpoints
    assert int(np.asarray(st2.eps.beats_rcvd).sum()) == \
        topo.n_endpoints * wl.dma_beats


# ----------------------------------------------------------------------
# direct all-to-all on the torus: exactly-once, beats the ring fallback
# ----------------------------------------------------------------------
def test_direct_all_to_all_on_torus_exactly_once_and_beats_ring():
    topo = build_torus(nx=4, ny=4)
    direct = CT.all_to_all(topo, data_kb=16, algo="direct", n_vcs=2)
    CT.check_schedule(direct)  # schedule-level exactly-once replay
    params = NocParams(n_vcs=2)
    est = CT.analytical_cycles(direct, params, topo)
    st, out = _run_sched(topo, direct, int(est * 1.5) + 500, params)
    np.testing.assert_array_equal(out["rx_bursts"], direct.expect_rx)
    assert int(np.asarray(st.eps.d_txns_left).sum()) == 0
    meas_d = CT.measured_cycles(out, topo)
    ring = CT.all_to_all(topo, data_kb=16, algo="ring")
    est_r = CT.analytical_cycles(ring, NocParams(), topo)
    _, out_r = _run_sched(topo, ring, int(est_r * 1.5) + 500, NocParams())
    np.testing.assert_array_equal(out_r["rx_bursts"], ring.expect_rx)
    meas_r = CT.measured_cycles(out_r, topo)
    assert meas_d < meas_r, f"direct {meas_d} should beat ring {meas_r}"


def test_auto_algo_follows_n_vcs_on_torus():
    topo = build_torus(nx=4, ny=4)
    assert CT.all_to_all(topo, data_kb=8).meta["algo"] == "ring"
    assert CT.all_to_all(topo, data_kb=8, n_vcs=2).meta["algo"] == "direct"
    # mesh stays direct either way, with no VC serialization term in meta
    mesh = CT.all_to_all(build_mesh(nx=4, ny=4), data_kb=8)
    assert mesh.meta["algo"] == "direct"
    assert "vc_chain" not in mesh.meta


# ----------------------------------------------------------------------
# seeded rank orders: the MoE dispatch with ranks placed at random
# ----------------------------------------------------------------------
# orders (np.random.default_rng(seed).permutation) that the 16 kB direct
# rotation with two streams wedges on the VC-less torus
A2A_ORDERS = [2, 3, 9]


def _seeded_all_to_all(seed, n_vcs):
    topo = build_torus(nx=4, ny=4)
    order = np.random.default_rng(seed).permutation(16).astype(np.int32)
    sched = CT.all_to_all(topo, data_kb=16, streams=2, order=order,
                          algo="direct", n_vcs=n_vcs)
    return topo, sched


@pytest.mark.parametrize("seed", A2A_ORDERS)
def test_seeded_direct_all_to_all_completes_exactly_once(seed):
    """Any placement of the ranks on the 2-VC torus delivers every chunk
    exactly once and retires every scheduled write."""
    topo, sched = _seeded_all_to_all(seed, 2)
    st, out = _run_sched(topo, sched, 1000, NocParams(n_vcs=2))
    np.testing.assert_array_equal(out["rx_bursts"], sched.expect_rx)
    assert int(np.asarray(st.eps.d_txns_left).sum()) == 0
    assert int(np.asarray(st.eps.d_outst).sum()) == 0


@pytest.mark.parametrize("seed", A2A_ORDERS)
def test_seeded_direct_all_to_all_wedges_without_vcs(seed):
    """The same schedule on the VC-less torus never completes: no receipt
    lands in the second half of a run twice as long as the 2-VC fabric
    needs."""
    topo, sched = _seeded_all_to_all(seed, 1)
    sim = S.build_sim(topo, NocParams(), CT.to_workload(topo, sched))
    st = S.run(sim, 1000)
    mid = np.asarray(st.eps.rx_bursts).copy()
    st = S.run(sim, 1000, st)
    rx = np.asarray(st.eps.rx_bursts)
    assert (rx != sched.expect_rx).any()
    np.testing.assert_array_equal(rx, mid)


def test_vc_and_sched_scopes_only_where_traced():
    """``noc.vc`` and ``noc.sched`` name ops of the 2-VC torus program
    with a schedule, and of neither 8x4 mesh program (``jit_scan``,
    ``jit_sweep``), where their branches are not traced."""
    from test_noc_scopes import _scan_text, _sweep_text

    topo, sched = _seeded_all_to_all(A2A_ORDERS[0], 2)
    sim = S.build_sim(topo, NocParams(n_vcs=2), CT.to_workload(topo, sched))
    text = _scan_text(sim, 8)
    for scope in ("noc.vc", "noc.sched"):
        assert re.search(rf'op_name="[^"]*[/(]{re.escape(scope)}[)/]', text), scope
    mesh = build_mesh(nx=4, ny=8, hbm_west=True)
    wl = T.dma_workload(mesh, "uniform", transfer_kb=1, n_txns=2, streams=4)
    sim8 = S.build_sim(mesh, NocParams(), wl)
    for text in (_scan_text(sim8, 8), _sweep_text(sim8, [wl, wl], 8)):
        assert "noc.vc" not in text and "noc.sched" not in text


# ----------------------------------------------------------------------
# the link stage's VC choice: elementwise over V, read back for `sent`
# ----------------------------------------------------------------------
def _spec_link_stage(out_buf, out_cnt, in_space, ep_space, topo, n_vcs):
    """The V>1 link stage as specified: physical wiring, a trailing VC axis
    and ``argmax`` for the lowest eligible VC, chosen once on each side of
    the wire. Returns what ``ref.link_stage`` returns."""
    import jax.numpy as jnp

    V = n_vcs
    R, Pp = topo.n_routers, topo.n_ports
    link_dst = topo.link_to
    link_src = np.full((R, Pp, 2), -1, np.int32)
    for r, p in zip(*np.nonzero(link_dst[..., 0] >= 0)):
        link_src[tuple(link_dst[r, p])] = (r, p)
    port_ep = np.full((R, Pp * V), -1, np.int32)
    port_ep[:, ::V] = topo.port_ep
    er, ep_p = topo.ep_attach[:, 0], topo.ep_attach[:, 1] * V

    heads, valid = out_buf[..., 0, :], out_cnt > 0
    vcs = jnp.arange(V)

    def first(elig):
        return elig & (vcs == jnp.argmax(elig, axis=-1)[..., None])

    sr = np.clip(link_src[..., 0], 0, R - 1)[..., None]
    slot = np.clip(link_src[..., 1], 0, Pp - 1)[..., None] * V + np.arange(V)
    elig = (valid[sr, slot] & (link_src[..., :1] >= 0)
            & in_space.reshape(R, Pp, V))
    up_head = heads[sr, slot].reshape(R, Pp * V, -1)
    accept = first(elig).reshape(R, Pp * V)

    dr = np.clip(link_dst[..., 0], 0, R - 1)[..., None]
    slot = np.clip(link_dst[..., 1], 0, Pp - 1)[..., None] * V + np.arange(V)
    elig = (valid.reshape(R, Pp, V) & in_space[dr, slot]
            & (link_dst[..., :1] >= 0))
    sent = first(elig).reshape(R, Pp * V) | (
        (port_ep >= 0) & valid & ep_space[np.clip(port_ep, 0, None)])
    return up_head, accept, sent, heads[er, ep_p], valid[er, ep_p] & ep_space


LINK_CASES = [("torus4x4", build_torus, 2), ("torus4x4", build_torus, 3),
              ("mesh4x4", build_mesh, 2)]


@pytest.mark.parametrize("name,build,n_vcs", LINK_CASES,
                         ids=[f"{n}-V{v}" for n, _, v in LINK_CASES])
def test_link_stage_matches_argmax_specification(name, build, n_vcs):
    """``ref.link_stage`` at ``n_vcs > 1`` (elementwise lowest-VC choice,
    ``sent`` read back from the downstream accept) equals the argmax
    formulation on seeded random snapshots, element by element: the
    accept mask, the accepted heads, ``sent`` and the endpoint outputs."""
    import jax
    import jax.numpy as jnp

    from repro.core.noc import engine as E
    from repro.kernels.noc_router import ref

    topo = build(nx=4, ny=4)
    tb = E.make_tables(topo, n_vcs)
    R, P, Dout, N = topo.n_routers, topo.n_ports * n_vcs, 2, 24
    rng = np.random.default_rng(1800 + n_vcs)
    out_buf = jnp.asarray(rng.integers(0, 1 << 20, (N, R, P, Dout, ref.NF)),
                          jnp.int32)
    out_cnt = jnp.asarray(rng.integers(0, Dout + 1, (N, R, P)), jnp.int32)
    in_space = jnp.asarray(rng.random((N, R, P)) < 0.6)
    ep_space = jnp.asarray(rng.random((N, topo.n_endpoints)) < 0.7)

    got = jax.vmap(lambda *s: ref.link_stage(
        *s[:3], tb.link_src, tb.link_dst, tb.port_ep, tb.ep_attach, s[3],
        n_vcs=n_vcs))(out_buf, out_cnt, in_space, ep_space)
    want = jax.vmap(lambda *s: _spec_link_stage(*s, topo, n_vcs))(
        out_buf, out_cnt, in_space, ep_space)
    got, want = jax.tree.map(np.asarray, (got, want))
    (up, acc, sent, ep_flit, ep_valid), (up0, acc0, sent0, ep_f0, ep_v0) = got, want
    np.testing.assert_array_equal(acc, acc0)
    np.testing.assert_array_equal(up[acc], up0[acc0])
    np.testing.assert_array_equal(sent, sent0)
    np.testing.assert_array_equal(ep_flit, ep_f0)
    np.testing.assert_array_equal(ep_valid, ep_v0)
    # the snapshots reach the choice: some wire has a VC eligible but
    # passed over for a lower one
    src_r, src_p = np.asarray(tb.link_src).transpose(2, 0, 1)
    valid = np.asarray(out_cnt > 0)[:, np.clip(src_r, 0, None), src_p]
    eligible = valid & (src_r >= 0) & np.asarray(in_space)
    assert acc.any() and (eligible & ~acc).any()


def _scoped_primitives(jaxpr, scope, inside=False):
    """Primitive names of every equation under named scope ``scope``,
    nested jaxprs included."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn.primitive.name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scoped_primitives(sub, scope, here)


def test_link_stage_vc_choice_has_no_reduce():
    """Under ``noc.vc`` the two-VC link stage traces to gathers and
    elementwise ops: no ``argmax`` and no ``reduce_*`` primitive (the
    argmax specification, traced under the same scope, is caught)."""
    import jax

    from repro.core.noc import engine as E
    from repro.kernels.noc_router import ref

    topo = build_torus(nx=4, ny=4)
    tb = E.make_tables(topo, 2)
    R, P = topo.n_routers, topo.n_ports * 2
    snap = (np.zeros((R, P, 2, ref.NF), np.int32), np.zeros((R, P), np.int32),
            np.zeros((R, P), bool), np.zeros(topo.n_endpoints, bool))

    def banned(prims):
        return sorted({p for p in prims
                       if p == "argmax" or p.startswith("reduce_")})

    jaxpr = jax.make_jaxpr(lambda *s: ref.link_stage(
        *s[:3], tb.link_src, tb.link_dst, tb.port_ep, tb.ep_attach, s[3],
        n_vcs=2))(*snap).jaxpr
    prims = list(_scoped_primitives(jaxpr, "noc.vc"))
    assert "gather" in prims
    assert banned(prims) == []

    def spec(*s):
        with jax.named_scope("noc.vc"):
            return _spec_link_stage(*s, topo, 2)

    assert "argmax" in banned(
        _scoped_primitives(jax.make_jaxpr(spec)(*snap).jaxpr, "noc.vc"))


# ----------------------------------------------------------------------
# analytical model: per-VC serialization term within 10% on the grid
# ----------------------------------------------------------------------
GRID = [
    (4, 4, 16, 1),
    (4, 4, 8, 2),
    (4, 2, 16, 1),
    (2, 2, 16, 1),
    pytest.param(4, 4, 32, 1, marks=pytest.mark.slow),
]


@pytest.mark.parametrize("nx,ny,kb,streams", GRID)
def test_model_matches_measured_direct_all_to_all(nx, ny, kb, streams):
    """rotation_all_to_all_cycles with the vc_chain serialization term
    tracks the measured torus grid within the repo's 10% accuracy bar."""
    topo = build_torus(nx=nx, ny=ny)
    sched = CT.all_to_all(topo, data_kb=kb, streams=streams, algo="direct",
                          n_vcs=2)
    assert "vc_chain" in sched.meta
    params = NocParams(n_vcs=2)
    est = CT.analytical_cycles(sched, params, topo)
    st, out = _run_sched(topo, sched, int(est * 1.6) + 500, params)
    np.testing.assert_array_equal(out["rx_bursts"], sched.expect_rx)
    meas = CT.measured_cycles(out, topo)
    assert abs(est - meas) <= 0.10 * meas, \
        f"torus {nx}x{ny} kb={kb} s={streams}: measured {meas} vs model {est}"


# ----------------------------------------------------------------------
# ML compiler: the rejection becomes a VC requirement
# ----------------------------------------------------------------------
def test_compiler_accepts_rejected_placement_with_two_vcs():
    """ParallelismSpec(dp=4, tp=2, pp=2) strides data-parallel rings around
    the 4x4 torus wrap: rejected at n_vcs=1 (channel-dependency cycle),
    compiled and delivered at n_vcs=2."""
    cfg = get_config("llama4-scout-17b-a16e").reduced()
    topo = build_torus(nx=4, ny=4)
    par = ML.ParallelismSpec(dp=4, tp=2, pp=2)
    with pytest.raises(ValueError, match="needs n_vcs >= 2"):
        ML.compile_traffic(cfg, par, topo, tokens_per_device=256)
    phases = ML.compile_traffic(cfg, par, topo, tokens_per_device=256,
                                n_vcs=2)
    assert [ph.name for ph in phases] == ["ddp", "tp", "pp"]
    for ph in phases:
        assert ML.required_vcs(topo, ph.sim_schedule) <= 2
        CT.check_schedule(ph.sim_schedule)
    # the offending phase really needs the VCs: its waits graph is cyclic
    assert any(ML.required_vcs(topo, ph.sim_schedule) == 2 for ph in phases)
    # and the fabric delivers it with n_vcs=2
    params = NocParams(n_vcs=2)
    ph = next(p for p in phases
              if ML.required_vcs(topo, p.sim_schedule) == 2)
    est = CT.analytical_cycles(ph.sim_schedule, params, topo)
    _, out = _run_sched(topo, ph.sim_schedule, int(est * 1.5) + 500, params)
    np.testing.assert_array_equal(out["rx_bursts"], ph.sim_schedule.expect_rx)
