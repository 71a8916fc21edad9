"""The simulator's main path compiles for a TPU v5e chip.

Compiled, not run, against a described ``v5e:2x2`` topology (the TPU
compiler is installed even where no chip is attached): the jnp scan of
the paper's 8x4 mesh, of a 32x32 mesh and of the 4x4 torus with two VCs,
and the Pallas router kernels with ``interpret=False``. What the chip's
compiler would refuse (Mosaic block shapes, gathers, boolean reshapes) or
what would not fit its 16 GB fails here. The topology is described inside
a fixture, never at import: only one process may load the TPU library.
"""
import dataclasses
import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.noc import engine as E
from repro.core.noc import sim as S
from repro.core.noc import traffic as T
from repro.core.noc.spec import FabricSpec, preset
from repro.kernels.noc_router import ops
from repro.kernels.noc_router.noc_router import router_cycle_pallas

V5E_HBM_BYTES = 16e9

SCANS = {
    "mesh8x4": (preset("mesh", big=True), 2000),
    "mesh32x32": (FabricSpec(topology="mesh", nx=32, ny=32), 200),
    "torus4x4_vc2": (FabricSpec(topology="torus", nx=4, ny=4, n_vcs=2), 1000),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sim(spec, **overrides):
    topo, params = spec.lower()
    wl = T.dma_workload(topo, "uniform", transfer_kb=8, n_txns=4)
    return S.build_sim(topo, dataclasses.replace(params, **overrides), wl)


def _compile_scan(sim, n_cycles, one_chip):
    state = _on(one_chip, jax.eval_shape(sim.init_state))
    return sim._scan_fn(n_cycles, with_trace=False).lower(state).compile()


@pytest.mark.parametrize("name", sorted(SCANS))
def test_jnp_scan_compiles_for_v5e(name, one_chip):
    spec, n_cycles = SCANS[name]
    mem = _compile_scan(_sim(spec), n_cycles, one_chip).memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{name}: {used / 1e9:.2f} GB"


def test_scan_compiled_for_v5e_keeps_the_layer_scopes(one_chip):
    """The chip's compiler keeps the scan step's named scopes in the op
    metadata of the 8x4 scan, where a profile's reduction reads them."""
    spec, _ = SCANS["mesh8x4"]
    text = _compile_scan(_sim(spec), 16, one_chip).as_text()
    for scope in ("noc.router", "noc.ingest", "noc.generators", "noc.memory",
                  "noc.inject"):
        assert f"/{scope}/" in text, scope


def test_torus_scan_compiled_for_v5e_picks_the_vc_without_a_reduce(one_chip):
    """The 2-VC torus scan keeps its ``noc.vc`` scope on the chip, where
    ``vc.device_us_per_fabric_cycle`` reads it, and the link stage's
    lowest-VC choice compiles to no reduce: no instruction's ``op_name``
    ends in ``noc.vc)/reduce``."""
    spec, _ = SCANS["torus4x4_vc2"]
    text = _compile_scan(_sim(spec), 16, one_chip).as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("noc.vc" in n for n in names)
    assert [n for n in names if re.search(r"noc\.vc\)?/reduce$", n)] == []


def _kernel_args(spec, n_channels=3, groups=None):
    topo, params = spec.lower()
    tb = E.make_tables(topo, params.n_vcs, groups=groups)
    st = E.init_fabric(topo, params.depth_in, params.depth_out, n_channels,
                       params.n_vcs, n_groups=tb.n_groups)
    args = (st.in_buf, st.in_cnt, st.out_buf, st.out_cnt, st.rr_ptr,
            st.wh_lock, tb.route, tb.link_src, tb.link_dst, tb.port_ep,
            tb.ep_attach,
            jax.ShapeDtypeStruct((n_channels, topo.n_endpoints), bool))
    extra = dict(vc_out=tb.vc_out, fork_out=tb.fork_out,
                 red_parent=tb.red_parent, red_need=tb.red_need,
                 red_acc=st.red_acc, red_got=st.red_got)
    extra = {k: v for k, v in extra.items() if v is not None}
    return args, extra, dict(n_vcs=params.n_vcs,
                             n_endpoints=topo.n_endpoints)


KERNELS = {
    "mesh8x4": (preset("mesh", big=True), None),
    "torus4x4_vc2": (FabricSpec(topology="torus", nx=4, ny=4, n_vcs=2), None),
    "mesh4x4_offload": (preset("mesh"),
                        [dict(root=0, members=[1, 2, 3, 5],
                              reduce=[1, 2, 3, 5])]),
}


@pytest.mark.parametrize("fused_fifo", [True, False],
                         ids=["fast_fifo", "naive_fifo"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_router_cycle_compiles_for_v5e(name, fused_fifo, one_chip):
    """Both FIFO datapaths of the apply kernel: ``fused_fifo=True``
    (``step_impl="fast"``) and the two-step pop/push (``"naive"``)."""
    spec, groups = KERNELS[name]
    args, extra, static = _kernel_args(spec, groups=groups)
    names = list(extra)

    def cycle(*a):
        return router_cycle_pallas(
            *a[:12], router_tile=8, fused_fifo=fused_fifo, interpret=False,
            **dict(zip(names, a[12:])), **static)

    lowered = jax.jit(cycle).lower(*_on(one_chip, [*args, *extra.values()]))
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def test_pallas_scan_compiles_for_v5e(one_chip, monkeypatch):
    """The whole 8x4 scan on the Pallas backend, kernels compiled (the
    backend sees the host CPU here, so interpret mode is switched off)."""
    monkeypatch.setattr(ops, "_interp", lambda interpret: False)
    sim = _sim(preset("mesh", big=True), backend="pallas")
    state = _on(one_chip, jax.eval_shape(sim.init_state))
    lowered = sim._scan_fn(2000, with_trace=False).lower(state)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
