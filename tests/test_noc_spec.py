"""FabricSpec pipeline: validate -> serialize -> lower, plus the sharded
design-space driver (repro.core.noc.dse).

Pins the tentpole contracts:
* construction-time validation catches bad configs with errors that NAME
  the offending field (wrong-topology shape fields, express spans that
  fit no link, torus workloads whose route union needs more VCs than the
  spec provides);
* dict / JSON / YAML round-trips are lossless and spec_hash is stable;
* lowering is bit-identical to the hand-built topology zoo;
* run_dse per-point results are bit-identical to running each point
  alone through sim.run_sweep, and the frontier artifact is
  deterministic.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import collectives as coll
from repro.core.noc import dse
from repro.core.noc import ml_traffic as ML
from repro.core.noc import sim as S
from repro.core.noc.params import NocParams
from repro.core.noc.spec import FabricSpec, preset
from repro.core.noc.topology import (
    build_mesh,
    build_multi_die,
    build_occamy,
    build_topology,
    build_torus,
)


# ----------------------------------------------------------------------
# serialization round-trips
# ----------------------------------------------------------------------
def test_roundtrip_dict_json_yaml():
    sp = preset("torus", n_vcs=2, workload="uniform", transfer_kb=2)
    assert FabricSpec.from_dict(sp.to_dict()) == sp
    assert FabricSpec.from_json(sp.to_json()) == sp
    assert FabricSpec.from_yaml(sp.to_yaml()) == sp
    h = sp.spec_hash()
    assert len(h) == 12 and int(h, 16) >= 0
    assert FabricSpec.from_json(sp.to_json()).spec_hash() == h


def test_hash_independent_of_key_order():
    sp = preset("mesh", workload="neighbor")
    shuffled = dict(reversed(list(sp.to_dict().items())))
    assert FabricSpec.from_dict(shuffled).spec_hash() == sp.spec_hash()


def test_yaml_comments_and_partial():
    sp = FabricSpec.from_yaml(
        "# a torus point\ntopology: torus\nnx: 4\nny: 4\nn_vcs: 2\n\n"
        "workload: 'uniform'\n")
    assert sp == FabricSpec(topology="torus", nx=4, ny=4, n_vcs=2,
                            workload="uniform")


# ----------------------------------------------------------------------
# validation: bad configs rejected at construction, fields named
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw, msg", [
    (dict(topology="ring"), "unknown topology"),
    (dict(topology="torus", hbm_west=True), r"\['hbm_west'\] do not apply"),
    (dict(topology="mesh", nx=4, ny=4, express=4), "express span 4"),
    (dict(n_channels=2), "n_channels"),
    (dict(topology="torus", nx=4, ny=4, workload="uniform"), "n_vcs >= 2"),
    (dict(topology="occamy", workload="uniform"), "no grid coordinates"),
    (dict(topology="mesh", hbm_west=False, workload="tiled-matmul"),
     "tiled-matmul"),
    (dict(workload="nope"), "unknown workload"),
    (dict(nx=0), "nx must be >= 1"),
    (dict(ni_order="reorder"), "ni_order"),
])
def test_rejections(kw, msg):
    with pytest.raises(ValueError, match=msg):
        FabricSpec(**kw)


def test_from_dict_rejects_unknown_keys():
    with pytest.raises(ValueError, match=r"\['bogus'\]"):
        FabricSpec.from_dict({"topology": "mesh", "bogus": 1})
    with pytest.raises(ValueError, match="field: value"):
        FabricSpec.from_yaml("topology\n")


def test_torus_vc_check_is_exact_not_heuristic():
    # bit-complement on the 4x4 torus routes one X then one Y hop per
    # flow — the waits graph is acyclic, so n_vcs=1 must be accepted
    # (a "multi-hop wrap => 2 VCs" shortcut would wrongly reject it)
    sp = FabricSpec(topology="torus", nx=4, ny=4, workload="bit-complement")
    assert sp.required_vcs() == 1
    # uniform closes ring cycles: rejected at 1 VC, accepted at 2
    sp2 = FabricSpec(topology="torus", nx=4, ny=4, n_vcs=2,
                     workload="uniform")
    assert sp2.required_vcs() == 2


def test_build_topology_names_unknown_kwargs():
    # regression: raw TypeError from the builder call -> named ValueError
    with pytest.raises(ValueError, match=r"\['hbm_west'\].*torus"):
        build_topology("torus", hbm_west=True)
    with pytest.raises(ValueError, match="unknown topology"):
        build_topology("hypercube")


# ----------------------------------------------------------------------
# lowering: bit-identical to the hand-built zoo
# ----------------------------------------------------------------------
def _assert_topo_equal(a, b):
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
            assert va is not None and vb is not None, f.name
            assert np.array_equal(va, vb), f.name
        else:
            assert va == vb, f.name


@pytest.mark.parametrize("spec, build", [
    (preset("mesh"), lambda: build_mesh(nx=4, ny=4)),
    (preset("mesh", big=True), lambda: build_mesh(nx=4, ny=8)),
    (preset("mesh", express=2), lambda: build_mesh(nx=4, ny=4, express=2)),
    (preset("torus"), lambda: build_torus(nx=4, ny=4)),
    (preset("multi_die"), lambda: build_multi_die(n_dies=2, nx=2, ny=4)),
    (preset("occamy"), lambda: build_occamy()),
], ids=["mesh", "mesh_big", "mesh_express", "torus", "multi_die", "occamy"])
def test_lowering_matches_zoo(spec, build):
    topo, params = spec.lower()
    _assert_topo_equal(topo, build())
    assert params == NocParams()


def test_preset_knob_overrides_lower_to_params():
    p = preset("mesh", n_channels=4, n_vcs=2, ni_order="rob",
               fused_cycles=8).params()
    assert p == NocParams(n_channels=4, n_vcs=2, ni_order="rob",
                          fused_cycles=8)


def test_group_key_batches_only_sweepables():
    a = preset("mesh", workload="uniform", transfer_kb=1)
    b = preset("mesh", workload="neighbor", transfer_kb=4, n_txns=2)
    assert a.group_key() == b.group_key()  # sweepable fields only
    assert a.group_key() != preset("mesh", n_channels=4,
                                   workload="uniform").group_key()
    assert a.group_key() != preset("mesh",
                                   workload="all-to-all").group_key()


# ----------------------------------------------------------------------
# run_dse: bit-identity vs sequential run_sweep + artifact determinism
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def dse_smoke():
    specs = dse.default_grid(smoke=True)
    results = dse.run_dse(specs, workers=1, return_states=True)
    return specs, results


def test_run_dse_matches_sequential_run_sweep(dse_smoke):
    specs, results = dse_smoke
    assert len(results) == len(specs) >= 4
    for sp, res in zip(specs, results):
        topo, params = sp.lower()
        wl = sp.build_workload(topo)
        sim = S.build_sim(topo, params, wl)
        st = S.run_sweep(sim, [wl], res["n_cycles_run"])[0]
        import jax

        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(res["state"])):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_frontier_artifact_deterministic(dse_smoke):
    specs, results = dse_smoke
    rows = [{k: v for k, v in r.items() if k != "state"} for r in results]
    art1 = dse.frontier_artifact(rows, grid="smoke")
    art2 = dse.frontier_artifact(list(reversed(rows)), grid="smoke")
    assert json.dumps(art1, sort_keys=True) == json.dumps(art2, sort_keys=True)
    assert art1["schema"] == dse.SCHEMA
    assert art1["n_points"] == len(specs)
    hashes = [p["spec_hash"] for p in art1["points"]]
    assert hashes == sorted(hashes)
    assert set(art1["frontier"]) <= set(hashes) and art1["frontier"]
    assert all(r["delivered"] for r in rows)  # budgets sized to finish


def test_run_dse_one_process_on_accelerator(monkeypatch):
    """Off the CPU backend the process that holds the devices is the only
    one: workers=None runs in-process and workers>1 raises."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr("os.cpu_count", lambda: 8)

    def no_pool(*args, **kwargs):
        raise AssertionError("run_dse started a worker pool")

    monkeypatch.setattr(dse, "_run_dse_pool", no_pool)
    specs = dse.default_grid(smoke=True)
    with pytest.raises(ValueError, match="workers=2"):
        dse.run_dse(specs, workers=2)
    results = dse.run_dse(specs, workers=None)
    assert len(results) == len(specs) and all(r["delivered"] for r in results)


def test_run_dse_requires_workload_binding():
    with pytest.raises(ValueError, match="workload binding"):
        dse.run_dse([preset("mesh")])


# ----------------------------------------------------------------------
# merged row-ring tolerance (the pinned MERGED_A2A_CHAIN_RTOL constant)
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_merged_a2a_chain_tolerance():
    """The MoE expert groups on the dateline-VC torus sit in the merged
    row-ring regime where the collective model over-serializes the shared
    wrap edges; the mismatch must stay within the constant that
    collective_bench gates those rows with."""
    from repro.configs import get_config

    par_kw, tokens = ML.DEMO_SPECS["moe"]
    topo, params = preset("torus", n_vcs=2).lower()
    cfg = get_config("llama4-scout-17b-a16e").reduced()
    phases = ML.compile_traffic(cfg, ML.ParallelismSpec(**par_kw), topo,
                                tokens_per_device=tokens, sim_cap_kb=4.0,
                                workloads=["moe"], n_vcs=2)
    for ph in phases:
        v = ML.validate_phase(topo, ph, params)
        err = abs(v["model"] - v["measured"]) / max(v["measured"], 1)
        assert v["delivered"]
        assert err <= coll.MERGED_A2A_CHAIN_RTOL, (ph.name, err)
