"""Partition rules: divisibility fallback, axis-reuse guard, rule sets."""
import jax
import pytest
from jax.sharding import AbstractMesh, AxisType
from jax.sharding import PartitionSpec as P

from repro.models.spec import PSpec
from repro.sharding.partition import (
    RuleSet,
    cache_rules,
    logical_to_pspec,
    serve_rules,
    sharding_tree,
    train_rules,
)


@pytest.fixture()
def mesh():
    # AbstractMesh: rule logic only needs shapes, not physical devices
    return AbstractMesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def test_divisible_dims_shard(mesh):
    rs = train_rules(mesh)
    spec = logical_to_pspec(PSpec((16, 8), ("embed", "mlp")), mesh, rs)
    assert spec == P("data", "model")


def test_indivisible_dim_falls_back(mesh):
    rs = train_rules(mesh)
    # 6 heads cannot split a 4-way model axis -> replicated + recorded
    spec = logical_to_pspec(PSpec((16, 6, 32), ("embed", "heads", None)), mesh, rs, "wq")
    assert spec == P("data")
    assert any("indivisible" in f for f in rs.fallbacks)


def test_axis_reuse_guard(mesh):
    rs = RuleSet(name="t", rules={"a": "model", "b": "model"})
    spec = logical_to_pspec(PSpec((8, 8), ("a", "b")), mesh, rs)
    assert spec == P("model")  # second dim falls back
    assert any("axis-reuse" in f for f in rs.fallbacks)


def test_multi_axis_rule(mesh):
    rs = RuleSet(name="t", rules={"batch": ("data", "model")})
    spec = logical_to_pspec(PSpec((8, 3), ("batch", None)), mesh, rs)
    assert spec == P(("data", "model"))


def test_sharding_tree_structure(mesh):
    schema = {"a": PSpec((8, 8), ("embed", "mlp")), "b": {"c": PSpec((4,), (None,))}}
    tree = sharding_tree(schema, mesh, train_rules(mesh))
    assert tree["a"].spec == P("data", "model")
    assert tree["b"]["c"].spec == P()


def test_serve_rules_tp_only_by_default(mesh):
    rs = serve_rules(mesh)
    spec = logical_to_pspec(PSpec((16, 8), ("embed", "mlp")), mesh, rs)
    assert spec == P(None, "model")
    rs2 = serve_rules(mesh, shard_params_data=True)
    spec2 = logical_to_pspec(PSpec((16, 8), ("embed", "mlp")), mesh, rs2)
    assert spec2 == P("data", "model")


def test_cache_rules_seq_shard(mesh):
    rs = cache_rules(mesh, seq_axes=("data", "model"))
    spec = logical_to_pspec(
        PSpec((4, 2, 64, 2, 8), ("layers", "batch", "seq_shard", "kv_heads", None)),
        mesh, rs, "kv")
    # batch=2 takes "data"; seq then shards over the free subset ("model",)
    assert spec[1] == "data"
    assert spec[2] == "model"
    assert any("axis-reuse" in f for f in rs.fallbacks)


def test_cache_rules_long_context_batch1(mesh):
    """long_500k: batch=1 can't shard -> the full mesh goes to the sequence."""
    rs = cache_rules(mesh, seq_axes=("data", "model"))
    spec = logical_to_pspec(
        PSpec((4, 1, 64, 2, 8), ("layers", "batch", "seq_shard", "kv_heads", None)),
        mesh, rs, "kv")
    assert spec[1] is None
    assert spec[2] == ("data", "model")
