"""Roofline machinery: HLO collective parsing (incl. while-trip
multiplication), analytic FLOPs sanity, and a live 8-device cross-check."""
import pytest

from conftest import run_subprocess
from repro.configs import SHAPES, get_config
from repro.launch import flops as FL
from repro.launch import roofline as RL

HLO = """
HloModule test

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body.7 (arg: (s32[], f32[16,64])) -> (s32[], f32[16,64]) {
  %arg = (s32[], f32[16,64]) parameter(0)
  %ar = f32[16,64]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%add.1
  %ag = f32[16,128]{1,0} all-gather(%y), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={1}
}

ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %w = (s32[], f32[16,64]) while(%init), condition=%cond.9, body=%body.7
  %rs = f32[4,4]{1,0} reduce-scatter(%z), replica_groups=[2,128]<=[256], dimensions={0}, to_apply=%add.1
}
"""


def test_parse_collectives_kinds_and_bytes():
    stats = RL.parse_collectives(HLO, 256, known_lengths={16})
    # while body trip = 16 (carry leading dim matches a known length)
    assert stats.counts == {"all-reduce": 1, "all-gather": 1, "reduce-scatter": 1}
    assert stats.dynamic_counts["all-reduce"] == 16
    assert stats.dynamic_counts["all-gather"] == 16
    assert stats.dynamic_counts["reduce-scatter"] == 1
    # bytes: AR 16*64*4 B * 2*(15/16) * trip16; AG 16*128*4 * (3/4) * 16;
    # RS 4*4*4 * 127 * 1
    ar = 16 * 64 * 4 * 2 * 15 / 16 * 16
    ag = 16 * 128 * 4 * 3 / 4 * 16
    rs = 4 * 4 * 4 * 127
    assert stats.bytes_by_kind["all-reduce"] == pytest.approx(ar)
    assert stats.bytes_by_kind["all-gather"] == pytest.approx(ag)
    assert stats.bytes_by_kind["reduce-scatter"] == pytest.approx(rs)


def test_group_size_parsing():
    assert RL._group_size("replica_groups=[16,32]<=[512]", 1) == 32
    assert RL._group_size("replica_groups={{0,1,2},{3,4,5}}", 1) == 3
    assert RL._group_size("no groups here", 7) == 7


def test_known_scan_lengths():
    cfg = get_config("mistral-large-123b")
    ks = RL.known_scan_lengths(cfg, SHAPES["train_4k"])
    assert 88 in ks  # layers
    assert 36 in ks  # causal pairs at 4096/512
    cfg2 = get_config("deepseek-v2-236b")
    ks2 = RL.known_scan_lengths(cfg2, SHAPES["train_4k"])
    assert 59 in ks2


@pytest.mark.parametrize("arch", ["mistral-large-123b", "deepseek-v2-236b",
                                  "mamba2-130m", "gemma3-4b"])
def test_useful_flops_ratio_sane(arch):
    """MODEL_FLOPS / analytic HLO flops must be a sensible fraction: the
    analytic count includes remat (4x fwd vs 6ND=3x matmul-only)."""
    cfg = get_config(arch)
    shape = SHAPES["train_4k"]
    est = FL.estimate(cfg, shape)
    model = RL.model_flops_per_device(cfg, shape, 1)
    ratio = model / est.flops
    assert 0.25 < ratio < 1.1, f"{arch}: ratio {ratio:.3f}"


def test_decode_flops_memory_bound():
    """Decode is memory-bound: bytes/flops ratio near 1 (reads params once)."""
    cfg = get_config("granite-8b")
    est = FL.estimate(cfg, SHAPES["decode_32k"])
    intensity = est.flops / est.hbm_bytes
    assert intensity < 300  # far below the ~240 flops/byte compute roofline


def test_live_trip_multiplication_8dev():
    """Real compile: a 6-layer scanned model must multiply per-layer
    collectives by 6 in the dynamic counts."""
    run_subprocess("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import roofline as RL
from repro.runtime import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
L, D, F = 6, 64, 128
params = jax.ShapeDtypeStruct((L, D, F), jnp.float32)
x = jax.ShapeDtypeStruct((8, D), jnp.float32)

def f(params, x):
    def body(x, p):
        h = jnp.tanh(x @ p)  # [8, F] partial over model
        h = jax.lax.with_sharding_constraint(h @ p.T, NamedSharding(mesh, P("data", None)))
        return h, None
    x, _ = jax.lax.scan(body, x, params)
    return x.sum()

with jax.set_mesh(mesh):
    comp = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P(None, None, "model")),
        NamedSharding(mesh, P("data", None)),
    )).lower(params, x).compile()
stats = RL.parse_collectives(comp.as_text(), 8, known_lengths={L})
total_static = sum(stats.counts.values())
total_dyn = sum(stats.dynamic_counts.values())
assert total_static > 0, "expected collectives in the TP matmul"
assert total_dyn >= total_static * L * 0.5, (stats.counts, stats.dynamic_counts)
print("TRIP_OK", stats.counts, stats.dynamic_counts)
""")
