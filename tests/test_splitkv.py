"""Explicit split-KV decode == monolithic decode, with the cache sequence
sharded across 8 devices (the long_500k serving schedule)."""
import pytest

from conftest import run_subprocess


@pytest.mark.slow
def test_split_kv_decode_8dev():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.attention import decode_attention
from repro.models.splitkv import split_kv_decode
from repro.runtime import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
B, S, H, KV, D = 2, 64, 4, 2, 16
ks = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
length = jnp.array([50, 64], jnp.int32)

ref = decode_attention(q, k, v, length)

for axes in (("data",), ("data", "model")):
    k_sh = jax.device_put(k, NamedSharding(mesh, P(None, axes)))
    v_sh = jax.device_put(v, NamedSharding(mesh, P(None, axes)))
    with jax.set_mesh(mesh):
        out = jax.jit(lambda q, k, v, l: split_kv_decode(
            q, k, v, l, mesh=mesh, seq_axes=axes))(q, k_sh, v_sh, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    print("SPLITKV_OK", axes)
""", devices=8, timeout=600)
