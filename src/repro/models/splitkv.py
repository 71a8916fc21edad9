"""Explicit split-KV decode attention over a mesh axis (shard_map).

The long-context serving path: the KV cache sequence is sharded across
devices; each shard computes a partial attention (m, l, o) over its slice and
the endpoint combine (log-sum-exp merge) restores the exact softmax — the
FlooNoC pattern of out-of-order partial responses reordered at the endpoint
rather than in the network.

The GSPMD baseline reaches the same schedule implicitly; this explicit form
pins it (no partitioner discretion) and is what the §Perf long-context cells
build on.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import combine_partials, decode_attention_partial


def split_kv_decode(q, k_cache, v_cache, cache_len, *, mesh, seq_axes=("data",),
                    scale=None):
    """q: [B, 1, H, D]; caches: [B, S, KV, D] with S sharded over seq_axes;
    cache_len: [B] global valid length. Returns [B, 1, H, Dv]."""
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    S = k_cache.shape[1]
    S_loc = S // n_shards

    def local(q, k, v, length):
        # my shard covers global positions [off, off + S_loc)
        idx = jnp.zeros((), jnp.int32)
        stride = 1
        for a in reversed(seq_axes):
            idx = idx + jax.lax.axis_index(a) * stride
            stride = stride * jax.lax.axis_size(a)
        off = idx * S_loc
        kpos = off + jnp.arange(S_loc, dtype=jnp.int32)[None, :]
        valid = kpos < length[:, None]
        m, l, o = decode_attention_partial(q[:, 0], k, v, valid, scale=scale)
        out = combine_partials(m, l, o, seq_axes if len(seq_axes) > 1 else seq_axes[0])
        return out[:, None].astype(q.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, None, None), P(None, seq_axes, None, None),
                  P(None, seq_axes, None, None), P(None)),
        out_specs=P(None, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, cache_len)
