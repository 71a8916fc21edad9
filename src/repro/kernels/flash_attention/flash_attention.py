"""Causal flash attention, TPU Pallas.

Grid (BH, nq, nk) with the k dimension sequential ("arbitrary"): running
(m, l, acc) live in VMEM scratch across k steps — the online-softmax state
never leaves VMEM, and q/k/v tiles stream HBM->VMEM via BlockSpecs. MXU dims
(block_q, block_k, head_dim) should be multiples of 128 on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, scale, causal,
            block_q, block_k, nk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)  # [bq, D]
    k = k_ref[0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0].astype(jnp.float32)  # [bk, Dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]
    if causal:
        qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1)
    acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot(p, v)
    m_sc[...] = m_new

    @pl.when(j == nk - 1)
    def _done():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[:, None]).astype(
            o_ref.dtype
        )


def flash_attention_bhsd(q, k, v, *, causal: bool = True, block_q: int = 128,
                         block_k: int = 128, scale=None, interpret: bool = False):
    """q: [BH, Sq, D]; k, v: [BH, Skv, D(v)]. Returns [BH, Sq, Dv]."""
    BH, Sq, D = q.shape
    Skv, Dv = k.shape[1], v.shape[2]
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    scale = scale if scale is not None else D ** -0.5

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=bq, block_k=bk, nk=nk
    )
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
