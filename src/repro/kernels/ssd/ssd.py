"""Mamba-2 SSD (state-space duality) chunked scan, TPU Pallas.

Grid (BH, n_chunks) with the chunk dimension sequential: the inter-chunk
state [P, N] is carried in VMEM scratch across chunk steps (never spills to
HBM), while per-chunk tiles of x/dt/B/C stream in via BlockSpecs. The
intra-chunk quadratic part maps onto the MXU (Q x Q and Q x N matmuls).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, y_ref, state_sc, *, nc):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[0].astype(jnp.float32)  # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)  # [Q]
    Bv = b_ref[0].astype(jnp.float32)  # [Q, N]
    Cv = c_ref[0].astype(jnp.float32)  # [Q, N]
    A = -jnp.exp(alog_ref[0].astype(jnp.float32))  # scalar
    D = d_ref[0].astype(jnp.float32)
    Q = x.shape[0]

    ldt = dt * A  # [Q] log decay per step (negative)
    cs = jnp.cumsum(ldt)  # inclusive
    cs_total = cs[-1]

    # intra-chunk: y[i] = sum_{j<=i} exp(cs_i - cs_j) (C_i . B_j) dt_j x_j
    CB = jax.lax.dot_general(Cv, Bv, (((1,), (1,)), ((), ())))  # [Q, Q]
    dec = cs[:, None] - cs[None, :]
    iq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    M = jnp.where(iq >= jq, jnp.exp(dec) * CB * dt[None, :], 0.0)
    y = jax.lax.dot(M, x)  # [Q, P]

    # inter-chunk: y[i] += exp(cs_i) * C_i . S_prev  (S_prev: [N, P])
    y = y + jnp.exp(cs)[:, None] * jax.lax.dot(Cv, state_sc[...])

    # state update: S = exp(cs_total) * S_prev + sum_j exp(cs_total - cs_j) dt_j B_j x_j^T
    w = jnp.exp(cs_total - cs) * dt  # [Q]
    state_sc[...] = jnp.exp(cs_total) * state_sc[...] + jax.lax.dot_general(
        Bv * w[:, None], x, (((0,), (0,)), ((), ()))
    )  # [N, P]

    y_ref[0] = (y + D * x).astype(y_ref.dtype)


def ssd_bhqp(x, dt, Bv, Cv, A_log, D, *, chunk: int = 128, interpret: bool = False):
    """x: [BH, S, P]; dt: [BH, S]; Bv/Cv: [BH, S, N]; A_log/D: [BH].
    Returns y: [BH, S, P]."""
    BH, S, P = x.shape
    N = Bv.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    return pl.pallas_call(
        functools.partial(_kernel, nc=nc),
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q), lambda b, c: (b, c)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, dt, Bv, Cv, A_log, D)
