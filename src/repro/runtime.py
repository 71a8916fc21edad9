"""Runtime context threaded through model code: mesh handle + axis names +
implementation knobs. Keeps model functions pure while letting them issue
shard_map'd collectives (MoE dispatch, split-KV decode, floo gradient sync).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Device mesh with Auto-typed axes (sharding propagated by GSPMD)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh() -> jax.sharding.Mesh:
    return make_mesh((1, 1), ("data", "model"))


@dataclass(frozen=True)
class Runtime:
    mesh: Any  # jax.sharding.Mesh
    attn_impl: str = "flash"  # "flash" | "naive"
    remat: bool = True
    block_q: int = 512
    block_k: int = 512
    moe_capacity_factor: float = 2.0
    # long-context decode: shard the KV cache sequence over the data axes
    seq_shard_cache: bool = False
    # True when model code already runs inside a manual shard_map (explicit
    # DDP): sharding constraints become no-ops and MoE uses the ambient axes
    manual: bool = False
    # fsdp2d perf variant: batch spans the model axis too (no TP); MoE then
    # must dispatch tokens via all-to-all instead of replicated-gather
    batch_over_model: bool = False
    moe_impl: str = "gather"  # "gather" | "a2a"
    # FSDP weight-gathering: constrain layer weights to replicated inside the
    # (scanned) block so GSPMD inserts per-layer all-gather (fwd) and
    # reduce-scatter (bwd) instead of partial-summing activations
    gather_weights: bool = False
    # int8 KV-cache quantization for decode (per-token-per-head scales)
    cache_quant: bool = False

    @property
    def axis_model(self) -> str:
        return "model"

    @property
    def batch_axes(self) -> tuple[str, ...]:
        if self.batch_over_model:
            return tuple(a for a in self.mesh.axis_names if a in ("data", "model"))
        return tuple(a for a in self.mesh.axis_names if a != "model")

    @property
    def n_model(self) -> int:
        return self.mesh.shape["model"]

    @property
    def n_batch(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    def with_(self, **kw) -> "Runtime":
        import dataclasses

        return dataclasses.replace(self, **kw)


def default_runtime() -> Runtime:
    return Runtime(mesh=single_device_mesh())
