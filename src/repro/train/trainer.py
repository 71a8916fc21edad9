"""Trainer: GSPMD mode (FSDP x TP via partition rules, big models) and
explicit-DDP mode (shard_map + FlooNoC multi-stream gradient sync — the
paper's end-to-end transport made visible), with checkpointing, NaN guard,
straggler monitor, and preemption handling.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import Checkpointer, latest_step
from repro.configs.base import ModelConfig
from repro.core import collectives as coll
from repro.core import scheduler as sched
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.models import model as M
from repro.models.spec import count_params_tree
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, opt_state_schema
from repro.runtime import Runtime
from repro.sharding.partition import sharding_tree, train_rules
from repro.train.fault_tolerance import NanGuard, PreemptionHandler, StragglerMonitor


@dataclass
class TrainerConfig:
    steps: int = 50
    log_every: int = 10
    ckpt_every: int = 0  # 0 = disabled
    ckpt_dir: str | None = None
    mode: str = "gspmd"  # "gspmd" | "ddp"
    n_streams: int = 0  # 0 = ask the NoC-aware scheduler
    compress_pod: bool = False
    seed: int = 0
    opt: AdamWConfig = field(default_factory=AdamWConfig)


class Trainer:
    def __init__(self, cfg: ModelConfig, data_cfg: DataConfig, tcfg: TrainerConfig,
                 rt: Runtime | None = None):
        self.cfg, self.dcfg, self.tcfg = cfg, data_cfg, tcfg
        if rt is None:
            n = jax.device_count()
            from repro.runtime import make_mesh

            rt = Runtime(mesh=make_mesh((n, 1), ("data", "model")))
        self.rt = rt
        self.mesh = rt.mesh
        self.batch_axes = rt.batch_axes
        self.monitor = StragglerMonitor()
        self.nan_guard = NanGuard()
        self.preempt = PreemptionHandler(install=False)
        self.ckpt = Checkpointer(tcfg.ckpt_dir) if tcfg.ckpt_dir else None
        self.source = SyntheticLM(data_cfg)
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        cfg, rt, tcfg = self.cfg, self.rt, self.tcfg
        mesh = self.mesh
        psch = M.param_schema(cfg)
        self.rules = train_rules(mesh)
        self.p_sh = sharding_tree(psch, mesh, self.rules)
        self.o_sh = sharding_tree(opt_state_schema(psch), mesh, self.rules)
        self.batch_spec = P(self.batch_axes)
        n_params = count_params_tree(psch)

        if tcfg.n_streams == 0:
            plan = sched.suggest(
                n_params * 4, data_shards=rt.n_batch,
                pods=mesh.shape.get("pod", 1), compute_s=1.0,
            )
            self.n_streams = plan["n_streams"]
        else:
            self.n_streams = tcfg.n_streams

        if tcfg.mode == "ddp":
            rt_local = rt.with_(manual=True)
            sync_cfg = coll.SyncConfig(
                n_streams=self.n_streams,
                intra_axes=tuple(a for a in self.batch_axes if a != "pod"),
                pod_axis="pod" if "pod" in mesh.axis_names else None,
                compress_pod=tcfg.compress_pod,
            )

            def local_step(params, opt_state, batch):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: M.loss_fn(cfg, p, batch, rt_local), has_aux=True
                )(params)
                grads, _ = coll.multi_stream_sync(grads, sync_cfg)
                metrics = coll.narrow_sync(metrics, tuple(mesh.axis_names))
                params, opt_state, om = adamw_update(tcfg.opt, params, grads, opt_state)
                return params, opt_state, {**metrics, **om}

            pspec = jax.tree.map(lambda _: P(), self.p_sh)
            step_fn = jax.shard_map(
                local_step, mesh=mesh,
                in_specs=(pspec, jax.tree.map(lambda _: P(), self.o_sh),
                          P(*self.batch_spec, None)),
                out_specs=(pspec, jax.tree.map(lambda _: P(), self.o_sh), P()),
                check_vma=False,
            )
            self.p_sh = jax.tree.map(lambda s: NamedSharding(mesh, P()), self.p_sh)
            self.o_sh = jax.tree.map(lambda s: NamedSharding(mesh, P()), self.o_sh)
            self.step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        else:

            def step(params, opt_state, batch):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: M.loss_fn(cfg, p, batch, rt), has_aux=True
                )(params)
                params, opt_state, om = adamw_update(tcfg.opt, params, grads, opt_state)
                return params, opt_state, {**metrics, **om}

            self.step_fn = jax.jit(
                step, in_shardings=(self.p_sh, self.o_sh, None),
                donate_argnums=(0, 1),
            )

    # ------------------------------------------------------------------
    def init_state(self):
        with jax.set_mesh(self.mesh):
            params = jax.jit(
                lambda k: M.init_params(self.cfg, k), out_shardings=self.p_sh
            )(jax.random.key(self.tcfg.seed))
            opt = jax.jit(adamw_init, out_shardings=self.o_sh)(params)
        return params, opt

    def _device_batch(self, batch: dict):
        out = {}
        for k, v in batch.items():
            spec = P(self.batch_axes, *([None] * (v.ndim - 1)))
            dt = jnp.bfloat16 if v.dtype == np.float32 and k in ("patch_embeds", "frames") else v.dtype
            out[k] = jax.device_put(jnp.asarray(v, dt), NamedSharding(self.mesh, spec))
        return out

    # ------------------------------------------------------------------
    def run(self, resume: bool = True):
        start = 0
        params = opt = None
        if resume and self.ckpt is not None:
            s = latest_step(self.ckpt.dir)
            if s is not None:
                params, opt = self.restore(s)
                start = s
        if params is None:
            params, opt = self.init_state()

        history = []
        last_good = None
        with jax.set_mesh(self.mesh):
            for step in range(start, self.tcfg.steps):
                if self.preempt.requested:
                    if self.ckpt:
                        self.ckpt.save(step, {"params": params, "opt": opt}, block=True)
                    break
                t0 = time.time()
                batch = self._device_batch(self.source.batch_for_step(step))
                new_params, new_opt, metrics = self.step_fn(params, opt, batch)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                self.monitor.record("host0", dt)
                if self.nan_guard.check(loss):
                    params, opt = new_params, new_opt
                    last_good = None
                else:  # skip the update (donated buffers: fall back to ckpt/init)
                    if last_good is not None:
                        params, opt = last_good
                history.append({"step": step, "loss": loss, "time_s": dt,
                                **{k: float(v) for k, v in metrics.items()}})
                if self.tcfg.log_every and step % self.tcfg.log_every == 0:
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f} ms",
                          flush=True)
                if self.ckpt and self.tcfg.ckpt_every and (step + 1) % self.tcfg.ckpt_every == 0:
                    self.ckpt.save(step + 1, {"params": params, "opt": opt},
                                   metadata={"arch": self.cfg.name})
        if self.ckpt:
            self.ckpt.wait()
        return params, opt, history

    def restore(self, step: int):
        from repro.models.spec import struct_tree

        psch = M.param_schema(self.cfg)
        like = {
            "params": M.param_structs(self.cfg),
            "opt": struct_tree(opt_state_schema(psch)),
        }
        sh = {"params": self.p_sh, "opt": self.o_sh}
        out = self.ckpt.restore(step, like, sh)
        return out["params"], out["opt"]
