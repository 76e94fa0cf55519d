"""Distributed training step + host-side loop with fault tolerance.

* grad accumulation via ``lax.scan`` over microbatches (XLA overlaps the
  previous microbatch's reduce-scatter with the next's compute),
* remat (``jax.checkpoint``) on the layer-stack scan,
* ZeRO-1 optimizer-moment sharding,
* optional PowerSGD cross-pod gradient compression under partial-manual
  ``shard_map`` (pod manual, data/model left to the SPMD partitioner),
* checkpoint/restart with SIGTERM (preemption) handling, deterministic
  data replay, and elastic-rescale restore (mesh-independent checkpoints).
"""

from __future__ import annotations

import functools
import signal
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import checkpoint as ckpt_lib
from repro.configs.base import ModelConfig
from repro.data.synthetic import DataConfig, make_batch
from repro.dist import sharding as shd
from repro.dist.compat import manual_shard_map
from repro.launch.mesh import POD, dp_axes
from repro.models.model import Model
from repro.optim.adamw import adamw_update
from repro.optim.grad_compress import CompressorConfig, compressed_psum
from repro.optim.schedule import lr_at
from repro.train.state import RunConfig, TrainState, init_train_state

__all__ = ["make_train_step", "train_state_shardings", "train_loop"]


def _microbatch(batch: Any, m: int, i: jnp.ndarray) -> Any:
    def slice_mb(x):
        mb = x.shape[0] // m
        return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
    return jax.tree.map(slice_mb, batch)


def _accum_grads(model: Model, params: Any, batch: Any, run: RunConfig,
                 shard_map_safe: bool = False):
    """Mean loss/grads over ``run.microbatches`` sequential microbatches.

    ``shard_map_safe`` avoids ``lax.scan`` while-loops entirely (unrolled
    layer stack, Python-loop microbatches): XLA's SPMD partitioner aborts
    on while loops inside partially-manual shard_map regions, which is
    where the PowerSGD step runs.
    """
    m = run.microbatches

    def loss_fn(p, mb):
        loss, metrics = model.loss_fn(p, mb, remat=run.remat,
                                      remat_policy=run.remat_policy,
                                      unroll_layers=shard_map_safe)
        return loss, metrics

    if m == 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    if shard_map_safe:
        loss_sum = jnp.zeros(())
        grads_sum = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        metrics = None
        for i in range(m):
            mb = _microbatch(batch, m, jnp.asarray(i))
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            grads_sum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                     grads_sum, grads)
            loss_sum = loss_sum + loss
        grads = jax.tree.map(lambda g: g / m, grads_sum)
        return loss_sum / m, metrics, grads

    def body(carry, i):
        loss_acc, grads_acc = carry
        mb = _microbatch(batch, m, i)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        grads_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
        return (loss_acc + loss, grads_acc), metrics

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grads_sum), metrics = jax.lax.scan(
        body, (jnp.zeros(()), zeros), jnp.arange(m))
    grads = jax.tree.map(lambda g: g / m, grads_sum)
    metrics = jax.tree.map(lambda x: x[-1], metrics)
    return loss_sum / m, metrics, grads


def make_train_step(model: Model, mesh, run: RunConfig,
                    state_shardings, batch_shardings) -> Callable:
    """Build the jitted (state, batch) -> (state, metrics) step."""

    def opt_update(state: TrainState, grads, loss, metrics, ef=None):
        lr = lr_at(state.step, peak=run.optimizer.lr_peak,
                   total_steps=run.total_steps, warmup=run.warmup_steps,
                   kind=model.cfg.lr_schedule)
        new_params, new_opt, om = adamw_update(run.optimizer, grads, state.opt,
                                               state.params, lr)
        metrics = dict(metrics, loss=loss, lr=lr, **om)
        return TrainState(params=new_params, opt=new_opt, step=state.step + 1,
                          ef=ef if ef is not None else state.ef), metrics

    if run.grad_compression == "powersgd" and POD in mesh.axis_names:
        ccfg = CompressorConfig(rank=run.powersgd_rank, axis=POD,
                                min_size=run.powersgd_min_size)

        def step(state: TrainState, batch):
            def podwise(params, ef, pod_batch):
                loss, metrics, grads = _accum_grads(model, params, pod_batch, run,
                                                    shard_map_safe=True)
                key = jax.random.fold_in(jax.random.PRNGKey(17), state.step)
                grads, new_ef, cbytes = compressed_psum(grads, ef, ccfg, key)
                loss = jax.lax.pmean(loss, POD)
                return loss, metrics, grads, new_ef, cbytes

            in_specs = (P(), P(), P(POD))
            out_specs = (P(), P(), P(), P(), P())
            loss, metrics, grads, new_ef, cbytes = manual_shard_map(
                podwise, mesh, in_specs, out_specs, manual_axes={POD},
            )(state.params, state.ef, batch)
            new_state, metrics = opt_update(state, grads, loss, metrics, ef=new_ef)
            metrics.update({k: v for k, v in cbytes.items()})
            return new_state, metrics
    else:
        def step(state: TrainState, batch):
            loss, metrics, grads = _accum_grads(model, state.params, batch, run)
            return opt_update(state, grads, loss, metrics)

    return jax.jit(step,
                   in_shardings=(state_shardings, batch_shardings),
                   out_shardings=(state_shardings, None),
                   donate_argnums=(0,))


def train_state_shardings(cfg: ModelConfig, mesh, state: Any, run: RunConfig):
    """Shardings for the TrainState pytree (ZeRO-1 moments if enabled)."""
    pspec = shd.param_pspecs(cfg, state.params, mesh)
    opt_spec = shd.zero1_pspecs(cfg, state.params, mesh) if run.zero1 else pspec
    ef_spec = jax.tree.map(lambda x: P(*(None,) * x.ndim), state.ef) if state.ef is not None else None

    def to_shard(tree):
        return shd.shardings_for(mesh, tree)

    from repro.train.state import TrainState as TS
    from repro.optim.adamw import AdamWState
    return TS(
        params=to_shard(pspec),
        opt=AdamWState(mu=to_shard(opt_spec), nu=to_shard(opt_spec),
                       count=NamedSharding(mesh, P())),
        step=NamedSharding(mesh, P()),
        ef=to_shard(ef_spec) if ef_spec is not None else None,
    )


# ---------------------------------------------------------------------------
# Host loop with fault tolerance


class _Preemption:
    """SIGTERM → finish the current step, checkpoint, exit cleanly."""

    def __init__(self):
        self.flagged = False
        try:
            signal.signal(signal.SIGTERM, self._handle)
        except ValueError:
            pass  # not on main thread (tests)

    def _handle(self, *_):
        self.flagged = True


def train_loop(model: Model, mesh, run: RunConfig, data_cfg: DataConfig,
               steps: int | None = None, log_fn=print) -> TrainState:
    """Run (or resume) training; returns the final state."""
    cfg = model.cfg
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        state = init_train_state(params, run)
        st_shard = train_state_shardings(cfg, mesh, state, run)
        state = jax.device_put(state, st_shard)

        start = ckpt_lib.latest_step(run.ckpt_dir)
        if start is not None:
            state = ckpt_lib.restore(run.ckpt_dir, start, state, st_shard)
            log_fn(f"[restore] resumed from step {start}")

        abstract_batch = jax.eval_shape(lambda: make_batch(data_cfg, cfg, 0))
        b_shard = shd.shardings_for(mesh, shd.batch_pspecs(cfg, abstract_batch, mesh))
        step_fn = make_train_step(model, mesh, run, st_shard, b_shard)

        pre = _Preemption()
        total = steps or run.total_steps
        t0 = time.time()
        while int(state.step) < total:
            s = int(state.step)
            batch = jax.device_put(make_batch(data_cfg, cfg, s), b_shard)
            state, metrics = step_fn(state, batch)
            if s % run.log_every == 0:
                m = {k: float(v) for k, v in metrics.items() if jnp.ndim(v) == 0}
                log_fn(f"[step {s}] " + " ".join(f"{k}={v:.4g}" for k, v in sorted(m.items()))
                       + f" ({time.time()-t0:.1f}s)")
            if run.ckpt_every and s > 0 and s % run.ckpt_every == 0:
                ckpt_lib.save_async(run.ckpt_dir, s, state)
            if pre.flagged:
                log_fn("[preempt] SIGTERM received — checkpointing and exiting")
                ckpt_lib.save(run.ckpt_dir, int(state.step), state)
                break
        ckpt_lib.wait_pending()
        return state
