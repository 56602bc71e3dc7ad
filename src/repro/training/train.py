"""Train-step construction: grad accumulation, SPMD sharding, cross-pod
compressed gradient sync, fault-tolerant driver loop.

Two step builders:

* :func:`build_train_step` — pure-SPMD: autodiff's implicit data-parallel
  all-reduce handles gradient sync (XLA overlaps it with the backward
  pass); microbatch grad accumulation via an inner scan.
* :func:`build_train_step_compressed` — partial-manual ``shard_map`` over
  the ``pod`` axis only: each pod computes gradients on its sub-batch
  (data/model axes stay under GSPMD), then the *cross-pod* sync runs the
  int8 error-feedback compressor from :mod:`repro.training.compression` —
  the expensive inter-pod links carry 4× less traffic.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distribution.sharding import current_ctx, pspec
from repro.training.compression import ef_compress_sync, init_error_feedback
from repro.training.optimizer import (OptCfg, OptState, adamw_update,
                                      init_opt_state)


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    err: Any | None          # error-feedback buffers (compressed sync only)


def init_train_state(model, rng, *, compressed: bool = False) -> TrainState:
    params = model.init(rng)
    return TrainState(params=params, opt=init_opt_state(params),
                      err=init_error_feedback(params) if compressed
                      else None)


def state_specs(model, *, compressed: bool = False):
    """PartitionSpec pytree matching a TrainState (under the active ctx)."""
    ps = model.param_specs()
    return TrainState(
        params=ps,
        opt=OptState(m=ps, v=ps, step=P()),
        err=ps if compressed else None)


def _accum_grads(loss_fn, params, tokens, labels, microbatches: int):
    """Mean loss/grads over ``microbatches`` sequential slices of batch."""
    if microbatches <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        return loss, grads
    B = tokens.shape[0]
    assert B % microbatches == 0
    mb = B // microbatches
    tk = tokens.reshape(microbatches, mb, *tokens.shape[1:])
    lb = labels.reshape(microbatches, mb, *labels.shape[1:])

    def body(carry, x):
        loss_acc, g_acc = carry
        t, l = x
        loss, g = jax.value_and_grad(loss_fn)(params, t, l)
        return (loss_acc + loss,
                jax.tree.map(jnp.add, g_acc, g)), None

    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero_g),
                                    (tk, lb))
    inv = 1.0 / microbatches
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def build_train_step(model, opt_cfg: OptCfg, *, microbatches: int = 1):
    """Standard SPMD train step: (state, tokens, labels) → (state, metrics)."""

    def train_step(state: TrainState, tokens, labels):
        loss, grads = _accum_grads(model.loss, state.params, tokens, labels,
                                   microbatches)
        new_p, new_opt, metrics = adamw_update(opt_cfg, state.params, grads,
                                               state.opt)
        metrics["loss"] = loss
        return TrainState(new_p, new_opt, state.err), metrics

    return train_step


def build_train_step_compressed(model, opt_cfg: OptCfg, *,
                                microbatches: int = 1):
    """Cross-pod int8 error-feedback gradient sync (multi-pod meshes).

    Requires an active sharding context whose mesh has a ``pod`` axis.
    The loss is averaged per pod; the compressed psum then averages over
    pods, so gradients match the uncompressed step up to quantization.
    """
    from repro.distribution.sharding import ShardCtx, sharding_ctx
    ctx = current_ctx()
    assert ctx is not None and ctx.pod_axis is not None, \
        "compressed sync needs a multi-pod mesh context"
    pod = ctx.pod_axis
    mesh = ctx.mesh
    # Inside the pod-manual region the model must not reference the pod
    # axis (it is manual there); batch data-parallelism continues over
    # the in-pod data axis, model/data sharding stays GSPMD-auto.
    inner_rules = dict(ctx.rules)
    inner_rules["batch"] = "data"
    inner_ctx = ShardCtx(mesh=mesh, rules=inner_rules, dp_axes=("data",),
                         tp_axis=ctx.tp_axis, pod_axis=None)

    def local(state: TrainState, tokens, labels):
        with sharding_ctx(inner_ctx):     # trace-time rebinding
            loss, grads = _accum_grads(model.loss, state.params, tokens,
                                       labels, microbatches)
            grads, new_err = ef_compress_sync(grads, state.err, pod)
            loss = jax.lax.pmean(loss, pod)
            new_p, new_opt, metrics = adamw_update(opt_cfg, state.params,
                                                   grads, state.opt)
        metrics["loss"] = loss
        return TrainState(new_p, new_opt, new_err), metrics

    # shard_map specs name only the manual axis: state replicated across
    # pods, batch split on its leading dim; everything else is auto.
    rep = jax.tree.map(lambda _: P(), model.param_specs(),
                       is_leaf=lambda s: isinstance(s, P))
    state_sp = TrainState(params=rep, opt=OptState(m=rep, v=rep, step=P()),
                          err=rep)
    batch_spec = P(pod)
    metric_sp = {"grad_norm": P(), "lr": P(), "loss": P()}
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(state_sp, batch_spec, batch_spec),
        out_specs=(state_sp, metric_sp),
        axis_names={pod}, check_vma=False)


# ---------------------------------------------------------------------------
# Fault-tolerant driver (checkpoint/restart around a step function)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunReport:
    steps_done: int
    restarts: int
    final_loss: float
    losses: list


def run_with_restarts(step_fn, state, data_iter, *, n_steps: int,
                      ckpt_mgr=None, ckpt_every: int = 50,
                      max_restarts: int = 3,
                      failure_hook=None) -> tuple[Any, RunReport]:
    """Run ``n_steps``, checkpointing every ``ckpt_every``; on an exception
    restore the last checkpoint and continue (node-failure semantics: any
    step may die; progress resumes from the last durable state).

    ``failure_hook(step)`` (tests) may raise to inject failures.
    ``data_iter(step)`` must be resumable by step index so replayed steps
    see identical data.
    """
    restarts = 0
    losses = []
    step = 0
    state0 = state                       # durable initial state (step 0)
    if ckpt_mgr is not None and ckpt_mgr.latest_step() is not None:
        state, step = ckpt_mgr.restore(state)
    while step < n_steps:
        try:
            if failure_hook is not None:
                failure_hook(step)
            tokens, labels = data_iter(step)
            state, metrics = step_fn(state, tokens, labels)
            losses.append(float(metrics["loss"]))
            step += 1
            if ckpt_mgr is not None and step % ckpt_every == 0:
                ckpt_mgr.save(state, step)
        except Exception:                                  # noqa: BLE001
            restarts += 1
            if restarts > max_restarts:
                raise
            if ckpt_mgr is None:
                raise
            if ckpt_mgr.latest_step() is None:
                state, step = state0, 0   # failed before first checkpoint
            else:
                state, step = ckpt_mgr.restore(state)
    if ckpt_mgr is not None:
        ckpt_mgr.save(state, step)
        ckpt_mgr.wait()
    return state, RunReport(steps_done=step, restarts=restarts,
                            final_loss=losses[-1] if losses else float("nan"),
                            losses=losses)
