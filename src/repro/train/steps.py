"""Train-step construction: loss -> grad -> optimizer, with gradient
accumulation (microbatching) and mixed precision (fp32 master params, model
casts to cfg.dtype internally).

``opt`` may be a built ``GradientTransformation`` (any chain / partition)
or a declarative ``repro.config.OptimizerConfig`` — the latter is lowered
through ``repro.core.build_optimizer`` so call sites can stay config-only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig
from repro.core import (GradientTransformation, apply_updates,
                        build_optimizer, global_norm)
from repro.telemetry import collect as telemetry_collect


def _as_transform(opt) -> GradientTransformation:
    if isinstance(opt, OptimizerConfig):
        return build_optimizer(opt)
    return opt


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray

    @staticmethod
    def create(params, opt) -> "TrainState":
        opt = _as_transform(opt)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))


def build_train_step(model, opt,
                     microbatches: int = 1,
                     grad_clip_norm: Optional[float] = None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    microbatches > 1: the global batch splits on the leading axis and
    gradients accumulate in fp32 across a lax.scan — peak activation memory
    drops by ~microbatches at the cost of re-running the forward.
    """
    opt = _as_transform(opt)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def compute_grads(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, loss, metrics

        def split(x):
            b = x.shape[0]
            assert b % microbatches == 0, (b, microbatches)
            return x.reshape((microbatches, b // microbatches) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(acc, mb):
            grads_acc, loss_acc = acc
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
            return (grads_acc, loss_acc + loss), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), _ = jax.lax.scan(body, (zeros, 0.0), micro)
        inv = 1.0 / microbatches
        grads = jax.tree.map(lambda g: g * inv, grads)
        loss = loss_sum * inv
        return grads, loss, {"loss": loss}

    def train_step(state: TrainState, batch):
        # named scopes tag the step's device ops in a profile (metadata
        # only: the compiled program is unchanged)
        with jax.named_scope("loss_and_grads"):
            grads, loss, metrics = compute_grads(state.params, batch)
        with jax.named_scope("optimizer"):
            if grad_clip_norm is not None:
                norm = global_norm(grads)
                scale = jnp.minimum(1.0, grad_clip_norm / (norm + 1e-12))
                grads = jax.tree.map(lambda g: g * scale, grads)
                metrics = dict(metrics, grad_norm=norm)
            updates, opt_state = opt.update(grads, state.opt_state,
                                            state.params)
            params = apply_updates(state.params, updates)
        new_state = TrainState(params=params, opt_state=opt_state,
                               step=state.step + 1)
        metrics = dict(metrics, loss=loss, step=state.step)
        # Optimizer telemetry rides out of the jitted step alongside the
        # metrics: per-group scalar aggregates of the in-state snapshots
        # (repro.telemetry).  Empty dict — the metrics pytree is unchanged
        # — unless the optimizer was built with telemetry enabled.
        metrics.update(telemetry_collect.telemetry_metrics(opt_state))
        return new_state, metrics

    return train_step
