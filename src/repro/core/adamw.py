"""AdamW baseline (Loshchilov & Hutter) — the paper's reference optimizer.

Note: following the paper's memory accounting (Table 2), the first moment is
allocated even when ``b1 = 0`` ("AdamW still allocates memory for the first
moment"), matching the PyTorch implementation the paper measured.

:func:`scale_by_adam` is the pure bias-corrected preconditioner;
:func:`adamw` is the documented chain

    chain(scale_by_adam(b1, b2, eps),
          add_decayed_weights(wd),
          scale_by_schedule(lr),
          scale(-1.0))

bit-identical to the former monolithic implementation.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.transform import (add_decayed_weights, scale,
                                  scale_by_schedule)
from repro.core.types import GradientTransformation, chain


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: "float | Callable" = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AdamWState:
    step: jnp.ndarray
    m: object          # pytree like params, float32
    v: object          # pytree like params, float32


def scale_by_adam(b1: float = 0.9, b2: float = 0.999,
                  eps: float = 1e-8) -> GradientTransformation:
    """Bias-corrected Adam direction ``m_hat / (sqrt(v_hat) + eps)``.

    Both moments shard exactly like the params they mirror (the
    ``state_sharding_spec`` hook forwards the param specs verbatim).
    """

    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return AdamWState(step=jnp.zeros((), jnp.int32),
                          m=jax.tree.map(z, params),
                          v=jax.tree.map(z, params))

    @jax.named_scope("adamw")
    def update(grads, state: AdamWState, params):
        del params
        step = state.step + 1
        t = step.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def upd(g, m, v):
            g32 = g.astype(jnp.float32)
            m = b1 * m + (1.0 - b1) * g32
            v = b2 * v + (1.0 - b2) * jnp.square(g32)
            mhat = m / bc1
            vhat = v / bc2
            return mhat / (jnp.sqrt(vhat) + eps), m, v

        out = jax.tree.map(upd, grads, state.m, state.v)
        # tree-of-tuples -> tuple-of-trees
        treedef = jax.tree.structure(grads)
        flat = treedef.flatten_up_to(out)
        dirs = jax.tree.unflatten(treedef, [o[0] for o in flat])
        ms = jax.tree.unflatten(treedef, [o[1] for o in flat])
        vs = jax.tree.unflatten(treedef, [o[2] for o in flat])
        return dirs, AdamWState(step=step, m=ms, v=vs)

    def spec(state: AdamWState, param_specs):
        del state
        return AdamWState(step=P(), m=param_specs, v=param_specs)

    return GradientTransformation(init, update, spec)


def adamw(cfg: AdamWConfig,
          decay_mask: Optional[Callable] = None) -> GradientTransformation:
    """AdamW as a documented chain (see module docstring)."""
    return chain(
        scale_by_adam(cfg.b1, cfg.b2, cfg.eps),
        add_decayed_weights(cfg.weight_decay, decay_mask),
        scale_by_schedule(cfg.lr),
        scale(-1.0),
    )
