"""What every plain reference shares, whatever its architecture: matrix
products in float32 at ``Precision.HIGHEST`` or in the control's lower
precision, and the mean next-token loss with its gradient taken a block
of rows at a time.

``precision="fp8"`` rounds both operands of every product to float8 e4m3
with a per-tensor scale first: the lower-precision control that a sound
comparison must reject, the same for every configuration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8 e4m3fn


def _round(x, precision: str):
    if precision == "f32":
        return x.astype(jnp.float32)
    if precision == "fp8":
        x = x.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(precision)


def dot(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def mean_nll(logits_fn, params, tokens, model: dict, precision: str):
    """Mean next-token cross entropy over every position of the rows;
    ``logits_fn(params, tokens, model, precision)`` is the reference's
    forward."""
    z = logits_fn(params, tokens, model, precision)[:, :-1]
    gold = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.scipy.special.logsumexp(z, axis=-1) - gold)


@functools.partial(jax.jit, static_argnames=("logits_fn", "model_items",
                                             "rows", "precision"))
def _loss_and_grad(logits_fn, params, tokens, model_items, rows, precision):
    model = dict(model_items)
    blocks = tokens.reshape(-1, rows, tokens.shape[1])
    grad_fn = jax.value_and_grad(
        lambda p, blk: mean_nll(logits_fn, p, blk, model, precision))

    def body(acc, blk):
        loss, g = grad_fn(params, blk)
        return jax.tree.map(jnp.add, acc, (loss, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, g), _ = jax.lax.scan(body, zero, blocks)
    n = blocks.shape[0]
    return loss / n, jax.tree.map(lambda x: x / n, g)


def loss_and_grad(logits_fn, params, tokens, model: dict, rows: int,
                  precision: str = "f32"):
    """Mean loss over the batch and its gradient, ``rows`` rows at a time
    (every row has the same number of positions, so the batch mean is
    the mean of the block means)."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, str))))
    return _loss_and_grad(logits_fn, params, jnp.asarray(tokens), items,
                          rows, precision)
