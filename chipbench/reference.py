"""The plain reference the benchmark holds the program to: GPT-2 as this
repository's model configuration defines it (pre-norm LayerNorm blocks,
learned positions, tanh-GELU MLP with biases, no q/k/v biases, output head
tied to the token embedding), the synthetic token stream the train loop
feeds, and the weights every run starts from.

It imports nothing of the program.  Matrix products run in float32 at
``Precision.HIGHEST``; ``precision="fp8"`` rounds both operands of every
product to float8 e4m3 with a per-tensor scale first, which is the
lower-precision control that a sound comparison must reject.  Long
batches go through in blocks of rows, each layer rematerialised, so the
reference fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

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


# ---------------------------------------------------------------------------
# Weights: made on the device from the seed, in one jitted call
# ---------------------------------------------------------------------------

def init_params(model: dict, key) -> dict:
    """GPT-2 weights in the program's parameter layout: token and position
    tables N(0, 0.02), matrices N(0, 1/d_in), and biases and norm weights
    drawn too (small, not the usual zeros and ones), so that a path that
    dropped one would show."""
    d, f, v, n = (model["d_model"], model["d_ff"], model["vocab"],
                  model["n_layers"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def norm(shape):
        return {"w": 1.0 + normal(shape, 0.05), "b": normal(shape, 0.02)}

    blocks = {
        "norm1": norm((n, d)),
        "attn": {"wq": normal((n, d, d), d ** -0.5),
                 "wk": normal((n, d, d), d ** -0.5),
                 "wv": normal((n, d, d), d ** -0.5),
                 "wo": normal((n, d, d), d ** -0.5)},
        "norm2": norm((n, d)),
        "mlp": {"w_up": normal((n, d, f), d ** -0.5),
                "b_up": normal((n, f), 0.02),
                "w_down": normal((n, f, d), f ** -0.5),
                "b_down": normal((n, d), 0.02)},
    }
    return {"embed": normal((v, d), 0.02),
            "pos_embed": normal((model["max_seq_len"], d), 0.02),
            "blocks": blocks,
            "final_norm": norm((d,))}


# ---------------------------------------------------------------------------
# Forward, loss, gradients
# ---------------------------------------------------------------------------

def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def hidden(params, tokens, model: dict, precision: str = "f32"):
    """Final-norm hidden states (B, T, d) of a causal GPT-2 forward."""
    h_n, eps = model["n_heads"], model["norm_eps"]
    b, t = tokens.shape
    d = model["d_model"]
    hd = d // h_n
    x = params["embed"][tokens] + params["pos_embed"][None, :t]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def block(x, bp):
        h = _layer_norm(x, bp["norm1"], eps)
        a = bp["attn"]
        q = dot("btd,de->bte", h, a["wq"], precision).reshape(b, t, h_n, hd)
        k = dot("btd,de->bte", h, a["wk"], precision).reshape(b, t, h_n, hd)
        v = dot("btd,de->bte", h, a["wv"], precision).reshape(b, t, h_n, hd)
        s = dot("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = dot("bhqk,bkhd->bqhd", w, v, precision).reshape(b, t, d)
        x = x + dot("btd,de->bte", o, a["wo"], precision)
        h = _layer_norm(x, bp["norm2"], eps)
        m = bp["mlp"]
        u = _gelu(dot("btd,df->btf", h, m["w_up"], precision) + m["b_up"])
        x = x + dot("btf,fd->btd", u, m["w_down"], precision) + m["b_down"]
        return x, None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return _layer_norm(x, params["final_norm"], eps)


def logits(params, tokens, model: dict, precision: str = "f32"):
    return dot("btd,vd->btv", hidden(params, tokens, model, precision),
               params["embed"], precision)


def mean_nll(params, tokens, model: dict, precision: str = "f32"):
    """Mean next-token cross entropy over every position of the rows."""
    z = logits(params, tokens, model, precision)[:, :-1]
    gold = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.scipy.special.logsumexp(z, axis=-1) - gold)


@functools.partial(jax.jit, static_argnames=("model_items", "rows",
                                             "precision"))
def _loss_and_grad(params, tokens, model_items, rows, precision):
    model = dict(model_items)
    blocks = tokens.reshape(-1, rows, tokens.shape[1])
    grad_fn = jax.value_and_grad(mean_nll)

    def body(acc, blk):
        loss, g = grad_fn(params, blk, model, precision)
        return jax.tree.map(jnp.add, acc, (loss, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, g), _ = jax.lax.scan(body, zero, blocks)
    n = blocks.shape[0]
    return loss / n, jax.tree.map(lambda x: x / n, g)


def loss_and_grad(params, tokens, model: dict, rows: int,
                  precision: str = "f32"):
    """Mean loss over the batch and its gradient, ``rows`` rows at a time
    (every row has the same number of positions, so the batch mean is
    the mean of the block means)."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, str))))
    return _loss_and_grad(params, jnp.asarray(tokens), items, rows,
                          precision)


# ---------------------------------------------------------------------------
# The train loop's synthetic token stream, written out from its definition
# ---------------------------------------------------------------------------

def synthetic_batch(vocab: int, seq: int, rows: int, seed: int,
                    step: int) -> np.ndarray:
    """Row i of step s: Zipf(1.1) unigrams from
    ``default_rng(SeedSequence([seed, s, i]))`` with the second half of
    the row a copy of the first (an induction pattern)."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = 1.0 / ranks ** 1.1
    probs = probs / probs.sum()
    out = np.empty((rows, seq), np.int32)
    half = seq // 2
    for i in range(rows):
        rng = np.random.default_rng(np.random.SeedSequence([seed, step, i]))
        row = rng.choice(vocab, size=seq, p=probs)
        row[half:2 * half] = row[:half]
        out[i] = row
    return out
