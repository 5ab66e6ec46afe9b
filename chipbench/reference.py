"""The plain reference the benchmark holds GPT-2 to: GPT-2 as this
repository's model configuration defines it (pre-norm LayerNorm blocks,
learned positions, tanh-GELU MLP with biases, no q/k/v biases, output head
tied to the token embedding), the weights every run starts from, and its
FLOP counts.

A configuration names its reference by its ``reference`` key, a path
relative to the benchmark directory; every part of a run that depends on
the architecture comes from that module.  A reference provides:

- ``init_params(model, key)``: the weights, in the program's parameter
  layout, made on the device from ``key`` (called under ``jax.jit``);
- ``loss_and_grad(params, tokens, model, rows, precision)``: the mean
  next-token loss over the batch and its gradient, ``rows`` rows at a
  time;
- ``logits(params, tokens, model, precision)``: (B, T, vocab) logits of a
  causal forward;
- ``train_flops_per_token(model, seq)``: the model FLOPs of one trained
  token, forward and backward, no recomputation counted;
- ``forward_flops(model, context)``: one token forward, attending to
  ``context`` positions.

``model`` is the configuration's ``model`` dict; ``precision`` is
``"f32"``, or ``"fp8"`` for the lower-precision control (``refops.py``).
The reference imports nothing of the program.  Long batches go through in
blocks of rows, each layer rematerialised, so the reference fits beside
nothing else on one chip.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import refops
from refops import dot

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




def loss_and_grad(params, tokens, model: dict, rows: int,
                  precision: str = "f32"):
    return refops.loss_and_grad(logits, params, tokens, model, rows,
                                precision)


# ---------------------------------------------------------------------------
# FLOP counts (mfu.train, mfu.serve)
# ---------------------------------------------------------------------------

def matmul_params(model: dict) -> int:
    """Weights that take part in a matrix product per token: the blocks'
    projections and MLP, and the tied output head (the token and position
    tables are read by lookup, not multiplied)."""
    d, f = model["d_model"], model["d_ff"]
    return model["n_layers"] * (4 * d * d + 2 * d * f) \
        + model["vocab"] * d


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward plus backward, no recomputation counted: 6 per matmul
    weight, plus causal attention.  A token at position i attends to i + 1
    positions: QK^T and AV take 2 (i + 1) d each forward, so the mean over
    a sequence of ``seq`` is 2 d (seq + 1) per layer forward, times 3."""
    d, n = model["d_model"], model["n_layers"]
    return 6.0 * matmul_params(model) + 6.0 * n * d * (seq + 1)


def forward_flops(model: dict, context: float) -> float:
    """One token forward through the model attending to ``context``
    positions (itself included)."""
    d, n = model["d_model"], model["n_layers"]
    return 2.0 * matmul_params(model) + 4.0 * n * d * context
