"""A plain reference of the program's ``dense`` family in its other form
(``models/transformer.py`` with ``norm: rmsnorm``, ``act: swiglu``,
``pos_embedding: rope``, untied head): pre-norm RMSNorm blocks, grouped
query attention with interleaved rotary embeddings on q and k, a SwiGLU
MLP without biases, and an output head of its own.  The benchmark's tests
drop it in beside a configuration that names it, to show that a new
architecture enters the harness as new files.  It provides what every
reference provides (``chipbench/reference.py``) and imports nothing of
the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import refops
from refops import dot


def _sizes(model: dict):
    d, h = model["d_model"], model["n_heads"]
    return d, h, model["n_kv_heads"], model.get("head_dim") or d // h


def init_params(model: dict, key) -> dict:
    """Weights in the program's parameter layout: token table N(0, 0.02),
    matrices N(0, 1/d_in), norm weights drawn about 1."""
    d, h, kv, hd = _sizes(model)
    f, v, n = model["d_ff"], model["vocab"], model["n_layers"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def norm(shape):
        return {"w": 1.0 + normal(shape, 0.05)}

    blocks = {
        "norm1": norm((n, d)),
        "attn": {"wq": normal((n, d, h * hd), d ** -0.5),
                 "wk": normal((n, d, kv * hd), d ** -0.5),
                 "wv": normal((n, d, kv * hd), d ** -0.5),
                 "wo": normal((n, h * hd, d), (h * hd) ** -0.5)},
        "norm2": norm((n, d)),
        "mlp": {"w_gate": normal((n, d, f), d ** -0.5),
                "w_up": normal((n, d, f), d ** -0.5),
                "w_down": normal((n, f, d), f ** -0.5)},
    }
    return {"embed": normal((v, d), 0.02), "blocks": blocks,
            "final_norm": norm((d,)), "lm_head": normal((d, v), d ** -0.5)}


def _rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * p["w"]


def _rope(x, theta):
    """x: (B, T, H, hd); position t turns the pair (x[2i], x[2i+1]) by
    t / theta^(2i / hd)."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def logits(params, tokens, model: dict, precision: str = "f32"):
    d, h, kv, hd = _sizes(model)
    eps, theta = model["norm_eps"], model["rope_theta"]
    b, t = tokens.shape
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def block(x, bp):
        y = _rms_norm(x, bp["norm1"], eps)
        a = bp["attn"]
        q = dot("btd,de->bte", y, a["wq"], precision).reshape(b, t, h, hd)
        k = dot("btd,de->bte", y, a["wk"], precision).reshape(b, t, kv, hd)
        v = dot("btd,de->bte", y, a["wv"], precision).reshape(b, t, kv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
        s = dot("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = dot("bhqk,bkhd->bqhd", w, v, precision).reshape(b, t, h * hd)
        x = x + dot("bte,ed->btd", o, a["wo"], precision)
        y = _rms_norm(x, bp["norm2"], eps)
        m = bp["mlp"]
        g = jax.nn.silu(dot("btd,df->btf", y, m["w_gate"], precision))
        u = g * dot("btd,df->btf", y, m["w_up"], precision)
        return x + dot("btf,fd->btd", u, m["w_down"], precision), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _rms_norm(x, params["final_norm"], eps)
    return dot("btd,dv->btv", x, params["lm_head"], precision)


def loss_and_grad(params, tokens, model: dict, rows: int,
                  precision: str = "f32"):
    return refops.loss_and_grad(logits, params, tokens, model, rows,
                                precision)


def matmul_params(model: dict) -> int:
    """Weights multiplied per token: the projections, the three MLP
    matrices and the output head (the token table is a lookup)."""
    d, h, kv, hd = _sizes(model)
    f = model["d_ff"]
    return model["n_layers"] * (2 * d * h * hd + 2 * d * kv * hd
                                + 3 * d * f) + d * model["vocab"]


def train_flops_per_token(model: dict, seq: int) -> float:
    """6 per matmul weight, plus causal attention (QK^T and AV over the
    query heads, a mean of (seq + 1) / 2 positions), times 3 with the
    backward."""
    _, h, _, hd = _sizes(model)
    return 6.0 * matmul_params(model) \
        + 6.0 * model["n_layers"] * h * hd * (seq + 1)


def forward_flops(model: dict, context: float) -> float:
    _, h, _, hd = _sizes(model)
    return 2.0 * matmul_params(model) \
        + 4.0 * model["n_layers"] * h * hd * context
