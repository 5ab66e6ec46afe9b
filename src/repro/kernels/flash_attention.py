"""Pallas TPU flash attention (causal/full, GQA) — forward kernel.

The §Perf analysis (EXPERIMENTS.md) shows the dominant HBM traffic of every
train cell is the attention score tensors crossing fusion boundaries; this
kernel keeps the (q_block, k_block) scores in VMEM with the standard
online-softmax recurrence, so per-head HBM traffic drops from O(S²) to
O(S·dh).

Grid: (batch*kv_heads*groups, Sq/BQ) — one program per (head, q-block);
the kv loop runs *inside* the kernel over Sk/BK so the running (m, l, acc)
never leave VMEM.  Blocks: q (BQ, dh), k/v (BK, dh) with BQ = BK = 512 by
default: VMEM ≈ (BQ + 2·BK)·dh·4 + BQ·BK·4 ≈ 2.3 MiB at dh = 128 — double
-buffering head-room in 16 MiB VMEM.  dh is padded to the 128-lane quantum
by the wrapper.

The backward pass uses the jnp chunked path (attention.py) via
``jax.custom_vjp`` — recompute-based, matching what the dry-run lowers;
a fused backward kernel is the natural next step on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, sk: int,
            causal: bool, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
    dh = q.shape[-1]

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, dh), jnp.float32)

    n_kv = sk // bk
    if causal:
        # blocks strictly above the diagonal contribute nothing
        last = jnp.minimum(((qi + 1) * bq + bk - 1) // bk, n_kv)
    else:
        last = n_kv

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * bk, bk), :].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0, pl.ds(ki * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, last, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret",
                                    "scale"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           causal: bool = True, bq: int = 512, bk: int = 512,
                           interpret: bool = False,
                           scale: "float | None" = None) -> jnp.ndarray:
    """q: (H, Sq, dh), k/v: (H, Sk, dh) — heads pre-broadcast (GQA groups
    expanded by the wrapper).  Sq % bq == 0, Sk % bk == 0, dh % 128 == 0
    (wrapper pads; pass ``scale`` = 1/sqrt(true_dh) when padded)."""
    h, sq, dh = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    kernel = functools.partial(_kernel, bq=bq, bk=bk, sk=sk, causal=causal,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda hh, qi: (hh, qi, 0)),
            pl.BlockSpec((1, sk, dh), lambda hh, qi: (hh, 0, 0)),
            pl.BlockSpec((1, sk, dh), lambda hh, qi: (hh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda hh, qi: (hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, dh), q.dtype),
        interpret=interpret,
    )(q, k, v)

