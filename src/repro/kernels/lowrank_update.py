"""Pallas TPU kernel: fused Adapprox elementwise update.

Computes, tile by tile and WITHOUT materialising V in HBM:

    V    = b2 * max(Q @ U^T, 0) + (1 - b2) * G^2        (per (bm, bn) tile)
    out  = G / (sqrt(V) + eps)
    vfro = sum(V^2)                                      (per-tile partials)

Memory-traffic analysis (the reason this kernel exists): the jnp path reads
G, writes V (m*n f32), reads V, writes out — 3x(m*n) f32 of HBM traffic plus
the factor reads.  The fused kernel reads G and the skinny factors once and
writes out once: ~2.4x less HBM traffic for the optimizer's elementwise
stage, which is memory-bound (arithmetic intensity ~r flops/byte on the
Q @ U^T tile, ~1 on the elementwise tail).

VMEM tiling: block (bm, r) of Q, (bn, r) of U, (bm, bn) of G live in VMEM;
the (bm, r) x (r, bn) product hits the MXU with r padded to a multiple of
128 by the wrapper in ops.py.  Default bm = bn = 256: VMEM footprint
~ 2*256*r_max*4 + 2*256*256*4 bytes ~= 1.5 MiB at r = 256 — comfortably
inside the ~16 MiB VMEM budget, leaving room for double buffering.
Scalars and the per-tile ||V||^2 partials sit in SMEM, laid out as in
fused_update.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_update import SCALARS_SPEC, TILE_SPEC


def _kernel(q_ref, u_ref, g_ref, s_ref, out_ref, vfro_ref):
    q = q_ref[...].astype(jnp.float32)          # (bm, r)
    u = u_ref[...].astype(jnp.float32)          # (bn, r)
    g = g_ref[...].astype(jnp.float32)          # (bm, bn)
    b2 = s_ref[0, 0]
    eps = s_ref[0, 1]
    low = jax.lax.dot_general(q, u, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (bm, bn)
    v = b2 * jnp.maximum(low, 0.0) + (1.0 - b2) * g * g
    out_ref[...] = g / (jnp.sqrt(v) + eps)
    vfro_ref[0, 0] = jnp.sum(v * v)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def lowrank_update_pallas(q: jnp.ndarray, u: jnp.ndarray, g: jnp.ndarray,
                          b2: jnp.ndarray, eps: jnp.ndarray,
                          bm: int = 256, bn: int = 256,
                          interpret: bool = False):
    """q: (m, r) f32, u: (n, r) f32, g: (m, n).  m % bm == 0, n % bn == 0
    (ops.py pads).  Returns (out (m, n) f32, vfro () f32)."""
    m, r = q.shape
    n = u.shape[0]
    gm, gn = m // bm, n // bn

    out, vfro = pl.pallas_call(
        _kernel,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            SCALARS_SPEC,                       # (1, 2) = [b2, eps]
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            TILE_SPEC,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((gm, gn, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, u, g, jnp.stack([b2.astype(jnp.float32),
                          eps.astype(jnp.float32)]).reshape(1, 2))
    return out, jnp.sum(vfro)
