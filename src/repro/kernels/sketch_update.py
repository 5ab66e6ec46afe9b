"""Pallas TPU kernel: fused count-min sketch EMA update + per-row query.

Computes, in ONE pass over the gradient and without a dense (rows, d)
second moment in HBM:

    S_t[j, b, :] = b2 * S_{t-1}[j, b, :]
                   + (1 - b2) * sum_{i : idx[j, i] = b} G[i, :]^2
    vhat[i, :]   = min_j S_t[j, idx[j, i], :]

Scatter and gather are both expressed as one-hot matmuls so the MXU does
the bucketing: per depth j, ``one_hot(idx[j])`` is (br, w) and the scatter
contribution is ``one_hot^T @ G^2`` -> (w, bd), while the query is
``one_hot @ S_t[j]`` -> (br, bd).  The gather matmul is exact in f32 (each
output row sums a single non-zero term); the scatter matmul sums colliding
rows in a different order than ``jax.ops.segment_sum``, so kernel-vs-oracle
parity is tolerance-level, like the other kernels in this package.

Grid (nd, 2, nr): d-blocks outermost, then phase, then row-blocks.  For a
fixed d-block the output table block (depth, w, bd) keeps the SAME index
across every (phase, row) step, so it stays resident in VMEM — phase 0
initialises it to ``b2 * S_{t-1}`` at the first row-block, accumulates the
scatter over row-blocks, and phase 1 reads the completed table back for
the min-over-depth gather (TPU grids run sequentially, so phase 0 finishes
before phase 1 starts).  The vhat block is fully overwritten in phase 1,
so its phase-0 placeholder write never matters.

VMEM (f32, every blocked operand double-buffered by the pipeline): the
table in and out blocks 2 * 2 * depth*w*bd, G and vhat 2 * 2 * br*bd, the
index block 2 * depth*br, plus the (br, w) one-hot and (w, bd) scatter
product live in the body.  At the launcher's default depth = 4,
w = 2048, bd = 128, br = 256 that is ~19.6 MiB — over the TPU's 16 MiB
default scoped-VMEM limit, so the call sets ``vmem_limit_bytes`` from
:func:`vmem_bytes` with headroom; ops.py shrinks bd first when the table
is wider.  b2 is a (1, 1) SMEM operand (a scalar load must come from
SMEM or VMEM).  Padding contract (ops.py): padded rows carry zero
gradient and bucket 0, so they scatter no mass; padded buckets are never
queried.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIB = 1 << 20


def vmem_bytes(depth: int, w: int, bd: int, br: int) -> int:
    """VMEM the kernel needs at one block plan (see module docstring)."""
    blocks = 2 * (2 * depth * w * bd + 2 * br * bd + depth * br)
    body = br * w + w * bd + br * bd
    return 4 * (blocks + body)


def _kernel(idx_ref, g_ref, table_ref, b2_ref, new_ref, vhat_ref):
    phase = pl.program_id(1)
    i = pl.program_id(2)
    depth, w = table_ref.shape[0], table_ref.shape[1]
    br = g_ref.shape[0]
    b2 = b2_ref[0, 0]
    idx = idx_ref[...]                                       # (depth, br)
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (br, w), 1)

    @pl.when(jnp.logical_and(phase == 0, i == 0))
    def _init():
        new_ref[...] = b2 * table_ref[...]

    @pl.when(phase == 0)
    def _scatter():
        g = g_ref[...].astype(jnp.float32)
        gsq = g * g
        for j in range(depth):                               # static unroll
            one_hot = (idx[j].reshape(br, 1) == iota_w).astype(jnp.float32)
            contrib = jax.lax.dot_general(
                one_hot, gsq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (w, bd)
            new_ref[j, :, :] = new_ref[j, :, :] + (1.0 - b2) * contrib
        vhat_ref[...] = jnp.zeros(vhat_ref.shape, jnp.float32)

    @pl.when(phase == 1)
    def _gather():
        acc = None
        for j in range(depth):
            one_hot = (idx[j].reshape(br, 1) == iota_w).astype(jnp.float32)
            got = jax.lax.dot_general(
                one_hot, new_ref[j, :, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (br, bd)
            acc = got if acc is None else jnp.minimum(acc, got)
        vhat_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("br", "bd", "interpret"))
def sketch_update_pallas(table: jnp.ndarray, g: jnp.ndarray,
                         idx: jnp.ndarray, b2: jnp.ndarray,
                         br: int = 256, bd: int = 128,
                         interpret: bool = False):
    """table: (depth, w, d) f32, g: (rows, d), idx: (depth, rows) int32.
    rows % br == 0, d % bd == 0, w a lane multiple (ops.py pads).
    Returns (S_t (depth, w, d) f32, vhat (rows, d) f32)."""
    depth, w, d = table.shape
    rows = g.shape[0]
    nr, nd = rows // br, d // bd

    new, vhat = pl.pallas_call(
        _kernel,
        grid=(nd, 2, nr),
        in_specs=[
            pl.BlockSpec((depth, br), lambda dd, p, i: (0, i)),
            pl.BlockSpec((br, bd), lambda dd, p, i: (i, dd)),
            pl.BlockSpec((depth, w, bd), lambda dd, p, i: (0, 0, dd)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # b2 (1, 1)
        ],
        out_specs=[
            pl.BlockSpec((depth, w, bd), lambda dd, p, i: (0, 0, dd)),
            pl.BlockSpec((br, bd), lambda dd, p, i: (i, dd)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((depth, w, d), jnp.float32),
            jax.ShapeDtypeStruct((rows, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(depth, w, bd, br) + 8 * _MIB),
        interpret=interpret,
    )(idx, g, table, jnp.reshape(b2.astype(jnp.float32), (1, 1)))
    return new, vhat
