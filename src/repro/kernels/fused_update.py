"""Pallas TPU kernels: the fused two-pass Adapprox update pipeline.

The elementwise tail of the optimizer — reconstruct V, divide, RMS-clip,
first-moment EMA, cosine guidance — is memory-bound, and the plain jnp
path makes ~7 full (m, n) HBM passes per factored leaf.  These kernels cut
it to ~3:

  pass 1 (``fused_precond_pallas``): per (bm, bn) tile, reconstruct
      V = b2 * max(Q @ U^T, 0) + (1 - b2) * G^2 in VMEM, write the raw
      update direction u_hat = G / (sqrt(V) + eps) ONCE, and emit per-tile
      partial reductions alongside it: sum(V^2) (adaptive rank / implicit
      S-RSI), sum(u_hat^2) (RMS clip) and, when guidance is on,
      dot(m1, u_hat) + sum(m1^2).  The (gm, gn) partial grids are summed on
      the host — O(tiles) scalars, negligible traffic.

      Two optional tile-load extensions close the remaining HBM gaps:

      * ``with_fold=True`` additionally emits the amortized-refresh fold
        projection ``(G^2)^T Q`` as a third per-tile partial: each (i, j)
        tile contributes ``(G_tile^2)^T Q_tile`` (bn, r) to row i of a
        (gm, n, r) partial tensor, host-summed over i on the same
        partial-reduction path as vfro/usq.  G is already resident in the
        tile registers for u_hat, so on fold steps the separate
        ``sq_matmul_t`` pass over G — read G, materialise G^T, read it
        again — disappears (see ops.one_sided_fold / roofline.py).

      * quantized factors: pass Q / U as ``(q8, scale, zero)`` triples
        (core/quantized.py layout, block height == bm == bn) and the tile
        load applies ``deq = (q8 + 127) * scale + zero`` in VMEM — the
        int8 factors never round-trip through fp32 HBM.  Rows past the
        true (m, n) are statically masked to 0 so padded tiles keep every
        partial reduction exact (an affine codec dequantizes padding to
        ``zero``, not 0, without the mask).

  pass 2 (``fused_apply_pallas``): one read-modify-write applying the
      host-combined scalars: u_c = u_hat / denom (RMS clip),
      acc = b1 * m1 + (1 - b1) * u_c (update-EMA first moment),
      m_out = acc * out_scale, m1_new = acc * store_scale (guidance).
      ``m1`` is aliased to ``m1_new`` via ``input_output_aliases`` so the
      first moment is updated in place — no extra HBM allocation.

Traffic per factored leaf (f32 words, b1 > 0, guidance off, skinny
factor reads shared by both sides): unfused = reconstruct (read G, write
V) + divide (read G, V; write u_hat) + rms reduce (read u_hat) + clip
(rmw u_hat) + EMA (read u_c, m1; write m1) ~ 11 m*n; fused = pass 1
(read G, write u_hat) + pass 2 (read u_hat, m1; write m1 == m_out)
~ 5 m*n.  On fold steps the PR-4 pipeline additionally paid ~3 m*n for
the standalone (G^2)^T Q (read G, write G^T, read G^T); ``with_fold``
replaces that with 2 * gm * n * r partial words — >= 1.3x fewer fold-step
bytes at r <= bm / 2, 1.6x at small r.  See
benchmarks/roofline.py::optimizer_update_traffic for the full per-stage
model and tests/test_fused.py for the pinned ratios.

VMEM tiling matches lowrank_update.py: blocks (bm, r) of Q, (bn, r) of U,
(bm, bn) of G / m1 with r padded to the 128-lane quantum by ops.py;
bm = bn = 256 keeps the footprint ~2 MiB, well inside the ~16 MiB budget
(and equals core/quantized.py's BLOCK_ROWS, so a quantized tile needs
exactly one scale/zero row).

TPU placement rules the specs below follow (interpret mode checks none of
them): scalar operands are one (1, k) row in SMEM, and every per-tile
partial is a (1, 1) SMEM block of a (gm, gn, 1, 1) output — a block's
last two dims must be (8, 128)-aligned or span the array, and only SMEM
holds a scalar store.  The quantized scale/zero rows ride as (gm, 1, r)
for the same reason.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a (1, 1) SMEM block of a (gm, gn, 1, 1) per-tile partial output
TILE_SPEC = pl.BlockSpec((None, None, 1, 1), lambda i, j: (i, j, 0, 0),
                          memory_space=pltpu.SMEM)
SCALARS_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _deq_tile(q8_ref, scale_ref, zero_ref, base_row: jnp.ndarray,
              true_rows: int):
    """In-register dequant of one factor tile: the EXACT
    core/quantized.dequantize formula, plus a static row mask so rows past
    the matrix's true extent read as 0 (keeping padded-tile partials and
    padded output rows exactly zero, as on the f32 path)."""
    vals = ((q8_ref[...].astype(jnp.float32) + 127.0) * scale_ref[...]
            + zero_ref[...])
    rows = base_row + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    return jnp.where(rows < true_rows, vals, 0.0)


def _make_precond_kernel(guided: bool, with_fold: bool, quantized: bool,
                         m_true: int, n_true: int, bm: int, bn: int):
    """Build the pass-1 kernel body for one (guided, fold, quantized)
    variant — one code path instead of eight hand-written bodies."""

    def kernel(*refs):
        it = iter(refs)
        if quantized:
            q = _deq_tile(next(it), next(it), next(it),
                          pl.program_id(0) * bm, m_true)
            u = _deq_tile(next(it), next(it), next(it),
                          pl.program_id(1) * bn, n_true)
        else:
            q = next(it)[...].astype(jnp.float32)      # (bm, r)
            u = next(it)[...].astype(jnp.float32)      # (bn, r)
        g = next(it)[...].astype(jnp.float32)          # (bm, bn)
        m1 = next(it)[...].astype(jnp.float32) if guided else None
        s_ref = next(it)
        out_ref, vfro_ref, usq_ref = next(it), next(it), next(it)
        m1dot_ref, m1sq_ref = (next(it), next(it)) if guided else (None,
                                                                   None)
        fold_ref = next(it) if with_fold else None

        b2 = s_ref[0, 0]
        eps = s_ref[0, 1]
        low = jax.lax.dot_general(q, u, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        v = b2 * jnp.maximum(low, 0.0) + (1.0 - b2) * g * g
        out = g / (jnp.sqrt(v) + eps)
        out_ref[...] = out
        vfro_ref[0, 0] = jnp.sum(v * v)
        usq_ref[0, 0] = jnp.sum(out * out)
        if guided:
            m1dot_ref[0, 0] = jnp.sum(m1 * out)
            m1sq_ref[0, 0] = jnp.sum(m1 * m1)
        if with_fold:
            # (G_tile^2)^T Q_tile: contract the bm rows already resident
            # for u_hat — the fold projection rides the update loop.
            fold_ref[0, :, :] = jax.lax.dot_general(
                g * g, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    return kernel


@functools.partial(jax.jit, static_argnames=("bm", "bn", "with_fold",
                                             "m_true", "n_true",
                                             "interpret"))
def fused_precond_pallas(q, u, g: jnp.ndarray, m1, b2, eps,
                         bm: int = 256, bn: int = 256,
                         with_fold: bool = False,
                         m_true: int | None = None,
                         n_true: int | None = None,
                         interpret: bool = False):
    """Pass 1 for every variant.  q: (m, r) f32 OR an int8
    ``(q8 (m, r), scale (gm, r), zero (gm, r))`` triple; u likewise over
    (n, r) / gn; g: (m, n); m1: (m, n) f32 or None (guidance off).
    m % bm == 0, n % bn == 0, r % 128 == 0 (ops.py pads; zero padding —
    plus the in-kernel row mask on the quantized path — leaves every
    reduction untouched).  ``m_true`` / ``n_true``: the unpadded extents,
    required when quantized.  Returns
    ``(u_hat (m, n) f32, vfro (), usq (), m1dot, m1sq, yfold)`` with the
    per-tile partial grids already summed; m1dot/m1sq are None without
    m1, yfold ((n, r) f32 = (G^2)^T Q) is None unless ``with_fold``.
    """
    quantized = isinstance(q, tuple)
    guided = m1 is not None
    m, r = (q[0] if quantized else q).shape
    n = (u[0] if quantized else u).shape[0]
    gm, gn = m // bm, n // bn
    scalars = jnp.stack([jnp.asarray(b2, jnp.float32),
                         jnp.asarray(eps, jnp.float32)]).reshape(1, 2)

    inputs, in_specs = [], []
    if quantized:
        rows3 = lambda a: a.reshape(a.shape[0], 1, a.shape[1])
        inputs += [q[0], rows3(q[1]), rows3(q[2]),
                   u[0], rows3(u[1]), rows3(u[2])]
        in_specs += [
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((None, 1, r), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, r), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((None, 1, r), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, r), lambda i, j: (j, 0, 0)),
        ]
    else:
        inputs += [q, u]
        in_specs += [
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
        ]
    inputs.append(g)
    in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j)))
    if guided:
        inputs.append(m1)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j)))
    inputs.append(scalars)
    in_specs.append(SCALARS_SPEC)

    tile = jax.ShapeDtypeStruct((gm, gn, 1, 1), jnp.float32)
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                 TILE_SPEC, TILE_SPEC]
    out_shape = [jax.ShapeDtypeStruct((m, n), jnp.float32), tile, tile]
    if guided:
        out_specs += [TILE_SPEC, TILE_SPEC]
        out_shape += [tile, tile]
    if with_fold:
        out_specs.append(pl.BlockSpec((1, bn, r), lambda i, j: (i, j, 0)))
        out_shape.append(jax.ShapeDtypeStruct((gm, n, r), jnp.float32))

    kernel = _make_precond_kernel(guided, with_fold, quantized,
                                  m_true if m_true is not None else m,
                                  n_true if n_true is not None else n,
                                  bm, bn)
    res = pl.pallas_call(kernel, grid=(gm, gn), in_specs=in_specs,
                         out_specs=out_specs, out_shape=out_shape,
                         interpret=interpret)(*inputs)
    res = list(res)
    out = res.pop(0)
    vfro = jnp.sum(res.pop(0))
    usq = jnp.sum(res.pop(0))
    m1dot = jnp.sum(res.pop(0)) if guided else None
    m1sq = jnp.sum(res.pop(0)) if guided else None
    yfold = jnp.sum(res.pop(0), axis=0) if with_fold else None
    return out, vfro, usq, m1dot, m1sq, yfold


def _apply_kernel(u_ref, m1_ref, s_ref, out_ref, m1_new_ref):
    # s_ref: (1, 5) = [denom, b1, 1 - b1, out_scale, store_scale].
    # (1 - b1) is precomputed by the wrapper in python-f64-then-round —
    # the same coefficient the jnp paths use — rather than re-derived in
    # f32 here.
    u = u_ref[...].astype(jnp.float32)
    m1 = m1_ref[...].astype(jnp.float32)
    u_c = u / s_ref[0, 0]
    acc = s_ref[0, 1] * m1 + s_ref[0, 2] * u_c
    out_ref[...] = acc * s_ref[0, 3]
    m1_new_ref[...] = acc * s_ref[0, 4]


def _apply_shared_kernel(u_ref, m1_ref, s_ref, m1_new_ref):
    # Shared-output variant: when out_scale == store_scale (guidance "off"
    # or "stored") the step direction IS the new first moment, exactly as
    # in the unfused path — write it once and let the caller alias.
    u = u_ref[...].astype(jnp.float32)
    m1 = m1_ref[...].astype(jnp.float32)
    u_c = u / s_ref[0, 0]
    acc = s_ref[0, 1] * m1 + s_ref[0, 2] * u_c
    m1_new_ref[...] = acc * s_ref[0, 4]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_apply_shared_pallas(u_hat: jnp.ndarray, m1: jnp.ndarray,
                              scalars: jnp.ndarray,
                              bm: int = 256, bn: int = 256,
                              interpret: bool = False):
    """Single-output :func:`fused_apply_pallas` for out_scale ==
    store_scale: returns m1_new (= m_out), saving one full (m, n) HBM
    write.  ``m1`` is aliased to the output."""
    m, n = u_hat.shape
    gm, gn = m // bm, n // bn
    return pl.pallas_call(
        _apply_shared_kernel,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            SCALARS_SPEC,                           # (1, 5)
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        input_output_aliases={1: 0},                 # m1 -> m1_new
        interpret=interpret,
    )(u_hat, m1, scalars)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_apply_pallas(u_hat: jnp.ndarray, m1: jnp.ndarray,
                       scalars: jnp.ndarray,
                       bm: int = 256, bn: int = 256,
                       interpret: bool = False):
    """u_hat/m1: (m, n) f32, scalars: (1, 5) f32 = [denom, b1, 1 - b1,
    out_scale, store_scale].  m % bm == 0, n % bn == 0 (ops.py pads).  ``m1`` is
    aliased to the ``m1_new`` output (updated in place — the EMA buffer
    never exists twice in HBM).  Returns (m_out, m1_new), both (m, n) f32.
    """
    m, n = u_hat.shape
    gm, gn = m // bm, n // bn
    return pl.pallas_call(
        _apply_kernel,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            SCALARS_SPEC,                           # (1, 5)
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        input_output_aliases={1: 1},                 # m1 -> m1_new
        interpret=interpret,
    )(u_hat, m1, scalars)
