"""Public jit'd wrappers around the Pallas kernels.

Responsibilities: padding to block multiples, dtype handling, platform
dispatch (TPU -> compiled Pallas; CPU -> interpret mode for tests, or the
pure-jnp reference for speed), and batching via vmap.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_update import (fused_apply_pallas,
                                        fused_apply_shared_pallas,
                                        fused_precond_pallas)
from repro.kernels.lowrank_update import lowrank_update_pallas
from repro.kernels.sketch_update import sketch_update_pallas
from repro.kernels.srsi_matmul import sq_matmul_pallas

# Mode: "auto" (pallas on TPU, ref elsewhere), "pallas" (force, interpret on
# CPU — used by kernel tests and the CI pallas-interpret job via the
# REPRO_KERNEL_MODE env var), "ref" (force reference).
_MODE = os.environ.get("REPRO_KERNEL_MODE", "auto")
if _MODE not in ("auto", "pallas", "ref"):
    raise ValueError(
        f"REPRO_KERNEL_MODE={_MODE!r} (expected auto|pallas|ref)")

# Mixed-shape bucketing (pallas dispatch only; the ref path never pads, so
# the default chain's arithmetic is untouched): raw dims are rounded up a
# coarse ladder before the block size is chosen, so a many-leaf stack of
# near-miss shapes compiles to a handful of kernel instances instead of
# one per (shape, r_store) signature.  Zero padding + the kernels' exact
# partial reductions make the rounding bit-neutral (tests/test_kernels.py
# pins bucketed == unbucketed bitwise).  REPRO_KERNEL_BUCKETS=off or
# set_bucketing(False) restores exact-shape dispatch.
_BUCKETED = os.environ.get("REPRO_KERNEL_BUCKETS", "on").lower() \
    not in ("0", "off", "false")


def set_mode(mode: str) -> None:
    global _MODE
    assert mode in ("auto", "pallas", "ref")
    _MODE = mode


def set_bucketing(on: bool) -> None:
    global _BUCKETED
    _BUCKETED = bool(on)


# Trace-time census of pallas dispatch signatures: every kernel launch
# records (kernel, padded operand shapes, block plan).  Distinct keys are
# exactly the jit cache keys of the underlying pallas wrappers, i.e. the
# number of kernel instances XLA compiles — tests assert a ragged
# many-leaf stack stays at a handful of instances under bucketing.
_INSTANCES: dict = {}


def _note_instance(kernel: str, shapes: tuple, blocks: tuple) -> None:
    key = (kernel, shapes, blocks)
    _INSTANCES[key] = _INSTANCES.get(key, 0) + 1


def kernel_instances() -> dict:
    return dict(_INSTANCES)


def reset_kernel_instances() -> None:
    _INSTANCES.clear()


def resolved_mode() -> str:
    """The mode actually in effect: "pallas" | "interpret" | "ref".
    Benchmarks record this so TPU and CPU runs are distinguishable."""
    use, interp = _use_pallas()
    if not use:
        return "ref"
    return "interpret" if interp else "pallas"


def _use_pallas() -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    platform = jax.default_backend()
    if _MODE == "ref":
        return False, False
    if _MODE == "pallas":
        return True, platform != "tpu"
    return platform == "tpu", False


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pick_block(dim: int, target: int = 256, align: int = 8) -> int:
    """Largest block <= target that keeps padding waste < ~2x for tiny dims."""
    if dim >= target:
        return target
    # round tiny dims up to the alignment quantum
    return max(align, ((dim + align - 1) // align) * align)


def _bucket_dim(dim: int) -> int:
    """Round a raw dim up the bucket ladder: fine steps where leaves are
    small and shapes diverse, coarse where padding waste is relatively
    cheap.  dims > 256 already land on 256-multiples via _pad_to(block),
    so the ladder's work is consolidating the sub-256 long tail."""
    mult = 64 if dim <= 512 else (256 if dim <= 2048 else 512)
    return ((dim + mult - 1) // mult) * mult


def _tile_plan(dim: int, target: int = 256, align: int = 8) -> int:
    """Block size for one axis of a pallas dispatch.  With bucketing on
    (default) the dim is first rounded up the bucket ladder, so the
    subsequent ``_pad_to(x, block)`` lands mixed raw shapes on a small
    set of padded signatures — e.g. 100 -> 128, 130 -> 192, 320 -> 512 —
    instead of one 8-aligned signature per raw dim."""
    d = _bucket_dim(dim) if _BUCKETED else dim
    return _pick_block(d, target, align)


def _q_block_rows() -> int:
    """core/quantized.py's codec block height (lazy import: the codec is
    only needed on the int8 path and core imports this module)."""
    from repro.core.quantized import BLOCK_ROWS
    return BLOCK_ROWS


def lowrank_update(q: jnp.ndarray, u: jnp.ndarray, g: jnp.ndarray,
                   b2: float, eps: float,
                   with_frob: bool = False):
    """Fused V-reconstruct + elementwise update (see ref.lowrank_update).

    Accepts arbitrary leading batch dims on (q, u, g) jointly.
    """
    use, interp = _use_pallas()

    def one(q2, u2, g2):
        if not use:
            out, fro = ref.lowrank_update(q2, u2, g2, b2, eps)
            return out, fro
        m, n = g2.shape
        bm, bn = _tile_plan(m), _tile_plan(n)
        # r padded to a lane multiple so the MXU tile is aligned.
        qp = _pad_to(_pad_to(q2.astype(jnp.float32), bm, 0), 128, 1)
        up = _pad_to(_pad_to(u2.astype(jnp.float32), bn, 0), 128, 1)
        gp = _pad_to(_pad_to(g2, bm, 0), bn, 1)
        _note_instance("lowrank_update", (qp.shape, up.shape, gp.shape),
                       (bm, bn))
        out, fro = lowrank_update_pallas(qp, up, gp,
                                         jnp.asarray(b2), jnp.asarray(eps),
                                         bm=bm, bn=bn, interpret=interp)
        return out[:m, :n], fro

    fn = one
    for _ in range(g.ndim - 2):
        fn = jax.vmap(fn)
    out, fro = fn(q, u, g)
    return (out, fro) if with_frob else out


def fused_precond(q, u, g: jnp.ndarray,
                  b2: float, eps: float,
                  m1: jnp.ndarray | None = None,
                  with_vfro: bool = True,
                  with_fold: bool = False):
    """Pass 1 of the fused two-pass update pipeline (see ref.fused_precond):
    raw update direction + whole-matrix reductions in one read of G, with V
    reconstructed tile-wise and never stored.  Pass ``m1`` to additionally
    get the guidance partials streamed in the same pass.

    ``q`` / ``u`` are (…, m|n, r) f32 arrays OR ``QuantizedMatrix`` triples
    (core/quantized.py): on the kernel path the int8 payload is dequantized
    per tile in VMEM (block height == the forced bm = bn = BLOCK_ROWS) so
    the factors never materialize in fp32 HBM; on the ref path they are
    dequantized up front with the exact same formula, so both backends see
    bit-identical factor values.

    ``with_fold=True`` additionally returns the amortized-refresh fold
    projection ``yfold = (G^2)^T Q`` (…, n, r), emitted from the same tile
    loop that reads G for u_hat (per-row-block partials, host-summed like
    vfro/usq) — on fold steps this kills the standalone ``sq_matmul_t``
    pass over G.

    Accepts arbitrary leading batch dims on (q, u, g, m1) jointly.
    Returns (u_hat, vfro, usq, m1dot, m1sq, yfold); m1dot/m1sq are None
    when ``m1`` is None, yfold is None unless ``with_fold``.
    ``with_vfro=False`` returns None for vfro on the ref path (the
    reduction is skipped — fold steps never consume it); the Pallas
    kernels always emit the per-tile partial since it rides the update
    loop for free, and the wrapper simply drops it.
    """
    use, interp = _use_pallas()
    quantized = hasattr(q, "q8")

    def one(q2, u2, g2, m12):
        if not use:
            if quantized:
                from repro.core.quantized import dequantize
                q2f, u2f = dequantize(q2), dequantize(u2)
            else:
                q2f, u2f = q2, u2
            out, vfro, usq, m1dot, m1sq, y = ref.fused_precond(
                q2f, u2f, g2, b2, eps, m1=m12, with_vfro=with_vfro,
                with_fold=with_fold)
            return out, vfro, usq, m1dot, m1sq, y
        m_, n_ = g2.shape
        if quantized:
            # the codec's block height IS the tile plan: one (scale, zero)
            # row per (bm, r) tile of int8 payload, so dequant fuses into
            # the tile load.  scale/zero row counts already equal the
            # padded grid (quantize pads ragged blocks internally).
            bm = bn = _q_block_rows()
            r_t = q2.q8.shape[-1]
            qp = (_pad_to(_pad_to(q2.q8, bm, 0), 128, 1),
                  _pad_to(q2.scale, 128, 1), _pad_to(q2.zero, 128, 1))
            up = (_pad_to(_pad_to(u2.q8, bn, 0), 128, 1),
                  _pad_to(u2.scale, 128, 1), _pad_to(u2.zero, 128, 1))
            mt, nt = m_, n_
            shapes = (qp[0].shape, up[0].shape)
        else:
            bm, bn = _tile_plan(m_), _tile_plan(n_)
            r_t = q2.shape[-1]
            qp = _pad_to(_pad_to(q2.astype(jnp.float32), bm, 0), 128, 1)
            up = _pad_to(_pad_to(u2.astype(jnp.float32), bn, 0), 128, 1)
            mt = nt = None
            shapes = (qp.shape, up.shape)
        gp = _pad_to(_pad_to(g2, bm, 0), bn, 1)
        mp = (None if m12 is None
              else _pad_to(_pad_to(m12.astype(jnp.float32), bm, 0), bn, 1))
        _note_instance("fused_precond", shapes + (gp.shape,),
                       (bm, bn, m12 is not None, with_fold, quantized))
        out, vfro, usq, m1dot, m1sq, y = fused_precond_pallas(
            qp, up, gp, mp, jnp.asarray(b2), jnp.asarray(eps),
            bm=bm, bn=bn, with_fold=with_fold, m_true=mt, n_true=nt,
            interpret=interp)
        # the kernel always emits the vfro per-tile partial (it rides the
        # update loop for free); drop it here so the return contract
        # matches the ref path backend-independently
        return (out[:m_, :n_], vfro if with_vfro else None, usq,
                m1dot, m1sq, None if y is None else y[:n_, :r_t])

    fn = one
    for _ in range(g.ndim - 2):
        fn = jax.vmap(fn)
    return fn(q, u, g, m1)


def fused_apply(u_hat: jnp.ndarray, m1: jnp.ndarray | None,
                denom: jnp.ndarray, b1: float,
                out_scale: jnp.ndarray, store_scale: jnp.ndarray,
                shared_out: bool = False):
    """Pass 2 of the fused pipeline (see ref.fused_apply): clip + first-
    moment EMA + guidance scales in one read-modify-write; on the Pallas
    path ``m1`` is donated to its output (updated in place).

    ``u_hat``/``m1``: (*batch, m, n); ``denom``/``out_scale``/
    ``store_scale``: (*batch,) scalars from the host combine.  With ``m1``
    None (b1 = 0) the EMA collapses to a single scaled copy, which is one
    fused elementwise op on every backend — no kernel needed.
    ``shared_out=True`` (valid when out_scale == store_scale, i.e.
    guidance "off" or "stored") returns the SAME array as m_out and
    m1_new — exactly the unfused aliasing — saving one (m, n) HBM write
    on the kernel path.  Returns (m_out, m1_new); ``m1_new`` is None when
    ``m1`` is None.
    """
    use, interp = _use_pallas()

    if m1 is None:
        dn = jnp.asarray(denom).reshape(jnp.shape(denom) + (1, 1))
        os_ = jnp.asarray(out_scale).reshape(jnp.shape(out_scale) + (1, 1))
        return (u_hat / dn) * os_, None

    def one(u2, m12, d, os_, ss):
        if not use:
            out, m1n = ref.fused_apply(u2, m12, d, b1, os_, ss)
            return (m1n, m1n) if shared_out else (out, m1n)
        m_, n_ = u2.shape
        bm, bn = _tile_plan(m_), _tile_plan(n_)
        up = _pad_to(_pad_to(u2.astype(jnp.float32), bm, 0), bn, 1)
        mp = _pad_to(_pad_to(m12.astype(jnp.float32), bm, 0), bn, 1)
        _note_instance("fused_apply", (up.shape,), (bm, bn, shared_out))
        scalars = jnp.stack([d.astype(jnp.float32),
                             jnp.asarray(b1, jnp.float32),
                             jnp.asarray(1.0 - b1, jnp.float32),
                             os_.astype(jnp.float32),
                             ss.astype(jnp.float32)]).reshape(1, 5)
        if shared_out:
            m1n = fused_apply_shared_pallas(up, mp, scalars, bm=bm, bn=bn,
                                            interpret=interp)
            m1n = m1n[:m_, :n_]
            return m1n, m1n
        out, m1n = fused_apply_pallas(up, mp, scalars, bm=bm, bn=bn,
                                      interpret=interp)
        return out[:m_, :n_], m1n[:m_, :n_]

    fn = one
    for _ in range(u_hat.ndim - 2):
        fn = jax.vmap(fn)
    return fn(u_hat, m1, jnp.asarray(denom), jnp.asarray(out_scale),
              jnp.asarray(store_scale))


def sq_matmul(g: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(G*G) @ X with G^2 fused (see ref.sq_matmul)."""
    use, interp = _use_pallas()

    def one(g2, x2):
        if not use:
            return ref.sq_matmul(g2, x2)
        m, n = g2.shape
        s = x2.shape[1]
        bm, bn = _tile_plan(m), _tile_plan(n)
        gp = _pad_to(_pad_to(g2, bm, 0), bn, 1)
        xp = _pad_to(_pad_to(x2.astype(jnp.float32), bn, 0), 128, 1)
        _note_instance("sq_matmul", (gp.shape, xp.shape), (bm, bn))
        y = sq_matmul_pallas(gp, xp, bm=bm, bn=bn, interpret=interp)
        return y[:m, :s]

    fn = one
    for _ in range(g.ndim - 2):
        fn = jax.vmap(fn)
    return fn(g, x)


def sq_matmul_t(g: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """(G*G)^T @ Y — implemented as sq_matmul on the transpose.  NB: XLA
    materialises G^T in HBM before the custom call (a transpose copy is
    NOT folded into the kernel's tile streaming), so a standalone call
    costs ~3mn words of traffic on top of the matmul's reads — the reason
    fold steps route through ``fused_precond(..., with_fold=True)``, which
    emits the same product from pass 1's already-resident G tiles.  The
    roofline model (benchmarks/roofline.py) charges this stage honestly."""
    def one(g2, y2):
        return sq_matmul(g2.T, y2)

    fn = one
    for _ in range(g.ndim - 2):
        fn = jax.vmap(fn)
    return fn(g, y)


def one_sided_fold(u: jnp.ndarray, q: jnp.ndarray, g: jnp.ndarray,
                   b2: float,
                   col_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Rank-projected factor fold ``mask * (b2*U + (1-b2) (G^2)^T Q)`` —
    the between-refresh update of Adapprox's amortized S-RSI.  The hot
    (G^2)^T Q product goes through the fused ``sq_matmul_t`` Pallas kernel
    dispatch (G^2 never materialised, batching included); the rank-r EMA +
    mask broadcast over any leading batch dims.  ``col_mask`` (r,) is
    shared across the batch.
    """
    y = sq_matmul_t(g, q)
    folded = b2 * u.astype(jnp.float32) + (1.0 - b2) * y
    if col_mask is not None:
        folded = folded * col_mask[None, :]
    return folded


def sketch_update(table: jnp.ndarray, g: jnp.ndarray, idx: jnp.ndarray,
                  b2: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused count-min EMA scatter + min-over-depth query (see
    ref.sketch_update).  table: (depth, width, d) f32, g: (rows, d) any
    float, idx: (depth, rows) int32.  Returns (table_new, vhat).

    Padding contract: rows pad with zero gradient and bucket 0 (no mass
    scattered, query sliced away); the bucket axis pads to a lane multiple
    (padded buckets are never indexed); the inner axis pads to the block
    and is sliced back.
    """
    use, interp = _use_pallas()
    if not use:
        return ref.sketch_update(table, g, idx, b2)
    depth, width, d = table.shape
    rows = g.shape[0]
    br = _pick_block(rows, target=256, align=8)
    # shrink the inner block when the resident (depth, width, bd) table
    # pair would blow the VMEM budget (see sketch_update.py docstring)
    bd_target = 128 if depth * width > 4096 else 256
    bd = _pick_block(d, target=bd_target, align=128)
    tab = _pad_to(_pad_to(table.astype(jnp.float32), 128, 1), bd, 2)
    gp = _pad_to(_pad_to(g, br, 0), bd, 1)
    ip = _pad_to(idx, br, 1)

    def call(tab, gp, ip):
        return sketch_update_pallas(tab, gp, ip, jnp.asarray(b2), br=br,
                                    bd=bd, interpret=interp)

    # XLA cannot partition a Mosaic kernel.  Under a mesh every device
    # runs it on its own slice of the inner axis — each column is an
    # independent sketch, and FSDP already shards embedding tables there —
    # or, when the slices would not hold whole d-blocks, on all of it.
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.size > 1:
        ax = mesh.axis_names if (tab.shape[2] // bd) % mesh.size == 0 \
            else None
        call = jax.shard_map(
            call, mesh=mesh, check_vma=False,
            in_specs=(P(None, None, ax), P(None, ax), P()),
            out_specs=(P(None, None, ax), P(None, ax)))
    new, vhat = call(tab, gp, ip)
    return new[:, :width, :d], vhat[:rows, :d]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, bq: int = 512,
                    bk: int = 512) -> jnp.ndarray:
    """Flash attention for model-layout tensors.

    q: (B, Sq, H, dh), k/v: (B, Sk, KV, dh) with H % KV == 0 (GQA groups
    broadcast).  Pads dh to 128 lanes and folds (B, H) into the kernel
    grid.  On non-TPU backends runs the kernel in interpret mode ("pallas"
    test mode) or falls back to the reference (auto).
    """
    use, interp = _use_pallas()
    b, sq, h, dh = q.shape
    kv = k.shape[2]
    groups = h // kv
    kx = jnp.repeat(k, groups, axis=2)
    vx = jnp.repeat(v, groups, axis=2)

    if not use:
        # reference path via plain softmax attention
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       kx.astype(jnp.float32)) / jnp.sqrt(float(dh))
        if causal:
            sk = kx.shape[1]
            mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vx)

    dh_pad = (-dh) % 128
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dh_pad)))
    kp = jnp.pad(kx, ((0, 0), (0, 0), (0, 0), (0, dh_pad)))
    vp = jnp.pad(vx, ((0, 0), (0, 0), (0, 0), (0, dh_pad)))
    # (B, S, H, dh) -> (B*H, S, dh)
    fold = lambda t: jnp.moveaxis(t, 2, 1).reshape(b * h, t.shape[1],
                                                   dh + dh_pad)
    bq_eff = min(bq, sq)
    bk_eff = min(bk, kx.shape[1])
    out = flash_attention_pallas(fold(qp), fold(kp), fold(vp),
                                 causal=causal, bq=bq_eff, bk=bk_eff,
                                 interpret=interp,
                                 scale=1.0 / (dh ** 0.5))
    out = out.reshape(b, h, sq, dh + dh_pad)[:, :, :, :dh]
    return jnp.moveaxis(out, 1, 2)
