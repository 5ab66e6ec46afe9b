"""Count-min sketch second-moment preconditioner — the embedding backend.

Adapprox's low-rank factorization (and Adafactor's rank-1 scheme) is the
wrong compression for embedding tables: rows update sparsely and the
second-moment spectrum is flat, so a rank-k basis wastes memory and S-RSI
refresh FLOPs on mass it cannot capture.  Following the Count-Sketch-
Optimizers line of work, :func:`scale_by_sketch` instead holds the Adam
second moment in a depth-d x width-w count-min sketch per leaf:

    update:  S[j, h_j(i), :] <- b2 * S[j, h_j(i), :] + (1 - b2) * G[i, :]^2
    query:   vhat[i, :] = min_j S[j, h_j(i), :] / (1 - b2^t)

with the dense-Adam first moment kept EXACT (it does not tolerate the
collision over-estimate the way the denominator does).  Memory per
sketched leaf: depth * width * inner f32 for the second moment instead of
rows * inner — independent of the vocabulary size.  The count-min query
never underestimates the exact per-row EMA (all additions are
non-negative, decay is uniform, min-over-depth preserves the bound), so
collisions can only make the preconditioner more conservative.

A leaf is sketched when it is >= 2-D with leading dim >= ``min_rows``
(the ``"embeddings"`` GroupSpec selector applies the same predicate at
routing time); other leaves owned by this transform fall back to exact
dense Adam, bitwise-identical to :func:`repro.core.adamw.scale_by_adam`,
so the transform is total and safe as a catch-all.

Hash seeds are STATIC pytree metadata (universal hashing
``((a*i + b) mod p) mod width`` with p = 2^31 - 1), derived
deterministically from ``cfg.seed`` and the leaf position — bucket
indices are trace-time constants, nothing random happens inside the
update, and a fresh ``init`` rebuilds identical seeds (which is what lets
checkpoint restore re-derive the treedef).

The fused scatter + query goes through ``kernels.ops.sketch_update``
(Pallas on TPU, jnp oracle elsewhere, ``REPRO_KERNEL_MODE`` override).

:func:`sketch` is the documented chain

    chain(scale_by_sketch(cfg),
          add_decayed_weights(wd),
          scale_by_schedule(lr),
          scale(-1.0))
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.transform import (add_decayed_weights, scale,
                                  scale_by_schedule)
from repro.core.types import GradientTransformation, chain
from repro.kernels import ops
from repro.telemetry.snapshot import (SketchSnapshot, init_sketch_snapshot,
                                      snapshot_spec)

_PRIME = (1 << 31) - 1          # Mersenne prime for universal hashing
_MASK64 = (1 << 64) - 1


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    lr: "float | Callable" = 1e-3          # used by the sketch() chain only
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0              # used by the sketch() chain only
    depth: int = 4                         # hash functions (min-over-depth)
    width: int = 2048                      # buckets per hash
    min_rows: int = 1024                   # leading-dim threshold to sketch
    seed: int = 0                          # hash-seed derivation root
    telemetry: bool = False                # carry SketchSnapshot in state


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SketchLeaf:
    """Sketched second moment for one >= 2-D leaf of shape (rows, *inner).

    table: (depth, width, prod(inner)) f32 — the count-min EMA.
    m:     exact first moment, param shape f32; None when b1 = 0.
    seeds: static ((a, b), ...) per depth — universal hash coefficients.
    shape: static param shape (the table flattens the inner dims away).
    """
    table: jnp.ndarray
    m: Optional[jnp.ndarray]
    seeds: tuple = dataclasses.field(default=(), metadata=dict(static=True))
    shape: tuple = dataclasses.field(default=(), metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SketchDense:
    """Exact dense-Adam fallback for leaves below the sketch threshold.
    The first moment is allocated even at b1 = 0, matching scale_by_adam
    (the paper's memory accounting)."""
    m: jnp.ndarray
    v: jnp.ndarray


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SketchState:
    step: jnp.ndarray                 # int32 scalar, counts from 0
    leaves: tuple                     # per-param SketchLeaf | SketchDense,
                                      # in jax.tree.flatten(params) order
    telemetry: Optional[SketchSnapshot] = None
                                      # cfg.telemetry: fixed-shape occupancy
                                      # / collision snapshot (None => state
                                      # pytree unchanged vs telemetry off)


def should_sketch(shape, min_rows: int) -> bool:
    """The ``"embeddings"`` predicate: >= 2-D with leading dim >= min_rows."""
    return len(shape) >= 2 and shape[0] >= min_rows


def _leaf_seeds(seed: int, leaf_idx: int, depth: int) -> tuple:
    """Deterministic (a, b) universal-hash pairs per depth — plain python
    ints (splitmix-style), stable across platforms and numpy versions."""
    x = (seed * 0x9E3779B97F4A7C15
         + (leaf_idx + 1) * 0xBF58476D1CE4E5B9) & _MASK64
    out = []
    for _ in range(depth):
        x = (x * 6364136223846793005 + 1442695040888963407) & _MASK64
        a = int((x >> 16) % (_PRIME - 1)) + 1          # a in [1, p)
        x = (x * 6364136223846793005 + 1442695040888963407) & _MASK64
        b = int((x >> 16) % _PRIME)                    # b in [0, p)
        out.append((a, b))
    return tuple(out)


def bucket_indices(n_rows: int, width: int, seeds: tuple) -> np.ndarray:
    """(depth, n_rows) int32 bucket per row per hash — computed with numpy
    at trace time (rows, width and seeds are all static), so the indices
    are constants in the jaxpr, not state."""
    i = np.arange(n_rows, dtype=np.int64)
    rows = [((a * i + b) % _PRIME) % width for (a, b) in seeds]
    return np.stack(rows).astype(np.int32)


def scale_by_sketch(cfg: SketchConfig) -> GradientTransformation:
    """Bias-corrected Adam direction with a count-min second moment on
    every >= 2-D leaf whose leading dim reaches ``cfg.min_rows``; exact
    dense Adam on the rest.  Learning rate / weight decay / descent sign
    are NOT applied — chain like the other preconditioners (see
    :func:`sketch`)."""

    def init(params):
        flat, _ = jax.tree.flatten(params)
        leaves = []
        for i, p in enumerate(flat):
            if should_sketch(p.shape, cfg.min_rows):
                inner = int(np.prod(p.shape[1:]))
                leaves.append(SketchLeaf(
                    table=jnp.zeros((cfg.depth, cfg.width, inner),
                                    jnp.float32),
                    m=(jnp.zeros(p.shape, jnp.float32)
                       if cfg.b1 > 0 else None),
                    seeds=_leaf_seeds(cfg.seed, i, cfg.depth),
                    shape=tuple(p.shape)))
            else:
                z = jnp.zeros(p.shape, jnp.float32)
                leaves.append(SketchDense(m=z, v=z))
        tel = None
        if cfg.telemetry:
            sidx = tuple(i for i, l in enumerate(leaves)
                         if isinstance(l, SketchLeaf))
            tel = init_sketch_snapshot(len(sidx), leaf_indices=sidx)
        return SketchState(step=jnp.zeros((), jnp.int32),
                           leaves=tuple(leaves), telemetry=tel)

    @jax.named_scope("sketch")
    def update(grads, state: SketchState, params):
        del params
        step = state.step + 1
        t = step.astype(jnp.float32)
        bc1 = 1.0 - cfg.b1 ** t
        bc2 = 1.0 - cfg.b2 ** t

        flat_g, treedef = jax.tree.flatten(grads)
        dirs, new_leaves, occs, overs = [], [], [], []
        for g, leaf in zip(flat_g, state.leaves):
            g32 = g.astype(jnp.float32)
            if isinstance(leaf, SketchLeaf):
                rows, inner = g.shape[0], leaf.table.shape[-1]
                idx = jnp.asarray(
                    bucket_indices(rows, leaf.table.shape[1], leaf.seeds))
                table_new, q = ops.sketch_update(
                    leaf.table, g.reshape(rows, inner), idx, cfg.b2)
                vhat = (q / bc2).reshape(g.shape)
                if leaf.m is not None:
                    m_new = cfg.b1 * leaf.m + (1.0 - cfg.b1) * g32
                    mhat = m_new / bc1
                else:
                    m_new, mhat = None, g32
                dirs.append(mhat / (jnp.sqrt(vhat) + cfg.eps))
                new_leaves.append(SketchLeaf(table=table_new, m=m_new,
                                             seeds=leaf.seeds,
                                             shape=leaf.shape))
                if state.telemetry is not None:
                    # occupancy: fraction of buckets holding any mass;
                    # overestimate proxy: total queried mass over total
                    # table mass (one depth row carries the whole EMA'd
                    # gsq mass), >= 1 and == 1 with zero collisions.
                    hit = (jnp.max(table_new, axis=-1) > 0.0)
                    occs.append(jnp.mean(hit.astype(jnp.float32)))
                    overs.append(jnp.sum(q)
                                 / jnp.maximum(jnp.sum(table_new[0]), 1e-30))
            else:
                m = cfg.b1 * leaf.m + (1.0 - cfg.b1) * g32
                v = cfg.b2 * leaf.v + (1.0 - cfg.b2) * jnp.square(g32)
                mhat = m / bc1
                vhat = v / bc2
                dirs.append(mhat / (jnp.sqrt(vhat) + cfg.eps))
                new_leaves.append(SketchDense(m=m, v=v))

        tel = state.telemetry
        if tel is not None:
            tel = SketchSnapshot(
                step=step,
                occupancy=(jnp.stack(occs) if occs
                           else jnp.zeros((0,), jnp.float32)),
                overestimate=(jnp.stack(overs) if overs
                              else jnp.zeros((0,), jnp.float32)),
                leaf_indices=tel.leaf_indices)
        return (jax.tree.unflatten(treedef, dirs),
                SketchState(step=step, leaves=tuple(new_leaves),
                            telemetry=tel))

    def spec(state: SketchState, param_specs):
        flat_specs = jax.tree.leaves(param_specs,
                                     is_leaf=lambda x: isinstance(x, P))
        leaves = []
        for pspec, leaf in zip(flat_specs, state.leaves):
            if isinstance(leaf, SketchLeaf):
                parts = list(pspec)
                parts += [None] * (len(leaf.shape) - len(parts))
                # the hashed row axis is gone from the table; the inner
                # axis maps to param axis 1 only when nothing was
                # flattened into it (2-D leaf), else replicate it.
                inner = parts[1] if len(leaf.shape) == 2 else None
                leaves.append(SketchLeaf(
                    table=P(None, None, inner),
                    m=P(*parts) if leaf.m is not None else None,
                    seeds=leaf.seeds, shape=leaf.shape))
            else:
                leaves.append(SketchDense(m=pspec, v=pspec))
        tel = (snapshot_spec(state.telemetry)
               if state.telemetry is not None else None)
        return SketchState(step=P(), leaves=tuple(leaves), telemetry=tel)

    return GradientTransformation(init, update, spec)


def sketch(cfg: SketchConfig,
           decay_mask: Optional[Callable] = None) -> GradientTransformation:
    """Sketch-Adam as a documented chain (see module docstring)."""
    return chain(
        scale_by_sketch(cfg),
        add_decayed_weights(cfg.weight_decay, decay_mask),
        scale_by_schedule(cfg.lr),
        scale(-1.0),
    )


def sketch_state(state) -> SketchState:
    """Extract the ``SketchState`` from a (possibly chained/partitioned)
    optimizer state — convenience for tests and metric probes."""
    from repro.core.adapprox import _find_states
    for sub in _find_states(state, SketchState):
        return sub
    raise ValueError("no SketchState found in optimizer state")
