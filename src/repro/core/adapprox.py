"""Adapprox (Algorithm 3): Adam with a randomized-low-rank second moment.

Paper-faithful properties (validated in tests/test_adapprox.py):
  * no bias correction;
  * update clipping  u <- u / max(1, RMS(u)/d)  (Shazeer & Stern);
  * the first moment accumulates the *update* ``G/(sqrt(V)+eps)``, not the
    gradient;
  * decoupled weight decay (AdamW style);
  * the second moment lives only as factors (Q, U) between steps:
    ``V_t = b2 * Q_{t-1} U_{t-1}^T + (1 - b2) * G_t^2`` is rebuilt each step,
    used for the update, and re-factored with (adaptive-rank) S-RSI;
  * optional cosine-similarity guidance (Sec. 3.5).

Engineering modes (beyond-paper, all default-off => the default object IS the
faithful baseline):
  * ``implicit=True``: run S-RSI against the implicit operator so V is never
    materialised in HBM (the jnp fallback still forms one transient (m, n)
    f32 tile-set for the elementwise update; the Pallas kernel path removes
    even that).
  * ``use_kernels=True``: fused Pallas TPU kernels for the elementwise update
    and the sketch matmuls (kernels/).
  * ``rank.mode='exact'``: minimal-k selection instead of the paper's
    incremental probe.
  * ``warm_start=True`` (+ ``n_iter_warm``, ``warm_drift_xi``): seed S-RSI
    from the stored U so 1-2 power iterations replace the cold l = 5.
  * ``refresh_every=T``: full S-RSI every T steps; between refreshes the
    factors absorb gradients via the one-sided fold
    ``U <- b2*U + (1-b2)(G^2)^T Q`` under the frozen basis Q — the
    elementwise update remains exact w.r.t. the implicit operator.
  * ``bucketed=True``: same-shape factored leaves run as ONE vmapped
    trace per shape bucket instead of N sequential per-leaf traces.
  * ``fused_update=True``: the whole elementwise tail (V-reconstruct ->
    divide -> RMS clip -> update-EMA first moment -> guidance) runs as a
    two-pass pipeline: pass 1 emits the raw update direction plus every
    reduction the tail needs (V never stored); the clip/guidance scalars
    combine on-host; pass 2 applies them in one read-modify-write
    (kernels/fused_update.py on TPU, the ref oracles elsewhere).
    Bit-exact vs the unfused path for ``guidance="off"``; guidance modes
    agree to fp tolerance (reassociated reductions).

Composition: :func:`scale_by_adapprox` is the pure preconditioner — it maps
gradients to the (positive) update direction ``m_out`` and owns only the
factored/dense second moment, the update-EMA first moment, RMS clipping and
guidance.  :func:`adapprox` is the documented chain

    chain(scale_by_adapprox(cfg),
          add_decayed_weights(cfg.weight_decay),
          scale_by_schedule(cfg.lr),
          scale(-1.0))

which reproduces the monolithic seed implementation bit-for-bit (same
arithmetic, same order, same PRNG folding).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import factored as F
from repro.core import rank as R
from repro.core import srsi as S
from repro.core.transform import (add_decayed_weights, scale,
                                  scale_by_schedule)
from repro.core.types import GradientTransformation, chain
from repro.resilience.guards import (GuardConfig, GuardState, guard_spec,
                                     init_guard_state)
from repro.telemetry.snapshot import (TelemetrySnapshot, init_snapshot,
                                      snapshot_spec)


@dataclasses.dataclass(frozen=True)
class AdapproxConfig:
    lr: "float | Callable" = 1e-3          # float or schedule(step) -> lr
    b1: float = 0.9                        # 0.0 disables the first moment
    b2: float = 0.999
    eps: float = 1e-8
    clip_d: float = 1.0                    # RMS clip threshold d
    weight_decay: float = 0.0
    rank: R.RankConfig = dataclasses.field(default_factory=R.RankConfig)
    k_max_frac: float = 0.25               # k_max = frac * min(m, n)
    oversample: int = 5                    # p
    n_iter: int = 5                        # l (power iterations)
    min_dim_factor: int = 128              # factor only if min(m,n) >= this
    guidance: str = "off"                  # "off" | "update" | "stored"
    guidance_max_scale: float = 10.0       # safety clamp on 1/(1-theta+eps)
    implicit: bool = False                 # S-RSI on implicit operator
    use_kernels: bool = False              # Pallas fused update path
    factor_dtype: str = "float32"          # "int8": 4x smaller factors
    seed: int = 0
    # --- amortized-refresh perf knobs (all default-off => bit-exact vs the
    # paper-faithful baseline; see docs in scale_by_adapprox)
    refresh_every: int = 1                 # full S-RSI every T steps; between
                                           # refreshes fold G^2 into U under
                                           # the frozen basis Q (exact w.r.t.
                                           # the implicit operator)
    warm_start: bool = False               # seed S-RSI from the stored U
    n_iter_warm: int = 1                   # l when warm-started (1-2 suffice)
    warm_drift_xi: float = 0.5             # drift guard: cold-restart the
                                           # sketch when stored xi exceeds this
    bucketed: bool = False                 # group same-shape leaves into one
                                           # vmapped S-RSI + update per bucket
    fused_update: bool = False             # two-pass fused elementwise tail:
                                           # pass 1 emits u_hat + the clip /
                                           # guidance reductions with V never
                                           # stored; pass 2 applies clip +
                                           # first-moment EMA + guidance in
                                           # one read-modify-write (bit-exact
                                           # vs the unfused path for
                                           # guidance="off"; see
                                           # tests/test_fused.py)
    # --- telemetry subsystem (repro.telemetry; both default-off => the
    # state pytree and the update arithmetic are unchanged)
    telemetry: bool = False                # carry a fixed-shape
                                           # TelemetrySnapshot (per-leaf xi /
                                           # rank / clip activation,
                                           # refresh-vs-fold counters) in the
                                           # state; collection reuses values
                                           # the update already computes, so
                                           # updates stay BITWISE identical
                                           # to telemetry=False
    dynamic_refresh: bool = False          # carry refresh_every as a traced
                                           # int32 scalar in the state so the
                                           # closed-loop controller
                                           # (telemetry/controller.py) can
                                           # retune the cadence at runtime
                                           # with ZERO recompilation
    # --- resilience (repro.resilience; default None => state pytree and
    # arithmetic unchanged)
    guards: Optional[GuardConfig] = None   # per-leaf xi guards: a blow-up
                                           # past guards.xi_trip forces a
                                           # full S-RSI refresh next step;
                                           # after guards.max_demotions
                                           # CONSECUTIVE trips the leaf
                                           # falls back to the exact dense
                                           # second moment (per-leaf
                                           # lax.cond; needs a dense shadow
                                           # buffer, so demotion allocates
                                           # only when max_demotions > 0).
                                           # Forces the per-leaf path
                                           # (bucketed stacking would batch
                                           # the per-leaf demotion cond
                                           # into a select).


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AdapproxState:
    step: jnp.ndarray                 # int32 scalar, counts from 0
    key: jax.Array                    # base PRNG key
    leaves: tuple                     # per-param FactoredLeaf | DenseLeaf,
                                      # in jax.tree.flatten(params) order
    telemetry: Optional[TelemetrySnapshot] = None
                                      # cfg.telemetry: per-step fixed-shape
                                      # snapshot (None => absent, the state
                                      # pytree is unchanged vs pre-telemetry)
    refresh_every: Optional[jnp.ndarray] = None
                                      # cfg.dynamic_refresh: the S-RSI
                                      # refresh cadence as a TRACED int32
                                      # scalar — the controller retunes it
                                      # without retriggering compilation
    guards: Optional[GuardState] = None
                                      # cfg.guards: per-factored-leaf trip /
                                      # forced-refresh / demotion state
                                      # (None => absent, pytree unchanged)


def _rms(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.mean(jnp.square(x)) + 1e-30)


def _refresh_pred(step, refresh_t):
    """THE refresh-vs-fold predicate: full S-RSI at t = 1, 1+T, 1+2T, ...
    ``refresh_t`` may be a Python int or a traced int32 scalar
    (``dynamic_refresh``).  Single definition shared by the update branch
    dispatch and the telemetry counters, so they can never desynchronize."""
    return (step % refresh_t) == (1 % refresh_t)


def _fused_scalars(usq, m1dot, m1sq, size: int, cfg: AdapproxConfig,
                   guidance: bool):
    """Host-side combine of the pass-1 reductions into the three scalars
    pass 2 needs: ``(denom, out_scale, store_scale)``.

    ``denom = max(1, rms/d)`` reproduces the unfused clip bit-for-bit
    (``sqrt(usq/size + 1e-30)`` lowers to the same HLO as
    ``sqrt(mean(square(u)) + 1e-30)``).  The guidance scalars are recovered
    algebraically from the UNclipped pass-1 partials — with ``c = 1/denom``
    and ``acc = b1*m1 + (1-b1)*c*u_hat``:

        sum(u_c^2)   = usq / denom^2
        dot(u_c, m1) = m1dot / denom
        num          = b1*dot(u_c, m1) + (1-b1)*sum(u_c^2)
        sum(acc^2)   = b1^2*m1sq + 2*b1*(1-b1)*dot(u_c, m1)
                       + (1-b1)^2*sum(u_c^2)

    — the same quantities the unfused path reduces from the clipped
    arrays, reassociated, so guidance modes agree to fp tolerance (~1e-6
    rel) rather than bitwise; guidance="off" stays bitwise.
    """
    rms = jnp.sqrt(usq / size + 1e-30)
    denom = jnp.maximum(1.0, rms / cfg.clip_d)
    one = jnp.ones_like(denom)
    if not guidance:
        return denom, one, one
    su = usq / (denom * denom)
    du = m1dot / denom
    b1 = cfg.b1
    num = b1 * du + (1.0 - b1) * su
    accsq = (b1 * b1) * m1sq + 2.0 * b1 * (1.0 - b1) * du \
        + (1.0 - b1) ** 2 * su
    den = jnp.sqrt(su) * jnp.sqrt(accsq)
    theta = num / (den + 1e-30)
    gscale = jnp.clip(1.0 / (1.0 - theta + cfg.eps), 0.0,
                      cfg.guidance_max_scale)
    if cfg.guidance == "stored":
        return denom, gscale, gscale     # Eq. (18): the stored m1 is scaled
    return denom, gscale, one            # "update": step direction only


# Lazy module handles: repro.kernels.ops / repro.core.quantized are only
# needed on the kernel / int8 paths, and importing them per traced update
# call (the old inline ``from repro.kernels import ops``) put an import-lock
# acquisition + sys.modules lookup inside the hot per-leaf Python loop.
_KERNEL_OPS = None
_QUANTIZED = None


def _kernel_ops():
    global _KERNEL_OPS
    if _KERNEL_OPS is None:
        from repro.kernels import ops
        _KERNEL_OPS = ops
    return _KERNEL_OPS


def _quantized():
    global _QUANTIZED
    if _QUANTIZED is None:
        from repro.core import quantized
        _QUANTIZED = quantized
    return _QUANTIZED


def _leaf_r_store(shape: tuple[int, ...], cfg: AdapproxConfig) -> int:
    """Stored factor width for a (…, m, n) leaf."""
    m, n = shape[-2], shape[-1]
    if cfg.rank.mode == "static":
        r = min(cfg.rank.k_init, min(m, n))
    else:
        r = R.resolve_k_max(shape, cfg.rank, cfg.k_max_frac)
    return max(1, r)


def _leaf_oversample(shape: tuple[int, ...], r_store: int,
                     cfg: AdapproxConfig) -> int:
    """Paper constraint (k + p) <= min(m, n)."""
    m, n = shape[-2], shape[-1]
    return max(0, min(cfg.oversample, min(m, n) - r_store))


def _init_leaf(p: jnp.ndarray, cfg: AdapproxConfig):
    m1 = jnp.zeros(p.shape, jnp.float32) if cfg.b1 > 0 else None
    if F.should_factor(p.shape, cfg.min_dim_factor):
        bd = F.batch_dims(p.shape)
        m, n = p.shape[-2], p.shape[-1]
        r = _leaf_r_store(p.shape, cfg)
        k0 = cfg.rank.k_init if cfg.rank.mode != "static" else r
        q0 = jnp.zeros(bd + (m, r), jnp.float32)
        u0 = jnp.zeros(bd + (n, r), jnp.float32)
        if cfg.factor_dtype == "int8":
            QZ = _quantized()
            q0, u0 = QZ.quantize(q0), QZ.quantize(u0)
        return F.FactoredLeaf(
            q=q0,
            u=u0,
            k=jnp.full(bd, min(k0, r), jnp.int32),
            xi=jnp.zeros(bd, jnp.float32),
            m1=m1,
        )
    return F.DenseLeaf(v=jnp.zeros(p.shape, jnp.float32), m1=m1)


# ---------------------------------------------------------------------------
# Per-matrix (2D) factored update
# ---------------------------------------------------------------------------

def _factored_update_2d(g, q, u, k, xi_prev, m1, key, step,
                        cfg: AdapproxConfig,
                        r_store: int, p_eff: int, k_max_leaf: int,
                        refresh_t=None, force_refresh=None):
    """``refresh_t``: the refresh cadence as a traced int32 scalar
    (``cfg.dynamic_refresh``) or ``None`` (the compile-time
    ``cfg.refresh_every`` applies).  Returns one extra trailing output vs
    the pre-telemetry signature — ``clip_active`` (f32 scalar, 1.0 when
    the RMS clip engaged) — which is free to compute and dead-code
    eliminated when the caller drops it (telemetry off).

    ``force_refresh``: optional traced int32 scalar (the xi guard's
    per-leaf flag, ``cfg.guards``) OR-ed into the refresh predicate — a
    tripped leaf re-factorizes immediately instead of waiting out the
    fold cadence.  It rides in via closure like ``step``, so it stays an
    unbatched scalar under vmap and the cond remains a real branch."""
    g32 = g.astype(jnp.float32)
    dynamic = cfg.dynamic_refresh and refresh_t is not None
    r_every = refresh_t if dynamic else cfg.refresh_every
    # Lazy int8: with fused_update + factor_dtype="int8" the caller passes
    # the stored QuantizedMatrix triples straight through — pass 1
    # dequantizes per tile in VMEM and the f32 factors never materialize
    # in HBM on the update path.  Only the skinny refresh/fold branch
    # (inside its lax.cond, O((m+n) r) transient) sees f32 factors.
    is_q8 = hasattr(q, "q8")

    def _deq():
        QZ = _quantized()
        return QZ.dequantize(q), QZ.dequantize(u)

    # The skinny f32 view of the factors the refresh/fold branches consume
    # must be dequantized OUTSIDE the cond, for the same reason pass 1
    # stays outside it (see below): XLA contracts the codec's mul-add to
    # fma differently across program contexts, and the eager unfused path
    # dequantizes up front — in-branch dequant breaks the bitwise
    # contract.  O((m+n) r) transient, invisible next to the O(mn) update.
    q32u32 = _deq() if is_q8 else None

    v_op = None if is_q8 else S.make_implicit_v(q, u, g32, cfg.b2)

    # V_t is needed every step for the elementwise update unless the fused
    # pipeline (or the lowrank_update kernel) reconstructs it tile-wise;
    # the dense-S-RSI refresh reuses it.
    vmat = None
    if not cfg.fused_update and not cfg.use_kernels:
        vmat = v_op.materialize()          # paper-faithful: V_t formed

    # --- fused pass 1: u_hat + every tail reduction in one read of G, V
    # never stored (the dense-S-RSI refresh, if any, re-forms it inside
    # its lax.cond branch, so fold steps skip the materialisation).
    # ||V||_F^2 rides along only when the implicit S-RSI will consume it.
    # NOTE pass 1 must stay OUTSIDE the refresh/fold cond: XLA's fusion of
    # the V expression is not bit-stable across program contexts (fma
    # contraction differs), and the bitwise contract compares against the
    # unfused path, which forms V outside the cond.
    vfro = None
    yfold = None
    if cfg.fused_update:
        need_guid = cfg.b1 > 0 and cfg.guidance != "off"
        # Fold-fused: on an amortized-refresh cadence pass 1 also emits
        # the fold projection (G^2)^T Q from its already-resident G tiles,
        # so fold steps skip the standalone sq_matmul_t pass over G.
        # Computed EVERY step (pass 1 must stay outside the cond, see
        # above) and discarded on refresh steps — O(gm n r) partial words,
        # cheap next to the 3 m n the fold pass used to cost.
        with_fold = dynamic or cfg.refresh_every > 1
        with jax.named_scope("precondition"):
            (u_hat_raw, vfro, usq, m1dot, m1sq,
             yfold) = _kernel_ops().fused_precond(
                q, u, g32, cfg.b2, cfg.eps, m1=m1 if need_guid else None,
                with_vfro=cfg.implicit, with_fold=with_fold)

    @jax.named_scope("srsi")
    def _run_srsi(n_it: int, u0, use_warm):
        op = (v_op if v_op is not None
              else S.make_implicit_v(*q32u32, g32, cfg.b2))
        if cfg.implicit:
            # ||V||_F^2 from the already-materialised V when we have one
            # (use_kernels=False), or from the fused pass-1 partials —
            # rebuilding it via the streaming frob_sq would duplicate the
            # O(mnr) reconstruct.
            if vfro is not None:
                fs = vfro
            else:
                fs = None if vmat is None else jnp.sum(jnp.square(vmat))
            return S.srsi_implicit(op, r_store, p_eff, n_it, key,
                                   frob_sq=fs, u0=u0, use_warm=use_warm)
        vm = vmat if vmat is not None else op.materialize()
        return S.srsi_dense(vm, r_store, p_eff, n_it, key,
                            u0=u0, use_warm=use_warm)

    def _refresh():
        """Full S-RSI re-factorisation + adaptive rank (the seed path)."""
        if cfg.warm_start:
            # Seed the subspace iteration from the stored U; the drift
            # guard falls back to a cold Gaussian sketch when the last
            # approximation error regressed past warm_drift_xi (srsi.py
            # additionally re-randomizes zero columns: init, rank growth).
            # Step 1 has no subspace to inherit, so it runs the full cold
            # iteration (scalar predicate => stays a real branch under
            # vmap).  A *drift-guard* cold restart keeps n_iter_warm —
            # its predicate is per-leaf (batched), so a cond would decay
            # to a both-branches select under vmap and always pay the
            # full-l cost; instead the re-randomized sketch re-converges
            # over the next couple of warm refreshes (power iterations
            # accumulate across steps on the slow-moving EMA operator).
            use_warm = xi_prev <= cfg.warm_drift_xi
            u_seed = q32u32[1] if is_q8 else u
            res = jax.lax.cond(
                step == 1,
                lambda: _run_srsi(cfg.n_iter, None, None),
                lambda: _run_srsi(cfg.n_iter_warm, u_seed, use_warm))
        else:
            res = _run_srsi(cfg.n_iter, None, None)
        # --- adaptive rank (Algorithm 2 over the captured-energy CDF)
        k_new = R.select_rank(res.cum_energy, res.frob_sq, cfg.rank,
                              k_max_leaf, step, jnp.minimum(k, k_max_leaf),
                              refresh_every=r_every)
        xi = R.xi_of_k(res.cum_energy, res.frob_sq, k_new)
        mask = S.col_mask(r_store, k_new)
        return res.q * mask[None, :], res.u * mask[None, :], k_new, xi

    def _fold():
        """Between refreshes: fold G_t^2 into U under the frozen basis Q —
        U <- mask * (b2*U + (1-b2) (G^2)^T Q), the exact projection of
        V_t = b2 V_{t-1} + (1-b2) G^2 onto span(Q).  O(mnr) matmul, no
        subspace iteration, no QR.  With the fold-fused pass 1 (yfold
        from above) the matmul has already been paid for by the update's
        read of G and only the rank-r EMA runs here."""
        mask = S.col_mask(r_store, jnp.minimum(k, k_max_leaf))
        q32, u32 = q32u32 if is_q8 else (q, u)
        if yfold is not None:
            # yfold is the same single-dot (G^2)^T Q product the branches
            # below compute (one HLO, bit-stable in or out of the cond),
            # and the EMA runs inside the branch in both layouts — the
            # fused == unfused bitwise contract holds.
            u_new = (cfg.b2 * u32
                     + (1.0 - cfg.b2) * yfold) * mask[None, :]
        elif cfg.use_kernels:
            u_new = _kernel_ops().one_sided_fold(u32, q32, g32, cfg.b2,
                                                 mask)
        else:
            u_new = (cfg.b2 * u32
                     + (1.0 - cfg.b2) * ((g32 * g32).T @ q32)) \
                * mask[None, :]
        return q32, u_new, k, xi_prev

    if dynamic:
        # Traced cadence: the refresh/fold cond is always present in the
        # program and the predicate depends only on traced scalars, so a
        # host-side cadence change re-uses the compiled executable (zero
        # recompilation — tests/test_telemetry.py).  T = 1 refreshes every
        # step through the cond (same arithmetic as the direct call).
        pred = _refresh_pred(step, refresh_t)
    elif cfg.refresh_every > 1:
        # step counts from 1; refresh at t = 1, 1+T, 1+2T, ...  The scalar
        # predicate is unbatched under vmap, so lax.cond stays a real
        # branch (fold steps never pay for the S-RSI HLO).
        pred = _refresh_pred(step, cfg.refresh_every)
    else:
        pred = None                        # refresh every step, no cond
    if pred is not None:
        if force_refresh is not None:
            pred = jnp.logical_or(pred, force_refresh > 0)
        q_new, u_new, k_new, xi = jax.lax.cond(pred, _refresh, _fold)
    else:
        q_new, u_new, k_new, xi = _refresh()

    with jax.named_scope("precondition"):
        # --- elementwise tail, fused: host-combine the pass-1 reductions
        # into the clip / guidance scalars, then one read-modify-write
        # (pass 2) applies clip + first-moment EMA + guidance together.
        if cfg.fused_update:
            denom, out_scale, store_scale = _fused_scalars(
                usq, m1dot, m1sq, g32.size, cfg, need_guid)
            clip_active = (denom > 1.0).astype(jnp.float32)
            if cfg.b1 > 0:
                # guidance "off"/"stored": out_scale == store_scale, so the
                # step direction IS the new first moment (same as unfused) —
                # the shared-output kernel writes it once.
                m_out, m1_new = _kernel_ops().fused_apply(
                    u_hat_raw, m1, denom, cfg.b1, out_scale, store_scale,
                    shared_out=cfg.guidance != "update")
            else:
                m_out, m1_new = _kernel_ops().fused_apply(
                    u_hat_raw, None, denom, cfg.b1, out_scale, store_scale)
            return m_out, q_new, u_new, k_new, xi, m1_new, clip_active

        # --- elementwise update from V_t (prev factors + fresh G^2),
        # unfused
        if cfg.use_kernels:
            u_hat = _kernel_ops().lowrank_update(q, u, g32, cfg.b2, cfg.eps)
        else:
            u_hat = g32 / (jnp.sqrt(vmat) + cfg.eps)

        clip_denom = jnp.maximum(1.0, _rms(u_hat) / cfg.clip_d)
        clip_active = (clip_denom > 1.0).astype(jnp.float32)
        u_hat = u_hat / clip_denom

        # --- first moment over updates + optional cosine guidance
        if cfg.b1 > 0:
            m1_acc = cfg.b1 * m1 + (1.0 - cfg.b1) * u_hat
            if cfg.guidance != "off":
                num = jnp.sum(u_hat * m1_acc)
                den = (jnp.sqrt(jnp.sum(u_hat**2))
                       * jnp.sqrt(jnp.sum(m1_acc**2)))
                theta = num / (den + 1e-30)
                scale = jnp.clip(1.0 / (1.0 - theta + cfg.eps), 0.0,
                                 cfg.guidance_max_scale)
                if cfg.guidance == "stored":
                    m1_acc = m1_acc * scale    # Eq. (18) literally
                    m_out = m1_acc
                else:                  # "update": scale applied step only
                    m_out = m1_acc * scale
            else:
                m_out = m1_acc
            m1_new = m1_acc
        else:
            m_out, m1_new = u_hat, None

        return m_out, q_new, u_new, k_new, xi, m1_new, clip_active


def _leaf_meta(w_shape, r_store: int, cfg: AdapproxConfig):
    p_eff = _leaf_oversample(w_shape, r_store, cfg)
    k_max_leaf = (r_store if cfg.rank.mode == "static"
                  else R.resolve_k_max(w_shape, cfg.rank, cfg.k_max_frac))
    return p_eff, k_max_leaf


def _dequant_factors(leaf: F.FactoredLeaf, cfg: AdapproxConfig):
    if cfg.factor_dtype == "int8":
        QZ = _quantized()
        return QZ.dequantize(leaf.q), QZ.dequantize(leaf.u)
    return leaf.q, leaf.u


def _lazy_q8(cfg: AdapproxConfig) -> bool:
    """True when int8 factors skip the upfront dequant and ride into the
    fused pipeline as QuantizedMatrix triples (dequant fused into the
    pass-1 tile loads; refresh/fold dequantize transiently in-branch)."""
    return cfg.factor_dtype == "int8" and cfg.fused_update


def _run_factored_core(g, q32, u32, k, xi, m1, keys, step,
                       cfg: AdapproxConfig, r_store: int, p_eff: int,
                       k_max_leaf: int, n_batch: int, refresh_t=None,
                       force_refresh=None):
    """vmap ``_factored_update_2d`` over ``n_batch`` leading axes — the
    shared engine of the per-leaf path (n_batch = len(batch_dims)) and the
    bucketed path (one extra stacking axis).  ``step``, ``refresh_t`` and
    ``force_refresh`` ride in via closure, so they stay UNbatched scalars
    under vmap and the refresh/fold ``lax.cond`` remains a real branch."""
    fn = functools.partial(_factored_update_2d, cfg=cfg, r_store=r_store,
                           p_eff=p_eff, k_max_leaf=k_max_leaf)
    # ``m1`` may be None (b1 = 0); None is an empty pytree so it passes
    # through vmap untouched.
    core = lambda g, q, u, k, xi, m1, key: fn(g, q, u, k, xi, m1, key, step,
                                              refresh_t=refresh_t,
                                              force_refresh=force_refresh)
    mapped = F.vmap_over_batch(core, n_batch)
    return mapped(g, q32, u32, k, xi, m1, keys)


def _update_factored(g, leaf: F.FactoredLeaf, w, key, step,
                     cfg: AdapproxConfig, refresh_t=None, force_refresh=None):
    bd = F.batch_dims(w.shape)
    if _lazy_q8(cfg):
        # Dequant-fused: the stored QuantizedMatrix triples flow straight
        # into fused pass 1 (per-tile dequant in VMEM) — no upfront f32
        # materialisation of the factors.
        leaf_q, leaf_u = leaf.q, leaf.u
        r_store = leaf.q.q8.shape[-1]
    else:
        leaf_q, leaf_u = _dequant_factors(leaf, cfg)
        r_store = leaf_q.shape[-1]
    p_eff, k_max_leaf = _leaf_meta(w.shape, r_store, cfg)
    keys = F.batched_keys(key, bd)
    m_out, q, u, k, xi, m1, clip = _run_factored_core(
        g, leaf_q, leaf_u, leaf.k, leaf.xi, leaf.m1, keys, step, cfg,
        r_store, p_eff, k_max_leaf, len(bd), refresh_t, force_refresh)
    if cfg.factor_dtype == "int8":
        QZ = _quantized()
        q, u = QZ.quantize(q), QZ.quantize(u)
    return (m_out, F.FactoredLeaf(q=q, u=u, k=k, xi=xi, m1=m1),
            (clip, k_max_leaf))


def _update_factored_guarded(g, leaf: F.FactoredLeaf, w, key, step,
                             cfg: AdapproxConfig, refresh_t, guard):
    """Per-leaf update under the xi guard (``cfg.guards``).

    ``guard = (force_refresh, demoted, dense_v)`` — per-leaf int32 scalars
    from the prior :class:`GuardState` plus the leaf's dense shadow buffer
    (``None`` when ``max_demotions == 0``; then only forced refresh
    applies and the factored path runs unconditionally).

    A demoted leaf runs the exact dense second moment on its shadow
    buffer: same elementwise tail as ``_update_dense`` but with the
    PER-MATRIX RMS clip of the factored path (reduced over the trailing
    two axes, so batched leaves clip slice-wise exactly like before
    demotion), guidance off, factors/k frozen, xi pinned to 0 — a demoted
    leaf reads as healthy downstream.  The dispatch is a scalar-predicate
    ``lax.cond``, so un-demoted leaves never execute the dense HLO.

    Returns ``(m_out, new_leaf, (clip, k_max_leaf), dense_v_new)``.
    """
    force, demoted, dense_v = guard
    r_store = (leaf.q.q8.shape[-1] if cfg.factor_dtype == "int8"
               else leaf.q.shape[-1])
    _, k_max_leaf = _leaf_meta(w.shape, r_store, cfg)
    if dense_v is None:
        m_out, nl, tap = _update_factored(g, leaf, w, key, step, cfg,
                                          refresh_t, force_refresh=force)
        return m_out, nl, tap, None

    def _fact_branch():
        m_out, nl, tap = _update_factored(g, leaf, w, key, step, cfg,
                                          refresh_t, force_refresh=force)
        return (m_out, nl.q, nl.u, nl.k, nl.xi, nl.m1, tap[0], dense_v)

    def _dense_branch():
        g32 = g.astype(jnp.float32)
        v = cfg.b2 * dense_v + (1.0 - cfg.b2) * jnp.square(g32)
        u_hat = g32 / (jnp.sqrt(v) + cfg.eps)
        rms = jnp.sqrt(jnp.mean(jnp.square(u_hat), axis=(-2, -1)) + 1e-30)
        clip_denom = jnp.maximum(1.0, rms / cfg.clip_d)
        clip_active = (clip_denom > 1.0).astype(jnp.float32)
        u_hat = u_hat / clip_denom[..., None, None]
        if leaf.m1 is not None:
            m1_new = cfg.b1 * leaf.m1 + (1.0 - cfg.b1) * u_hat
            m_out = m1_new
        else:
            m1_new, m_out = None, u_hat
        return (m_out, leaf.q, leaf.u, leaf.k, jnp.zeros_like(leaf.xi),
                m1_new, clip_active, v)

    m_out, q, u, k, xi, m1, clip, dv = jax.lax.cond(
        demoted > 0, _dense_branch, _fact_branch)
    return (m_out, F.FactoredLeaf(q=q, u=u, k=k, xi=xi, m1=m1),
            (clip, k_max_leaf), dv)


def _update_factored_bucket(gs, leaves, ws, idxs, step_key, step,
                            cfg: AdapproxConfig, refresh_t=None):
    """One vmapped S-RSI + update for a bucket of same-signature leaves.

    All leaves share ``(batch_dims, m, n, r_store)`` (see
    ``F.leaf_signature``), so their state stacks along a new leading axis
    and the whole bucket traces ONCE — for a transformer stack with dozens
    of shape-sharing projection matrices this collapses N sequential HLO
    copies into one batched program (smaller HLO, fewer launches).  Each
    slice sees exactly the per-leaf PRNG key ``fold_in(step_key, i)`` and
    the same arithmetic, merely batched — updates, factors, rank and first
    moment are bit-identical to the per-leaf loop (the metrics-only ``xi``
    scalar can wobble 1 ulp from batched-vs-unbatched XLA fusion; see
    tests/test_refresh.py).
    """
    bd = F.batch_dims(ws[0].shape)
    if _lazy_q8(cfg):
        # QuantizedMatrix is a NamedTuple pytree: stacking fieldwise keeps
        # the triples intact for the dequant-fused pass-1 loads.
        stk = lambda ms: jax.tree.map(lambda *xs: jnp.stack(xs), *ms)
        q_stk = stk([leaf.q for leaf in leaves])
        u_stk = stk([leaf.u for leaf in leaves])
        r_store = q_stk.q8.shape[-1]
    else:
        deq = [_dequant_factors(leaf, cfg) for leaf in leaves]
        q_stk = jnp.stack([q for q, _ in deq])
        u_stk = jnp.stack([u for _, u in deq])
        r_store = q_stk.shape[-1]
    p_eff, k_max_leaf = _leaf_meta(ws[0].shape, r_store, cfg)
    g_stk = jnp.stack(gs)          # uniform dtype: part of the signature
    k_stk = jnp.stack([leaf.k for leaf in leaves])
    xi_stk = jnp.stack([leaf.xi for leaf in leaves])
    m1_stk = (jnp.stack([leaf.m1 for leaf in leaves])
              if leaves[0].m1 is not None else None)
    keys = jnp.stack([
        F.batched_keys(jax.random.fold_in(step_key, i), bd) for i in idxs])
    m_out, q, u, k, xi, m1, clip = _run_factored_core(
        g_stk, q_stk, u_stk, k_stk, xi_stk, m1_stk, keys, step, cfg,
        r_store, p_eff, k_max_leaf, len(bd) + 1, refresh_t)
    results = []
    for j in range(len(idxs)):
        qj, uj = q[j], u[j]
        if cfg.factor_dtype == "int8":
            QZ = _quantized()
            qj, uj = QZ.quantize(qj), QZ.quantize(uj)
        m1j = m1[j] if m1 is not None else None
        results.append((m_out[j],
                        F.FactoredLeaf(q=qj, u=uj, k=k[j], xi=xi[j], m1=m1j),
                        (clip[j], k_max_leaf)))
    return results


@jax.named_scope("precondition")
def _update_dense(g, leaf: F.DenseLeaf, cfg: AdapproxConfig):
    g32 = g.astype(jnp.float32)
    v = cfg.b2 * leaf.v + (1.0 - cfg.b2) * jnp.square(g32)
    u_hat = g32 / (jnp.sqrt(v) + cfg.eps)
    if cfg.fused_update:
        # Same pass-2 fusion as the factored leaves (dense leaves have no
        # guidance): the leaf is viewed as one (1, size) row so the pass-2
        # kernel / oracle applies clip + EMA in a single read-modify-write.
        denom, out_scale, store_scale = _fused_scalars(
            jnp.sum(jnp.square(u_hat)), None, None, u_hat.size, cfg,
            guidance=False)
        clip_active = (denom > 1.0).astype(jnp.float32)
        u2 = u_hat.reshape(1, -1)
        if leaf.m1 is not None:
            m_out2, m1_new2 = _kernel_ops().fused_apply(
                u2, leaf.m1.reshape(1, -1), denom, cfg.b1,
                out_scale, store_scale, shared_out=True)
            return (m_out2.reshape(u_hat.shape),
                    F.DenseLeaf(v=v, m1=m1_new2.reshape(u_hat.shape)),
                    clip_active)
        m_out2, _ = _kernel_ops().fused_apply(u2, None, denom, cfg.b1,
                                              out_scale, store_scale)
        return m_out2.reshape(u_hat.shape), F.DenseLeaf(v=v, m1=None), \
            clip_active
    clip_denom = jnp.maximum(1.0, _rms(u_hat) / cfg.clip_d)
    clip_active = (clip_denom > 1.0).astype(jnp.float32)
    u_hat = u_hat / clip_denom
    if leaf.m1 is not None:
        m1 = cfg.b1 * leaf.m1 + (1.0 - cfg.b1) * u_hat
        m_out = m1
    else:
        m1, m_out = None, u_hat
    return m_out, F.DenseLeaf(v=v, m1=m1), clip_active


# ---------------------------------------------------------------------------
# Telemetry assembly (cfg.telemetry; repro.telemetry.snapshot)
# ---------------------------------------------------------------------------

def _assemble_snapshot(prev: TelemetrySnapshot, step, new_leaves, taps,
                       refresh_t, cfg: AdapproxConfig) -> TelemetrySnapshot:
    """Fold this step's per-leaf taps into the fixed-shape snapshot.

    Everything here is a scalar mean over values the update already
    produced (xi / k live in the new leaves, clip flags in ``taps``) —
    collection adds no reductions over parameter-sized arrays, which is
    what keeps its overhead in the noise (see
    ``adapprox_refresh5_warm1_telemetry`` in BENCH_step_time.json).
    """
    f32 = jnp.float32
    xi, k, k_frac = [], [], []
    for leaf, tap in zip(new_leaves, taps):
        if not isinstance(leaf, F.FactoredLeaf):
            continue
        _, k_max_leaf = tap
        xi.append(jnp.mean(leaf.xi))
        kf = jnp.minimum(leaf.k, k_max_leaf).astype(f32)
        k.append(jnp.mean(kf))
        k_frac.append(jnp.mean(kf / k_max_leaf))
    clip_rate = [jnp.mean(tap[0] if isinstance(tap, tuple) else tap)
                 for tap in taps]

    def stack(xs, n):
        return jnp.stack(xs) if xs else jnp.zeros((n,), f32)

    if cfg.dynamic_refresh and refresh_t is not None:
        t_now = refresh_t
        did = _refresh_pred(step, t_now).astype(f32)
    else:
        t_now = jnp.asarray(cfg.refresh_every, jnp.int32)
        if cfg.refresh_every > 1:
            did = _refresh_pred(step, cfg.refresh_every).astype(f32)
        else:
            did = jnp.ones((), f32)    # refresh_every=1: every step refreshes
    return TelemetrySnapshot(
        step=step,
        xi=stack(xi, 0), k=stack(k, 0), k_frac=stack(k_frac, 0),
        clip_rate=stack(clip_rate, len(taps)),
        did_refresh=did,
        refresh_steps=prev.refresh_steps + did.astype(jnp.int32),
        fold_steps=prev.fold_steps + (1 - did).astype(jnp.int32),
        refresh_every=t_now,
        leaf_indices=prev.leaf_indices,
        dense_indices=prev.dense_indices,
    )


# ---------------------------------------------------------------------------
# Sharding protocol
# ---------------------------------------------------------------------------

def _factored_leaf_spec(pspec: P, has_m1: bool) -> F.FactoredLeaf:
    """Param (…, m, n) with spec (…, a, b):
    q (…, m, r) -> (…, a, None); u (…, n, r) -> (…, b, None);
    k/xi (…,) -> batch part; m1 -> param spec.  (The factors of a sharded
    matrix shard along the same axes as the matrix itself.)"""
    parts = list(pspec)
    bd, a, b = parts[:-2], parts[-2], parts[-1]
    return F.FactoredLeaf(
        q=P(*bd, a, None), u=P(*bd, b, None),
        k=P(*bd), xi=P(*bd),
        m1=P(*parts) if has_m1 else None)


def _state_spec(state: AdapproxState, param_specs) -> AdapproxState:
    flat_specs = jax.tree.leaves(param_specs,
                                 is_leaf=lambda x: isinstance(x, P))
    leaves = []
    for pspec, leaf in zip(flat_specs, state.leaves):
        has_m1 = leaf.m1 is not None
        if isinstance(leaf, F.FactoredLeaf):
            leaves.append(_factored_leaf_spec(pspec, has_m1))
        else:
            leaves.append(F.DenseLeaf(v=pspec, m1=pspec if has_m1 else None))
    # telemetry scalars / per-leaf vectors and the dynamic cadence scalar
    # are replicated on every device — nothing to shard, no host sync
    # beyond the existing metric fetch.
    tel = (snapshot_spec(state.telemetry)
           if state.telemetry is not None else None)
    re_spec = P() if state.refresh_every is not None else None
    g_spec = None
    if state.guards is not None:
        fpspecs = [pspec for pspec, leaf in zip(flat_specs, state.leaves)
                   if isinstance(leaf, F.FactoredLeaf)]
        g_spec = guard_spec(state.guards, fpspecs)
    return AdapproxState(step=P(), key=P(), leaves=tuple(leaves),
                         telemetry=tel, refresh_every=re_spec,
                         guards=g_spec)


# ---------------------------------------------------------------------------
# xi-guard bookkeeping (cfg.guards; repro.resilience.guards)
# ---------------------------------------------------------------------------

def _advance_guard_state(gstate: GuardState, gcfg: GuardConfig,
                         cfg: AdapproxConfig, new_leaves, dv_out):
    """Fold this step's xi outcomes into the next :class:`GuardState`.

    A leaf trips when its WORST batch slice exceeds ``xi_trip`` (max, not
    the telemetry mean — one blown slice corrupts that slice's updates
    regardless of how healthy its siblings are).  Trips are consecutive:
    any calm step resets the leaf's count.  A trip schedules a forced
    full refresh for the NEXT step; ``max_demotions`` consecutive trips
    demote the leaf instead, seeding its dense shadow buffer from the
    just-refreshed factors (``max(Q Uᵀ, 0)`` — the reconstruction can go
    epsilon-negative, and sqrt of that is a NaN factory).  The seeding
    cond has a scalar predicate, so steps without a demotion never pay
    the O(mnr) reconstruction.
    """
    f_leaves = [l for l in new_leaves if isinstance(l, F.FactoredLeaf)]
    if not f_leaves:
        return gstate
    xi_vec = jnp.stack([jnp.max(l.xi) for l in f_leaves])
    already = gstate.demoted > 0
    tripped = jnp.logical_and(xi_vec > gcfg.xi_trip, ~already)
    trips = jnp.where(tripped, gstate.trips + 1, 0).astype(jnp.int32)
    if gcfg.max_demotions > 0:
        newly = jnp.logical_and(~already, trips >= gcfg.max_demotions)
        demoted = jnp.maximum(gstate.demoted, newly.astype(jnp.int32))
        force = jnp.logical_and(tripped, ~newly).astype(jnp.int32)
        dense_v = []
        for j, leaf in enumerate(f_leaves):
            def _seed(leaf=leaf):
                q32, u32 = _dequant_factors(leaf, cfg)
                recon = jnp.einsum("...mr,...nr->...mn", q32, u32)
                return jnp.maximum(recon, 0.0)
            dense_v.append(jax.lax.cond(
                newly[j], _seed, lambda j=j: dv_out[j]))
        demotions = (gstate.demotions
                     + jnp.sum(newly).astype(jnp.int32))
        dense_v = tuple(dense_v)
    else:
        demoted = gstate.demoted
        force = tripped.astype(jnp.int32)
        dense_v = gstate.dense_v
        demotions = gstate.demotions
    return GuardState(
        trips=trips, force_refresh=force, demoted=demoted,
        trip_total=gstate.trip_total + jnp.sum(tripped).astype(jnp.int32),
        demotions=demotions, dense_v=dense_v)


# ---------------------------------------------------------------------------
# Public factories
# ---------------------------------------------------------------------------

def scale_by_adapprox(cfg: AdapproxConfig) -> GradientTransformation:
    """The pure Adapprox preconditioner: gradients -> update direction.

    Owns the factored second moment (S-RSI refresh, adaptive rank), the
    update-EMA first moment, per-matrix RMS clipping and cosine guidance.
    Learning rate, weight decay and the descent sign are NOT applied —
    chain with ``add_decayed_weights`` / ``scale_by_schedule`` / ``scale``
    (see :func:`adapprox`).  ``cfg.lr`` / ``cfg.weight_decay`` are ignored
    here.
    """

    def init(params):
        flat, _ = jax.tree.flatten(params)
        leaves = tuple(_init_leaf(p, cfg) for p in flat)
        tel = None
        if cfg.telemetry:
            fidx = tuple(i for i, l in enumerate(leaves)
                         if isinstance(l, F.FactoredLeaf))
            didx = tuple(i for i, l in enumerate(leaves)
                         if not isinstance(l, F.FactoredLeaf))
            tel = init_snapshot(len(fidx), len(leaves), cfg.refresh_every,
                                leaf_indices=fidx, dense_indices=didx)
        r_every = (jnp.asarray(cfg.refresh_every, jnp.int32)
                   if cfg.dynamic_refresh else None)
        gstate = None
        if cfg.guards is not None:
            fshapes = [p.shape for p, l in zip(flat, leaves)
                       if isinstance(l, F.FactoredLeaf)]
            gstate = init_guard_state(fshapes, cfg.guards.max_demotions)
        return AdapproxState(step=jnp.zeros((), jnp.int32),
                             key=jax.random.PRNGKey(cfg.seed),
                             leaves=leaves, telemetry=tel,
                             refresh_every=r_every, guards=gstate)

    @jax.named_scope("adapprox")
    def update(grads, state: AdapproxState, params):
        step = state.step + 1              # paper counts from t = 1
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        step_key = jax.random.fold_in(state.key, step)
        refresh_t = state.refresh_every if cfg.dynamic_refresh else None

        n_leaves = len(flat_p)
        outs = [None] * n_leaves
        new_leaves = [None] * n_leaves
        # per-leaf telemetry taps: (clip_active, k_max_leaf | None).  The
        # clip flag is an output the update computes anyway; when
        # cfg.telemetry is off nothing consumes it and XLA dead-code
        # eliminates it, so the off path stays bitwise-identical.
        taps = [None] * n_leaves

        gcfg, gstate = cfg.guards, state.guards
        # guards force the per-leaf path: the per-leaf demotion lax.cond
        # would decay to a both-branches select inside a bucketed vmap.
        if not (cfg.bucketed and gcfg is None):
            dv_out = (list(gstate.dense_v)
                      if gcfg is not None and gstate.dense_v else None)
            j = 0                        # factored-leaf ordinal
            for i, (g, leaf, w) in enumerate(
                    zip(flat_g, state.leaves, flat_p)):
                if isinstance(leaf, F.FactoredLeaf):
                    if gcfg is not None:
                        guard = (gstate.force_refresh[j], gstate.demoted[j],
                                 dv_out[j] if dv_out is not None else None)
                        d, nl, tap, dv = _update_factored_guarded(
                            g, leaf, w, jax.random.fold_in(step_key, i),
                            step, cfg, refresh_t, guard)
                        if dv_out is not None:
                            dv_out[j] = dv
                    else:
                        d, nl, tap = _update_factored(
                            g, leaf, w, jax.random.fold_in(step_key, i),
                            step, cfg, refresh_t)
                    j += 1
                else:
                    d, nl, clip = _update_dense(g, leaf, cfg)
                    tap = (clip, None)
                outs[i], new_leaves[i], taps[i] = d, nl, tap
        else:
            # Bucketed execution: dense leaves update inline; factored
            # leaves group by (batch_dims, m, n, dtype) signature and run
            # one vmapped trace per bucket (bit-identical — per-leaf PRNG
            # folding is preserved inside the bucket).
            buckets: dict = {}
            for i, (g, leaf, w) in enumerate(
                    zip(flat_g, state.leaves, flat_p)):
                if isinstance(leaf, F.FactoredLeaf):
                    buckets.setdefault(
                        F.leaf_signature(w.shape, g.dtype), []).append(i)
                else:
                    d, nl, clip = _update_dense(g, leaf, cfg)
                    outs[i], new_leaves[i], taps[i] = d, nl, (clip, None)
            for idxs in buckets.values():
                if len(idxs) == 1:          # singleton: skip stack/unstack
                    i = idxs[0]
                    outs[i], new_leaves[i], taps[i] = _update_factored(
                        flat_g[i], state.leaves[i], flat_p[i],
                        jax.random.fold_in(step_key, i), step, cfg,
                        refresh_t)
                    continue
                res = _update_factored_bucket(
                    [flat_g[i] for i in idxs],
                    [state.leaves[i] for i in idxs],
                    [flat_p[i] for i in idxs],
                    idxs, step_key, step, cfg, refresh_t)
                for i, (d, nl, tap) in zip(idxs, res):
                    outs[i], new_leaves[i], taps[i] = d, nl, tap

        tel = None
        if cfg.telemetry:
            tel = _assemble_snapshot(state.telemetry, step, new_leaves,
                                     taps, refresh_t, cfg)
        new_gstate = None
        if gcfg is not None:
            new_gstate = _advance_guard_state(gstate, gcfg, cfg, new_leaves,
                                              dv_out)
        updates = jax.tree.unflatten(treedef, outs)
        return updates, AdapproxState(step=step, key=state.key,
                                      leaves=tuple(new_leaves),
                                      telemetry=tel,
                                      refresh_every=state.refresh_every,
                                      guards=new_gstate)

    return GradientTransformation(init, update, _state_spec)


def adapprox(cfg: AdapproxConfig,
             decay_mask: Optional[Callable] = None) -> GradientTransformation:
    """Algorithm 3 as a documented chain (bit-identical to the former
    monolithic implementation for any config):

        preconditioner -> + wd*W -> * lr_t -> * (-1)

    ``decay_mask``: optional mask forwarded to ``add_decayed_weights``
    (e.g. ``transform.mask_nd(2)`` to exempt biases/norms from decay).
    """
    return chain(
        scale_by_adapprox(cfg),
        add_decayed_weights(cfg.weight_decay, decay_mask),
        scale_by_schedule(cfg.lr),
        scale(-1.0),
    )


def _find_states(state, cls):
    """Yield every ``cls`` instance inside an (arbitrarily nested) optimizer
    state — chains are tuples, partitions are dicts."""
    if isinstance(state, cls):
        yield state
        return
    if isinstance(state, (tuple, list)):
        for s in state:
            yield from _find_states(s, cls)
    elif isinstance(state, dict):
        for s in state.values():
            yield from _find_states(s, cls)
    elif hasattr(state, "inner"):           # PartitionState
        yield from _find_states(state.inner, cls)


def rank_metrics(state) -> dict:
    """Mean effective rank / xi across factored leaves (for logging).

    Accepts a bare ``AdapproxState`` or any chain/partition state
    containing one.
    """
    ks, xis = [], []
    for sub in _find_states(state, AdapproxState):
        for leaf in sub.leaves:
            if isinstance(leaf, F.FactoredLeaf):
                ks.append(jnp.mean(leaf.k.astype(jnp.float32)))
                xis.append(jnp.mean(leaf.xi))
    if not ks:
        return {}
    return {"adapprox/mean_rank": jnp.mean(jnp.stack(ks)),
            "adapprox/mean_xi": jnp.mean(jnp.stack(xis))}


def adapprox_state(state) -> AdapproxState:
    """Extract the ``AdapproxState`` from a (possibly chained/partitioned)
    optimizer state — convenience for tests and metric probes."""
    for sub in _find_states(state, AdapproxState):
        return sub
    raise ValueError("no AdapproxState found in optimizer state")
