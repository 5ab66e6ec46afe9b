"""Grouped-query attention with KV cache, causal/full masking, qk-norm,
QKV bias, and RoPE — weights kept 2D (see layers.py docstring).

Decode uses a static-shape ring of length ``cache_len`` with a position
mask — the production pattern (no dynamic shapes, O(cache_len) per token).
Sequence-sharded caches: the softmax here is written with plain reductions
so GSPMD can partition the S axis of the cache and insert the partial
max/sum collectives itself (flash-decoding style combine).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L

NEG_INF = -1e30


def attn_init(key, cfg, d: int) -> dict:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, cfg.n_heads * hd),
        "wk": L.dense_init(ks[1], d, cfg.n_kv_heads * hd),
        "wv": L.dense_init(ks[2], d, cfg.n_kv_heads * hd),
        "wo": L.dense_init(ks[3], cfg.n_heads * hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def attn_specs(cfg) -> dict:
    s = {"wq": ("embed", "q_heads"), "wk": ("embed", "kv_heads"),
         "wv": ("embed", "kv_heads"), "wo": ("q_heads", "embed")}
    if cfg.qkv_bias:
        s["bq"] = ("q_heads",)
        s["bk"] = ("kv_heads",)
        s["bv"] = ("kv_heads",)
    if cfg.qk_norm:
        s["q_norm"] = (None,)
        s["k_norm"] = (None,)
    return s


class KVCache(NamedTuple):
    k: jnp.ndarray        # (B, S_max, KV, dh)
    v: jnp.ndarray        # (B, S_max, KV, dh)


def init_kv_cache(batch: int, cache_len: int, cfg, dtype) -> KVCache:
    hd = cfg.resolved_head_dim
    shape = (batch, cache_len, cfg.n_kv_heads, hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _project_qkv(cfg, p, x, positions):
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(cfg, q, k, v, mask):
    """q: (B, Sq, H, dh), k/v: (B, Sk, KV, dh), mask: (B|1, Sq, Sk) bool."""
    hd = q.shape[-1]
    groups = cfg.n_heads // cfg.n_kv_heads
    b, sq = q.shape[:2]
    sk = k.shape[1]
    q = q.reshape(b, sq, cfg.n_kv_heads, groups, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out.reshape(b, sq, cfg.n_heads * hd)


# Above this sequence length, full attention switches to the chunked
# (online-softmax / Rabe-Staats) path: the (Sq, Sk) score matrix is never
# materialised — peak attention memory drops from O(Sq*Sk) to
# O(q_chunk * k_chunk) per head group.  At 32k context the naive path's
# scores alone are ~17 GiB/device; chunked is ~0.1 GiB.
CHUNK_THRESHOLD = 4096
Q_CHUNK = 1024
K_CHUNK = 1024


def _sdpa_chunked(cfg, q, k, v, *, causal: bool,
                  q_chunk: int = Q_CHUNK, k_chunk: int = K_CHUNK):
    """Blockwise attention with a running (max, sum, acc) online softmax.

    q: (B, Sq, H, dh), k/v: (B, Sk, KV, dh).  Sq % q_chunk == 0 and
    Sk % k_chunk == 0 (shape cells are powers of two; smoke shapes take the
    naive path).  This is the jnp-level analogue of a flash-attention
    kernel: on TPU the Pallas version would tile the same loop into VMEM,
    the HLO here already has the right O(S) memory behaviour for the
    dry-run.
    """
    hd = q.shape[-1]
    groups = cfg.n_heads // cfg.n_kv_heads
    b, sq = q.shape[:2]
    sk = k.shape[1]
    kv = cfg.n_kv_heads
    assert sq % q_chunk == 0 and sk % k_chunk == 0, (sq, sk)
    nq, nk = sq // q_chunk, sk // k_chunk
    scale = 1.0 / jnp.sqrt(float(hd))

    qc = q.reshape(b, nq, q_chunk, kv, groups, hd)
    kc = k.reshape(b, nk, k_chunk, kv, hd)
    vc = v.reshape(b, nk, k_chunk, kv, hd)

    @jax.checkpoint
    def q_step(_, qi):
        # rematerialised: the VJP of a plain scan would SAVE every
        # per-chunk probability block (= the full S^2 matrix again);
        # checkpointing recomputes them — flash-attention's backward.
        qblk = qc[:, qi].astype(jnp.float32) * scale   # (b, qc, kv, g, hd)
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk = kc[:, ki].astype(jnp.float32)       # (b, kc, kv, hd)
            vblk = vc[:, ki].astype(jnp.float32)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qblk, kblk)
            if causal:
                k_pos = ki * k_chunk + jnp.arange(k_chunk)
                msk = k_pos[None, :] <= q_pos[:, None]  # (qc, kc)
                s = jnp.where(msk[None, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p, vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, groups, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, groups, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kv, groups, q_chunk, hd), jnp.float32)
        if causal:
            # causal: kv chunks beyond the diagonal contribute nothing;
            # bound the inner scan at the diagonal block.
            n_kv = jnp.minimum(
                (qi * q_chunk + q_chunk + k_chunk - 1) // k_chunk, nk)
        else:
            n_kv = nk

        def bounded(carry, ki):
            def live(c):
                return kv_step(c, ki)[0]
            return jax.lax.cond(ki < n_kv, live, lambda c: c, carry), None

        (m, l, acc), _ = jax.lax.scan(bounded, (m0, l0, a0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)    # (b,kv,g,qc,hd)
        out = jnp.moveaxis(out, 3, 1)                   # (b,qc,kv,g,hd)
        return None, out

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, b, q_chunk, kv, g, hd) -> (b, sq, H*hd)
    outs = jnp.moveaxis(outs, 0, 1).reshape(b, sq, cfg.n_heads * hd)
    return outs.astype(v.dtype)


def sdpa_auto(cfg, q, k, v, *, causal: bool):
    """Dispatch: chunked for long sequences, naive otherwise; the
    ``attn_impl`` config knob forces either path (perf hillclimbing)."""
    sq, sk = q.shape[1], k.shape[1]
    impl = getattr(cfg, "attn_impl", "auto")
    divisible = sq % Q_CHUNK == 0 and sk % K_CHUNK == 0
    if impl == "chunked" and divisible:
        return _sdpa_chunked(cfg, q, k, v, causal=causal)
    if impl != "naive" and sq > CHUNK_THRESHOLD and divisible:
        return _sdpa_chunked(cfg, q, k, v, causal=causal)
    if causal:
        mask = (jnp.arange(sk)[None, None, :] <= jnp.arange(sq)[None, :, None])
    else:
        mask = jnp.ones((1, sq, sk), bool)
    return _sdpa(cfg, q, k, v, mask)


def attn_apply_full(cfg, p, x, *, causal: bool,
                    positions: Optional[jnp.ndarray] = None,
                    kv_override: Optional[tuple] = None) -> jnp.ndarray:
    """Full-sequence attention (train / prefill / encoder).

    kv_override: (k, v) for cross-attention (keys from the encoder)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(cfg, p, x, positions)
    if kv_override is not None:
        k, v = kv_override
    out = sdpa_auto(cfg, q, k, v, causal=causal)
    return out @ p["wo"].astype(x.dtype)


def attn_prefill(cfg, p, x, cache: KVCache) -> tuple[jnp.ndarray, KVCache]:
    """Causal attention over the prompt; fills cache[:, :S]."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = sdpa_auto(cfg, q, k, v, causal=True)
    new_cache = KVCache(
        k=jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                       (0, 0, 0, 0)),
        v=jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                       (0, 0, 0, 0)))
    return out @ p["wo"].astype(x.dtype), new_cache


def attn_decode(cfg, p, x, cache: KVCache,
                pos: jnp.ndarray) -> tuple[jnp.ndarray, KVCache]:
    """One-token step. x: (B, 1, D); pos: () int32 current position."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions)
    ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                      (0, pos, 0, 0))
    s_max = ck.shape[1]
    mask = (jnp.arange(s_max)[None, None, :] <= pos)
    out = _sdpa(cfg, q, ck, cv, mask)
    return out @ p["wo"].astype(x.dtype), KVCache(k=ck, v=cv)


# -- paged (block-table) KV cache ---------------------------------------------
# The pool holds every layer: (L, num_blocks, block_size, KV*dh), lane-dense
# (a minor dimension of dh alone would pad to the 128-lane tile, and the
# compiler would then store the pool with num_blocks minor and relayout
# it on every access).  The layer loop carries the whole pool: each layer
# writes its new tokens with one scatter at [layer, block, offset] and
# reads with one gather pool[layer, block_tables], so the pool is updated
# in place and no layer slice of it is ever materialised.  A sequence owns
# an ordered list of block ids (its block table) and a scalar position.
# The read gathers through the table into the LOGICAL dense layout (B,
# n_blocks_per_slot * block_size, KV, dh) and runs the exact same
# ``_sdpa`` reduction as the dense cache — positions at or beyond the
# per-row length are masked to NEG_INF, whose softmax weight underflows to
# exactly 0.0, so stale data in padded/recycled blocks can never leak into
# the output.  When the logical length equals ``cache_len`` this is
# BITWISE identical to ``attn_decode`` on a dense cache holding the same
# tokens (tests/test_serve.py pins it); the memory win is that the POOL is
# shared — slots only hold blocks their sequence actually reached, instead
# of reserving cache_len worst-case each.


def _paged_write(pool, layer, bids, offs, x):
    """pool: (L, NB, bs, KV*dh); x: (N, KV, dh) written at [layer,
    bids[i], offs[i]]."""
    return pool.at[layer, bids, offs].set(
        x.reshape(x.shape[0], -1).astype(pool.dtype))


def _paged_gather(pool, layer, block_tables, n_kv: int):
    """pool: (L, NB, bs, KV*dh); block_tables: (B, nbt) ->
    (B, nbt*bs, KV, dh), layer ``layer``'s rows in logical order."""
    g = pool[layer, block_tables]                # (B, nbt, bs, KV*dh)
    b, nbt, bs, w = g.shape
    return g.reshape(b, nbt * bs, n_kv, w // n_kv)


def attn_decode_paged(cfg, p, x, pk, pv, layer, block_tables, positions):
    """One-token step against the block pool, per-row positions.

    x: (B, 1, D); pk/pv: (L, NB, bs, KV*dh) the whole pool; layer: ()
    int32 this layer's index into it; block_tables: (B, nbt) int32;
    positions: (B,) int32 — row i's token lands at logical position
    positions[i] (physical block block_tables[i, positions[i] // bs],
    offset positions[i] % bs).  Rows parked on the null block (table all
    zeros, position 0) scatter garbage into block 0, which only ever
    appears masked — see serve/kv_cache.py for why block 0 is reserved.
    """
    bs = pk.shape[2]
    q, k, v = _project_qkv(cfg, p, x, positions[:, None])
    bids = jnp.take_along_axis(block_tables, (positions // bs)[:, None],
                               axis=1)[:, 0]                    # (B,)
    offs = positions % bs
    pk = _paged_write(pk, layer, bids, offs, k[:, 0])
    pv = _paged_write(pv, layer, bids, offs, v[:, 0])
    kall = _paged_gather(pk, layer, block_tables, cfg.n_kv_heads)
    vall = _paged_gather(pv, layer, block_tables, cfg.n_kv_heads)
    s = kall.shape[1]
    mask = jnp.arange(s)[None, None, :] <= positions[:, None, None]
    out = _sdpa(cfg, q, kall, vall, mask)
    return out @ p["wo"].astype(x.dtype), pk, pv


def attn_prefill_paged(cfg, p, x, pk, pv, layer, block_table, p0):
    """Causal attention over ONE prompt chunk, writing through the block
    table.  x: (1, C, D) — chunk tokens at logical positions
    p0..p0+C-1; pk/pv and layer as in ``attn_decode_paged``;
    block_table: (nbt,) int32 for this one slot; p0: () int32.  The chunk
    attends to everything already in the slot's blocks (earlier chunks)
    plus itself, causally.  Chunk padding past the real prompt length
    writes garbage k/v at positions the NEXT chunk (or decode) overwrites
    before they are ever unmasked, so bucketed chunk shapes stay
    compile-once without a pad mask.
    """
    _, c, _ = x.shape
    bs = pk.shape[2]
    tok_pos = p0 + jnp.arange(c)
    q, k, v = _project_qkv(cfg, p, x, tok_pos[None, :])
    bids = block_table[tok_pos // bs]                           # (C,)
    offs = tok_pos % bs
    pk = _paged_write(pk, layer, bids, offs, k[0])
    pv = _paged_write(pv, layer, bids, offs, v[0])
    kall = _paged_gather(pk, layer, block_table[None, :], cfg.n_kv_heads)
    vall = _paged_gather(pv, layer, block_table[None, :], cfg.n_kv_heads)
    s = kall.shape[1]
    mask = jnp.arange(s)[None, None, :] <= tok_pos[None, :, None]
    out = _sdpa(cfg, q, kall, vall, mask)
    return out @ p["wo"].astype(x.dtype), pk, pv
