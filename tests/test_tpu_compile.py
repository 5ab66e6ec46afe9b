"""Compile every kernel ``kernels/ops.py`` can dispatch on a TPU for a
described (not attached) v5e chip, at GPT-2 345M leaf shapes, and GPT-2
117M's paged serving programs at the serving benchmark's shapes.

Interpret mode cannot see what the TPU compiler refuses: scalar loads
from un-placed memory, blocks not aligned to the (8, 128) tiling, or
more VMEM than a kernel may use.  These compiles can, without a chip.
Nothing runs, so nothing here checks values (the interpret-mode parity
tests do that).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file.  Keep all described-topology compiles in this one file.
"""
from __future__ import annotations

import os
import re
from math import prod

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.core.quantized import BLOCK_ROWS
from repro.kernels import ops

D, F, L = 1024, 4096, 24          # GPT-2 345M: d_model, d_ff, layers
VOCAB, SEQ, HEADS = 50257, 1024, 16
R = 128                           # k_max of the launcher's adapprox config


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def cache_off():
    """The persistent cache off: what is compiled for a described chip
    cannot be read back here."""
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture
def compiled_pallas(monkeypatch, cache_off):
    """Kernel dispatch forced onto the compiled (non-interpret) Pallas
    path."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))


@pytest.fixture
def compiled_text(one_chip, compiled_pallas):
    """Compile ``fn`` for one described v5e chip; returns the compiled
    HLO text."""
    def compile_(fn, *shapes):
        args = [jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), s)
            for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _factors(m, n, batch=(), quantized=False):
    if not quantized:
        return _s(batch + (m, R)), _s(batch + (n, R))

    def trip(rows):
        nb = -(-rows // BLOCK_ROWS)
        return (_s(batch + (rows, R), jnp.int8), _s(batch + (nb, R)),
                _s(batch + (nb, R)))
    return trip(m), trip(n)


# (m, n, batch): the stacked per-layer MLP leaves in both orientations,
# and one unstacked square attention leaf.
LEAVES = [(D, F, (L,)), (F, D, (L,)), (D, D, ())]
LEAF_IDS = ["w_up", "w_down", "attn"]


@pytest.mark.parametrize("variant", ["plain", "guided", "fold", "int8"])
@pytest.mark.parametrize("m,n,batch", LEAVES, ids=LEAF_IDS)
def test_fused_precond_compiles(compiled_text, variant, m, n, batch):
    from repro.core.quantized import QuantizedMatrix
    quant = variant == "int8"
    q, u = _factors(m, n, batch, quantized=quant)
    guided = variant == "guided"

    def fn(q, u, g, m1):
        if quant:
            q, u = QuantizedMatrix(*q), QuantizedMatrix(*u)
        return ops.fused_precond(q, u, g, 0.999, 1e-8,
                                 m1=m1 if guided else None,
                                 with_fold=variant == "fold")

    text = compiled_text(fn, q, u, _s(batch + (m, n)), _s(batch + (m, n)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shared", [False, True], ids=["two_out", "shared"])
@pytest.mark.parametrize("m,n,batch", LEAVES, ids=LEAF_IDS)
def test_fused_apply_compiles(compiled_text, shared, m, n, batch):
    def fn(u_hat, m1, denom, os_, ss):
        return ops.fused_apply(u_hat, m1, denom, 0.9, os_, ss,
                               shared_out=shared)

    text = compiled_text(fn, _s(batch + (m, n)), _s(batch + (m, n)),
                         _s(batch), _s(batch), _s(batch))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("transposed", [False, True], ids=["gx", "gtx"])
@pytest.mark.parametrize("m,n,batch", LEAVES, ids=LEAF_IDS)
def test_sq_matmul_compiles(compiled_text, transposed, m, n, batch):
    rows = m if transposed else n
    fn = ops.sq_matmul_t if transposed else ops.sq_matmul
    text = compiled_text(fn, _s(batch + (m, n)), _s(batch + (rows, R)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n,batch", LEAVES, ids=LEAF_IDS)
def test_lowrank_update_compiles(compiled_text, m, n, batch):
    q, u = _factors(m, n, batch)

    def fn(q, u, g):
        return ops.lowrank_update(q, u, g, 0.999, 1e-8, with_frob=True)

    text = compiled_text(fn, q, u, _s(batch + (m, n)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [VOCAB, SEQ], ids=["embed", "pos_embed"])
def test_sketch_update_compiles(compiled_text, rows):
    # the launcher's default sketch: depth 4, width 2048
    def fn(table, g, idx):
        return ops.sketch_update(table, g, idx, 0.999)

    text = compiled_text(fn, _s((4, 2048, D)), _s((rows, D)),
                         _s((4, rows), jnp.int32))
    assert "tpu_custom_call" in text


def test_sketch_update_shards_over_four_chips(topo, compiled_pallas):
    """Under a (data=4) mesh the sketch kernel runs per chip on a quarter
    of the inner axis — the FSDP layout of the embedding — with no
    gather (XLA cannot partition a Mosaic kernel by itself)."""
    mesh = make_mesh((4,), ("data",), devices=topo.devices)

    def placed(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    def fn(table, g, idx):
        return ops.sketch_update(table, g, idx, 0.999)

    with jax.set_mesh(mesh):
        text = jax.jit(fn).lower(
            placed((4, 2048, D), jnp.float32, None, None, "data"),
            placed((VOCAB, D), jnp.float32, None, "data"),
            placed((4, VOCAB), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"f32[4,2048,{D // 4}]" in text
    assert "all-gather" not in text


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_attention_compiles(compiled_text, causal):
    qkv = _s((8, SEQ, HEADS, D // HEADS), jnp.bfloat16)

    def fn(q, k, v):
        return ops.flash_attention(q, k, v, causal=causal)

    text = compiled_text(fn, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


# -- paged serving: the KV pool is updated in place ---------------------------
# GPT-2 117M as chipbench/configs/gpt2-117m.json serves it.
SLOTS, CACHE_LEN, BLOCK, CHUNK = 128, 1024, 16, 512

_INSTR = re.compile(
    r"^\s*(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
# opcodes that hand a buffer on (or write into it in place) without
# making another array of its size
_PASS_ON = {"parameter", "get-tuple-element", "bitcast", "scatter"}


def _pool_sized_ops(text, nb, bs, width):
    """Instructions of the compiled HLO ``text`` that make an array of
    at least one layer of the pool (a dimension of ``nb`` blocks or ``nb
    * bs`` rows), other than those in ``_PASS_ON`` and fusions whose root
    is a scatter."""
    roots, sized, comp = {}, [], None
    for line in text.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            comp = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        root, name, dims, op = m.groups()
        if root:
            roots[comp] = op
        shape = [int(d) for d in dims.split(",") if d]
        if ((nb in shape or nb * bs in shape)
                and prod(shape) >= nb * bs * width):
            sized.append((name, op, _CALLS.search(line)))
    return [f"{name} ({op})" for name, op, calls in sized
            if op not in _PASS_ON
            and not (op == "fusion" and calls
                     and roots.get(calls.group(1)) == "scatter")]


@pytest.mark.parametrize("program,temp_gib", [("decode", 2.5),
                                              ("prefill", 0.5)])
def test_paged_serving_updates_pool_in_place(one_chip, cache_off, program,
                                             temp_gib):
    """The engine's decode step (all slots) and one prefill chunk, pool
    donated as the engine donates it: the pool's output aliases the
    donated argument, no instruction copies, slices or stacks the pool
    (only scatters write it), and the temporaries stay small."""
    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config("gpt2-117m"))
    nbt = CACHE_LEN // BLOCK
    nb = SLOTS * nbt + 1

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return placed(_s(shape, jnp.int32))

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = placed(jax.eval_shape(lambda: model.init_paged_cache(nb, BLOCK)))

    def decode(params, pool, tokens, tables, positions):
        logits, pool = model.decode_paged(params, pool, tokens, tables,
                                          positions)
        return jnp.argmax(logits[:, -1, :], axis=-1), pool

    def prefill(params, pool, tokens, table, p0, last_idx):
        logits, pool = model.prefill_paged(params, pool, tokens, table, p0,
                                           last_idx)
        return jnp.argmax(logits[0, -1, :]), pool

    if program == "decode":
        fn, args = decode, (i32(SLOTS, 1), i32(SLOTS, nbt), i32(SLOTS))
    else:
        fn, args = prefill, (i32(1, CHUNK), i32(nbt), i32(), i32())
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile()

    layers, width = pool["k"].shape[0], prod(pool["k"].shape[3:])
    assert _pool_sized_ops(compiled.as_text(), nb, BLOCK, width) == []
    mem = compiled.memory_analysis()
    pool_bytes = 2 * layers * nb * BLOCK * width * pool["k"].dtype.itemsize
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < temp_gib * 2 ** 30
