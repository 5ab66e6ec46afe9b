"""The count-min sketch second-moment backend (repro.core.sketch):
kernel parity sweeps, routing, the no-underestimate invariant, the
dense-Adam fallback, memory accounting, sharding specs, telemetry, and
convergence on an embedding-dominated problem."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.config import OptimizerConfig
from repro.core import (apply_updates, build_optimizer, make_optimizer,
                        scale_by_adam, tree_nbytes)
from repro.core.sketch import (SketchConfig, SketchDense, SketchLeaf,
                               _leaf_seeds, bucket_indices, scale_by_sketch,
                               should_sketch, sketch_state)
from repro.distributed import sharding as SH
from repro.kernels import ops, ref
from repro.telemetry import validate_event
from repro.telemetry.runtime import TelemetryRuntime


# ---------------------------------------------------------------------------
# kernel parity: fused hashed EMA update + min-over-depth query
# ---------------------------------------------------------------------------

# (rows, width, depth, inner): aligned, big-aligned, unaligned, degenerate
SKETCH_SHAPES = [
    (256, 128, 4, 256),
    (37, 5, 3, 16),
    (1000, 130, 2, 100),
    (8, 3, 1, 4),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture()
def force_pallas():
    ops.set_mode("pallas")      # interpret=True on CPU
    yield
    ops.set_mode("auto")


def _mk_sketch(rows, width, depth, inner, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    table = jnp.abs(jax.random.normal(key, (depth, width, inner),
                                      jnp.float32))
    g = jax.random.normal(jax.random.fold_in(key, 1),
                          (rows, inner)).astype(dtype)
    idx = jnp.asarray(bucket_indices(rows, width,
                                     _leaf_seeds(seed, 0, depth)))
    return table, g, idx


@pytest.mark.parametrize("rows,width,depth,inner", SKETCH_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sketch_update_matches_ref(force_pallas, rows, width, depth, inner,
                                   dtype):
    table, g, idx = _mk_sketch(rows, width, depth, inner, dtype)
    new_k, q_k = ops.sketch_update(table, g, idx, 0.999)
    new_r, q_r = ref.sketch_update(table, g, idx, 0.999)
    # scatter parity is tolerance-level (matmul vs segment-sum summation
    # order); the gather is a single-term dot and stays exact
    np.testing.assert_allclose(np.asarray(new_k), np.asarray(new_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_r),
                               rtol=2e-4, atol=2e-4)


SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import make_mesh
from repro.core.sketch import _leaf_seeds, bucket_indices
from repro.kernels import ops, ref
rows, width, depth, inner = 300, 256, 2, 1024   # 4 d-blocks of 256
key = jax.random.PRNGKey(0)
table = jnp.abs(jax.random.normal(key, (depth, width, inner)))
g = jax.random.normal(jax.random.fold_in(key, 1), (rows, inner))
idx = jnp.asarray(bucket_indices(rows, width, _leaf_seeds(0, 0, depth)))
mesh = make_mesh((4,), ("data",))
put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
ops.set_mode("pallas")
fn = jax.jit(lambda t, g, i: ops.sketch_update(t, g, i, 0.999))
with jax.set_mesh(mesh):
    new_k, q_k = fn(put(table, None, None, "data"), put(g, None, "data"),
                    put(idx))
    text = fn.lower(put(table, None, None, "data"), put(g, None, "data"),
                    put(idx)).as_text()
assert "shard_map" in text or "sdy.manual_computation" in text, text[:2000]
new_r, q_r = ref.sketch_update(table, g, idx, 0.999)
np.testing.assert_allclose(np.asarray(new_k), np.asarray(new_r),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_r),
                           rtol=2e-4, atol=2e-4)
assert new_k.sharding.spec == P(None, None, "data"), new_k.sharding
print("SHARDED_SKETCH_OK")
"""


def test_sketch_update_under_mesh_matches_ref():
    """Under a 4-device mesh the kernel runs per device on its slice of
    the inner axis (a Mosaic kernel cannot be partitioned by XLA) and
    still matches the oracle (subprocess: needs its own device count)."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH=str(repo / "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "SHARDED_SKETCH_OK" in out.stdout, out.stderr[-3000:]


def test_sketch_update_oracle_is_ema_scatter():
    """Hand-check the oracle on a collision: two rows hashed to the same
    bucket accumulate, the query returns the shared bucket."""
    table = jnp.zeros((1, 2, 1), jnp.float32)
    g = jnp.asarray([[1.0], [2.0], [3.0]])
    idx = jnp.asarray([[0, 0, 1]], jnp.int32)
    new, q = ref.sketch_update(table, g, idx, 0.5)
    np.testing.assert_allclose(np.asarray(new[0, :, 0]),
                               [0.5 * (1 + 4), 0.5 * 9])
    np.testing.assert_allclose(np.asarray(q[:, 0]), [2.5, 2.5, 4.5])


# ---------------------------------------------------------------------------
# routing + the transform
# ---------------------------------------------------------------------------

def test_should_sketch_predicate():
    assert should_sketch((1024, 64), 1024)
    assert should_sketch((2048, 8, 4), 1024)
    assert not should_sketch((1023, 64), 1024)      # below the row floor
    assert not should_sketch((4096,), 1024)         # 1-D never sketches
    assert not should_sketch((), 1024)


def test_no_underestimate_through_transform():
    """End to end through scale_by_sketch: the implied vhat never drops
    below the exact dense-Adam vhat (collisions only add mass)."""
    cfg = SketchConfig(b1=0.0, b2=0.9, eps=0.0, depth=2, width=16,
                       min_rows=8)
    params = {"e": jnp.zeros((64, 4))}
    opt = scale_by_sketch(cfg)
    state = opt.init(params)
    exact_v = np.zeros((64, 4), np.float32)
    key = jax.random.PRNGKey(0)
    for t in range(1, 5):
        key, sub = jax.random.split(key)
        g = {"e": jax.random.normal(sub, (64, 4))}
        upd, state = opt.update(g, state, params)
        exact_v = 0.9 * exact_v + 0.1 * np.square(np.asarray(g["e"]))
        bc2 = 1.0 - 0.9 ** t
        # direction = g / sqrt(vhat_sketch); vhat_sketch >= vhat_exact
        # (eps = 0, b1 = 0) => |direction| <= |g| / sqrt(vhat_exact)
        bound = np.abs(np.asarray(g["e"])) / np.sqrt(exact_v / bc2)
        assert np.all(np.abs(np.asarray(upd["e"])) <= bound * (1 + 1e-5))


def test_dense_fallback_bitwise_matches_scale_by_adam():
    """Leaves below min_rows run EXACT dense Adam — bitwise, not close."""
    params = {"w": jnp.full((8, 4), 0.3), "b": jnp.full((5,), -0.2)}
    sk = scale_by_sketch(SketchConfig(b1=0.9, b2=0.999, eps=1e-8,
                                      min_rows=1024))
    ad = scale_by_adam(b1=0.9, b2=0.999, eps=1e-8)
    s_sk, s_ad = sk.init(params), ad.init(params)
    key = jax.random.PRNGKey(1)
    for _ in range(3):
        key, sub = jax.random.split(key)
        g = jax.tree.map(
            lambda p: jax.random.normal(sub, p.shape), params)
        u_sk, s_sk = sk.update(g, s_sk, params)
        u_ad, s_ad = ad.update(g, s_ad, params)
        for a, b in zip(jax.tree.leaves(u_sk), jax.tree.leaves(u_ad)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_make_optimizer_matches_build_optimizer():
    params = {"e": jnp.full((64, 8), 0.4), "b": jnp.full((3,), 0.1)}
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.2), params)
    m = make_optimizer("sketch", lr=0.05, depth=2, width=32, min_rows=16)
    b = build_optimizer(OptimizerConfig(
        name="sketch", schedule="constant", lr=0.05, weight_decay=0.0,
        sketch_depth=2, sketch_width=32, embedding_min_rows=16))
    u_m, _ = m.update(grads, m.init(params), params)
    u_b, _ = b.update(grads, b.init(params), params)
    for a, c in zip(jax.tree.leaves(u_m), jax.tree.leaves(u_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_seeds_are_deterministic_and_rebuildable():
    """A fresh init rebuilds identical static metadata (what lets
    checkpoint restore re-derive the treedef) and distinct leaves get
    distinct hash seeds."""
    params = {"e1": jnp.zeros((32, 4)), "e2": jnp.zeros((32, 4))}
    opt = scale_by_sketch(SketchConfig(min_rows=8, depth=2, width=16))
    s1, s2 = opt.init(params), opt.init(params)
    assert jax.tree.structure(s1) == jax.tree.structure(s2)
    assert s1.leaves[0].seeds == s2.leaves[0].seeds
    assert s1.leaves[0].seeds != s1.leaves[1].seeds


# ---------------------------------------------------------------------------
# memory accounting
# ---------------------------------------------------------------------------

def test_sketch_memory_reduction_vs_dense_adam():
    """The headline: >= 4x optimizer-state reduction on an embedding leaf
    at b1 = 0 (second moment only; the table is vocab-independent)."""
    params = {"emb": jnp.zeros((8192, 64))}
    sk = scale_by_sketch(SketchConfig(b1=0.0, depth=4, width=256,
                                      min_rows=1024))
    ad = scale_by_adam()
    n_sk = tree_nbytes(sk.init(params))
    n_ad = tree_nbytes(ad.init(params))
    assert n_ad >= 4 * n_sk, (n_ad, n_sk)
    # b1 > 0 allocates the exact first moment on top of the table
    n_m = tree_nbytes(scale_by_sketch(SketchConfig(
        b1=0.9, depth=4, width=256, min_rows=1024)).init(params))
    assert n_m >= n_sk + params["emb"].size * 4


def test_sketch_table_size_independent_of_rows():
    cfg = SketchConfig(b1=0.0, depth=4, width=256, min_rows=64)
    small = scale_by_sketch(cfg).init({"e": jnp.zeros((64, 32))})
    big = scale_by_sketch(cfg).init({"e": jnp.zeros((4096, 32))})
    assert tree_nbytes(small) == tree_nbytes(big)


# ---------------------------------------------------------------------------
# state_sharding_spec protocol
# ---------------------------------------------------------------------------

def test_opt_state_shardings_via_protocol_sketch():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    params = {"emb": jnp.zeros((2048, 64)), "b": jnp.zeros((64,))}
    opt = make_optimizer("sketch", min_rows=1024, depth=2, width=32)
    state_struct = jax.eval_shape(opt.init, params)
    pspecs = {"emb": P("data", "model"), "b": P("model")}
    sh = SH.opt_state_shardings(opt, state_struct, pspecs, mesh)
    st = sh[0]                         # chain stage 0: scale_by_sketch
    # flatten order: b=0 (dense fallback), emb=1 (sketched)
    assert st.leaves[0].m.spec == P("model")
    assert st.leaves[0].v.spec == P("model")
    # hashed row axis is gone -> replicate depth/width, inner follows the
    # param's axis-1 spec (2-D leaf, nothing flattened into it)
    assert st.leaves[1].table.spec == P(None, None, "model")
    assert st.leaves[1].m.spec == P("data", "model")
    assert st.step.spec == P()


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_sketch_telemetry_snapshot_and_event():
    params = {"e": jnp.zeros((64, 4)), "b": jnp.zeros((3,))}
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.1), params)
    cfg = dict(b2=0.999, depth=2, width=16, min_rows=8)
    on = scale_by_sketch(SketchConfig(telemetry=True, **cfg))
    off = scale_by_sketch(SketchConfig(telemetry=False, **cfg))
    u_on, s_on = on.update(grads, on.init(params), params)
    u_off, _ = off.update(grads, off.init(params), params)
    # collection never changes the update
    for a, b in zip(jax.tree.leaves(u_on), jax.tree.leaves(u_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    snap = s_on.telemetry
    assert snap.occupancy.shape == (1,) and snap.leaf_indices == (1,)
    occ = float(snap.occupancy[0])
    over = float(snap.overestimate[0])
    # 64 rows into 16 buckets: every bucket hit; collisions guaranteed
    assert occ == 1.0
    assert over >= 1.0
    # the host-side event conforms to the sink schema
    ev = TelemetryRuntime._sketch_event(3, "embeddings",
                                        jax.device_get(snap))
    ev["schema"] = 1
    validate_event(ev)
    assert ev["mean_occupancy"] == occ and ev["mean_overestimate"] == over


# ---------------------------------------------------------------------------
# convergence: embedding-dominated problem
# ---------------------------------------------------------------------------

def test_sketch_converges_like_adam_on_embeddings():
    """Embedding regression (sparse row updates, the backend's target
    workload): the sketch-Adam loss tracks dense Adam within tolerance."""
    vocab, dim = 256, 16
    key = jax.random.PRNGKey(0)
    target = jax.random.normal(key, (vocab, dim)) * 0.5
    ids = jax.random.randint(jax.random.fold_in(key, 1), (8, 64), 0, vocab)
    params0 = {"emb": jnp.zeros((vocab, dim))}

    def loss_fn(p, batch):
        return jnp.mean((p["emb"][batch] - target[batch]) ** 2)

    def run(opt):
        params, state = params0, opt.init(params0)

        @jax.jit
        def step(p, s, batch):
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
            upd, s = opt.update(g, s, p)
            return apply_updates(p, upd), s, loss

        for t in range(200):
            params, state, loss = step(params, state, ids[t % 8])
        return float(loss)

    loss0 = float(loss_fn(params0, ids[0]))
    l_adam = run(make_optimizer("adamw", lr=0.05))
    l_sketch = run(make_optimizer("sketch", lr=0.05, depth=4, width=512,
                                  min_rows=64))
    assert l_sketch < 0.05 * loss0, (loss0, l_sketch)
    assert l_sketch < 3.0 * l_adam + 1e-6, (l_adam, l_sketch)


def test_sketch_state_extractor():
    params = {"e": jnp.zeros((64, 4))}
    opt = make_optimizer("sketch", min_rows=8, depth=2, width=16)
    st = sketch_state(opt.init(params))
    assert isinstance(st.leaves[0], SketchLeaf)
    assert not isinstance(st.leaves[0], SketchDense)
