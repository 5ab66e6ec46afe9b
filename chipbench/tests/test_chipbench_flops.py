"""The operation and byte counts the per-layer metrics use, against the
shapes of the model and of the optimizer state the program builds: the
model's FLOPs from its reference, the kernels' costs from the reference's
parameter shapes."""
import json

import jax
import pytest

import chipbench_tiny as T
import flops
import reference

CFG = json.loads((T.HERE / "configs" / "gpt2-345m.json").read_text())
MODEL = CFG["model"]
SHAPES = [x.shape for x in jax.tree.leaves(jax.eval_shape(
    lambda k: reference.init_params(MODEL, k), jax.random.PRNGKey(0)))]


def _traffic(name):
    return json.loads((T.HERE / "traffic" / f"{name}.json").read_text())


def test_matmul_params_are_gpt2_medium():
    shapes = jax.eval_shape(lambda k: reference.init_params(MODEL, k),
                            jax.random.PRNGKey(0))
    # the stacked block matrices (layers, m, n) and the tied token table
    mats = [x.size for x in jax.tree.leaves(shapes["blocks"]) if x.ndim == 3]
    mats.append(shapes["embed"].size)
    assert reference.matmul_params(MODEL) == sum(mats) == 353_453_056
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert 354e6 < total < 356e6          # GPT-2 medium, no q/k/v biases


def test_train_flops_per_token():
    fpt = reference.train_flops_per_token(MODEL, 1024)
    assert fpt == 6 * 353_453_056 + 6 * 24 * 1024 * 1025
    # causal attention: QK^T and AV take 4 (i + 1) d a layer forward at
    # position i, times 3 with the backward, averaged over positions
    direct = sum(3 * 4 * (i + 1) * 1024 * 24 for i in range(1024)) / 1024
    assert fpt - 6 * 353_453_056 == pytest.approx(direct)


def _opt_state_shapes(traffic):
    from repro.core import build_optimizer
    from repro.launch.train import optimizer_config
    o = traffic["optimizer"]
    opt = build_optimizer(optimizer_config(
        o["name"], o["steps"], o["lr"], mixed_groups=o["mixed_groups"],
        **o["knobs"]))
    return jax.eval_shape(
        lambda k: opt.init(reference.init_params(MODEL, k)),
        jax.random.PRNGKey(0))


def test_sketch_cost_matches_the_program_tables():
    traffic = _traffic("train.adapprox")
    state = _opt_state_shapes(traffic)
    sketch = state.inner["embeddings"][0]
    tables = [leaf.table.shape for leaf in sketch.leaves]
    o = traffic["optimizer"]
    assert tables == [(o["sketch_depth"], o["sketch_width"], inner)
                      for _, inner in flops.sketch_leaves(SHAPES, o)]
    f, b = flops.sketch_update_cost(SHAPES, o)
    rows_inner = sum(r * i for r, i in flops.sketch_leaves(SHAPES, o))
    table_words = sum(d * w * i for d, w, i in tables)
    assert b == 4 * (2 * rows_inner + 2 * table_words
                     + o["sketch_depth"] * (50257 + 1024))
    assert f == 3 * o["sketch_depth"] * rows_inner


def test_fused_costs_match_the_program_factors():
    traffic = _traffic("train.fused")
    state = _opt_state_shapes(traffic)
    leaves = state.inner["factored"][0].leaves
    got = sorted((l.q.shape[0], l.q.shape[1], l.u.shape[1], l.q.shape[2])
                 for l in leaves)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fp", T.HERE / "metrics" / "fused_precond_roofline.py")
    fp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fp)
    o = traffic["optimizer"]
    want = []
    for count, m, n in flops.factored_matrices(SHAPES, o):
        want += [(24, m, n, fp.RANK)] * (count // 24)
    assert got == sorted(want)
    f, b = flops.fused_apply_cost(SHAPES, o)
    assert b == 4 * 3 * sum(c * m * n for c, m, n in
                            flops.factored_matrices(SHAPES, o))


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share, bound = flops.roofline_share(0.0, 819e9, 2.0, peaks)
    assert (share, bound) == (50.0, "memory")
    share, bound = flops.roofline_share(197e12, 1.0, 1.0, peaks)
    assert (share, bound) == (100.0, "compute")


# The formulas as flops.py held them while they assumed GPT-2's layout:
# GPT-2's reference and the kernels' costs from shapes must read the same.
def _old_matmul_params(m):
    d, f = m["d_model"], m["d_ff"]
    return m["n_layers"] * (4 * d * d + 2 * d * f) + m["vocab"] * d


def _old_factored(m):
    d, f, n = m["d_model"], m["d_ff"], m["n_layers"]
    return [(4 * n, d, d), (n, d, f), (n, f, d)]


@pytest.mark.parametrize("name", ["gpt2-345m", "gpt2-117m"])
def test_gpt2_counts_are_the_former_formulas(name):
    model = json.loads((T.HERE / "configs" / f"{name}.json").read_text())[
        "model"]
    d, n = model["d_model"], model["n_layers"]
    for seq in (32, 1000, 1024):
        assert reference.train_flops_per_token(model, seq) == \
            6.0 * _old_matmul_params(model) + 6.0 * n * d * (seq + 1)
    for context in (1, 37, 513, 1024):
        assert reference.forward_flops(model, context) == \
            2.0 * _old_matmul_params(model) + 4.0 * n * d * context


def test_kernel_costs_are_the_former_formulas():
    o = _traffic("train.fused")["optimizer"]
    d, v, rows_min = MODEL["d_model"], MODEL["vocab"], o["embedding_min_rows"]
    old_sketch = [s for s in [(v, d), (MODEL["max_seq_len"], d)]
                  if s[0] >= rows_min]
    assert flops.sketch_leaves(SHAPES, o) == old_sketch
    old = _old_factored(MODEL)
    assert flops.fused_apply_cost(SHAPES, o) == (
        float(sum(c * 3 * m * n for c, m, n in old)),
        float(sum(c * 4 * 3 * m * n for c, m, n in old)))
    for fold in (False, True):
        r = 128
        assert flops.fused_precond_cost(SHAPES, o, r, fold) == (
            float(sum(c * (2 * m * n * r * (2 if fold else 1) + 8 * m * n)
                      for c, m, n in old)),
            float(sum(c * 4 * (2 * m * n + (m + n) * r
                               + (n * r if fold else 0))
                      for c, m, n in old)))
