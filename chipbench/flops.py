"""Operation and byte counts from shapes, kept with the benchmark so that
every PR computes them the same way.  ``model`` is a configuration's
``model`` dict (GPT-2 layout: q, k, v, o and a two-matrix MLP per layer,
output head tied to the token table)."""
from __future__ import annotations

F32 = 4


def matmul_params(model: dict) -> int:
    """Weights that take part in a matrix product per token: the blocks'
    projections and MLP, and the tied output head (the token and position
    tables are read by lookup, not multiplied)."""
    d, f = model["d_model"], model["d_ff"]
    return model["n_layers"] * (4 * d * d + 2 * d * f) \
        + model["vocab"] * d


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward plus backward, no recomputation counted: 6 per matmul
    weight, plus causal attention.  A token at position i attends to i + 1
    positions: QK^T and AV take 2 (i + 1) d each forward, so the mean over
    a sequence of ``seq`` is 2 d (seq + 1) per layer forward, times 3."""
    d, n = model["d_model"], model["n_layers"]
    return 6.0 * matmul_params(model) + 6.0 * n * d * (seq + 1)


def forward_flops(model: dict, context: float) -> float:
    """One token forward through the model attending to ``context``
    positions (itself included)."""
    d, n = model["d_model"], model["n_layers"]
    return 2.0 * matmul_params(model) + 4.0 * n * d * context


def sketch_leaves(model: dict, opt: dict) -> list:
    """(rows, inner) of the tables the count-min sketch owns: 2-D leaves
    with at least ``embedding_min_rows`` rows."""
    rows_min = opt["embedding_min_rows"]
    leaves = [(model["vocab"], model["d_model"]),
              (model["max_seq_len"], model["d_model"])]
    return [s for s in leaves if s[0] >= rows_min]


def sketch_update_cost(model: dict, opt: dict) -> tuple:
    """(flops, bytes) one step's sketch updates need: read the f32
    gradient and write the per-row estimate (rows x inner each), read and
    write the (depth, width, inner) table, read the bucket indices; per
    element and hash one square-add for the scatter and one min for the
    query."""
    depth, width = opt["sketch_depth"], opt["sketch_width"]
    flops = nbytes = 0
    for rows, inner in sketch_leaves(model, opt):
        nbytes += F32 * (2 * rows * inner + 2 * depth * width * inner
                         + depth * rows)
        flops += 3 * depth * rows * inner
    return float(flops), float(nbytes)


def factored_matrices(model: dict) -> list:
    """(count, m, n) of the stacked matrices Adapprox factors."""
    d, f, n = model["d_model"], model["d_ff"], model["n_layers"]
    return [(4 * n, d, d), (n, d, f), (n, f, d)]


def fused_precond_cost(model: dict, rank: int, with_fold: bool) -> tuple:
    """Pass 1 of the fused update: read G and both factors, write the raw
    update direction and (with the fold) the (n, r) projection; rebuild V
    tile-wise (2 m n r) and, with the fold, (G^2)^T Q (2 m n r), plus
    about 8 elementwise operations per entry."""
    flops = nbytes = 0
    for count, m, n in factored_matrices(model):
        mm = 2 * m * n * rank * (2 if with_fold else 1)
        flops += count * (mm + 8 * m * n)
        nbytes += count * F32 * (2 * m * n + (m + n) * rank
                                 + (n * rank if with_fold else 0))
    return float(flops), float(nbytes)


def fused_apply_cost(model: dict) -> tuple:
    """Pass 2 with the shared output: read the raw direction and the first
    moment, write the new first moment (= the step direction); clip scale
    and EMA, 3 operations per entry."""
    flops = nbytes = 0
    for count, m, n in factored_matrices(model):
        flops += count * 3 * m * n
        nbytes += count * F32 * 3 * m * n
    return float(flops), float(nbytes)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple:
    """(share of the roofline in %, the bound that applies)."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
