"""Operation and byte counts of the optimizer's kernels, from the shapes
of the parameters (``jax.eval_shape`` of the configuration's reference's
``init_params``) as ``optim_ref.route`` gives them to the optimizer's
families, and the roofline share, kept with the benchmark so that every
PR computes them the same way.  The model's own FLOP counts depend on its
architecture and come from its reference (``reference.py``)."""
from __future__ import annotations

import math

import optim_ref

F32 = 4


def _routed(shapes: list, opt: dict, family: str) -> list:
    return [s for s, f in zip(shapes, optim_ref.route(shapes, opt))
            if f == family]


def sketch_leaves(shapes: list, opt: dict) -> list:
    """(rows, inner) of the tables the count-min sketch owns."""
    return [(s[0], math.prod(s[1:])) for s in _routed(shapes, opt, "sketch")]


def sketch_update_cost(shapes: list, opt: dict) -> tuple:
    """(flops, bytes) one step's sketch updates need: read the f32
    gradient and write the per-row estimate (rows x inner each), read and
    write the (depth, width, inner) table, read the bucket indices; per
    element and hash one square-add for the scatter and one min for the
    query."""
    depth, width = opt["sketch_depth"], opt["sketch_width"]
    flops = nbytes = 0
    for rows, inner in sketch_leaves(shapes, opt):
        nbytes += F32 * (2 * rows * inner + 2 * depth * width * inner
                         + depth * rows)
        flops += 3 * depth * rows * inner
    return float(flops), float(nbytes)


def factored_matrices(shapes: list, opt: dict) -> list:
    """(count, m, n) of the stacked matrices Adapprox factors: a leaf's
    leading axes count its matrices."""
    return [(math.prod(s[:-2]), s[-2], s[-1])
            for s in _routed(shapes, opt, "adapprox")]


def fused_precond_cost(shapes: list, opt: dict, rank: int,
                       with_fold: bool) -> tuple:
    """Pass 1 of the fused update: read G and both factors, write the raw
    update direction and (with the fold) the (n, r) projection; rebuild V
    tile-wise (2 m n r) and, with the fold, (G^2)^T Q (2 m n r), plus
    about 8 elementwise operations per entry."""
    flops = nbytes = 0
    for count, m, n in factored_matrices(shapes, opt):
        mm = 2 * m * n * rank * (2 if with_fold else 1)
        flops += count * (mm + 8 * m * n)
        nbytes += count * F32 * (2 * m * n + (m + n) * rank
                                 + (n * rank if with_fold else 0))
    return float(flops), float(nbytes)


def fused_apply_cost(shapes: list, opt: dict) -> tuple:
    """Pass 2 with the shared output: read the raw direction and the first
    moment, write the new first moment (= the step direction); clip scale
    and EMA, 3 operations per entry."""
    flops = nbytes = 0
    for count, m, n in factored_matrices(shapes, opt):
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
