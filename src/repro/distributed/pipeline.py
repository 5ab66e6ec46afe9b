"""Pipeline parallelism (GPipe schedule) over a mesh axis.

Layers are split into S stages, the stage dim sharded over ``axis``; each
tick every stage processes one microbatch and hands its activation to the
next stage with a ``ppermute``.  The bubble is the usual (S-1)/(M+S-1)
fraction.  Because ``ppermute`` is differentiable (its transpose is the
reverse permute), the whole pipeline is a plain jax function: ``jax.grad``
through ``pipeline_apply`` yields the reverse-schedule backward pass with
no extra machinery.

Intended use on the production mesh: stages over the ``pod`` axis (cross-
pod DCN carries only the (mb, seq, d_model) boundary activations instead
of full gradient all-reduces — the classic reason to pipeline across the
slow domain).  The unit test runs 4 stages on 4 host devices and checks
exact equivalence with sequential layer application, forward and grad.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_micro,
                   mesh: Mesh, axis: str = "stage"):
    """Run ``stage_fn(params_stage, x) -> x`` as an S-stage GPipe pipeline.

    stage_params: pytree with leading (S, ...) dim, sharded over ``axis``.
    x_micro: (M, mb, ...) microbatched inputs (replicated).
    Returns (M, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]

    def body(params_local, micro):
        # params_local leaves: (1, ...) local stage slice
        params_local = jax.tree.map(lambda p: p[0], params_local)
        s_idx = jax.lax.axis_index(axis)
        m = micro.shape[0]
        ticks = m + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            outputs, prev = carry
            # stage 0 injects microbatch t (bubble ticks feed zeros)
            inject = jnp.where(t < m, 1, 0)
            x_in = jnp.where(s_idx == 0,
                             micro[jnp.clip(t, 0, m - 1)]
                             * inject.astype(micro.dtype),
                             prev)
            y = stage_fn(params_local, x_in)
            # last stage commits microbatch t - (S-1)
            out_idx = t - (n_stages - 1)
            outputs = jnp.where(
                (s_idx == n_stages - 1) & (out_idx >= 0),
                outputs.at[jnp.clip(out_idx, 0, m - 1)].set(y),
                outputs)
            prev = jax.lax.ppermute(y, axis, perm)
            return (outputs, prev), None

        outputs = jnp.zeros_like(micro)
        prev = jnp.zeros_like(micro[0])
        (outputs, _), _ = jax.lax.scan(tick, (outputs, prev),
                                       jnp.arange(ticks))
        # everyone returns; only the last stage's buffer is nonzero, so a
        # psum broadcasts it (small boundary tensor, one hop in practice)
        return jax.lax.psum(outputs, axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)


def split_stages(layer_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major."""
    def rs(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape((n_stages, l // n_stages) + p.shape[1:])
    return jax.tree.map(rs, layer_params)


def stage_fn_from_layers(layer_fn: Callable) -> Callable:
    """layer_fn(params_layer, x) -> x  lifted to a stage (scan over the
    stage's layer slice)."""
    def stage(params_stage, x):
        def body(x, lp):
            return layer_fn(lp, x), None
        x, _ = jax.lax.scan(body, x, params_stage)
        return x
    return stage
