"""Train cells: the program's own loop, ``repro.train.train``, on its own
synthetic stream, timed over whole steps.

Set-up makes the weights and the optimizer state on the device in one
jitted call from the seed and hands that state to one ``train`` call.  The
loop's per-step state hook (the ``telemetry`` argument) is the probe: after
step 1 it reads what the optimizer state says of the first gradient,
after the last reference step the parameters' change, and after the
warm-up steps it opens the window; the window closes at the first step
boundary ``seconds`` later.  Nothing compiles in the window: the step
program compiled at step 1 and both probe programs ran before it.

After the window the peak memory is read, the program's state is freed,
and the plain reference (the module the configuration names, with
``optim_ref.py``) runs the same first steps from the same seed; the run is
correct when the step losses, the first gradient and the parameters'
change agree leaf by leaf within the cell's limits
(``limits/<workload>.json``).

The norms alone cannot see Adapprox's second moment: its first updates are
RMS-clipped, so the norm of a factored leaf's change is set by the clip
whatever the moment holds.  For every Adapprox matrix the comparison also
takes two things that depend on direction, each through a seeded Gaussian
projection ``X @ Omega`` (``Omega``: (n, 8), made by the benchmark from the
seed, the same on both sides): the factored second moment after step 1,
``Q U^T``, against the reference's exact ``(1 - b2) G_1^2``; and the
matrix's change after the reference steps.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import faults
import harness
import optim_ref
import stream
import trace_reduce


class WindowClosed(Exception):
    """Raised from the step hook to end the loop when the window closes."""


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


PROJ_COLS = 8


def _precond_state(s):
    # a chain's state is a tuple whose first entry is the preconditioner's
    return s[0] if isinstance(s, tuple) else s


def projections(key, shapes: list, fams: list) -> dict:
    """Per Adapprox leaf (params' flatten index), the seeded Gaussian
    ``Omega`` of shape (n, PROJ_COLS) both sides project with."""
    return {i: jax.random.normal(jax.random.fold_in(key, 0x0E6A + i),
                                 (s[-1], PROJ_COLS), jnp.float32)
            for i, (s, f) in enumerate(zip(shapes, fams)) if f == "adapprox"}


def _project(x, omega):
    return jnp.matmul(x.astype(jnp.float32), omega,
                      precision=jax.lax.Precision.HIGHEST)


def factored_v_projections(opt_state, n_leaves: int, omegas: dict) -> dict:
    """``Q U^T Omega`` of every factored leaf the optimizer state holds,
    by params' flatten index; a leaf the program does not factor is
    missing (and reads as not correct)."""
    found = {}

    def walk(state, own):
        st = _precond_state(state)
        if hasattr(st, "leaves"):
            for i, leaf in zip(own, st.leaves):
                if hasattr(leaf, "xi") and i in omegas:
                    q, u = leaf.q.astype(jnp.float32), leaf.u.astype(
                        jnp.float32)
                    found[i] = jnp.matmul(
                        q, jnp.swapaxes(u, -1, -2) @ omegas[i],
                        precision=jax.lax.Precision.HIGHEST)

    if hasattr(opt_state, "inner"):
        labels = opt_state.labels
        for label, sub in opt_state.inner.items():
            walk(sub, [i for i, l in enumerate(labels) if l == label])
    else:
        walk(opt_state, list(range(n_leaves)))
    return found


def first_grad_numbers(opt_state, n_leaves: int, b1: float):
    """Leaf by leaf (params' flatten order), the first gradient's norm as
    the optimizer state holds it after one step, ``||m|| / (1 - b1)``,
    where the state keeps a first moment of the gradient (Adam and sketch
    leaves); NaN for a factored Adapprox leaf, whose first moment is of
    the clipped update: its factors are compared by
    ``factored_v_projections`` instead."""
    def group_numbers(state):
        st = _precond_state(state)
        if hasattr(st, "leaves"):
            return [jnp.float32(jnp.nan) if hasattr(leaf, "xi")
                    else _norm(leaf.m) / (1 - b1) for leaf in st.leaves]
        return [_norm(m) / (1 - b1) for m in jax.tree.leaves(st.m)]

    if hasattr(opt_state, "inner"):
        labels = opt_state.labels
        out = [None] * n_leaves
        for label, sub in opt_state.inner.items():
            own = [i for i, l in enumerate(labels) if l == label]
            for i, x in zip(own, group_numbers(sub)):
                out[i] = x
        return jnp.stack(out)
    return jnp.stack(group_numbers(opt_state))


@dataclasses.dataclass
class _Probe:
    """The loop's per-step state hook (duck-types the telemetry runtime
    the loop accepts: ``on_step``, ``flush``, ``sink``)."""
    seconds: float
    warm: int
    ref_steps: int
    trace_steps: int
    trace_dir: object
    first_fn: object
    change_fn: object
    key: object
    sink: object = None
    times: dict = dataclasses.field(default_factory=dict)
    first: object = None
    change: object = None
    tracer: object = None
    window_ann: object = None
    tracer_open: float = 0.0

    def on_step(self, step, state):
        if step == 1:
            self.first = jax.device_get(self.first_fn(state.opt_state))
        if step == self.ref_steps:
            self.change = jax.device_get(self.change_fn(state.params,
                                                        self.key))
        now = time.perf_counter()
        self.times[step] = now
        if step == self.warm and self.trace_dir is not None:
            self.window_ann = trace_reduce.open_window(self.trace_dir)
            self.times[step] = time.perf_counter()
            self.tracer_open = self.tracer.now()
        if step > self.warm:
            if self.trace_dir is not None:
                if step - self.warm >= self.trace_steps:
                    trace_reduce.close_window(self.window_ann)
                    raise WindowClosed
            elif now - self.times[self.warm] >= self.seconds:
                raise WindowClosed
        return state

    def flush(self):
        pass


def run(*, config, traffic, limits, reference, chips, seed, seconds, trace,
        t_process, workdir, fault=None):
    harness.one_chip(chips)
    from repro.config import ModelConfig
    from repro.core import build_optimizer
    from repro.data import DataConfig
    from repro.launch.train import optimizer_config
    from repro.models import build_model
    from repro.telemetry.trace import Tracer
    from repro.train import LoopConfig, train
    from repro.train.steps import TrainState

    mdict = config["model"]
    batch, seq = config["train"]["batch"], config["train"]["seq"]
    ospec = traffic["optimizer"]
    ref_steps, warm = traffic["ref_steps"], traffic["warm_steps"]
    model = build_model(ModelConfig(**mdict))
    opt = build_optimizer(optimizer_config(
        ospec["name"], ospec["steps"], ospec["lr"],
        mixed_groups=ospec["mixed_groups"], **ospec.get("knobs", {})))
    model, opt = faults.plant_train(fault, model, opt)
    key = harness.prng_key(jax, seed)
    init = functools.partial(reference.init_params, mdict)
    shapes = [x.shape for x in jax.tree.leaves(jax.eval_shape(init, key))]
    n_leaves = len(shapes)
    omegas = projections(key, shapes, optim_ref.route(shapes, ospec))

    make_state = jax.jit(lambda k: TrainState.create(init(k), opt))
    first_fn = jax.jit(lambda s: (
        first_grad_numbers(s, n_leaves=n_leaves, b1=ospec["b1"]),
        factored_v_projections(s, n_leaves, omegas)))

    def change_numbers(p, k):
        pairs = list(zip(jax.tree.leaves(p), jax.tree.leaves(init(k))))
        return (jnp.stack([_norm(a - b) for a, b in pairs]),
                {i: _project(pairs[i][0] - pairs[i][1], w)
                 for i, w in omegas.items()})
    change_fn = jax.jit(change_numbers)

    tracer = Tracer(sink=harness.ListSink()) if trace else None
    probe = _Probe(seconds=seconds, warm=warm, ref_steps=ref_steps,
                   trace_steps=traffic["trace_steps"],
                   trace_dir=workdir if trace else None,
                   first_fn=first_fn, change_fn=change_fn, key=key,
                   tracer=tracer)
    losses = {}
    data_cfg = DataConfig(vocab=mdict["vocab"], seq_len=seq,
                          global_batch=batch, seed=seed)
    try:
        # the state is made inside the call's arguments, so no reference
        # to the initial state outlives the first step
        train(model, opt, data_cfg,
              LoopConfig(total_steps=1 << 30, log_every=1),
              state=make_state(key), telemetry=probe, tracer=tracer,
              metric_hook=lambda s, m: losses.__setitem__(s, m["loss"]))
    except WindowClosed:
        pass
    gc.collect()
    peak = harness.memory_peak_bytes(jax)

    steps = sorted(probe.times)
    last = steps[-1]
    t_open = probe.times[warm]
    window = probe.times[last] - t_open
    tokens = (last - warm) * batch * seq
    e2e = {"train_tokens_per_s": tokens / window,
           "peak_hbm_gib": peak / harness.GIB,
           "setup_s": t_open - t_process}

    checks = compare(reference, mdict, ospec, seed, batch, seq, ref_steps,
                     rows=traffic["ref_rows"], losses=losses,
                     first=probe.first, change=probe.change, limits=limits)

    reduced, context = None, None
    if trace:
        reduced = trace_reduce.reduce_dir(workdir)
        spans = tracer.sink.events
        reduced.label_gaps([(e["name"], e["t0_s"] - probe.tracer_open,
                             e["t0_s"] + e["dur_s"] - probe.tracer_open)
                            for e in spans])
        context = {"kind": "train", "trace": reduced,
                   "spans": spans, "model": mdict, "param_shapes": shapes,
                   "batch": batch, "seq": seq, "optimizer": ospec,
                   "steps_traced": last - warm, "window_s": window,
                   "traced_steps": list(range(warm + 1, last + 1))}
    return harness.RunResult(end_to_end=e2e, checks=checks,
                             attempted=last, failed=0,
                             memory_peak_bytes=peak, trace=reduced,
                             context=context)


def reference_readings(reference, mdict, ospec, seed, batch, seq, ref_steps,
                       rows, precision="f32") -> dict:
    """The reference's step losses, first-gradient norms (every leaf),
    parameter change after ``ref_steps`` steps, which leaves keep a first
    moment of the gradient (the ones whose first gradient is compared),
    and for Adapprox leaves the projections of ``(1 - b2) G_1^2`` and of
    the change, from the same seed."""
    key = harness.prng_key(jax, seed)
    p0 = jax.jit(functools.partial(reference.init_params, mdict))(key)
    flat0, treedef = jax.tree.flatten(p0)
    ref = optim_ref.Reference(flat0, ospec)
    omegas = projections(key, [x.shape for x in flat0], ref.fams)
    flat = flat0
    losses, first, v = [], None, None
    for t in range(ref_steps):
        tokens = stream.synthetic_batch(mdict["vocab"], seq, batch, seed, t)
        loss, grads = reference.loss_and_grad(
            jax.tree.unflatten(treedef, flat), tokens, mdict, rows,
            precision)
        losses.append(float(loss))
        g = jax.tree.leaves(grads)
        if t == 0:
            first = np.asarray(ref.first_grad_numbers(g))
            v = jax.device_get(jax.jit(lambda gs: {
                i: _project((1 - ospec["b2"]) * jnp.square(gs[i]), w)
                for i, w in omegas.items()})(g))
        flat = ref.step(flat, g)
        del grads, g
    change, proj = jax.device_get(jax.jit(lambda a, b: (
        jnp.stack([_norm(x - y) for x, y in zip(a, b)]),
        {i: _project(a[i] - b[i], w) for i, w in omegas.items()}))(
            flat, flat0))
    return {"losses": losses, "first": first, "change": change,
            "moment": np.asarray(ref.moment), "v": v, "proj": proj}


def leaf_gap(prog, ref, keep=None) -> float:
    """Worst leaf: |program - reference| against the reference's number
    of that leaf or of the median leaf, whichever is larger."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    med = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(gaps[keep]))


def projection_gap(prog: dict, ref: dict) -> float:
    """Worst matrix (a stacked leaf's leading axes are matrices of their
    own): ``||prog - ref||_F`` over the projection, against the larger of
    that matrix's ``||ref||_F`` and the median matrix's.  A matrix the
    program did not produce reads infinite.  NaN where there is none (no
    Adapprox leaf in the cell)."""
    if not ref:
        return float("nan")
    num, den = [], []
    for i, r in ref.items():
        r = np.asarray(r, np.float64).reshape(-1, *np.shape(r)[-2:])
        if i not in prog:
            num.append(np.full(len(r), np.inf))
        else:
            p = np.asarray(prog[i], np.float64).reshape(r.shape)
            num.append(np.sqrt(np.sum(np.square(p - r), axis=(-2, -1))))
        den.append(np.sqrt(np.sum(np.square(r), axis=(-2, -1))))
    num, den = np.concatenate(num), np.concatenate(den)
    return float(np.max(num / np.maximum(den, np.median(den))))


def readings(prog: dict, ref: dict) -> dict:
    """The numbers compared.  The first gradient is compared on the
    leaves that keep a first moment of it (``moment``); for Adapprox
    leaves the second moment after step 1 and the direction of the change
    are compared through their projections.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the change (none in GPT-2 as configured)."""
    ref_first = ref["first"]
    keep = ref_first >= 1e-3 * np.median(ref_first)
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["losses"], ref["losses"]))
    return {"loss_rel_gap": float(loss),
            "first_grad_gap": leaf_gap(prog["first"], ref_first,
                                       ref["moment"]),
            "param_change_gap": leaf_gap(prog["change"], ref["change"],
                                         keep),
            "factored_v_gap": projection_gap(prog["v"], ref["v"]),
            "factored_change_gap": projection_gap(
                {i: p for i, p in prog["proj"].items() if keep[i]},
                {i: r for i, r in ref["proj"].items() if keep[i]})}


def compare(reference, mdict, ospec, seed, batch, seq, ref_steps, rows,
            losses, first, change, limits):
    ref = reference_readings(reference, mdict, ospec, seed, batch, seq,
                             ref_steps, rows)
    if first is None or change is None:
        nums = {}
    else:
        nums = readings({"losses": [losses.get(t + 1, float("nan"))
                                    for t in range(ref_steps)],
                         "first": first[0], "v": first[1],
                         "change": change[0], "proj": change[1]}, ref)
    return [harness.Check(k, nums.get(k, float("nan")), limits[k])
            for k in sorted(limits)]
