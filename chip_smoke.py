#!/usr/bin/env python3
"""Drive the main paths once on a TPU, at published widths, and check them.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip FSDP path only

One chip:
  * train: GPT-2 345M (24 layers, d_model 1024, 16 heads, vocab 50257,
    seq 1024, batch 8) through ``repro.train.train`` with the launcher's
    default optimizer (the mixed sketch / Adapprox / dense-Adam chain),
    then a second run with the fused update, ``refresh_every=5`` and warm
    start, then one step from the same state and batch with the compiled
    kernels and with the reference ops, compared leaf by leaf;
  * serve: GPT-2 117M through ``ContinuousEngine``, greedy, mixed prompt
    lengths; each first token is checked against a plain forward pass.

``--chips 4``: GPT-2 345M sharded over ``(data=4)`` with FSDP for a few
steps, against the same batches on one device.

Weights are random from a fixed seed.  Everything runs in this one process
(a chip belongs to one process).  Any failed check raises, so the exit
code is non-zero and the result line is not printed.  Without a TPU, or
without the repository's ``src/`` beside this file, it fails at start.
The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import build_optimizer  # noqa: E402
from repro.data import DataConfig, DataIterator  # noqa: E402
from repro.distributed import sharding as SH  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.train import optimizer_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import ContinuousConfig, ContinuousEngine, Request  # noqa
from repro.train import LoopConfig, train  # noqa: E402
from repro.train.steps import build_train_step  # noqa: E402

# Peak LR of the 5-step warmup.  The launcher's default 3e-3 suits its
# smoke models; on GPT-2 345M the loss rises again once the warmup passes
# about 2e-3.
LR = 1e-3
STEPS = 5
SEED = 0
# Compiled kernels vs reference ops, one step from the same state: the
# largest relative Frobenius error any state leaf may show.  The two
# programs differ in MXU pass precision (f32 matmuls may run as bf16
# passes, 2^-8 relative), in reduction order, and in how XLA fuses the
# shared bf16 backward pass (1.3e-3 on a leaf no kernel writes, on a TPU
# v5e); an indexing or reduction bug in a kernel shows as an O(1) error.
KERNEL_REL_TOL = 1e-2
# FSDP (data=4) vs one device, same batches: relative loss difference.
# Only the partitioning of reductions differs; per step it grows from the
# float reassociation of the first step through the optimizer state.
MESH_LOSS_REL_TOL = 1e-2
# Serving: the engine's first token must be the argmax of a plain forward
# pass; a different token is accepted only as a near-tie, its reference
# logit within this much of the maximum (paged and plain attention round
# their bf16 activations differently).
TIE_LOGIT = 3e-2


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _check_losses(tag: str, losses: list, vocab: int) -> None:
    check(all(math.isfinite(x) for x in losses), f"{tag}: losses {losses}")
    check(abs(losses[0] - math.log(vocab)) < 0.5,
          f"{tag}: step-1 loss {losses[0]:.4f} vs ln(V) "
          f"{math.log(vocab):.4f}")
    check(losses[-1] < losses[0], f"{tag}: loss did not drop {losses}")


def _optimizer(steps: int, **knobs):
    """The launcher's default adapprox optimizer (mixed groups)."""
    return build_optimizer(optimizer_config("adapprox", steps, LR,
                                            mixed_groups=True, **knobs))


def _run_train(tag, model, opt, data_cfg, steps, **train_kw):
    t0 = time.perf_counter()
    state, hist = train(model, opt, data_cfg,
                        LoopConfig(total_steps=steps, log_every=1),
                        **train_kw)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    later = sorted(h["step_time_s"] for h in hist[1:])
    log(f"train[{tag}]: losses {[round(x, 4) for x in losses]}; step 1 "
        f"(compile + run) {hist[0]['step_time_s']:.1f} s, median later "
        f"step {later[len(later) // 2]:.3f} s, wall {wall:.1f} s "
        f"(host clock)")
    return state, losses


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def kernel_vs_ref_step(model, opt, state, batch) -> None:
    """One step from ``state`` with the compiled kernels and one with the
    reference ops; every state leaf must agree within KERNEL_REL_TOL."""
    outs = {}
    for mode in ("auto", "ref"):
        ops.set_mode(mode)
        try:
            step = jax.jit(build_train_step(model, opt))
            new, metrics = step(state, batch)
            outs[mode] = jax.device_get((new, metrics["loss"]))
            del new, metrics
        finally:
            ops.set_mode("auto")
    (k_state, k_loss), (r_state, r_loss) = outs["auto"], outs["ref"]
    worst, n = ("", 0.0), 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(k_state),
                            jax.tree.leaves(r_state)):
        a, b = np.asarray(a), np.asarray(b)
        check(a.shape == b.shape, f"leaf {path} shape {a.shape} {b.shape}")
        if not np.issubdtype(a.dtype, np.floating):
            check(np.array_equal(a, b), f"leaf {path}: {a} vs {b}")
            continue
        check(bool(np.all(np.isfinite(a))), f"non-finite leaf {path}")
        err = _rel_err(a, b)
        n += 1
        if err > worst[1]:
            worst = (jax.tree_util.keystr(path), err)
    log(f"kernels vs ref: loss {float(k_loss):.6f} / {float(r_loss):.6f}, "
        f"{n} float leaves, worst relative error {worst[1]:.3e} at "
        f"{worst[0]} (tolerance {KERNEL_REL_TOL})")
    check(worst[1] <= KERNEL_REL_TOL, f"kernel/ref mismatch at {worst[0]}")


def train_phase(cfg, batch: int, seq: int, steps: int) -> None:
    check(ops.resolved_mode() == "pallas",
          f"kernel mode {ops.resolved_mode()!r}, want compiled pallas")
    model = build_model(cfg)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch)
    state, losses = _run_train("mixed chain", model, _optimizer(steps),
                               data_cfg, steps)
    _check_losses("mixed chain", losses, cfg.vocab)
    del state
    # fused tail + amortized refresh: steps 2..5 fold, so the compare step
    # below (step ``steps``) is a fold step, deterministic in its inputs
    opt = _optimizer(steps, fused_update=True, refresh_every=5,
                     warm_start=True)
    state, losses = _run_train("fused, refresh 5, warm", model, opt,
                               data_cfg, steps - 1)
    _check_losses("fused", losses, cfg.vocab)
    data = DataIterator(data_cfg, start_step=steps - 1)
    try:
        nxt = next(data)
    finally:
        data.close()
    nxt.pop("step")
    kernel_vs_ref_step(model, opt, state, nxt)


def serve_phase(cfg, prompt_lens, max_new: int) -> None:
    check(ops.resolved_mode() == "pallas",
          f"kernel mode {ops.resolved_mode()!r}, want compiled pallas")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    eng = ContinuousEngine(model, params, ContinuousConfig(
        slots=4, cache_len=cfg.max_seq_len, block_size=16,
        prefill_chunk=512))
    rng = np.random.default_rng(SEED)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, size=n)
                    .astype(np.int32), max_new_tokens=max_new)
            for i, n in enumerate(prompt_lens)]
    t0 = time.perf_counter()
    eng.run(reqs)
    wall = time.perf_counter() - t0
    # reference: one plain forward over the prompts padded to a common
    # length; causal attention makes position n-1 independent of the pad
    width = max(prompt_lens)
    toks = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :len(r.prompt)] = r.prompt
    last = np.array([len(r.prompt) - 1 for r in reqs], np.int32)
    logits = np.asarray(jax.jit(
        lambda p, t, i: model.forward(p, t)[0][jnp.arange(len(i)), i]
        .astype(jnp.float32))(params, jnp.asarray(toks), jnp.asarray(last)))
    exact = 0
    for i, r in enumerate(reqs):
        check(r.done and len(r.out_tokens) == max_new,
              f"req {r.uid}: {len(r.out_tokens)} of {max_new} tokens")
        ref = logits[i]
        top, got = int(np.argmax(ref)), r.out_tokens[0]
        gap = float(ref[top] - ref[got])
        exact += got == top
        check(got == top or gap <= TIE_LOGIT,
              f"req {r.uid} (prompt {len(r.prompt)}): first token {got}, "
              f"forward argmax {top}, logit gap {gap:.4f}")
    log(f"serve: {len(reqs)} requests, prompts {list(prompt_lens)}, "
        f"{eng.tokens_emitted} tokens in {eng.steps} engine steps, "
        f"{wall:.1f} s wall incl. compile (host clock); first token = "
        f"forward argmax for {exact}/{len(reqs)}, the rest within "
        f"{TIE_LOGIT} of it")


def fsdp_phase(cfg, batch: int, seq: int, steps: int) -> None:
    """GPT-2 345M over (data=4) with FSDP vs the same batches on one
    device: state sharded over every device, losses matching."""
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, {len(devices)}")
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch)
    mesh = make_mesh((4,), ("data",))
    model = build_model(cfg, mesh)
    model.constrain = SH.make_act_constrainer(mesh, "train")
    opt = _optimizer(steps)
    bstruct = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    ssh, bsh = SH.train_shardings(model, opt, mesh, bstruct, fsdp=True)
    st4, l4 = _run_train("fsdp data=4", model, opt, data_cfg, steps,
                         state_shardings=ssh, batch_shardings=bsh)
    _check_losses("fsdp", l4, cfg.vocab)
    total = split = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(st4):
        sh = leaf.sharding
        check(isinstance(sh, jax.sharding.NamedSharding)
              and sh.device_set == set(devices),
              f"{jax.tree_util.keystr(path)} on {sh}")
        total += leaf.nbytes
        if not sh.is_fully_replicated:
            split += leaf.nbytes
    log(f"fsdp: every state leaf a NamedSharding over 4 devices; "
        f"{split / total:.1%} of {total / 2**30:.2f} GiB split across them")
    check(split / total > 0.9, "most state bytes should be split")
    del st4
    st1, l1 = _run_train("one device", build_model(cfg), opt, data_cfg,
                         steps)
    del st1
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    log(f"fsdp vs one device: relative loss differences "
        f"{[f'{x:.2e}' for x in rel]} (tolerance {MESH_LOSS_REL_TOL})")
    check(max(rel) <= MESH_LOSS_REL_TOL, "sharded losses diverge")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the FSDP (data=4) phase")
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX sees {dev}); "
                         f"refusing to run on another backend")
    log(f"device: {dev}; compilation cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    gpt2_345m = get_config("gpt2-345m")
    if args.chips == 4:
        fsdp_phase(gpt2_345m, batch=8, seq=1024, steps=STEPS)
    else:
        train_phase(gpt2_345m, batch=8, seq=1024, steps=STEPS)
        serve_phase(get_config("gpt2-117m"),
                    prompt_lens=(7, 64, 190, 333, 700), max_new=8)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
