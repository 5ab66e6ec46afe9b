"""Training driver CLI.

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-117m --smoke \
        --steps 200 --optimizer adapprox --ckpt-dir /tmp/ckpt

``--smoke`` trains the reduced config (CPU-sized); without it the full
config is built at its published widths.  GPT-2 117M/345M fit one TPU
v5e chip with no mesh (``chip_smoke.py`` runs the 345M step there);
larger configs need ``--mesh``.  All the fault-tolerance machinery
(atomic async checkpoints, preemption flush, restart-resume, straggler
monitor) is active either way.  JAX's persistent compilation cache is on
(``repro.compile_cache``).

Sharded runs: ``--mesh 4,2`` builds a (data=4, model=2) device mesh (three
numbers add a leading DCN ``pod`` axis, one number is pure data
parallelism) and derives param / optimizer-state / batch shardings through
``distributed.sharding.train_shardings`` — optimizer state is sharded
alongside FSDP params (``--fsdp``, default on), which is where Adapprox's
factored-state memory savings actually materialise per device.  On a CPU
host, set ``REPRO_TRAIN_DEVICES=8`` (or export the matching ``XLA_FLAGS``)
to get virtual devices for the mesh.

``--mixed-groups`` (default for adapprox) makes the optimizer a
``partition`` chain with three state families: the count-min sketch on
embedding tables (>= ``--embedding-min-rows`` rows; ``--sketch-width`` /
``--sketch-depth`` size the hashed second moment), Adapprox on factorable
matrices, dense bias-corrected Adam on 1-D/small leaves — per-layer
sensitivity without blanket factorization (Kalra et al., 2025 / Shazeer &
Stern, 2018).

Telemetry: ``--telemetry-dir DIR`` streams per-group optimizer snapshots
(xi / rank / clip activation / refresh counters) and straggler events as
schema-validated JSONL (``repro.telemetry``); ``--auto-refresh`` adds the
closed-loop controller, which adapts each group's S-RSI refresh cadence
from observed xi drift at runtime — the cadence is a traced state scalar,
so retunes never recompile the step.

Tracing: ``--trace-dir DIR`` records host-side span events (data-wait /
dispatch / device-sync / checkpoint phases of every train step, with
refresh-vs-fold attribution) for ``tools/traceview.py``;
``--metrics-every N`` adds periodic counter/histogram snapshots and a
Prometheus text dump at exit.
"""
from __future__ import annotations

import os

if os.environ.get("REPRO_TRAIN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_TRAIN_DEVICES"]
                               + " " + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede the jax import: jax locks the device count on first init.

import argparse
import logging
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointConfig
from repro.compat import make_mesh
from repro.compile_cache import enable_compile_cache
from repro.config import (OptimizerConfig, TelemetryConfig,
                          default_mixed_groups)
from repro.configs import get_config, get_smoke_config
from repro.core import build_optimizer
from repro.data import DataConfig
from repro.distributed import sharding as SH
from repro.models import build_model
from repro.train import LoopConfig, train

log = logging.getLogger(__name__)


def optimizer_config(name: str, steps: int, lr: float,
                     refresh_every: int = 1, warm_start: bool = False,
                     bucketed: bool = False, fused_update: bool = False,
                     quantize_factors: bool = False,
                     mixed_groups: bool = False, telemetry: bool = False,
                     dynamic_refresh: bool = False,
                     sketch_width: int = 2048, sketch_depth: int = 4,
                     embedding_min_rows: int = 1024,
                     guards: bool = False, guard_xi_trip: float = 0.75,
                     max_demotions: int = 0) -> OptimizerConfig:
    """The launcher's OptimizerConfig: cosine schedule derived from the run
    length, paper-faithful Adapprox adaptive-rank settings.  The amortized-
    refresh knobs (refresh_every / warm_start / bucketed, adapprox only)
    trade a bounded amount of factorization freshness for step time — see
    repro.core's module docstring for the measured curve.  With
    ``mixed_groups`` the adapprox config becomes the production partition
    chain (dense Adam on 1-D/small leaves, Adapprox on matrices)."""
    common = dict(name=name, lr=lr, schedule="cosine",
                  warmup_steps=max(steps // 20, 5), total_steps=steps,
                  min_lr=lr / 6, weight_decay=0.1,
                  groups=default_mixed_groups() if mixed_groups else (),
                  sketch_width=sketch_width, sketch_depth=sketch_depth,
                  embedding_min_rows=embedding_min_rows)
    if name == "adapprox":
        return OptimizerConfig(**common, rank_mode="paper", k=1, k_max=128,
                               xi_thresh=0.01, delta_s=10,
                               min_dim_factor=64, implicit=False,
                               refresh_every=refresh_every,
                               warm_start=warm_start, bucketed=bucketed,
                               fused_update=fused_update,
                               quantize_factors=quantize_factors,
                               telemetry=telemetry,
                               dynamic_refresh=dynamic_refresh,
                               guards=guards, guard_xi_trip=guard_xi_trip,
                               max_demotions=max_demotions)
    if name in ("adamw", "adafactor", "came"):
        # the factored group inherits the family, so --mixed-groups is a
        # matrices/rest split of the SAME optimizer here (dense Adam on
        # the rest group either way)
        return OptimizerConfig(**common)
    raise ValueError(name)


def parse_mesh(spec: str):
    """``"4,2"`` -> (data=4, model=2) mesh; one number -> pure DP
    ``(data,)``; three -> ``(pod, data, model)``."""
    shape = tuple(int(s) for s in spec.split(",") if s)
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}.get(len(shape))
    if axes is None:
        raise ValueError(f"--mesh takes 1-3 comma-separated sizes, "
                         f"got {spec!r}")
    n_dev = len(jax.devices())
    need = 1
    for s in shape:
        need *= s
    if need > n_dev:
        raise ValueError(
            f"--mesh {spec} needs {need} devices but only {n_dev} are "
            f"visible; set REPRO_TRAIN_DEVICES={need} for virtual CPU "
            f"devices")
    return make_mesh(shape, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-117m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adapprox")
    ap.add_argument("--refresh-every", type=int, default=1,
                    help="adapprox: full S-RSI every T steps (fold between)")
    ap.add_argument("--warm-start", action="store_true",
                    help="adapprox: warm-start S-RSI from the stored U")
    ap.add_argument("--bucketed", action="store_true",
                    help="adapprox: one vmapped trace per same-shape bucket")
    ap.add_argument("--fused-update", action="store_true",
                    help="adapprox: two-pass fused elementwise tail "
                         "(kernels/fused_update.py on TPU)")
    ap.add_argument("--quantize-factors", action="store_true",
                    help="adapprox: store the (Q, U) factors as int8 with "
                         "per-block scale/zero (core/quantized.py, ~4x "
                         "smaller factor state); with --fused-update the "
                         "dequant fuses into the pass-1 tile loads")
    ap.add_argument("--mesh", default=None,
                    help="device mesh sizes, e.g. '4,2' = (data=4, model=2);"
                         " omit for the single-device path")
    fsdp = ap.add_mutually_exclusive_group()
    fsdp.add_argument("--fsdp", dest="fsdp", action="store_true",
                      default=True,
                      help="shard params + optimizer state over the data "
                           "axis (default)")
    fsdp.add_argument("--no-fsdp", dest="fsdp", action="store_false")
    mg = ap.add_mutually_exclusive_group()
    mg.add_argument("--mixed-groups", dest="mixed_groups",
                    action="store_true", default=None,
                    help="partition chain: dense Adam on 1-D/small leaves, "
                         "adapprox on matrices (default for adapprox)")
    mg.add_argument("--no-mixed-groups", dest="mixed_groups",
                    action="store_false")
    ap.add_argument("--sketch-width", type=int, default=2048,
                    help="count-min sketch buckets per hash for the "
                         "embeddings group (--mixed-groups)")
    ap.add_argument("--sketch-depth", type=int, default=4,
                    help="count-min sketch hash functions (min-over-depth)")
    ap.add_argument("--embedding-min-rows", type=int, default=1024,
                    help="leading-dim threshold for the embeddings group: "
                         ">= 2-D leaves with at least this many rows take "
                         "the sketch second moment")
    ap.add_argument("--telemetry-dir", default=None,
                    help="stream optimizer/straggler telemetry as JSONL "
                         "events here (repro.telemetry schema)")
    ap.add_argument("--telemetry-every", type=int, default=1,
                    help="emit optimizer events every N steps")
    ap.add_argument("--auto-refresh", action="store_true",
                    help="adapprox: closed-loop controller retunes "
                         "refresh_every per group from observed xi drift "
                         "(implies in-jit telemetry + dynamic cadence)")
    ap.add_argument("--guards", action="store_true",
                    help="resilience: wrap the chain in the non-finite "
                         "skip-step guard and arm the per-leaf xi watchdog "
                         "(repro.resilience; default off — guards-off runs "
                         "are bitwise identical to builds without them)")
    ap.add_argument("--guard-skip-threshold", type=float, default=0.75,
                    help="xi level that counts as a factorization blow-up "
                         "(forces a full S-RSI refresh for that leaf)")
    ap.add_argument("--max-demotions", type=int, default=0,
                    help="consecutive xi trips before a leaf is demoted to "
                         "the exact dense second moment (0 = never demote, "
                         "forced refreshes only)")
    ap.add_argument("--trace-dir", default=None,
                    help="record host-side kind=\"span\" timing events "
                         "(train-step phases, checkpoint IO) here as "
                         "JSONL — analyse with tools/traceview.py; may "
                         "equal --telemetry-dir to share one stream")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="emit a kind=\"metric\" registry snapshot every "
                         "N steps (0 = off); a Prometheus text dump is "
                         "written to <trace-dir>/metrics.prom at exit")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(message)s")
    log.info("compilation cache: %s", enable_compile_cache())
    mixed = (args.optimizer == "adapprox" if args.mixed_groups is None
             else args.mixed_groups)
    cfg = (get_smoke_config(args.arch, max_seq_len=args.seq)
           if args.smoke else get_config(args.arch))
    mesh = parse_mesh(args.mesh) if args.mesh else None
    model = build_model(cfg, mesh)
    telemetry_on = args.telemetry_dir is not None or args.auto_refresh
    opt = build_optimizer(optimizer_config(
        args.optimizer, args.steps, args.lr,
        refresh_every=args.refresh_every, warm_start=args.warm_start,
        bucketed=args.bucketed, fused_update=args.fused_update,
        quantize_factors=args.quantize_factors,
        mixed_groups=mixed, telemetry=telemetry_on,
        dynamic_refresh=args.auto_refresh,
        sketch_width=args.sketch_width, sketch_depth=args.sketch_depth,
        embedding_min_rows=args.embedding_min_rows,
        guards=args.guards, guard_xi_trip=args.guard_skip_threshold,
        max_demotions=args.max_demotions))
    runtime = None
    if telemetry_on:
        from repro.telemetry import TelemetryRuntime
        runtime = TelemetryRuntime(TelemetryConfig(
            enabled=True, dir=args.telemetry_dir,
            emit_every=args.telemetry_every,
            auto_refresh=args.auto_refresh))
        log.info("telemetry on (dir=%s, auto_refresh=%s)",
                 args.telemetry_dir, args.auto_refresh)
    tracer = None
    trace_sink = None        # sink this launcher owns (closed at exit)
    reg = None
    if args.trace_dir is not None:
        from repro.telemetry import MetricsRegistry, SinkConfig, \
            TelemetrySink, Tracer
        reg = MetricsRegistry()
        if runtime is not None and args.trace_dir == args.telemetry_dir:
            span_sink = runtime.sink   # one dir -> one shared stream
        else:
            trace_sink = span_sink = TelemetrySink(
                SinkConfig(directory=args.trace_dir))
        tracer = Tracer(sink=span_sink, registry=reg)
        log.info("tracing on (dir=%s, metrics_every=%d)",
                 args.trace_dir, args.metrics_every)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)

    state_shardings = batch_shardings = None
    if mesh is not None:
        model.constrain = SH.make_act_constrainer(mesh, "train")
        batch_struct = {"tokens": jax.ShapeDtypeStruct(
            (args.batch, args.seq), jnp.int32)}
        state_shardings, batch_shardings = SH.train_shardings(
            model, opt, mesh, batch_struct, fsdp=args.fsdp)
        log.info("mesh %s, fsdp=%s, mixed_groups=%s",
                 dict(mesh.shape), args.fsdp, mixed)

    ckpt = (CheckpointConfig(directory=args.ckpt_dir,
                             save_every=args.ckpt_every)
            if args.ckpt_dir else None)
    try:
        state, history = train(
            model, opt, data_cfg,
            LoopConfig(total_steps=args.steps, log_every=args.log_every,
                       ckpt=ckpt),
            state_shardings=state_shardings,
            batch_shardings=batch_shardings,
            telemetry=runtime,
            tracer=tracer,
            metrics_every=args.metrics_every,
            install_signal_handler=ckpt is not None)
    finally:
        if runtime is not None:
            runtime.close()
        if tracer is not None:
            tracer.flush()
            if trace_sink is not None:
                trace_sink.close()
            prom = Path(args.trace_dir) / "metrics.prom"
            prom.write_text(reg.render())
            log.info("trace events + %s written under %s",
                     prom.name, args.trace_dir)
    if history:
        print(f"final loss: {history[-1]['loss']:.4f} "
              f"({history[-1]['step_time_s'] * 1e3:.0f} ms/step)")
    return state


if __name__ == "__main__":
    main()
