"""Fault-tolerant training loop, single-device or mesh-sharded.

Wires together: deterministic data pipeline, jitted train step, async
atomic checkpointing (+ preemption flush), straggler monitoring, metric
logging, and the telemetry subsystem (``repro.telemetry``): pass a
``TelemetryRuntime`` and the loop streams per-group optimizer snapshots
to its JSONL sink after every step, lets its closed-loop controller
retune the (traced) S-RSI refresh cadence in place, saves its controller
state into every checkpoint manifest, and flushes its sink on preemption
— the straggler monitor shares the same event stream.  Restart-safe by construction: on startup it restores the latest
committed checkpoint (if any) and fast-forwards the data stream to the
restored step — a killed job resumes bit-exact (validated in
tests/test_train_integration.py).

Sharded path: pass ``state_shardings`` (a ``TrainState``-shaped tree of
``NamedSharding``, e.g. from ``distributed.sharding.train_shardings``) and
optionally ``batch_shardings``.  The step function is then jitted with
``in_shardings`` / ``out_shardings`` (and donated state buffers when no
preemption handler needs to keep a host-reachable copy), fresh state is
initialised eagerly and re-placed under the shardings (jit-init with
``out_shardings`` — state born sharded, never resident on one device — is
planned for when partitioned RNG is mesh-invariant on our jax version;
see the inline note), host batches are placed under ``batch_shardings``,
and checkpoint restore re-places saved logical arrays under the current
shardings — which is exactly what makes save-on-mesh-A / resume-on-mesh-B
elastic restarts work (tests/test_sharded_train.py).
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.core import GradientTransformation
from repro.data import DataConfig, DataIterator
from repro.distributed.straggler import StragglerMonitor
from repro.telemetry.trace import NULL_TRACER
from repro.train.steps import TrainState, build_train_step

log = logging.getLogger(__name__)


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    log_every: int = 50
    ckpt: Optional[CheckpointConfig] = None
    microbatches: int = 1
    grad_clip_norm: Optional[float] = None
    # Cap on the in-memory metric history (a bounded deque of the most
    # recent entries).  None keeps every logged entry — the historical
    # behavior — which on a long production run grows host memory without
    # bound; set a cap and consume the full stream via metric_hook or the
    # telemetry sink instead.
    history_cap: Optional[int] = None


def train(model, opt: GradientTransformation, data_cfg: DataConfig,
          loop_cfg: LoopConfig, *,
          state: Optional[TrainState] = None,
          state_shardings=None,
          batch_shardings=None,
          metric_hook: Optional[Callable[[int, dict], None]] = None,
          telemetry=None,
          tracer=None,
          metrics_every: int = 0,
          registry=None,
          install_signal_handler: bool = False) -> tuple[TrainState, list]:
    """Returns (final_state, history of metric dicts).

    ``telemetry``: optional :class:`repro.telemetry.TelemetryRuntime`.
    Each step, after the existing loss sync, the runtime fetches the
    (scalar-sized) optimizer snapshots from the returned state, streams
    events to its JSONL sink, and — with the closed-loop controller
    enabled — writes retuned refresh cadences back into the state (a
    traced scalar: no recompilation).  Its controller state rides the
    checkpoint manifests (saved with every checkpoint, restored on
    resume), and its sink is flushed by the preemption handler chain and
    at loop exit.  The caller owns the runtime and closes it.

    ``tracer``: optional :class:`repro.telemetry.Tracer`.  Each step
    emits a host-side ``train_step`` span with ``data_wait`` /
    ``step_dispatch`` / ``device_sync`` children, attributed
    refresh-vs-fold from the in-jit snapshot counters when the optimizer
    collects them, then a ``step_end`` span over the bookkeeping after
    it (telemetry, registry, log readback, ``metric_hook``, the
    checkpoint test); checkpoint saves/restores get their own spans.  Spans
    never enter jit — the step function is untouched, so the
    bitwise-default-chain contract holds with tracing on.  The
    preemption handler chain drains open spans (``"truncated": true``)
    before the final checkpoint.  The caller owns the tracer's sink.

    ``metrics_every``: > 0 emits a ``kind="metric"`` registry snapshot
    (train_steps_total, train_step_seconds, train_loss) every N steps to
    the tracer's sink (or the telemetry runtime's).  ``registry``
    defaults to the tracer's, else the process-wide default.
    """
    ckpt = CheckpointManager(loop_cfg.ckpt) if loop_cfg.ckpt else None
    tr = tracer if tracer is not None else NULL_TRACER
    if ckpt is not None and tracer is not None:
        ckpt.tracer = tracer
    reg = None
    metric_sink = None
    if metrics_every > 0:
        from repro.telemetry import metrics as metrics_mod
        reg = registry if registry is not None else (
            tracer.registry if tracer is not None
            and tracer.registry is not None
            else metrics_mod.default_registry())
        metric_sink = (tracer.sink if tracer is not None
                       and tracer.sink is not None
                       else telemetry.sink if telemetry is not None
                       else None)

    if state is None:
        params = model.init(jax.random.PRNGKey(0))
        state = TrainState.create(params, opt)
        if state_shardings is not None:
            # Init eagerly, then re-place under the shardings.  (Jitting
            # the init with out_shardings would avoid materialising the
            # full state on one device, but on this jax version partitioned
            # RNG draws different init values per mesh — breaking the
            # any-mesh bitwise-continuation contract the resharding tests
            # pin down.  Flip to jit-init once jax_threefry_partitionable
            # is the default.)
            state = jax.device_put(state, state_shardings)

    # A caller-provided mid-run state resumes at its own step counter
    # (elastic_restore hands back exactly such a state); fresh states
    # carry step 0.  A committed checkpoint below overrides both.
    start_step = int(np.asarray(state.step))
    if ckpt is not None and ckpt.latest_step() is not None:
        # restore reshards: saved logical arrays re-placed under the
        # CURRENT shardings, whatever mesh the checkpoint was written on
        state, start_step = ckpt.restore(state, state_shardings)
        log.info("restored checkpoint at step %d", start_step)
        if telemetry is not None:
            # controller accumulators + cadence log resume from the
            # manifest, so the cadence-change sequence replays exactly
            # (the cadence scalar itself is optimizer state and was just
            # restored with it).  Keyed by the step restore actually
            # landed on — if it fell back past a corrupt latest
            # checkpoint, the meta must come from the same fallback.
            telemetry.restore_meta(ckpt.read_meta(start_step))

    step_fn = build_train_step(model, opt, microbatches=loop_cfg.microbatches,
                               grad_clip_norm=loop_cfg.grad_clip_norm)
    if state_shardings is not None:
        # Donating the input state halves optimizer-state residency, but a
        # preemption flush must be able to device_get the PRE-step state at
        # any instant — donation would leave it pointing at freed buffers —
        # so the flush path trades the alias away.
        donate = () if install_signal_handler else (0,)
        sharded_step = jax.jit(step_fn,
                               in_shardings=(state_shardings,
                                             batch_shardings),
                               out_shardings=(state_shardings, None),
                               donate_argnums=donate)
        mesh = jax.tree.leaves(state_shardings)[0].mesh

        def step_fn(state, batch):
            # traced under the mesh: kernels XLA cannot partition wrap
            # themselves in shard_map over it (kernels/ops.py)
            with jax.set_mesh(mesh):
                return sharded_step(state, batch)
    else:
        step_fn = jax.jit(step_fn)

    data = DataIterator(data_cfg, start_step=start_step)
    monitor = StragglerMonitor(
        sink=telemetry.sink if telemetry is not None else None)
    history = (collections.deque(maxlen=loop_cfg.history_cap)
               if loop_cfg.history_cap is not None else [])

    def _meta():
        return telemetry.manifest_meta() if telemetry is not None else None

    if ckpt is not None and install_signal_handler:
        # (state, step, controller-meta) captured as ONE tuple assigned in
        # ONE bytecode: a signal between separate assignments could pair a
        # step-N state with step-N+1 controller accumulators, and the
        # restored run would double-observe a step and diverge from the
        # cadence sequence the determinism tests pin.
        latest = {"snap": (state, start_step, _meta())}

        def _flush_state():
            # rides the preemption handler chain: drain open spans as
            # truncated events and the telemetry sink to disk, then hand
            # the state + controller meta to the blocking checkpoint
            # flush.  Best-effort: a sick sink (disk full on the
            # telemetry volume) must never cost the preemption
            # CHECKPOINT.  Both drains are lock-free (dict ops + counter
            # spins), so a SIGTERM that interrupted emit can't deadlock.
            if tracer is not None:
                try:
                    tracer.drain_open()
                    tracer.flush()
                except Exception:  # noqa: BLE001 — checkpoint comes first
                    log.exception("span drain failed during preemption; "
                                  "saving checkpoint anyway")
            if telemetry is not None:
                try:
                    telemetry.flush()
                except Exception:  # noqa: BLE001 — checkpoint comes first
                    log.exception("telemetry flush failed during "
                                  "preemption; saving checkpoint anyway")
            return latest["snap"]

        ckpt.install_preemption_handler(_flush_state)

    run_trace = tr.new_trace("train") if tracer is not None else None
    loop_t0 = time.monotonic()
    try:
        for step in range(start_step, loop_cfg.total_steps):
            with tr.span("train_step", trace=run_trace,
                         step=step + 1) as step_span:
                with tr.span("data_wait"):
                    batch = next(data)
                    batch.pop("step", None)
                    if batch_shardings is not None:
                        batch = jax.device_put(batch, batch_shardings)
                monitor.start()
                with tr.span("step_dispatch"):
                    state, metrics = step_fn(state, batch)
                with tr.span("device_sync"):
                    jax.block_until_ready(metrics["loss"])
                dt = monitor.stop()
                if tracer is not None:
                    phase = _refresh_phase(metrics)
                    if phase is not None:
                        step_span.set(phase=phase)

            with tr.span("step_end", trace=run_trace, step=step + 1):
                if telemetry is not None:
                    # fetch snapshots / emit events / retune cadences;
                    # the loop already synced on the loss, so this adds
                    # no device round-trip beyond the scalar fetch
                    state = telemetry.on_step(step + 1, state)

                if ckpt is not None and install_signal_handler:
                    latest["snap"] = (state, step + 1, _meta())

                if reg is not None:
                    reg.counter("train_steps_total",
                                help="train steps completed").inc()
                    reg.histogram("train_step_seconds",
                                  help="wall time per train step").observe(dt)
                    if (step + 1) % metrics_every == 0:
                        reg.gauge("train_loss",
                                  help="loss at the last snapshot").set(
                                      float(np.asarray(metrics["loss"])))
                        if metric_sink is not None:
                            metric_sink.emit(reg.snapshot(
                                t_s=time.monotonic() - loop_t0,
                                step=step + 1))

                if (step + 1) % loop_cfg.log_every == 0 or step == start_step:
                    m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                    m["step_time_s"] = dt
                    m["step"] = step + 1
                    history.append(m)
                    if metric_hook:
                        metric_hook(step + 1, m)
                    log.info("step %d loss %.4f (%.3fs)", step + 1,
                             m.get("loss", float("nan")), dt)

                if ckpt is not None and ckpt.should_save(step + 1):
                    with tr.span("checkpoint_save", trace=run_trace,
                                 step=step + 1):
                        ckpt.save(state, step + 1, extra_meta=_meta())
    finally:
        data.close()
        if ckpt is not None:
            if install_signal_handler:
                # before wait(): a failed async save re-raises there, and
                # the handler must not outlive this loop's state capture
                ckpt.uninstall_preemption_handler()
            ckpt.wait()
        if telemetry is not None:
            try:
                telemetry.flush()
            except Exception:  # noqa: BLE001 — same rule as the
                # preemption path: a sick sink must neither mask an
                # in-flight exception nor cost the final checkpoint
                log.exception("telemetry flush failed at loop exit")
        if tracer is not None:
            try:
                tracer.flush()
            except Exception:  # noqa: BLE001 — same rule
                log.exception("tracer flush failed at loop exit")

    if ckpt is not None:
        with tr.span("checkpoint_save", trace=run_trace,
                     step=loop_cfg.total_steps):
            ckpt.save(state, loop_cfg.total_steps, blocking=True,
                      extra_meta=_meta())
        if tracer is not None:
            try:
                tracer.flush()
            except Exception:  # noqa: BLE001
                log.exception("tracer flush failed after final save")
    return state, list(history)


def _refresh_phase(metrics: dict) -> Optional[str]:
    """Refresh-vs-fold attribution for the step span, read from the
    in-jit snapshot counters the optimizer already computes
    (``telemetry/<group>/did_refresh`` in the step metrics; absent when
    the optimizer collects no telemetry).  Host-side read of an
    already-synced scalar — nothing is added inside jit."""
    flags = [v for k, v in metrics.items() if k.endswith("/did_refresh")]
    if not flags:
        return None
    return ("refresh" if any(bool(np.asarray(f)) for f in flags)
            else "fold")
