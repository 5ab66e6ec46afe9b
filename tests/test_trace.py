"""Span-tracing suite (repro.telemetry.trace + the instrumented loops).

Pins the observability PR's contracts:
  * live spans nest per thread, inherit the enclosing trace id, and land
    in the JSONL sink as schema-valid ``kind="span"`` events;
  * ``drain_open`` (the preemption path) emits exactly ONE event per
    span — the truncated drain wins over the normal ``__exit__``;
  * ``check_events`` catches orphaned parents, negative durations and
    incomplete request waterfalls — the ``tools/traceview.py --check``
    CI gate;
  * the train loop emits a ``train_step`` span per step with
    data_wait / step_dispatch / device_sync children, refresh-vs-fold
    attribution from the in-jit snapshot counters, and checkpoint
    save/restore spans — while the trained state stays BITWISE identical
    to an untraced run (spans never enter jit);
  * live spans are profiler annotations too: a CPU profile of a traced
    train loop holds them on its host plane, nested as in the JSONL and
    at the offsets the benchmark computes; with no tracer,
    nothing is annotated or recorded;
  * the train step's device work carries the layers' named scopes
    (``loss_and_grads``, ``optimizer``, ``srsi``, ...) in its metadata.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointConfig
from repro.config import OptimizerConfig
from repro.core import build_optimizer
from repro.data import DataConfig
from repro.telemetry import (SinkConfig, TelemetrySink, Tracer,
                             check_events, chrome_trace, load_events,
                             span_stats, step_breakdown, validate_dir)
from repro.telemetry.trace import ROOT_SPAN
from repro.train import LoopConfig, train


def _tracer(tmp_path, sub="trace"):
    sink = TelemetrySink(SinkConfig(directory=str(tmp_path / sub)))
    return Tracer(sink=sink), sink, tmp_path / sub


def _drain(sink, d):
    sink.flush()
    sink.close()
    return load_events(d)


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_inherits_trace_and_parent(self, tmp_path):
        tracer, sink, d = _tracer(tmp_path)
        with tracer.span("outer") as o:
            with tracer.span("inner") as i:
                assert i.trace == o.trace
        events = _drain(sink, d)
        assert validate_dir(d) == 2
        by_name = {e["name"]: e for e in events}
        assert by_name["inner"]["parent"] == by_name["outer"]["span"]
        assert by_name["inner"]["trace"] == by_name["outer"]["trace"]
        assert "parent" not in by_name["outer"]
        assert by_name["outer"]["dur_s"] >= by_name["inner"]["dur_s"] >= 0
        assert check_events(events) == []

    def test_attrs_promote_step_uid(self, tmp_path):
        tracer, sink, d = _tracer(tmp_path)
        with tracer.span("s", step=7, uid=3, phase="refresh"):
            pass
        (e,) = _drain(sink, d)
        assert e["step"] == 7 and e["uid"] == 3
        assert e["attrs"] == {"phase": "refresh"}

    def test_record_builds_rooted_waterfall(self, tmp_path):
        tracer, sink, d = _tracer(tmp_path)
        t = tracer.new_trace("req")
        tracer.record("queued", 0.0, 0.5, t, parent=ROOT_SPAN)
        tracer.record("request", 0.0, 2.0, t, span=ROOT_SPAN)
        events = _drain(sink, d)
        assert check_events(events) == []
        root = next(e for e in events if e["name"] == "request")
        assert root["span"] == ROOT_SPAN

    def test_drain_open_emits_exactly_once(self, tmp_path):
        """A span open when drain_open fires (the SIGTERM path) is
        emitted truncated; the interrupted ``__exit__`` must NOT emit a
        second event for the same span id."""
        tracer, sink, d = _tracer(tmp_path)
        cm = tracer.span("interrupted")
        cm.__enter__()
        tracer.drain_open()
        cm.__exit__(None, None, None)
        events = _drain(sink, d)
        assert len(events) == 1
        assert events[0]["truncated"] is True
        assert events[0]["name"] == "interrupted"

    def test_null_tracer_sinkless_tracer_are_noops(self, tmp_path):
        from repro.telemetry import NULL_TRACER
        with NULL_TRACER.span("x") as h:
            h.set(step=1)
        NULL_TRACER.record("y", 0, 1, "t")
        NULL_TRACER.drain_open()
        sinkless = Tracer()       # times and discards
        with sinkless.span("z"):
            pass
        sinkless.flush()


# ---------------------------------------------------------------------------
# analysis helpers
# ---------------------------------------------------------------------------

def _sp(**kw):
    """Hand-built schema-valid span event."""
    e = {"kind": "span", "schema": 1, "trace": "t",
         "t0_s": 0.0, "dur_s": 1.0}
    e.update(kw)
    return e


def _finish(**kw):
    e = {"kind": "serve", "schema": 1, "event": "finish", "t_s": 1.0,
         "scheduler": "continuous", "uid": 0, "tokens": 5, "trace": "t"}
    e.update(kw)
    return e


class TestAnalysis:
    def test_span_stats_percentiles(self):
        events = [{"kind": "span", "name": "s", "trace": "t",
                   "span": f"s{i}", "t0_s": 0.0, "dur_s": float(i)}
                  for i in range(1, 101)]
        s = span_stats(events)["s"]
        assert s["count"] == 100
        assert s["p50_s"] == pytest.approx(50.5)
        assert s["p95_s"] == pytest.approx(95.05)
        assert s["p99_s"] == pytest.approx(99.01)

    def test_chrome_trace_structure(self, tmp_path):
        tracer, sink, d = _tracer(tmp_path)
        with tracer.span("a", step=1):
            with tracer.span("b"):
                pass
        ct = chrome_trace(_drain(sink, d))
        xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        ms = [e for e in ct["traceEvents"] if e["ph"] == "M"]
        assert {e["name"] for e in xs} == {"a", "b"}
        assert len(ms) == 1                      # one trace -> one tid
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
        assert xs[0]["args"].get("step") == 1 or \
            xs[1]["args"].get("step") == 1

    def test_check_events_flags_orphans(self):
        events = [_sp(name="child", span="s1", parent="missing")]
        assert any("orphaned" in p for p in check_events(events))

    def test_check_events_flags_negative_duration(self):
        events = [_sp(name="s", span="s1", dur_s=-0.1)]
        assert any("negative" in p for p in check_events(events))

    def test_check_events_flags_incomplete_waterfall(self):
        events = [
            _finish(),
            _sp(name="request", span=ROOT_SPAN, dur_s=1.0),
        ]
        probs = check_events(events)
        assert any("incomplete waterfall" in p for p in probs)
        # completing it silences the check
        events += [
            _sp(name="queued", span="s1", parent=ROOT_SPAN, dur_s=0.1),
            _sp(name="prefill_chunk", span="s2", parent=ROOT_SPAN,
                t0_s=0.1, dur_s=0.2),
            _sp(name="decode", span="s3", parent=ROOT_SPAN,
                t0_s=0.3, dur_s=0.6),
        ]
        assert check_events(events) == []

    def test_truncated_trace_exempt_from_completeness(self):
        events = [
            _finish(),
            _sp(name="request", span=ROOT_SPAN, dur_s=1.0,
                truncated=True),
        ]
        assert check_events(events) == []


# ---------------------------------------------------------------------------
# train-loop integration
# ---------------------------------------------------------------------------

class _QuadraticModel:
    """Minimal model satisfying the train-loop protocol; the 8x8 matrix
    leaf is factorable under min_dim_factor=4 (refresh/fold test)."""

    def init(self, key):
        del key
        return {"w": jnp.ones((8, 8))}

    def loss(self, params, batch):
        del batch
        l = jnp.sum(jnp.square(params["w"])) * 1e-3
        return l, {"loss": l}


_DATA = DataConfig(vocab=8, seq_len=4, global_batch=2)


def _adamw():
    return build_optimizer(OptimizerConfig(name="adamw",
                                           schedule="constant", lr=1e-3))


class TestTrainLoop:
    def test_step_spans_and_breakdown(self, tmp_path):
        tracer, sink, d = _tracer(tmp_path)
        train(_QuadraticModel(), _adamw(), _DATA,
              LoopConfig(total_steps=5, log_every=1), tracer=tracer)
        events = _drain(sink, d)
        assert check_events(events) == []
        stats = span_stats(events)
        for name in ("train_step", "data_wait", "step_dispatch",
                     "device_sync"):
            assert stats[name]["count"] == 5, name
        bd = step_breakdown(events)
        assert bd["steps"] == 5
        assert {p["phase"] for p in bd["phases"]} >= {
            "data_wait", "step_dispatch", "device_sync"}
        # shares account for the whole step
        assert sum(p["share"] for p in bd["phases"]) == pytest.approx(1.0)

    def test_tracing_is_bitwise_invisible(self, tmp_path):
        """Spans are host-side only: the trained state must be BITWISE
        identical with tracing on and off."""
        tracer, sink, d = _tracer(tmp_path)
        ref, _ = train(_QuadraticModel(), _adamw(), _DATA,
                       LoopConfig(total_steps=4, log_every=2))
        traced, _ = train(_QuadraticModel(), _adamw(), _DATA,
                          LoopConfig(total_steps=4, log_every=2),
                          tracer=tracer)
        sink.close()
        np.testing.assert_array_equal(np.asarray(ref.params["w"]),
                                      np.asarray(traced.params["w"]))

    def test_refresh_vs_fold_attribution(self, tmp_path):
        """train_step spans carry the refresh-vs-fold phase read from the
        in-jit snapshot counters (refresh_every=2: step 1 refreshes,
        step 2 folds, ...)."""
        tracer, sink, d = _tracer(tmp_path)
        opt = build_optimizer(OptimizerConfig(
            name="adapprox", schedule="constant", lr=1e-3, k=2,
            rank_mode="static", min_dim_factor=4, implicit=False,
            refresh_every=2, telemetry=True))
        train(_QuadraticModel(), opt, _DATA,
              LoopConfig(total_steps=4, log_every=1), tracer=tracer)
        events = _drain(sink, d)
        steps = sorted((e for e in events if e["name"] == "train_step"),
                       key=lambda e: e["step"])
        phases = [e["attrs"]["phase"] for e in steps]
        assert phases[0] == "refresh"
        assert set(phases) == {"refresh", "fold"}
        bd = step_breakdown(events)
        assert set(bd["refresh_vs_fold"]) == {"refresh", "fold"}

    def test_checkpoint_spans(self, tmp_path):
        tracer, sink, d = _tracer(tmp_path)
        ck = CheckpointConfig(directory=str(tmp_path / "ck"),
                              save_every=2, async_save=False)
        train(_QuadraticModel(), _adamw(), _DATA,
              LoopConfig(total_steps=4, log_every=2, ckpt=ck),
              tracer=tracer)
        # restart: restore gets its own span
        train(_QuadraticModel(), _adamw(), _DATA,
              LoopConfig(total_steps=5, log_every=2, ckpt=ck),
              tracer=tracer)
        events = _drain(sink, d)
        assert check_events(events) == []
        stats = span_stats(events)
        for name in ("checkpoint_save", "ckpt_gather", "ckpt_write"):
            assert stats[name]["count"] >= 2, name
        assert stats["ckpt_restore"]["count"] == 1


# ---------------------------------------------------------------------------
# one clock: live spans as profiler annotations
# ---------------------------------------------------------------------------

class _ListSink:
    def __init__(self):
        self.events = []

    def emit(self, ev):
        self.events.append(ev)

    def flush(self):
        pass


class _CountingAnnotation(jax.profiler.TraceAnnotation):
    made = 0

    def __init__(self, *args, **kwargs):
        type(self).made += 1
        super().__init__(*args, **kwargs)


def test_tracing_off_annotates_and_records_nothing(monkeypatch):
    """With no tracer, a train run and an engine run construct no
    profiler annotation and record no span; a tracer does both."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import ContinuousConfig, ContinuousEngine, Request
    emitted = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    monkeypatch.setattr(Tracer, "_emit",
                        lambda self, *a, **k: emitted.append(a[0]))
    _CountingAnnotation.made = 0
    train(_QuadraticModel(), _adamw(), _DATA,
          LoopConfig(total_steps=3, log_every=1))
    model = build_model(get_smoke_config("gpt2-117m"))
    engine = ContinuousEngine(
        model, model.init(jax.random.PRNGKey(0)),
        ContinuousConfig(slots=2, cache_len=64, block_size=16,
                         prefill_chunk=16))
    engine.sink = _ListSink()
    rng = np.random.default_rng(0)
    engine.run([Request(uid=i, prompt=rng.integers(0, 512, size=n)
                        .astype(np.int32), max_new_tokens=3)
                for i, n in enumerate((5, 20))])
    assert _CountingAnnotation.made == 0 and emitted == []
    assert engine.sink.events and all(
        e["kind"] == "serve" for e in engine.sink.events)
    train(_QuadraticModel(), _adamw(), _DATA,
          LoopConfig(total_steps=1, log_every=1), tracer=Tracer())
    assert _CountingAnnotation.made > 0 and "train_step" in emitted


def _host_annotations(trace_dir, names):
    """(name, start_ns, end_ns) of the events called ``names`` on the
    profile's host plane, in start order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name in names]
    return sorted(out, key=lambda a: (a[1], -a[2]))     # parents first


def test_spans_share_the_profilers_clock(tmp_path):
    """A CPU profile of a traced train loop holds every live span of the
    loop on its host plane, nested as in the JSONL; the benchmark's mapping
    (``t0_s`` less ``tracer.now()`` at the window's opening) agrees with
    each annotation's start within 1 ms."""
    names = ("train_step", "data_wait", "step_dispatch", "device_sync",
             "step_end")
    train(_QuadraticModel(), _adamw(), _DATA,
          LoopConfig(total_steps=1, log_every=1))         # compile first
    tracer = Tracer(sink=_ListSink())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            t_open = tracer.now()
            train(_QuadraticModel(), _adamw(), _DATA,
                  LoopConfig(total_steps=3, log_every=1), tracer=tracer)
    finally:
        jax.profiler.stop_trace()
    anns = _host_annotations(tmp_path, ("window",) + names)
    (_, w0, _), anns = anns[0], anns[1:]
    spans = sorted(tracer.sink.events,
                   key=lambda e: (e["t0_s"], "parent" in e))
    assert [e["name"] for e in spans] == [a[0] for a in anns]
    assert {e["name"] for e in spans} == set(names)
    by_id = {e["span"]: a for e, a in zip(spans, anns)}
    for e, (name, a0, a1) in zip(spans, anns):
        assert abs((e["t0_s"] - t_open) - (a0 - w0) * 1e-9) < 1e-3, name
        parent = by_id.get(e.get("parent"))
        if name in ("train_step", "step_end"):
            assert parent is None, name
        else:
            assert parent[0] == "train_step", name
            assert parent[1] <= a0 and a1 <= parent[2], name


# ---------------------------------------------------------------------------
# named device work
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,scopes", [
    ("adamw", ("/loss_and_grads/jvp(embed)/", "/loss_and_grads/jvp(blocks)/",
               "/loss_and_grads/transpose(jvp(blocks))/",
               "/loss_and_grads/jvp(head)/", "/optimizer/adamw/")),
    ("adapprox", ("/optimizer/adapprox/", "srsi)/", "precondition)/")),
])
def test_lowered_step_carries_layer_scopes(name, scopes):
    """The train step's op metadata names the layer each op belongs to
    (autodiff and vmap wrap a scope as ``jvp(blocks)``, ``vmap(srsi)``),
    so a device trace can attribute its time; ``optimizer`` is what the
    benchmark's optimizer reader matches."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.train import TrainState
    from repro.train.steps import build_train_step
    model = build_model(get_smoke_config("gpt2-117m", vocab=128))
    opt = build_optimizer(OptimizerConfig(
        name=name, schedule="constant", lr=1e-3, k=4, min_dim_factor=16,
        oversample=2, n_iter=2))
    state = jax.eval_shape(lambda k: TrainState.create(model.init(k), opt),
                           jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    text = jax.jit(build_train_step(model, opt)).lower(
        state, batch).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
