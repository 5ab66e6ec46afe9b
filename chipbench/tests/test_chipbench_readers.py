"""The per-layer readers of the optimizer's device time, the serving
scheduler's host time and the model FLOP/s utilisation, on hand-made
traces and span lists."""
from types import SimpleNamespace as NS

import pytest

import chipbench_tiny as T
import run
import trace_reduce as R

OPT = run.load_module(T.HERE / "metrics" / "optimizer_ms_per_step.train.py")
HOST = run.load_module(T.HERE / "metrics" / "host_ms_per_step.serve.py")
MFU_TRAIN = run.load_module(T.HERE / "metrics" / "mfu.train.py")
MFU_SERVE = run.load_module(T.HERE / "metrics" / "mfu.serve.py")


def _reduced(ops):
    """A reduced trace of one chip over the window 0..10 ms; ``ops``:
    (HLO instruction name, start_us, dur_us)."""
    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur, stats=[])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev(R.WINDOW, 0, 10_000_000)])])
    dev = NS(name="/device:TPU:0", lines=[NS(name=R.OPS_LINE, events=[
        ev(f"%{n} = f32[8]{{0}} {n.split('.')[0]}(...)", s * 1000,
           d * 1000) for n, s, d in ops])])
    return R.reduce_planes([host, dev])


def _scoped(monkeypatch, names):
    """The trace's HLO puts ``names`` under the optimizer scope."""
    monkeypatch.setattr(OPT, "newest_trace", lambda: "trace.xplane.pb")
    monkeypatch.setattr(OPT, "scoped_names", lambda path: set(names))


def test_optimizer_time_counts_leaf_ops_of_the_scope_once(monkeypatch):
    """A ``while`` of the optimizer (S-RSI's loop) encloses a fusion of
    its body: the fusion counts, the loop does not; ops outside the scope
    do not count."""
    _scoped(monkeypatch, ["while.2", "fusion.3", "fusion.4", "fusion.5"])
    tr = _reduced([
        ("fusion.1", 0, 3000),          # the backward pass
        ("while.2", 3000, 2000),
        ("fusion.3", 3100, 1500),       # inside while.2
        ("fusion.4", 5000, 400),
        ("fusion.5", 5400, 100),
        ("fusion.6", 5500, 100),
        ("fusion.4", 8000, 400),        # the next step
    ])
    ctx = {"trace": tr, "steps_traced": 2}
    assert OPT.read(ctx) == pytest.approx((1500 + 400 + 100 + 400) / 2e3)


def test_optimizer_time_is_none_without_the_scope(monkeypatch):
    tr = _reduced([("fusion.1", 0, 3000), ("while.2", 3000, 2000)])
    _scoped(monkeypatch, [])
    assert OPT.read({"trace": tr, "steps_traced": 2}) is None
    monkeypatch.setattr(OPT, "newest_trace", lambda: None)
    assert OPT.read({"trace": tr, "steps_traced": 2}) is None


def test_optimizer_scope_is_read_from_the_traces_hlo(tmp_path,
                                                     monkeypatch):
    """As on a TPU, the operations' stats carry no scope: the reader
    takes it from the program's HLO that the profiler keeps in the
    newest trace under the work directory."""
    import re

    import jax
    import jax.numpy as jnp

    def train_step(x):
        y = x @ x
        with jax.named_scope("optimizer"):
            return jnp.sin(y) * 2.0 - x

    step = jax.jit(train_step)
    x = jnp.ones((64, 64))
    text = step.lower(x).compile().as_text()
    names = dict(re.findall(r'^\s*(?:ROOT )?(\S+) = .*?op_name="([^"]*)"',
                            text, re.M))
    inside = [n for n, op in names.items() if "/optimizer/" in op]
    outside = [n for n, op in names.items() if "optimizer" not in op
               and n.rsplit(".", 1)[0] not in OPT.CONTROL]
    assert inside and outside
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path / "cell"))
    step(x).block_until_ready()
    jax.profiler.stop_trace()
    monkeypatch.setattr(OPT, "WORK", tmp_path)
    tr = _reduced([(inside[0], 0, 1000), (outside[0], 1000, 2000)])
    assert OPT.read({"trace": tr, "steps_traced": 1}) == pytest.approx(1.0)
    assert OPT.read({"trace": _reduced([(outside[0], 0, 2000)]),
                     "steps_traced": 1}) is None


def _span(name, sid, t0, dur, parent=None):
    e = {"kind": "span", "name": name, "trace": "e", "span": sid,
         "t0_s": t0, "dur_s": dur}
    if parent:
        e["parent"] = parent
    return e


def test_host_time_leaves_out_readbacks_and_steps_outside_the_window():
    """The window opens at 100 s on the tracer's clock and lasts 2 s:
    steps before it, after it or across its end are left out; a step's
    readbacks are the host waiting for the device, not host work."""
    spans = [
        _span("engine_step", "s1", 99.5, 0.3),                  # before
        _span("decode_readback", "s2", 99.6, 0.2, "s1"),
        _span("engine_step", "s3", 100.1, 0.3),
        _span("admit", "s4", 100.1, 0.001, "s3"),
        _span("prefill_readback", "s5", 100.15, 0.05, "s3"),
        _span("decode_readback", "s6", 100.2, 0.19, "s3"),
        _span("engine_step", "s7", 100.5, 0.2),
        _span("decode_readback", "s8", 100.55, 0.17, "s7"),
        _span("engine_step", "s9", 101.9, 0.3),                 # across end
        _span("decode_readback", "s10", 102.0, 0.1, "s9"),
        _span("decode", "s11", 100.0, 1.5, "root"),             # a request's
    ]
    ctx = {"spans": spans, "window": (30.0, 32.0), "tracer_open": 100.0}
    assert HOST.read(ctx) == pytest.approx(1e3 * (0.06 + 0.03) / 2)


def test_host_time_is_none_without_the_split():
    spans = [_span("engine_step", "s1", 100.1, 0.3),
             _span("queued", "s2", 99.0, 1.2, "root")]
    ctx = {"spans": spans, "window": (30.0, 32.0), "tracer_open": 100.0}
    assert HOST.read(ctx) is None
    assert HOST.read(dict(ctx, spans=[])) is None


def _stub_reference():
    """FLOP counts of no real model; the model dict the readers pass is
    empty, so GPT-2's formulas could not read it."""
    return NS(train_flops_per_token=lambda model, seq: 1e9 + seq,
              forward_flops=lambda model, context: 1e6 * context)


def test_mfu_train_reads_the_count_of_the_runs_reference():
    ctx = {"trace": NS(window_s=2.0, n_devices=1), "steps_traced": 3,
           "batch": 2, "seq": 8, "model": {},
           "reference": _stub_reference(),
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert MFU_TRAIN.read(ctx) == pytest.approx(
        100.0 * 3 * 2 * 8 * (1e9 + 8) / (2.0 * 1e12))


def test_mfu_serve_reads_the_count_of_the_runs_reference():
    """The window is 10 to 12 s into the run (100 s on the tracer's
    clock at its opening): a 4-token prefill chunk from position 2 inside
    it, and one request whose two decode tokens fall inside it, at
    contexts 6 and 7; a chunk after the window does not count."""
    spans = [{"name": "prefill_chunk", "t0_s": 100.5,
              "attrs": {"p0": 2, "tokens": 4}},
             {"name": "prefill_chunk", "t0_s": 103.0,
              "attrs": {"p0": 0, "tokens": 8}}]
    req = NS(uid=0, prompt=[0] * 5, out_tokens=[1, 2, 3])
    ctx = {"trace": NS(window_s=2.0, n_devices=1), "model": {},
           "reference": _stub_reference(), "window": (10.0, 12.0),
           "tracer_open": 100.0, "spans": spans, "t0": 50.0,
           "stamps": NS(first={0: 60.5}, done={0: 61.5}),
           "requests": [req], "peaks": {"bf16_flops_per_s": 1e12}}
    work = 1e6 * (3 + 4 + 5 + 6 + 6 + 7)
    assert MFU_SERVE.read(ctx) == pytest.approx(100.0 * work / 2e12)
