"""Serving-engine suite: continuous batching, the paged KV cache, and
the wave baseline.

Pins the PR's load-bearing claims:
  * paged decode is BITWISE identical to the dense cache path (the pool
    seeded from one dense prefill via ``pool_from_dense``);
  * wave and continuous produce IDENTICAL greedy streams for identical
    arrival order on equal-length prompts, and continuous matches a
    per-request solo wave reference on MIXED prompt lengths (the wave
    batch itself is pad-contaminated there — documented engine caveat);
  * ``BlockAllocator`` accounting: free-list reuse, the reservation
    ledger, double-free / exhaustion errors, and clean drain-down after
    an engine run;
  * the wave EOS-on-first-token and ``max_new_tokens<=0`` regressions;
  * admission backs off (with telemetry) instead of failing when the
    pool is occupancy-constrained, and bounded queues load-shed;
  * request churn never recompiles the jitted decode step.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import (ContinuousConfig, ContinuousEngine, Engine,
                         NULL_BLOCK, BlockAllocator, PoolExhausted, Request,
                         ServeConfig, SlotTable, pool_from_dense)
from repro.telemetry import SinkConfig, TelemetrySink, validate_dir

CACHE_LEN = 128
BLOCK_SIZE = 16
NBT = CACHE_LEN // BLOCK_SIZE
VOCAB = 512


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("gpt2-117m")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _reqs(lengths, budgets, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, VOCAB, size=n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lengths, budgets))]


def _clone(reqs):
    return [Request(uid=r.uid, prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens) for r in reqs]


def _cont(model, params, **over):
    kw = dict(slots=4, cache_len=CACHE_LEN, block_size=BLOCK_SIZE,
              prefill_chunk=32)
    kw.update(over)
    return ContinuousEngine(model, params, ContinuousConfig(**kw))


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_null_block_never_allocated(self):
        a = BlockAllocator(9, BLOCK_SIZE)
        ids = a.alloc(8)
        assert NULL_BLOCK not in ids
        assert sorted(ids) == list(range(1, 9))

    def test_free_then_reuse(self):
        a = BlockAllocator(5, BLOCK_SIZE)
        first = a.alloc(4)
        assert a.free_blocks() == 0
        a.free(first)
        assert a.free_blocks() == 4
        again = a.alloc(4)
        assert sorted(again) == sorted(first)

    def test_double_free_and_bad_ids_raise(self):
        a = BlockAllocator(5, BLOCK_SIZE)
        ids = a.alloc(2)
        a.free(ids)
        with pytest.raises(ValueError, match="double-free"):
            a.free([ids[0]])
        with pytest.raises(ValueError, match="invalid block id"):
            a.free([NULL_BLOCK])
        with pytest.raises(ValueError, match="invalid block id"):
            a.free([99])

    def test_exhaustion(self):
        a = BlockAllocator(5, BLOCK_SIZE)
        a.alloc(3)
        with pytest.raises(PoolExhausted):
            a.alloc(2)

    def test_reservation_ledger(self):
        a = BlockAllocator(9, BLOCK_SIZE)       # 8 usable
        assert a.reserve(5)
        assert a.available() == 3
        assert a.occupancy() == pytest.approx(5 / 8)
        # unreserved allocs may not raid the reservation
        with pytest.raises(PoolExhausted):
            a.alloc(4)
        got = a.alloc(3, reserved=True)         # draw against it
        assert len(got) == 3
        assert a.available() == 3               # 2 still reserved, 3 out
        a.release(2)                            # leftover at finish
        assert a.available() == 5
        assert not a.reserve(6)                 # over-ask reserves nothing
        assert a.available() == 5

    def test_blocks_for(self):
        a = BlockAllocator(5, 16)
        assert a.blocks_for(1) == 1
        assert a.blocks_for(16) == 1
        assert a.blocks_for(17) == 2

    def test_slot_table_padded(self):
        t = SlotTable([3, 1, 2])
        row = t.padded(6)
        assert row.dtype == np.int32
        assert row.tolist() == [3, 1, 2, 0, 0, 0]
        assert t.capacity(16) == 48


# ---------------------------------------------------------------------------
# paged cache vs dense cache: bitwise
# ---------------------------------------------------------------------------

def test_paged_decode_bitwise_matches_dense(model_and_params):
    """Seed the block pool from one dense prefill (pool_from_dense),
    then step both representations on identical fed tokens: the logits
    must match BITWISE every step."""
    model, params = model_and_params
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    b, plen = 2, 16
    prompts = rng.integers(0, VOCAB, size=(b, plen)).astype(np.int32)
    cache = model.init_cache(b, CACHE_LEN)
    logits, cache = jax.jit(model.prefill)(params, jnp.asarray(prompts),
                                           cache)
    alloc = BlockAllocator(b * NBT + 1, BLOCK_SIZE)
    tables = [SlotTable(alloc.alloc(NBT)) for _ in range(b)]
    pool = pool_from_dense(model, cache, tables, [plen] * b,
                           b * NBT + 1, BLOCK_SIZE)
    tabs = jnp.asarray(np.stack([t.padded(NBT) for t in tables]))
    toks = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    pos = np.full((b,), plen, np.int32)
    dense_step = jax.jit(model.decode_step)
    paged_step = jax.jit(model.decode_paged)
    for _ in range(6):
        ld, cache = dense_step(params, cache, toks)
        lp, pool = paged_step(params, pool, toks, tabs, jnp.asarray(pos))
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        toks = jnp.argmax(ld[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        pos += 1


def test_paged_calls_leave_other_blocks_untouched(model_and_params):
    """The pool is updated in place, donated as the engine donates it: a
    prefill chunk and decode steps write only blocks of the live tables
    (and the null block, which parked rows write to); every other block
    keeps its bytes."""
    model, params = model_and_params
    import jax.numpy as jnp
    nb = 4 * NBT + 1
    rng = np.random.default_rng(13)
    shape = model.init_paged_cache(nb, BLOCK_SIZE)["k"].shape
    before = {n: rng.standard_normal(shape).astype(jnp.bfloat16)
              for n in ("k", "v")}
    pool = {n: jnp.asarray(a) for n, a in before.items()}
    live = [[5, 9, 2], [7, 11]]                   # slots 0, 1; 2, 3 idle
    prefill = jax.jit(model.prefill_paged, donate_argnums=(1,))
    decode = jax.jit(model.decode_paged, donate_argnums=(1,))
    plen = 20                                     # padded to a 32 chunk
    chunk = np.zeros((1, 32), np.int32)
    chunk[0, :plen] = rng.integers(0, VOCAB, size=plen)
    _, pool = prefill(params, pool, jnp.asarray(chunk),
                      jnp.asarray(SlotTable(live[0]).padded(NBT)),
                      jnp.int32(0), jnp.int32(plen - 1))
    tabs = jnp.asarray(np.stack([SlotTable(t).padded(NBT)
                                 for t in live + [[], []]]))
    pos = np.array([plen, 10, 0, 0], np.int32)
    toks = jnp.asarray(rng.integers(0, VOCAB, size=(4, 1)), jnp.int32)
    for _ in range(5):
        logits, pool = decode(params, pool, toks, tabs, jnp.asarray(pos))
        toks = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        pos[:2] += 1
    written = {NULL_BLOCK} | {b for t in live for b in t}
    kept = [b for b in range(nb) if b not in written]
    for n in ("k", "v"):
        after = np.asarray(pool[n]).view(np.uint16)
        was = before[n].view(np.uint16)
        np.testing.assert_array_equal(after[:, kept], was[:, kept])
        for b in (5, 9, 7):                       # what the calls wrote
            assert (after[:, b] != was[:, b]).any()


# ---------------------------------------------------------------------------
# stream parity between schedulers
# ---------------------------------------------------------------------------

def test_wave_and_continuous_identical_streams(model_and_params):
    """Equal-length prompts (no wave pad contamination), identical
    arrival order: both schedulers must emit identical greedy streams,
    request by request — continuous batching changes WHEN tokens are
    computed, never WHICH."""
    model, params = model_and_params
    reqs = _reqs([12] * 6, [5, 17, 3, 9, 1, 7])
    wave_reqs, cont_reqs = _clone(reqs), _clone(reqs)
    Engine(model, params,
           ServeConfig(slots=4, cache_len=CACHE_LEN)).run(wave_reqs)
    eng = _cont(model, params)
    eng.run(cont_reqs)
    for w, c in zip(wave_reqs, cont_reqs):
        assert w.out_tokens == c.out_tokens, f"req {w.uid} diverged"
        assert len(c.out_tokens) == c.max_new_tokens
        assert c.done and c.done_s is not None
    # clean drain: every block is back in the pool, nothing reserved
    assert eng.alloc.free_blocks() == eng.alloc.usable
    assert eng.alloc.occupancy() == 0.0


def test_continuous_mixed_lengths_match_solo_reference(model_and_params):
    """Mixed prompt lengths batched continuously must match each request
    served ALONE (slots=1 wave = the unbatched reference): per-slot
    positions + block tables isolate rows completely."""
    model, params = model_and_params
    reqs = _reqs([5, 33, 17, 8, 26], [6, 4, 9, 3, 5], seed=3)
    cont_reqs = _clone(reqs)
    _cont(model, params, prefill_chunk=16).run(cont_reqs)
    solo = Engine(model, params, ServeConfig(slots=1, cache_len=CACHE_LEN))
    for r in reqs:
        ref = _clone([r])
        solo.run(ref)
        got = next(c for c in cont_reqs if c.uid == r.uid)
        assert got.out_tokens == ref[0].out_tokens, f"req {r.uid} diverged"


# ---------------------------------------------------------------------------
# wave regressions
# ---------------------------------------------------------------------------

def test_wave_eos_on_first_token(model_and_params):
    """EOS straight out of prefill must end the sequence at one token —
    the seed engine kept decoding its full budget past it."""
    model, params = model_and_params
    probe = _reqs([10], [1], seed=11)
    Engine(model, params,
           ServeConfig(slots=2, cache_len=CACHE_LEN)).run(probe)
    eos = probe[0].out_tokens[0]   # the greedy first token IS our "EOS"
    reqs = _reqs([10], [64], seed=11)
    Engine(model, params,
           ServeConfig(slots=2, cache_len=CACHE_LEN, eos_id=eos)).run(reqs)
    assert reqs[0].out_tokens == [eos]
    assert reqs[0].done and reqs[0].first_token_s is not None

    cont = _reqs([10], [64], seed=11)
    _cont(model, params, eos_id=eos).run(cont)
    assert cont[0].out_tokens == [eos]


def test_zero_budget_emits_nothing(model_and_params):
    model, params = model_and_params
    for make in (lambda: Engine(model, params,
                                ServeConfig(slots=2, cache_len=CACHE_LEN)),
                 lambda: _cont(model, params)):
        reqs = _reqs([9, 9], [0, 3], seed=5)
        make().run(reqs)
        assert reqs[0].out_tokens == []
        assert reqs[0].done and reqs[0].done_s is not None
        assert len(reqs[1].out_tokens) == 3


# ---------------------------------------------------------------------------
# admission, occupancy, load shedding
# ---------------------------------------------------------------------------

def test_admission_backs_off_under_full_occupancy(model_and_params,
                                                  tmp_path):
    """A pool sized for ONE request must serve many: admission waits at
    the occupancy watermark (emitting backoff telemetry) and recycles
    blocks as requests finish — never PoolExhausted, never a wrong
    stream."""
    model, params = model_and_params
    cache_len, nbt = 64, 64 // BLOCK_SIZE
    sink = TelemetrySink(SinkConfig(directory=str(tmp_path)))
    eng = ContinuousEngine(
        model, params,
        ContinuousConfig(slots=2, cache_len=cache_len,
                         block_size=BLOCK_SIZE, prefill_chunk=16,
                         num_blocks=nbt + 1),     # ONE slot's worth
        sink=sink)
    reqs = _reqs([16, 16, 16], [48 - 16, 40 - 16, 20], seed=9)
    eng.run(reqs)
    sink.flush()
    sink.close()
    for r in reqs:
        assert len(r.out_tokens) == r.max_new_tokens
        assert r.done
    assert eng.alloc.free_blocks() == eng.alloc.usable
    events = [json.loads(line)
              for p in sorted(tmp_path.glob("events-*.jsonl"))
              for line in p.read_text().splitlines()]
    kinds = {e["event"] for e in events}
    assert "backoff" in kinds, "full pool must emit admission backoff"
    assert {"admit", "first_token", "finish"} <= kinds
    # schema-valid end to end
    assert validate_dir(tmp_path) == len(events)


def test_bounded_queue_load_sheds(model_and_params):
    model, params = model_and_params
    eng = _cont(model, params, slots=1, max_queue=2)
    reqs = _reqs([8] * 4, [4] * 4, seed=2)
    # all four arrive at t=0, BEFORE the first scheduler step admits
    # anything: two fill the bounded queue, two are shed
    eng.run(reqs)
    served = [r for r in reqs if not r.rejected]
    shed = [r for r in reqs if r.rejected]
    assert len(shed) == 2
    assert all(r.out_tokens == [] and r.done for r in shed)
    assert all(len(r.out_tokens) == r.max_new_tokens for r in served)


def test_oversized_request_rejected_upfront(model_and_params):
    model, params = model_and_params
    eng = _cont(model, params)
    with pytest.raises(ValueError, match="span"):
        eng.run(_reqs([64], [CACHE_LEN], seed=1))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.run([Request(uid=0, prompt=np.zeros(0, np.int32),
                         max_new_tokens=4)])


# ---------------------------------------------------------------------------
# compile-once
# ---------------------------------------------------------------------------

def test_request_churn_never_recompiles_decode(model_and_params):
    """The jitted decode step sees fixed shapes; block tables and
    positions are DATA.  Mixed prompt lengths and budgets across many
    admissions must leave exactly one decode executable, and prefill at
    most one per chunk bucket."""
    model, params = model_and_params
    eng = _cont(model, params, prefill_chunk=32)
    reqs = _reqs([5, 12, 33, 8, 40, 21, 9, 17],
                 [3, 7, 4, 11, 2, 5, 6, 8], seed=4)
    eng.run(reqs)
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() <= 3   # buckets 8/16/32
    eng.run(_reqs([6, 14, 27], [4, 3, 5], seed=8))
    assert eng._decode_jit._cache_size() == 1


# ---------------------------------------------------------------------------
# span waterfalls (trace-id join contract)
# ---------------------------------------------------------------------------

class TestWaterfalls:
    def _run_traced(self, tmp_path, engine, reqs, **run_kw):
        from repro.telemetry import MetricsRegistry, Tracer
        sink = TelemetrySink(SinkConfig(directory=str(tmp_path)))
        tracer = Tracer(sink=sink, registry=MetricsRegistry())
        engine.sink = sink
        engine.set_tracer(tracer)
        engine.run(reqs, **run_kw)
        tracer.flush()
        sink.flush()
        sink.close()
        from repro.telemetry import load_events
        assert validate_dir(tmp_path) > 0
        return load_events(tmp_path), tracer

    def test_continuous_requests_reconstruct_complete_waterfalls(
            self, model_and_params, tmp_path):
        from repro.telemetry import check_events
        from repro.telemetry.trace import ROOT_SPAN
        model, params = model_and_params
        reqs = _reqs([5, 17, 33, 9, 40], [6, 3, 5, 1, 4])
        events, tracer = self._run_traced(tmp_path,
                                          _cont(model, params), reqs)
        assert check_events(events) == []
        spans = [e for e in events if e["kind"] == "span"]
        finishes = [e for e in events
                    if e["kind"] == "serve" and e["event"] == "finish"]
        assert len(finishes) == len(reqs)
        for f in finishes:
            # every finish joins its waterfall by trace id alone
            mine = [s for s in spans if s["trace"] == f["trace"]]
            names = {s["name"] for s in mine}
            assert {"request", "queued"} <= names
            assert "prefill_chunk" in names
            root = next(s for s in mine if s["name"] == "request")
            assert root["span"] == ROOT_SPAN
            assert root["uid"] == f["uid"]
            assert root["attrs"]["tokens"] == f["tokens"]
            # phases nest under the root and inside its window
            for s in mine:
                if s is root:
                    continue
                assert s["parent"] == ROOT_SPAN
                assert s["t0_s"] >= root["t0_s"] - 1e-6
        # chunked prefill: the 33/40-token prompts crossed prefill_chunk=32
        chunky = [f["trace"] for f in finishes if f["uid"] in (2, 4)]
        for t in chunky:
            n = sum(1 for s in spans
                    if s["trace"] == t and s["name"] == "prefill_chunk")
            assert n == 2
        # registry rolled up the served requests
        reg = tracer.registry
        assert reg.counter("serve_requests_total").value(
            scheduler="continuous") == len(reqs)

    def test_wave_requests_reconstruct_complete_waterfalls(
            self, model_and_params, tmp_path):
        from repro.telemetry import check_events
        model, params = model_and_params
        reqs = _reqs([8, 8, 8], [4, 2, 6])
        eng = Engine(model, params,
                     ServeConfig(slots=4, cache_len=CACHE_LEN))
        events, _ = self._run_traced(tmp_path, eng, reqs)
        assert check_events(events) == []
        spans = [e for e in events if e["kind"] == "span"]
        for name in ("request", "queued", "prefill"):
            assert sum(1 for s in spans if s["name"] == name) == len(reqs)

    def test_engine_step_records_its_phases(self, model_and_params):
        """One step that prefills a prompt's last chunk and decodes: every
        phase is a live child span of ``engine_step``, which counts the
        prompt tokens prefilled and the rows decoded."""
        from repro.telemetry import Tracer

        class Sink:
            events = []

            def emit(self, ev):
                self.events.append(ev)

        model, params = model_and_params
        eng = _cont(model, params)
        eng.set_tracer(Tracer(sink=Sink()))
        eng._ready.extend(_reqs([5, 20], [4, 4]))
        eng.step(0.0)        # admits both, prefills and decodes the first
        Sink.events.clear()
        eng.step(0.1)        # the second's only chunk, then both decode
        step, = [e for e in Sink.events if e["name"] == "engine_step"]
        kids = [e["name"] for e in Sink.events
                if e.get("parent") == step["span"]]
        assert sorted(kids) == sorted([
            "admit", "prefill_dispatch", "prefill_readback",
            "decode_prepare", "decode_dispatch", "decode_readback",
            "decode_commit"])
        assert step["attrs"] == {"prefill_tokens": 20, "decode_rows": 2}
        assert step["step"] == 2

    def test_tokens_are_stamped_after_their_readback(
            self, model_and_params, tmp_path):
        """A request's ``decode`` span runs from its first token to its
        finish, both stamped once the token is on the host: it starts
        after its prompt's ``prefill_readback`` and ends no earlier than
        its last ``decode_readback`` (one per token after the first)."""
        model, params = model_and_params
        reqs = _reqs([5, 17, 33, 9, 40], [6, 3, 5, 2, 4])
        events, _ = self._run_traced(tmp_path, _cont(model, params), reqs,
                                     arrivals=[0.0, 0.0, 0.05, 0.1, 0.1])
        spans = [e for e in events if e["kind"] == "span"]
        reads = sorted((e for e in spans if e["name"] == "decode_readback"),
                       key=lambda e: e["t0_s"])
        pre = [e for e in spans if e["name"] == "prefill_readback"]
        eps = 2e-6                   # the stream's microsecond rounding
        decodes = [e for e in spans if e["name"] == "decode"]
        assert len(decodes) == len(reqs)
        for d in decodes:
            tokens = next(e["attrs"]["tokens"] for e in spans
                          if e["trace"] == d["trace"]
                          and e["name"] == "request")
            assert any(abs(p["t0_s"] + p["dur_s"] - d["t0_s"]) < 1e-3
                       and p["t0_s"] + p["dur_s"] <= d["t0_s"] + eps
                       for p in pre)
            mine = [r for r in reads if r["t0_s"] >= d["t0_s"] - eps]
            last = mine[tokens - 2]
            assert d["t0_s"] + d["dur_s"] >= last["t0_s"] + last["dur_s"] \
                - eps

    def test_traceview_check_passes_on_engine_run(self, model_and_params,
                                                   tmp_path):
        import os
        import subprocess
        import sys
        from pathlib import Path
        model, params = model_and_params
        self._run_traced(tmp_path, _cont(model, params),
                         _reqs([5, 40, 9], [3, 4, 1]))
        root = Path(__file__).resolve().parent.parent
        env = dict(os.environ, PYTHONPATH=str(root / "src"))
        p = subprocess.run([sys.executable, str(root / "tools" /
                                                "traceview.py"),
                            str(tmp_path), "--check"],
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "engine_step" in p.stdout and "decode_readback" in p.stdout

    def test_untraced_run_emits_no_spans(self, model_and_params, tmp_path):
        """tracer=None (the default) keeps the serve stream span-free —
        tracing is strictly opt-in."""
        model, params = model_and_params
        sink = TelemetrySink(SinkConfig(directory=str(tmp_path)))
        eng = _cont(model, params)
        eng.sink = sink
        eng.run(_reqs([5, 9], [3, 2]))
        sink.flush()
        sink.close()
        from repro.telemetry import load_events
        events = load_events(tmp_path)
        assert events and all(e["kind"] == "serve" for e in events)
        assert all("trace" not in e for e in events)
