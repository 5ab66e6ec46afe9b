"""Serve cells: open-loop arrivals into the program's ``ContinuousEngine``
over its paged KV cache, greedy decoding, timed from each request's due
time on the benchmark's own clock.

The traffic file fixes the rate and the length distributions.  A run
offers one schedule, the same for every seed, as a replayed log is: prompt
lengths, output lengths and inter-arrival gaps at stratified quantiles of
the stated distributions, in an order fixed by the traffic file.  The seed
draws the prompt tokens (and the weights): it changes what is computed,
not how much or when, so a tail compares across seeds.

Time stamps come from the engine's event sink, which it calls right after
the token of a ``first_token`` or ``finish`` event has been read back to
the host; the benchmark stamps those calls with its own clock.  A request
that is rejected or never finishes counts as a miss (infinite latency).

Correctness: after the run, with the engine freed, a sample drawn from the
seed of the finished requests (the one with the most output tokens always
among them) is run through the configuration's plain float32 reference,
prompt and served tokens together; the widest gap by which a served
token's reference logit lies below the reference's best logit at that
position must stay within the limit.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import faults
import harness
import trace_reduce

MISS = math.inf


def stratified(dist: dict, n: int) -> np.ndarray:
    """n draws of a clipped lognormal at the quantiles (i + 0.5) / n."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def make_requests(traffic: dict, rate: float, seconds: float, seed: int,
                  vocab: int):
    """(prompts, max_new, arrivals) of one run: ``rate * seconds``
    requests, Poisson arrivals (exponential gaps at stratified quantiles),
    prompt and output lengths paired by a fixed shuffle (so independent)
    and capped at ``max_total`` together, in the order the traffic's
    ``order_seed`` fixes; the seed draws the prompt tokens."""
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(traffic["order_seed"])
    plen = stratified(traffic["prompt"], n)
    olen = fixed.permutation(stratified(traffic["output"], n))
    olen = np.minimum(olen, traffic["max_total"] - plen)
    order = fixed.permutation(n)
    plen, olen = plen[order], olen[order]
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    arrivals = np.cumsum(fixed.permutation(gaps)) - gaps.min()
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5E7E]))
    prompts = [rng.integers(0, vocab, size=int(p)).astype(np.int32)
               for p in plen]
    return prompts, [int(o) for o in olen], arrivals.tolist()


class StampSink:
    """The engine's event sink, stamping first tokens and finishes with
    the benchmark's clock; it can also open and close the traced window
    on the engine's periodic ``stats`` event, at step boundaries."""

    def __init__(self):
        self.first, self.done = {}, {}
        self.hook = None

    def emit(self, ev):
        now = time.perf_counter()
        kind = ev.get("event")
        if kind == "first_token":
            self.first[ev["uid"]] = now
        elif kind == "finish":
            self.done[ev["uid"]] = now
        elif kind == "stats" and self.hook is not None:
            self.hook(now)


@dataclasses.dataclass
class TraceWindow:
    """Opens the profiler ``start`` seconds into the run and closes it
    ``length`` seconds later, both at engine steps."""
    start: float
    length: float
    trace_dir: object
    engine: object
    t0: float = 0.0
    ann: object = None
    opened: float = 0.0
    closed: float = 0.0
    tracer_open: float = 0.0

    def __call__(self, now):
        if self.ann is None and self.opened == 0.0 \
                and now - self.t0 >= self.start:
            self.ann = trace_reduce.open_window(self.trace_dir)
            self.opened = time.perf_counter()
            self.tracer_open = self.engine.tracer.now()
        elif self.ann is not None and now - self.opened >= self.length:
            trace_reduce.close_window(self.ann)
            self.ann = None
            self.closed = time.perf_counter()


def build(config: dict, reference, seed: int, sink, tracer=None):
    from repro.config import ModelConfig
    from repro.models import build_model
    from repro.serve import ContinuousConfig, ContinuousEngine
    mdict = config["model"]
    s = config["serve"]
    model = build_model(ModelConfig(**mdict))
    params = jax.jit(functools.partial(reference.init_params, mdict))(
        harness.prng_key(jax, seed))
    return ContinuousEngine(model, params, ContinuousConfig(
        slots=s["slots"], cache_len=s["cache_len"],
        block_size=s["block_size"], prefill_chunk=s["prefill_chunk"]),
        sink=sink, tracer=tracer)


def warm_up(engine, vocab: int) -> None:
    """Compile every program the traffic can reach: one prompt per
    prefill bucket (8 .. prefill_chunk) and the decode step."""
    from repro.serve import Request
    sizes, b = [], 8
    while b <= engine.cfg.prefill_chunk:
        sizes.append(b)
        b *= 2
    reqs = [Request(uid=-1 - i, prompt=np.full(n, 1 + i, np.int32),
                    max_new_tokens=2) for i, n in enumerate(sizes)]
    engine.run(reqs)


def serve(engine, prompts, max_new, arrivals):
    """One open-loop run; returns (requests, t0) with t0 the clock at the
    run's start, from which arrivals are due."""
    from repro.serve import Request
    reqs = [Request(uid=i, prompt=p, max_new_tokens=m)
            for i, (p, m) in enumerate(zip(prompts, max_new))]
    t0 = time.perf_counter()
    engine.run(reqs, arrivals=arrivals)
    return reqs, t0


def latencies(reqs, arrivals, t0, sink):
    """Per request: TTFT from the due time, and the time per output token
    after the first (requests with one output token have none)."""
    ttft, tpot = [], []
    for r, a in zip(reqs, arrivals):
        first, done = sink.first.get(r.uid), sink.done.get(r.uid)
        if r.rejected or not r.done or first is None or done is None:
            ttft.append(MISS)
            tpot.append(MISS)
            continue
        ttft.append(first - (t0 + a))
        if len(r.out_tokens) > 1:
            tpot.append((done - first) / (len(r.out_tokens) - 1))
    return ttft, tpot


def sample(reqs, seed: int, min_tokens: int, max_requests: int) -> list:
    """Finished requests for the check, drawn from the seed: the one with
    the most output tokens first, then others until ``min_tokens``."""
    done = [r for r in reqs if r.done and not r.rejected and r.out_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.out_tokens))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    out, total = [longest], len(longest.out_tokens)
    for i in rng.permutation(len(done)):
        r = done[int(i)]
        if total >= min_tokens or len(out) >= max_requests:
            break
        if r is not longest:
            out.append(r)
            total += len(r.out_tokens)
    return out


def served_gaps(reference, params, mdict, seqs: list, length: int,
                precision: str = "f32") -> list:
    """Widest logit gap per request; ``seqs`` holds (prompt, served
    tokens).  At each position that produced a served token, the gap is
    the reference's best logit less the reference's logit of the token
    judged: the served one, or with ``precision`` other than ``"f32"`` the
    one that forward in that precision puts first (the control)."""
    @jax.jit
    def one(params, tokens, start, n):
        ref = reference.logits(params, tokens[None], mdict, "f32")[0]
        pos = jnp.arange(length)
        live = (pos >= start) & (pos < start + n)
        if precision == "f32":
            pick = jnp.roll(tokens, -1)
        else:
            low = reference.logits(params, tokens[None], mdict, precision)[0]
            pick = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(live, jnp.max(ref, axis=-1) - got, 0.0))

    out = []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, np.asarray(served, np.int32)])
        buf = np.zeros((length,), np.int32)
        buf[:len(toks)] = toks
        out.append(float(one(params, jnp.asarray(buf), len(prompt) - 1,
                             len(served))))
    return out


def run(*, config, traffic, limits, reference, chips, seed, seconds, trace,
        t_process, workdir, fault=None):
    harness.one_chip(chips)
    from repro.telemetry.trace import Tracer
    mdict = config["model"]
    vocab = mdict["vocab"]
    sink = StampSink()
    spans = []
    tracer = Tracer(sink=harness.ListSink(spans)) if trace else None
    engine = build(config, reference, seed, sink, tracer)
    warm_up(engine, vocab)
    faults.plant_serve(fault, engine, vocab)
    prompts, max_new, arrivals = make_requests(
        traffic, traffic["rate"], seconds, seed, vocab)
    window = None
    if trace:
        window = TraceWindow(start=traffic["trace_start_s"],
                             length=traffic["trace_seconds"],
                             trace_dir=workdir, engine=engine)
        sink.hook = window
        spans.clear()      # the warm-up's spans are not the window's
    t_open = time.perf_counter()
    if window is not None:
        window.t0 = t_open
    reqs, t0 = serve(engine, prompts, max_new, arrivals)
    if window is not None and window.ann is not None:
        window(math.inf)       # the run ended before the window did
    t_end = time.perf_counter()
    ttft, tpot = latencies(reqs, arrivals, t0, sink)
    failed = sum(1 for x in ttft if x == MISS)
    peak = harness.memory_peak_bytes(jax)

    picked = sample(reqs, seed, traffic["check_tokens"],
                    traffic["check_max_requests"])
    seqs = [(r.prompt, list(r.out_tokens)) for r in picked]
    emitted = engine.tokens_emitted
    if window is not None:
        window.engine = None
    del engine
    gc.collect()
    # the reference makes its own weights from the seed
    params = jax.jit(functools.partial(reference.init_params, mdict))(
        harness.prng_key(jax, seed))
    gaps = served_gaps(reference, params, mdict, seqs,
                       config["serve"]["cache_len"])
    n_checked = sum(len(s) for _, s in seqs)
    checks = [harness.Check("served_logit_gap",
                            max(gaps) if gaps else math.nan,
                            limits["served_logit_gap"]),
              harness.Check("tokens_checked", float(n_checked),
                            float(limits["min_tokens_checked"]),
                            at_least=True)]

    done_tokens = sum(len(r.out_tokens) for r in reqs
                      if r.done and not r.rejected)
    e2e = {"ttft_ms_p95": 1e3 * harness.quantile(ttft, 0.95),
           "peak_hbm_gib": peak / harness.GIB,
           "setup_s": t_open - t_process}
    import sys
    print(f"serve: {len(reqs)} requests at {traffic['rate']} req/s over "
          f"{seconds} s, {len(reqs) - failed} finished, {failed} missed, "
          f"{emitted} tokens emitted, {done_tokens / (t_end - t0):.1f} "
          f"finished tokens/s; run {t_end - t0:.3f} s "
          f"(last arrival due {arrivals[-1] if arrivals else 0:.3f} s); "
          f"ttft p50 {1e3 * harness.quantile(ttft, 0.5):.1f} ms, "
          f"tpot p50 {1e3 * harness.quantile(tpot, 0.5):.2f} ms, "
          f"p95 {1e3 * harness.quantile(tpot, 0.95):.2f} ms",
          file=sys.stderr)

    reduced, context = None, None
    if trace:
        reduced = trace_reduce.reduce_dir(workdir)
        reduced.label_gaps([(e["name"], e["t0_s"] - window.tracer_open,
                             e["t0_s"] + e["dur_s"] - window.tracer_open)
                            for e in spans])
        context = {"kind": "serve", "trace": reduced, "spans": spans,
                   "model": mdict, "requests": reqs, "arrivals": arrivals,
                   "t0": t0, "stamps": sink,
                   "window": (window.opened - t0, window.closed - t0),
                   "tracer_open": window.tracer_open}
    return harness.RunResult(end_to_end=e2e, checks=checks,
                             attempted=len(reqs), failed=failed,
                             memory_peak_bytes=peak, trace=reduced,
                             context=context)
