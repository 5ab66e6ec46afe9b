#!/usr/bin/env python3
"""Find a serve cell's knee once, by one sweep of fixed rates on the chip
(not part of a run; the cell then offers load at a rate fixed in its
traffic file).

    python chipbench/sweep.py --workload gpt2-117m.serve.chat \
        --rates 10,20,40,80 --seconds 10 --seed 3

One engine, warmed once, serves the cell's traffic at each rate in turn.
Per rate it prints one JSON line: requests, completed tokens per second,
TTFT and time-per-token tails, and the drain (how long after the last
arrival was due the last request finished).  A rate is sustained when the
drain stays near one request's service time instead of growing with the
window: the backlog does not grow, and the median TTFT of the last
quarter of the arrivals stays near that of the first.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import run as run_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = harness.load_json(run_mod.ROOT / "BENCHMARK.json")
    cell = harness.find(bench["workloads"], args.workload, "workload")
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = harness.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    import jax
    harness.require_tpu(harness.device_info(jax), cell["chips"])
    harness.enable_cache(jax, run_mod.ROOT)
    drv = run_mod.load_module(HERE / "drivers" / "serve.py")
    vocab = config["model"]["vocab"]
    sink = drv.StampSink()
    engine = drv.build(config, run_mod.load_reference(HERE, config),
                       args.seed, sink)
    drv.warm_up(engine, vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        prompts, max_new, arrivals = drv.make_requests(
            traffic, rate, args.seconds, args.seed, vocab)
        emitted = engine.tokens_emitted
        sink.first.clear()
        sink.done.clear()
        reqs, t0 = drv.serve(engine, prompts, max_new, arrivals)
        t_end = time.perf_counter()
        ttft, tpot = drv.latencies(reqs, arrivals, t0, sink)
        q = harness.quantile
        quarter = max(1, len(ttft) // 4)
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "tokens_per_s": (engine.tokens_emitted - emitted)
            / (t_end - t0),
            "ttft_ms_p50": 1e3 * q(ttft, 0.5),
            "ttft_ms_p95": 1e3 * q(ttft, 0.95),
            "tpot_ms_p50": 1e3 * q(tpot, 0.5),
            "tpot_ms_p95": 1e3 * q(tpot, 0.95),
            "ttft_ms_p50_first_quarter": 1e3 * q(ttft[:quarter], 0.5),
            "ttft_ms_p50_last_quarter": 1e3 * q(ttft[-quarter:], 0.5),
            "drain_s": t_end - t0 - arrivals[-1]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
