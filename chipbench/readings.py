#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (not part of a run).

    python chipbench/readings.py --workload gpt2-345m.train.adapprox \
        --mode control --seeds 1,2,3
    python chipbench/readings.py --workload gpt2-117m.serve.chat \
        --mode fault:altered_token --seeds 1,2,3 --seconds 5

``control``: the plain reference in the program's place, computed in the
nearest precision below the configuration's bfloat16 products (float8
e4m3, scaled per tensor), and compared with the float32 reference by the
same numbers a run compares: the upper readings a limit must stay under.
``sound``: whole runs as the benchmark makes them, for more seeds of the
lower readings.  ``fault:<name>``: whole runs with a fault planted under
the timed path (``faults.py``).  One JSON line per seed; the runs of one call share a
process, so they need one chip, and compile once.
"""
from __future__ import annotations

import argparse
import io
import json
import sys
from contextlib import redirect_stdout
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import run as run_mod  # noqa: E402


def control_train(config, traffic, reference, seed):
    drv = run_mod.load_module(HERE / "drivers" / "train.py")
    mdict, ospec = config["model"], traffic["optimizer"]
    batch, seq = config["train"]["batch"], config["train"]["seq"]
    args = (reference, mdict, ospec, seed, batch, seq, traffic["ref_steps"],
            traffic["ref_rows"])
    ref = drv.reference_readings(*args, precision="f32")
    low = drv.reference_readings(*args, precision="fp8")
    return drv.readings(low, ref)


def control_serve(config, traffic, reference, seed, seconds):
    """The control's gap at each position of a run's own sampled prompts
    and served tokens: the sample comes from a sound run of the engine,
    then the engine is freed before the two reference forwards run."""
    import gc
    import jax
    drv = run_mod.load_module(HERE / "drivers" / "serve.py")
    mdict = config["model"]
    sink = drv.StampSink()
    engine = drv.build(config, reference, seed, sink)
    drv.warm_up(engine, mdict["vocab"])
    prompts, max_new, arrivals = drv.make_requests(
        traffic, traffic["rate"], seconds, seed, mdict["vocab"])
    reqs, _ = drv.serve(engine, prompts, max_new, arrivals)
    picked = drv.sample(reqs, seed, traffic["check_tokens"],
                        traffic["check_max_requests"])
    seqs = [(r.prompt, list(r.out_tokens)) for r in picked]
    del engine
    gc.collect()
    params = jax.jit(lambda k: reference.init_params(mdict, k))(
        harness.prng_key(jax, seed))
    n = config["serve"]["cache_len"]
    sound = drv.served_gaps(reference, params, mdict, seqs, n)
    low = drv.served_gaps(reference, params, mdict, seqs, n,
                          precision="fp8")
    del params
    jax.clear_caches()
    return {"served_logit_gap": max(low), "sound_gap": max(sound),
            "tokens": sum(len(s) for _, s in seqs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = harness.load_json(run_mod.ROOT / "BENCHMARK.json")
    cell = harness.find(bench["workloads"], args.workload, "workload")
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = harness.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    reference = run_mod.load_reference(HERE, config)
    import jax
    harness.require_tpu(harness.device_info(jax), cell["chips"])
    harness.enable_cache(jax, run_mod.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.mode == "control":
            if traffic["driver"] == "train":
                out = control_train(config, traffic, reference, seed)
            else:
                out = control_serve(config, traffic, reference, seed,
                                    args.seconds)
        elif args.mode == "sound" or args.mode.startswith("fault:"):
            fault = (args.mode.split(":", 1)[1]
                     if args.mode.startswith("fault:") else None)
            buf = io.StringIO()
            with redirect_stdout(buf):
                run_mod.main(["--workload", args.workload, "--seed",
                              str(seed), "--seconds", str(args.seconds)],
                             fault=fault)
            res = json.loads(buf.getvalue().strip().splitlines()[-1])
            out = {k: v["value"] for k, v in res["checks"].items()}
            out["correct"] = res["correct"]
        else:
            raise SystemExit(f"unknown mode {args.mode!r}")
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
