#!/usr/bin/env python3
"""On-chip benchmark entry point: one cell, one run, one result line.

    python chipbench/run.py --workload gpt2-345m.train.adapprox \
        --seed 7 --seconds 10 --trace 0

The cell is looked up by name in ``BENCHMARK.json`` at the checkout root;
its configuration, traffic mix and correctness limits are data files found
by name (``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json``), the configuration names its plain reference
(``reference``: the module that makes the weights and gives the loss,
the logits and the FLOP counts; see ``reference.py``), the traffic file
names the driver that runs it (``drivers/<driver>.py``), and every
per-layer metric is a reader of its own (``metrics/<metric>.py``).  Adding
a cell, a configuration, an architecture, a mix or a metric is adding
files and entries; nothing here changes.

The run fails, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the cell asks for more chips than its
driver shards over (no driver shards yet).  With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of part of the window.  Every run
checks what the timed path produced against the plain float32 reference
and prints each number compared beside its limit, last on stderr and
under ``checks`` (the last key) of the result line, which is the last
line of stdout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_module(path: Path):
    """Import a harness plug-in (driver, metric reader or reference) by
    file path; names may hold dots, so they are not importable as
    packages."""
    name = "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reference(base: Path, config: dict):
    """The module a configuration names as its plain reference, a path
    relative to the benchmark directory ``base``."""
    name = config.get("reference")
    if not name:
        raise SystemExit("chipbench: the configuration names no reference")
    path = (base / name).resolve()
    if not path.is_relative_to(base.resolve()) or not path.is_file():
        raise SystemExit(f"chipbench: no reference {name!r} under {base}")
    return load_module(path)


def main(argv=None, *, require_chip: bool = True, fault: str | None = None,
         base: Path = HERE, bench_path: Path | None = None) -> int:
    """``require_chip``, ``fault``, ``base`` and ``bench_path`` exist for
    the tests under ``chipbench/tests``: they drive a whole run on the CPU
    at a small size, from a directory of their own files, with or without
    a planted fault, through this same path."""
    args = parse(argv)
    bench = json.loads((bench_path or ROOT / "BENCHMARK.json").read_text())
    cell = harness.find(bench["workloads"], args.workload, "workload")
    config = harness.load_json(base / "configs" / f"{cell['config']}.json")
    traffic = harness.load_json(base / "traffic" / f"{cell['traffic']}.json")
    limits = harness.load_json(base / "limits" / f"{args.workload}.json")

    import jax  # the first touch of the chip

    device = harness.device_info(jax)
    if require_chip:
        harness.require_tpu(device, cell["chips"])
        harness.enable_cache(jax, ROOT)

    reference = load_reference(base, config)
    driver = load_module(base / "drivers" / f"{traffic['driver']}.py")
    run = driver.run(config=config, traffic=traffic, limits=limits,
                     reference=reference, chips=cell["chips"],
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_process=T_PROCESS,
                     workdir=harness.workdir(base, args.workload),
                     fault=fault)

    device["memory_peak_bytes"] = run.memory_peak_bytes
    if args.trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        wanted = harness.per_layer_for(bench, args.workload)
        run.context["reference"] = reference
        run.context["peaks"] = harness.peaks_for(
            harness.load_json(base / "peaks.json"), device["kind"])
        metrics = {}
        for m in wanted:
            reader = load_module(base / "metrics" / f"{m['name']}.py")
            value = reader.read(run.context)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        wanted = harness.end_to_end_for(bench, args.workload)
        metrics = {m["name"]: {"value": run.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in wanted}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    for c in run.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(harness.finite_or_none(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
