"""A benchmark directory at a size a CPU test can hold: the real drivers,
metric readers, GPT-2 reference and peaks, with tiny configurations, mixes
and limits of its own, laid out the way the harness finds the real ones
by name."""
from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import run  # noqa: E402

_MODEL = dict(family="dense", act="gelu", norm="layernorm",
              pos_embedding="learned", tie_embeddings=True, mlp_bias=True,
              norm_eps=1e-5, dtype="bfloat16", param_dtype="float32",
              remat="full", n_layers=2, d_model=64, n_heads=4,
              n_kv_heads=4, d_ff=256)

TRAIN = ("tiny-train.train.adapprox", "tiny-train.train.adamw",
         "tiny-train.train.fused")
SERVE = "tiny-serve.serve.chat"
# Limits at this size, between sound runs on the CPU (worst of several
# seeds: loss 5e-5, first gradient 2.5e-3, change 6.1e-3, factored second
# moment 0.18, factored change 0.24; served gap 0) and the faults and
# controls (0.07 at least in training, 1.0 for the factored numbers;
# served gap 4.9e-3 for the float8 control, 0.5 for an altered token).
TRAIN_LIMITS = {"loss_rel_gap": 1e-3, "first_grad_gap": 0.02,
                "param_change_gap": 0.03}
FACTORED_LIMITS = dict(TRAIN_LIMITS, factored_v_gap=0.45,
                       factored_change_gap=0.5)
SERVE_LIMITS = {"served_logit_gap": 1e-3, "min_tokens_checked": 30}


def limits_for(workload: str) -> dict:
    """A cell whose chain has Adapprox leaves compares their numbers too."""
    return TRAIN_LIMITS if workload.endswith(".adamw") else FACTORED_LIMITS


def _dump(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def make_base(tmp: Path) -> Path:
    """Copy the drivers, metric readers, GPT-2 reference and peaks; write
    tiny data files and a BENCHMARK.json naming the tiny cells."""
    base = tmp / "chipbench"
    for d in ("drivers", "metrics"):
        shutil.copytree(HERE / d, base / d)
    for f in ("peaks.json", "reference.py"):
        shutil.copy(HERE / f, base / f)
    _dump(base / "configs" / "tiny-train.json",
          {"reference": "reference.py",
           "model": dict(_MODEL, arch="tiny-train", vocab=1024,
                         max_seq_len=64), "train": {"batch": 4, "seq": 32}})
    _dump(base / "configs" / "tiny-serve.json",
          {"reference": "reference.py",
           "model": dict(_MODEL, arch="tiny-serve", vocab=512,
                         max_seq_len=128),
           "serve": {"slots": 4, "cache_len": 128, "block_size": 16,
                     "prefill_chunk": 32}})
    (base / "traffic").mkdir()
    for t in ("train.adapprox", "train.adamw", "train.fused"):
        shutil.copy(HERE / "traffic" / f"{t}.json", base / "traffic")
    _dump(base / "traffic" / "serve.chat.json",
          {"driver": "serve", "rate": 20.0,
           "prompt": {"median": 24, "sigma": 0.8, "min": 8, "max": 64},
           "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 16},
           "max_total": 96, "order_seed": 1, "check_tokens": 30,
           "check_max_requests": 8,
           "trace_start_s": 0.2, "trace_seconds": 0.3})
    for w in TRAIN:
        _dump(base / "limits" / f"{w}.json", limits_for(w))
    _dump(base / "limits" / f"{SERVE}.json", SERVE_LIMITS)
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    bench["workloads"] = [
        {"name": w, "config": w.split(".", 1)[0],
         "traffic": w.split(".", 1)[1], "chips": 1} for w in TRAIN + (SERVE,)]
    bench["end_to_end"] = [
        m for m in bench["end_to_end"] if "workloads" not in m] + [
        {"name": "train_tokens_per_s", "unit": "tokens/s",
         "better": "higher", "bound": 0.01, "source": "host_clock",
         "workloads": list(TRAIN)}] + [
        {"name": "ttft_ms_p95", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock", "workloads": [SERVE]}]
    bench["per_layer"] = []
    _dump(base / "BENCHMARK.json", bench)
    return base


def run_cell(base: Path, workload: str, seed: int, fault=None,
             seconds: float = 1.0) -> dict:
    """One whole run on the CPU through ``run.main``; its result line."""
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds)], require_chip=False,
                      fault=fault, base=base,
                      bench_path=base / "BENCHMARK.json")
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])
