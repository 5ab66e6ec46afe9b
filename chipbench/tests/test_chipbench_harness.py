"""The harness: it refuses to run without a TPU, finds every plug-in by
name, and the committed BENCHMARK.json names only files that exist."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny as T
import harness
import run

ROOT = T.HERE.parent


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = _run_py(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(T.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_committed_benchmark_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (T.HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (T.HERE / "limits" / f"{w['name']}.json").is_file()
        traffic = json.loads(
            (T.HERE / "traffic" / f"{w['traffic']}.json").read_text())
        assert (T.HERE / "drivers" / f"{traffic['driver']}.py").is_file()
        e2e = {m["name"] for m in harness.end_to_end_for(bench, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.per_layer_for(bench, w["name"])
        assert layer and all(m["moves"] in e2e for m in layer)
    for m in bench["per_layer"]:
        assert (T.HERE / "metrics" / f"{m['name']}.py").is_file()


def test_dropped_in_files_are_found_by_name(tmp_path):
    base = T.make_base(tmp_path)
    # a new configuration with a reference of its own, mix, limits file
    # and metric, and the entries that name them: no file of the harness
    # changes
    cfg = json.loads((base / "configs" / "tiny-train.json").read_text())
    cfg["model"]["arch"] = "tiny-wide"
    cfg["model"]["d_ff"] = 512
    cfg["reference"] = "reference_wide.py"
    shutil.copy(base / "reference.py", base / "reference_wide.py")
    (base / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    mix = json.loads((base / "traffic" / "train.adamw.json").read_text())
    mix["warm_steps"] = 5
    (base / "traffic" / "train.long.json").write_text(json.dumps(mix))
    (base / "limits" / "tiny-wide.train.long.json").write_text(
        json.dumps(T.TRAIN_LIMITS))
    (base / "metrics" / "steps_traced.train.py").write_text(
        "def read(ctx):\n    return float(ctx['steps_traced'])\n")
    bench = json.loads((base / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-wide.train.long",
                               "config": "tiny-wide",
                               "traffic": "train.long", "chips": 1})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-wide.train.long")
    bench["per_layer"].append(
        {"name": "steps_traced.train", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "train loop",
         "moves": "train_tokens_per_s",
         "workloads": ["tiny-wide.train.long"]})
    (base / "BENCHMARK.json").write_text(json.dumps(bench))

    names = [m["name"] for m in
             harness.per_layer_for(bench, "tiny-wide.train.long")]
    assert names == ["steps_traced.train"]
    reader = run.load_module(base / "metrics" / "steps_traced.train.py")
    assert reader.read({"steps_traced": 3}) == 3.0
    res = T.run_cell(base, "tiny-wide.train.long", seed=11)
    assert res["correct"], res["checks"]
    assert "chipbench_reference_wide" in sys.modules
    assert set(res["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}


def test_peaks_by_device_kind():
    table = json.loads((T.HERE / "peaks.json").read_text())
    v5e = harness.peaks_for(table, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for(table, "TPU v9 imaginary")


def test_large_seeds_keep_their_high_bits():
    import jax
    a = harness.prng_key(jax, 5)
    b = harness.prng_key(jax, 2 ** 33 + 5)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()
