"""A configuration brings its own reference: a second architecture runs
through both drivers with nothing but new files and appended entries, the
reference a configuration names is the one a run uses, and a cell that
asks for more chips than its driver shards over is refused."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny as T
import harness

ROPE_REF = "reference_rope_swiglu.py"
ROPE_CELLS = ("tiny-rope.train.adamw", "tiny-rope.train.adapprox",
              "tiny-rope.serve.chat")
# Names the same architecture with GPT-2's reference.
GPT2_CELLS = ("tiny-rope-gpt2ref.train.adamw",
              "tiny-rope-gpt2ref.serve.chat")
# The train cells keep the tiny limits (sound runs on the CPU, seeds 1 to
# 8, read at most: loss 4.4e-4, first gradient 3.0e-3, change 1.3e-2,
# factored moment 0.33, factored change 0.26; the float8 control at
# least 2.1e-3, 0.91, 0.97, 14, 1.0).  Its served gaps are of logits ten
# times GPT-2's tiny ones (an untied head, N(0, 1/d)): sound 0 to 0.070
# over seeds 1 to 14, the float8 control 0.147 to 0.499.
ROPE_SERVE_LIMITS = dict(T.SERVE_LIMITS, served_logit_gap=0.1)


def _rope_config(reference: str) -> dict:
    """The ``dense`` family as Llama-style models set it, at a CPU size."""
    model = dict(T._MODEL, arch="tiny-rope", norm="rmsnorm", act="swiglu",
                 pos_embedding="rope", rope_theta=10000.0,
                 tie_embeddings=False, mlp_bias=False, n_kv_heads=2,
                 vocab=1024, max_seq_len=128)
    return {"reference": reference, "model": model,
            "train": {"batch": 4, "seq": 32},
            "serve": {"slots": 4, "cache_len": 128, "block_size": 16,
                      "prefill_chunk": 32}}


def _add_cells(base, cells):
    """Appends cells (``<config>.<traffic>``) with the tiny limits, and
    their names to the end-to-end metrics they report."""
    bench = json.loads((base / "BENCHMARK.json").read_text())
    for w in cells:
        config, traffic = w.split(".", 1)
        kind = "serve" if traffic.startswith("serve") else "train"
        bench["workloads"].append({"name": w, "config": config,
                                   "traffic": traffic, "chips": 1})
        limits = (ROPE_SERVE_LIMITS if kind == "serve"
                  else T.limits_for(traffic))
        (base / "limits" / f"{w}.json").write_text(json.dumps(limits))
        e2e = "ttft_ms_p95" if kind == "serve" else "train_tokens_per_s"
        for m in bench["end_to_end"]:
            if m["name"] == e2e:
                m["workloads"].append(w)
    (base / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    base = T.make_base(tmp_path_factory.mktemp("tiny"))
    shutil.copy(T.HERE / "tests" / ROPE_REF, base / ROPE_REF)
    for name, ref in (("tiny-rope", ROPE_REF),
                      ("tiny-rope-gpt2ref", "reference.py")):
        (base / "configs" / f"{name}.json").write_text(
            json.dumps(_rope_config(ref)))
    _add_cells(base, ROPE_CELLS + GPT2_CELLS)
    return base


@pytest.mark.parametrize("workload", ROPE_CELLS)
def test_second_architecture_is_correct_through_both_drivers(base,
                                                             workload):
    res = T.run_cell(base, workload, seed=2 ** 33 + 29)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("workload", GPT2_CELLS)
def test_the_reference_the_configuration_names_is_used(base, workload):
    """GPT-2's reference makes GPT-2's weights (learned positions, biases,
    no gate, a tied head): the program of the rope configuration cannot
    run on them, or runs and disagrees."""
    try:
        res = T.run_cell(base, workload, seed=7)
    except (KeyError, TypeError):          # a tree or shape the model
        return                             # does not take
    assert not res["correct"], res["checks"]


def test_a_configuration_without_a_reference_is_refused(tmp_path):
    import run
    with pytest.raises(SystemExit, match="names no reference"):
        run.load_reference(tmp_path, {"model": {}})
    (tmp_path / "outside.py").write_text("")
    inner = tmp_path / "chipbench"
    inner.mkdir()
    with pytest.raises(SystemExit, match="no reference"):
        run.load_reference(inner, {"reference": "../outside.py"})


@pytest.mark.parametrize("workload", ["tiny-train.train.adamw", T.SERVE])
def test_four_chip_cell_is_refused_on_four_devices(tmp_path, workload):
    """On four devices a driver that does not shard would run on the
    first alone; it exits non-zero and prints no result instead."""
    base = T.make_base(tmp_path)
    bench = json.loads((base / "BENCHMARK.json").read_text())
    harness.find(bench["workloads"], workload, "workload")["chips"] = 4
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    script = (
        "import sys; from pathlib import Path\n"
        f"sys.path.insert(0, {str(T.HERE)!r})\n"
        "import jax, run\n"
        "print('devices', jax.device_count(), file=sys.stderr)\n"
        f"sys.exit(run.main(['--workload', {workload!r}, '--seed', '1',\n"
        "    '--seconds', '1'], require_chip=False,\n"
        f"    base=Path({str(base)!r}),\n"
        f"    bench_path=Path({str(base / 'BENCHMARK.json')!r})))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(T.HERE.parent / "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "devices 4" in p.stderr
    assert "does not shard" in p.stderr
    assert p.stdout.strip() == ""
