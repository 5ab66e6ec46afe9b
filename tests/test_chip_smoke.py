"""chip_smoke.py on the CPU: the script refuses to run without a TPU or
without the repo, and its phases — with every check they make on the
chip — pass at smoke size with the kernels interpreted, so the script
cannot rot between chip runs."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke as CS
from repro.configs import get_smoke_config
from repro.kernels import ops

REPO = Path(__file__).resolve().parent.parent

# GPT-2 345M's structure at smoke widths: the vocab and position tables
# are big enough (>= 1024 rows) to take the sketch family, as at full size
SMALL_345M = dict(vocab=2048, max_seq_len=1024, d_model=128, d_ff=256,
                  n_heads=4, n_kv_heads=4, head_dim=32)


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)
    return env


@pytest.fixture
def interpreted_as_on_chip(monkeypatch):
    """The chip's dispatch ("auto" = kernels, "ref" = references), with
    the kernels interpreted instead of compiled."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: (
        (False, False) if ops._MODE == "ref" else (True, True)))
    monkeypatch.setattr(ops, "resolved_mode", lambda: (
        "ref" if ops._MODE == "ref" else "pallas"))


@pytest.mark.parametrize("where", ["cpu_only", "script_alone"])
def test_refuses_to_run(tmp_path, where):
    script = REPO / "chip_smoke.py"
    if where == "script_alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    out = subprocess.run([sys.executable, str(script)], env=_env(),
                         cwd=script.parent, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_train_phase_small(interpreted_as_on_chip, capsys):
    cfg = get_smoke_config("gpt2-345m", **SMALL_345M)
    CS.train_phase(cfg, batch=2, seq=64, steps=5)
    assert "kernels vs ref" in capsys.readouterr().out


def test_serve_phase_small(interpreted_as_on_chip, capsys):
    cfg = get_smoke_config("gpt2-117m", max_seq_len=1024)
    CS.serve_phase(cfg, prompt_lens=(7, 64, 190, 333, 700), max_new=8)
    assert "serve: 5 requests" in capsys.readouterr().out


FSDP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import chip_smoke as CS
from repro.configs import get_smoke_config
from repro.kernels import ops
ops._use_pallas = lambda: (False, False) if ops._MODE == "ref" else (True, True)
CS.fsdp_phase(get_smoke_config("gpt2-345m", **%r), batch=8, seq=64, steps=5)
print("FSDP_PHASE_OK")
""" % (SMALL_345M,)


def test_fsdp_phase_small():
    """--chips 4 on four virtual CPU devices (subprocess: needs its own
    device count)."""
    out = subprocess.run([sys.executable, "-c", FSDP_SCRIPT],
                         env=_env(PYTHONPATH=str(REPO)), cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert "FSDP_PHASE_OK" in out.stdout, out.stderr[-3000:]
