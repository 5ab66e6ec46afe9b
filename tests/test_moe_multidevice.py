"""MoE expert parallelism on a REAL multi-device mesh (8 host devices,
subprocess): sharded EP (+FSDP gather) and EP-TP decode layouts must both
match the single-device oracle bit-for-tolerance."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from repro.config import ModelConfig, MoESpec
from repro.models import moe as MOE

cfg = ModelConfig(arch="t", family="moe", n_layers=1, d_model=32, n_heads=4,
                  n_kv_heads=4, d_ff=64, vocab=64,
                  moe=MoESpec(n_experts=8, top_k=2, d_ff_expert=16,
                              capacity_factor=64.0, impl="sort"))
p = MOE.moe_init(jax.random.PRNGKey(0), cfg, 32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))

from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
y_ref, aux_ref = MOE.moe_apply_local(cfg, p, x)

# EP over model + FSDP gather over data (train layout)
y_ep, aux_ep = MOE.moe_apply_sharded(cfg, p, x, mesh, dp_axes=("data",),
                                     gather_axes=("data",))
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-5)

# weights-stationary EP-TP (decode layout)
y_tp, aux_tp = MOE.moe_apply_ep_tp(cfg, p, x, mesh)
np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-5)
print("MOE_MULTIDEVICE_OK")
"""


def test_moe_ep_on_8_devices():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=400,
                         cwd=str(REPO))
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert "MOE_MULTIDEVICE_OK" in out.stdout
