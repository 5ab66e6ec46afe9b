"""GPipe pipeline parallelism: exact forward + gradient equivalence with
sequential execution, on 4 host devices (subprocess — needs its own XLA
device count)."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np
from repro.distributed.pipeline import (pipeline_apply, split_stages,
                                        stage_fn_from_layers)

L, D, M, MB = 8, 16, 6, 4
from repro.compat import make_mesh
mesh = make_mesh((4,), ("stage",))

key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (L, D, D)) * (1.0 / jnp.sqrt(D))
x = jax.random.normal(jax.random.fold_in(key, 1), (M, MB, D))

def layer_fn(wl, h):
    return jnp.tanh(h @ wl)

def sequential(w, x):
    def body(h, wl):
        return layer_fn(wl, h), None
    out, _ = jax.lax.scan(lambda h, wl: (layer_fn(wl, h), None), x, w)
    return out

stage_params = split_stages(w, 4)
stage_fn = stage_fn_from_layers(layer_fn)

out_pipe = pipeline_apply(stage_fn, stage_params, x, mesh)
out_seq = jax.vmap(lambda xm: sequential(w, xm))(
    x.reshape(M, 1, MB, D)[:, 0])
np.testing.assert_allclose(np.asarray(out_pipe), np.asarray(out_seq),
                           rtol=1e-5, atol=1e-5)

# gradient equivalence (ppermute transposes to the reverse schedule)
def loss_pipe(w):
    sp = split_stages(w, 4)
    return jnp.sum(pipeline_apply(stage_fn, sp, x, mesh) ** 2)

def loss_seq(w):
    return jnp.sum(jax.vmap(lambda xm: sequential(w, xm))(x) ** 2)

g_pipe = jax.grad(loss_pipe)(w)
g_seq = jax.grad(loss_seq)(w)
np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                           rtol=1e-4, atol=1e-4)
print("PIPELINE_OK")
"""


def test_gpipe_matches_sequential_forward_and_grad():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=str(REPO))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "PIPELINE_OK" in out.stdout
