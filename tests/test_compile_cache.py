"""The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise to the fixed in-checkout directory (subprocesses: the
helper changes process-wide jax config)."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SCRIPT = r"""
import os, sys
import jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
d = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == d, d
if sys.argv[1] in ("compile", "scopes"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
if sys.argv[1] == "scopes":
    def scoped(name):
        def step(x):
            with jax.named_scope(name):
                return jnp.cos(x) @ x.T
        return step
    for name in ("optimizer", "head"):
        jax.jit(scoped(name))(jnp.ones((8, 8))).block_until_ready()
print("CACHE_DIR=" + d)
print("DEFAULT_DIR=" + str(DEFAULT_DIR))
"""


def _run(env_dir, mode):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", SCRIPT, mode], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return dict(line.split("=", 1) for line in out.stdout.splitlines()
                if "=" in line)


def test_env_dir_receives_cache_entries(tmp_path):
    got = _run(tmp_path / "cc", "compile")
    assert got["CACHE_DIR"] == str(tmp_path / "cc")
    assert any(p.name.endswith("-cache")
               for p in (tmp_path / "cc").iterdir())


def test_default_dir_is_fixed_inside_checkout():
    got = _run(None, "config-only")
    assert got["CACHE_DIR"] == got["DEFAULT_DIR"] == str(REPO / ".jax_cache")


def test_named_scopes_get_entries_of_their_own(tmp_path):
    """Two programs that differ only in a named scope (metadata) are two
    cache entries, so each executable keeps its own op names."""
    def entries(d):
        return sum(p.name.endswith("-cache") for p in d.iterdir())
    _run(tmp_path / "plain", "compile")
    _run(tmp_path / "scoped", "scopes")
    assert entries(tmp_path / "scoped") == entries(tmp_path / "plain") + 2
