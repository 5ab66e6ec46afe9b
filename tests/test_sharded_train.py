"""Sharded training path on 8 virtual CPU devices (subprocess — needs its
own XLA device count): mesh-jitted train step with the mixed three-family
partition chain (count-min sketch on the token embedding, Adapprox on
matrices, dense Adam on the rest), live opt-state NamedShardings, and the
checkpoint resharding round trip.

Contracts pinned down (see the scripts for the assertions):

  * resharding is LOSSLESS: a checkpoint saved on a (4, 2) mesh restores
    bitwise-identically onto (2, 4), (8,) and a single device —
    ``PartitionState`` static labels and mid-``refresh_every`` factored
    state included;
  * same-mesh restart is bitwise-deterministic: save at step 3 of 5
    (mid-refresh-interval), restore on the same mesh, continue — losses
    and final params equal the uninterrupted run exactly;
  * checkpoint restore is equivalent to live resharding: a single-device
    continuation from the checkpoint matches a single-device continuation
    from the directly re-placed live state bitwise (serialization adds no
    error beyond placement);
  * continuation across DIFFERENT meshes matches to float-reassociation
    tolerance (GSPMD partitions matmul/grad reductions differently per
    mesh, so cross-mesh equality is ~1e-3 relative, not bitwise — the
    bitwise claims above are exactly the ones partitioning cannot touch).
"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

COMMON = r"""
import os, shutil, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.config import OptimizerConfig, default_mixed_groups
from repro.core import build_optimizer
from repro.models import build_model
from repro.data import DataConfig
from repro.train import LoopConfig, train
from repro.distributed import sharding as SH
from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.compat import make_mesh

VOCAB, SEQ, BATCH = 128, 32, 8

def make_opt():
    # refresh_every=2 so the step-3 checkpoint lands MID-interval: step 4
    # folds under the frozen basis, step 5 refreshes — the continuation
    # only stays exact if the factored state and step counter round-trip.
    # embedding_min_rows=64 puts the VOCAB=128 token embedding under the
    # count-min sketch, so all THREE state families ride the round trip
    # (the 32-row position embedding stays factored).
    return build_optimizer(OptimizerConfig(
        name="adapprox", schedule="constant", lr=1e-3, weight_decay=0.1,
        decay_mask="no_1d", min_dim_factor=32, k=4, rank_mode="static",
        implicit=False, refresh_every=2, groups=default_mixed_groups(),
        embedding_min_rows=64, sketch_width=256, sketch_depth=2))

def setup(mesh_spec):
    cfg = get_smoke_config("gpt2-117m", vocab=VOCAB, max_seq_len=SEQ)
    mesh = None
    if mesh_spec:
        axes = {1: ("data",), 2: ("data", "model")}[len(mesh_spec)]
        mesh = make_mesh(mesh_spec, axes)
    model = build_model(cfg, mesh)
    opt = make_opt()
    ssh = bsh = None
    if mesh is not None:
        model.constrain = SH.make_act_constrainer(mesh, "train")
        bstruct = {"tokens": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)}
        ssh, bsh = SH.train_shardings(model, opt, mesh, bstruct)
    return model, opt, ssh, bsh

def run(mesh_spec, total, ckpt_dir=None, state=None):
    model, opt, ssh, bsh = setup(mesh_spec)
    ck = CheckpointConfig(directory=ckpt_dir, save_every=10**9,
                          async_save=False) if ckpt_dir else None
    st, hist = train(model, opt,
                     DataConfig(vocab=VOCAB, seq_len=SEQ, global_batch=BATCH),
                     LoopConfig(total_steps=total, log_every=1, ckpt=ck),
                     state=state, state_shardings=ssh, batch_shardings=bsh)
    return st, [h["loss"] for h in hist]

def leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
"""

ROUNDTRIP = COMMON + r"""
base = tempfile.mkdtemp()

# --- uninterrupted sharded reference: 5 steps on (4, 2) -------------------
state5, l5 = run((4, 2), 5)

# the bitwise claims below cover all three families: the token embedding
# really is under the count-min sketch
from repro.core.sketch import SketchLeaf, sketch_state
sk = sketch_state(state5.opt_state)
assert any(isinstance(l, SketchLeaf) for l in sk.leaves), sk.leaves
print("SKETCH_FAMILY_PRESENT_OK")

# --- 3 steps on (4, 2), blocking save (mid-refresh-interval) --------------
d0 = os.path.join(base, "save42"); os.makedirs(d0)
state3, l3 = run((4, 2), 3, ckpt_dir=d0)
assert l3 == l5[:3], (l3, l5)

# --- resharding is lossless: restore bitwise on every target mesh ---------
restored = {}
for tag, mesh_spec in [("24", (2, 4)), ("8", (8,)), ("1", None)]:
    model, opt, ssh, _ = setup(mesh_spec)
    mgr = CheckpointManager(CheckpointConfig(directory=d0))
    like = jax.tree.map(np.asarray, state3)     # host template
    st, step = mgr.restore(like, ssh)
    assert step == 3, step
    assert leaves_equal(st, state3), f"restore on {tag} not bitwise"
    restored[tag] = st
print("RESTORE_BITWISE_OK")

# spot-check the resharded placement really is sharded on (2, 4)
st24 = restored["24"]
specs = {tuple(l.sharding.spec) for l in jax.tree.leaves(st24.params)
         if hasattr(l, "sharding") and l.ndim >= 2}
assert any(any(ax is not None for ax in s) for s in specs), specs
print("RESHARD_PLACED_OK")

# --- same-mesh restart is bitwise-deterministic ---------------------------
d1 = os.path.join(base, "cont42"); shutil.copytree(d0, d1)
state5b, l45 = run((4, 2), 5, ckpt_dir=d1)
assert l45 == l5[3:], (l45, l5[3:])
assert leaves_equal(state5b.params, state5.params), "same-mesh params diverged"
print("SAME_MESH_BITWISE_OK")

# --- checkpoint restore == live resharding (single-device continuation) ---
live1 = jax.device_put(jax.tree.map(np.asarray, state3), None)
_, l_live = run(None, 5, state=live1)
d2 = os.path.join(base, "cont1"); shutil.copytree(d0, d2)
_, l_ckpt = run(None, 5, ckpt_dir=d2)
assert l_ckpt == l_live, (l_ckpt, l_live)
print("CKPT_EQ_LIVE_OK")

# --- cross-mesh continuation: fp-reassociation tolerance only ------------
for tag, mesh_spec in [("24", (2, 4)), ("8", (8,))]:
    d = os.path.join(base, "cont" + tag); shutil.copytree(d0, d)
    _, lc = run(mesh_spec, 5, ckpt_dir=d)
    np.testing.assert_allclose(lc, l5[3:], rtol=1e-3, atol=0,
                               err_msg=f"cross-mesh {tag}")
    np.testing.assert_allclose(lc, l_ckpt, rtol=1e-3, atol=0)
print("CROSS_MESH_TOL_OK")
print("ROUNDTRIP_OK")
"""

TELEMETRY = COMMON + r"""
import repro.telemetry as T
from jax.sharding import NamedSharding

def make_opt():          # override COMMON's: telemetry + dynamic cadence
    return build_optimizer(OptimizerConfig(
        name="adapprox", schedule="constant", lr=1e-3, weight_decay=0.1,
        decay_mask="no_1d", min_dim_factor=32, k=4, rank_mode="static",
        implicit=False, refresh_every=2, telemetry=True,
        dynamic_refresh=True, groups=default_mixed_groups()))

base = tempfile.mkdtemp()
d0 = os.path.join(base, "tel42"); os.makedirs(d0)
state3, l3 = run((4, 2), 3, ckpt_dir=d0)

# --- 8-virtual-device snapshot replication: every telemetry leaf (and
# the traced cadence scalar) is a REPLICATED NamedSharding on the mesh
snaps = T.named_snapshots(state3.opt_state)
assert list(snaps) == ["factored"], list(snaps)
for leaf in jax.tree.leaves(snaps["factored"]):
    assert isinstance(leaf.sharding, NamedSharding), leaf.sharding
    assert leaf.sharding.is_fully_replicated, leaf.sharding
re = T.named_states(state3.opt_state)["factored"].refresh_every
assert isinstance(re.sharding, NamedSharding) and \
    re.sharding.is_fully_replicated
assert T.get_refresh_every(state3.opt_state) == {"factored": 2}
snap = snaps["factored"]
assert int(snap.refresh_steps) == 2 and int(snap.fold_steps) == 1, \
    (int(snap.refresh_steps), int(snap.fold_steps))   # refresh at 1, 3
print("SNAPSHOT_REPLICATED_OK")

# --- sharding-spec round trip: train_shardings derives telemetry specs
# through the state_sharding_spec protocol (replicated), for a DIFFERENT
# target mesh
model, opt, ssh, _ = setup((2, 4))
sh_snaps = T.named_snapshots(ssh.opt_state)
assert list(sh_snaps) == ["factored"]
for sh in jax.tree.leaves(sh_snaps["factored"]):
    assert isinstance(sh, NamedSharding) and sh.is_fully_replicated, sh
print("SPEC_ROUNDTRIP_OK")

# --- resharded restore is bitwise, telemetry counters + cadence included
mgr = CheckpointManager(CheckpointConfig(directory=d0))
like = jax.tree.map(np.asarray, state3)
st, step = mgr.restore(like, ssh)
assert step == 3
assert leaves_equal(st, state3), "telemetry state not bitwise on (2,4)"
assert T.get_refresh_every(st.opt_state) == {"factored": 2}
print("TELEMETRY_RESTORE_OK")

# --- runtime cadence change on the live sharded state lands replicated
# and the continuation runs under the new cadence
new_opt = T.set_refresh_every(st.opt_state, {"factored": 3})
re2 = T.named_states(new_opt)["factored"].refresh_every
assert isinstance(re2.sharding, NamedSharding) and \
    re2.sharding.is_fully_replicated
import dataclasses as _dc
st5, l45 = run((2, 4), 5, state=_dc.replace(st, opt_state=new_opt))
assert T.get_refresh_every(st5.opt_state) == {"factored": 3}
snap5 = T.named_snapshots(st5.opt_state)["factored"]
# steps 4, 5 under T=3: 4 % 3 = 1 -> refresh, 5 % 3 = 2 -> fold
assert int(snap5.refresh_steps) == 3 and int(snap5.fold_steps) == 2, \
    (int(snap5.refresh_steps), int(snap5.fold_steps))
print("TELEMETRY_CONT_OK")
"""

LAUNCHER = r"""
import os
os.environ["REPRO_TRAIN_DEVICES"] = "8"
from repro.launch import train as LT
import jax, numpy as np
from jax.sharding import NamedSharding
from repro.core import PartitionState, adapprox_state
from repro.core.adamw import AdamWState
from repro.core import factored as F

state = LT.main(["--smoke", "--steps", "2", "--log-every", "1",
                 "--batch", "8", "--seq", "32",
                 "--mesh", "4,2", "--mixed-groups",
                 "--embedding-min-rows", "256", "--sketch-width", "256",
                 "--sketch-depth", "2"])

# partition state with static labels survived the mesh-jitted step; the
# 512-row smoke vocab clears --embedding-min-rows 256, so the token
# embedding rides the sketch group
pstate = state.opt_state
assert isinstance(pstate, PartitionState), type(pstate)
assert set(pstate.inner) == {"dense", "embeddings", "factored"}, \
    pstate.inner.keys()
assert set(pstate.labels) == {"dense", "embeddings", "factored"}

# every live opt-state leaf carries a NamedSharding from the mesh jit
for leaf in jax.tree.leaves(state.opt_state):
    assert isinstance(leaf.sharding, NamedSharding), leaf.sharding
print("OPT_STATE_NAMED_SHARDINGS_OK")

# matrices ride the factored Adapprox group (sharded q/u factors), 1-D
# leaves the dense Adam group
ad = adapprox_state(pstate.inner["factored"])
fls = [l for l in ad.leaves if isinstance(l, F.FactoredLeaf)]
assert fls, "no factored leaves under the adapprox group"
assert any(any(ax is not None for ax in l.q.sharding.spec) for l in fls), \
    "no factored q factor is actually sharded"
adam = [s for s in pstate.inner["dense"] if isinstance(s, AdamWState)]
assert adam and all(x.ndim <= 1 or min(x.shape[-2:]) < 64
                    for x in jax.tree.leaves(adam[0].m)), \
    "dense Adam group should hold only 1-D/small leaves"

# the embeddings group holds the sketched token embedding: the hashed
# table replaces the row axis, the exact first moment shards with FSDP
from repro.core.sketch import SketchLeaf, sketch_state
sk = sketch_state(pstate.inner["embeddings"])
sls = [l for l in sk.leaves if isinstance(l, SketchLeaf)]
assert sls, "no sketched leaves under the embeddings group"
assert all(l.table.shape[:2] == (2, 256) for l in sls), \
    [l.table.shape for l in sls]
assert any(any(ax is not None for ax in l.m.sharding.spec) for l in sls), \
    "no sketch first moment is actually sharded"
print("SKETCH_GROUP_SHARDED_OK")
# params sharded too (FSDP default on)
assert any(any(ax is not None for ax in l.sharding.spec)
           for l in jax.tree.leaves(state.params) if l.ndim >= 2)
print("LAUNCHER_MESH_OK")
"""


def _run(script: str, name: str, timeout=1800):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, \
        f"{name} failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_resharding_round_trip():
    out = _run(ROUNDTRIP, "resharding round trip")
    for marker in ("SKETCH_FAMILY_PRESENT_OK",
                   "RESTORE_BITWISE_OK", "RESHARD_PLACED_OK",
                   "SAME_MESH_BITWISE_OK", "CKPT_EQ_LIVE_OK",
                   "CROSS_MESH_TOL_OK", "ROUNDTRIP_OK"):
        assert marker in out, out


def test_launcher_mesh_smoke():
    out = _run(LAUNCHER, "launcher mesh smoke")
    assert "OPT_STATE_NAMED_SHARDINGS_OK" in out, out
    assert "SKETCH_GROUP_SHARDED_OK" in out, out
    assert "LAUNCHER_MESH_OK" in out, out


def test_telemetry_sharded_snapshot():
    """8 virtual devices: telemetry snapshot + dynamic cadence leaves are
    replicated on the mesh, their sharding specs round-trip through the
    state_sharding_spec protocol for other meshes, resharded restore is
    bitwise (counters + cadence included), and a live cadence change on
    the sharded state stays replicated through continuation."""
    out = _run(TELEMETRY, "telemetry sharded snapshot")
    for marker in ("SNAPSHOT_REPLICATED_OK", "SPEC_ROUNDTRIP_OK",
                   "TELEMETRY_RESTORE_OK", "TELEMETRY_CONT_OK"):
        assert marker in out, out
