"""Elastic scaling: resume a checkpoint under a different mesh.

The serialization layer stores LOGICAL (global) arrays, so elasticity is:
  1. detect world size / topology at startup,
  2. build the new mesh + shardings,
  3. ``restore_pytree(..., shardings=new)`` — placement happens at load.

Data-stream elasticity is handled by the deterministic pipeline: batch t is
a pure function of (seed, step), so any host subset re-derives its slice
after re-partitioning (data/pipeline.py host_slice).

``plan_remesh`` is the policy piece: given a device count (possibly after
losing nodes) choose the nearest valid (pod, data, model) factorisation,
preferring to shrink the data axis (keeps TP intact so per-layer math and
factored-optimizer shapes are unchanged — only FSDP shard sizes move).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.compat import make_mesh


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    pods: int
    data: int
    model: int

    @property
    def devices(self) -> int:
        return self.pods * self.data * self.model

    def axes(self) -> tuple:
        if self.pods > 1:
            return ("pod", "data", "model")
        return ("data", "model")

    def shape(self) -> tuple:
        if self.pods > 1:
            return (self.pods, self.data, self.model)
        return (self.data, self.model)


def plan_remesh(available_devices: int, target_model: int = 16,
                max_pod_data: int = 16) -> MeshPlan:
    """Largest usable mesh with the given TP degree.

    Keeps `model` fixed (so parameter shard shapes are stable across the
    restart), re-factorises the rest into (pods, data).  Devices that do
    not fit the factorisation are left idle — the deterministic data
    pipeline re-balances over the surviving data shards.
    """
    if available_devices < target_model:
        # degrade TP as the last resort (power of two below the count)
        tm = 1
        while tm * 2 <= available_devices:
            tm *= 2
        target_model = tm
    usable = available_devices // target_model
    data = min(usable, max_pod_data)
    pods = usable // data
    return MeshPlan(pods=max(pods, 1), data=max(data, 1),
                    model=target_model)


def build_mesh(plan: MeshPlan):
    return make_mesh(plan.shape(), plan.axes())


def elastic_restore(ckpt_manager, like, make_shardings, *,
                    available_devices: Optional[int] = None,
                    target_model: int = 16):
    """End-to-end elastic resume: plan mesh -> build shardings -> restore.

    make_shardings(mesh) -> sharding pytree matching ``like``.
    Returns (state, step, mesh).
    """
    n = available_devices or len(jax.devices())
    plan = plan_remesh(n, target_model=target_model)
    mesh = build_mesh(plan)
    shardings = make_shardings(mesh)
    state, step = ckpt_manager.restore(like, shardings)
    return state, step, mesh
