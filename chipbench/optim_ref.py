"""Plain float32 references of the optimizer chains a train cell runs.

``adamw``: bias-corrected Adam with decoupled weight decay on every leaf.
``adapprox`` (the launcher's mixed chain): leaves are routed by shape,
first match wins — 2-D tables of at least ``embedding_min_rows`` rows to
Adam with a count-min sketch second moment, matrices whose two trailing
sizes are at least ``min_dim_factor`` to Adapprox, the rest to Adam.

Adapprox here keeps its second moment EXACT (``V = b2 V + (1 - b2) G^2``,
no bias correction), with the paper's per-matrix RMS update clip and
update-EMA first moment: it is what S-RSI approximates, at full rank.
The program's factored moment differs from it by its approximation error,
which the comparison's limits take in (PERF.md).  The count-min sketch is
written out from its definition (universal hashes ``((a i + b) mod p) mod
w`` with the coefficients derived from the optimizer seed and the leaf's
index in its group, bucket sums of ``G^2`` per hash, the minimum over
hashes), since which rows collide decides the update.

Every chain ends ``u <- u + wd * w; w <- w - lr_t * u``, with ``lr_t`` a
linear warm-up to the peak over ``max(steps // 20, 5)`` steps, then a
cosine to a sixth of the peak at ``steps``, ``t`` counting from 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_PRIME = (1 << 31) - 1
_MASK64 = (1 << 64) - 1


def lr_at(t: int, peak: float, steps: int) -> float:
    warm = max(steps // 20, 5)
    if t < warm:
        return peak * t / warm
    frac = min(max((t - warm) / max(1, steps - warm), 0.0), 1.0)
    low = peak / 6
    return low + 0.5 * (peak - low) * (1 + math.cos(math.pi * frac))


def hash_coefficients(seed: int, leaf_idx: int, depth: int) -> list:
    x = (seed * 0x9E3779B97F4A7C15 + (leaf_idx + 1) * 0xBF58476D1CE4E5B9) \
        & _MASK64
    out = []
    for _ in range(depth):
        x = (x * 6364136223846793005 + 1442695040888963407) & _MASK64
        a = int((x >> 16) % (_PRIME - 1)) + 1
        x = (x * 6364136223846793005 + 1442695040888963407) & _MASK64
        b = int((x >> 16) % _PRIME)
        out.append((a, b))
    return out


def buckets(rows: int, width: int, coefs: list) -> np.ndarray:
    i = np.arange(rows, dtype=np.int64)
    return np.stack([((a * i + b) % _PRIME) % width
                     for a, b in coefs]).astype(np.int32)


def route(shapes: list, opt: dict) -> list:
    """Per-leaf family, in the params' flatten order."""
    if opt["name"] == "adamw":
        return ["adam"] * len(shapes)
    fams = []
    for s in shapes:
        if len(s) >= 2 and s[0] >= opt["embedding_min_rows"]:
            fams.append("sketch")
        elif len(s) >= 2 and min(s[-2], s[-1]) >= opt["min_dim_factor"]:
            fams.append("adapprox")
        else:
            fams.append("adam")
    return fams


class Reference:
    """Steps the reference chain over a flat list of float32 leaves (one
    jitted program per step, the hash buckets baked in as constants)."""

    def __init__(self, params: list, opt: dict):
        self.opt = opt
        self.fams = route([p.shape for p in params], opt)
        # leaves whose state keeps a first moment of the gradient
        self.moment = [fam != "adapprox" for fam in self.fams]
        self.t = 0
        self.m = [jnp.zeros_like(p) for p in params]
        self.v, self.idx = [], []
        n_sketch = 0
        for p, fam in zip(params, self.fams):
            if fam == "sketch":
                coefs = hash_coefficients(opt["seed"], n_sketch,
                                          opt["sketch_depth"])
                n_sketch += 1
                self.idx.append(jnp.asarray(
                    buckets(p.shape[0], opt["sketch_width"], coefs)))
                inner = int(np.prod(p.shape[1:]))
                self.v.append(jnp.zeros((opt["sketch_depth"],
                                         opt["sketch_width"], inner)))
            else:
                self.idx.append(None)
                self.v.append(jnp.zeros_like(p))
        self._step = jax.jit(self._pure_step)
        self._first = jax.jit(self._pure_first)

    def step(self, params: list, grads: list) -> list:
        o = self.opt
        self.t += 1
        t = self.t
        scalars = jnp.asarray([lr_at(t, o["lr"], o["steps"]),
                               1 - o["b1"] ** t, 1 - o["b2"] ** t],
                              jnp.float32)
        params, self.m, self.v = self._step(params, grads, self.m, self.v,
                                            scalars)
        return params

    def _pure_step(self, params, grads, ms, vs, scalars):
        o = self.opt
        b1, b2, eps = o["b1"], o["b2"], o["eps"]
        lr, bc1, bc2 = scalars[0], scalars[1], scalars[2]
        out, new_m, new_v = [], [], []
        for i, (p, g, fam) in enumerate(zip(params, grads, self.fams)):
            g = g.astype(jnp.float32)
            if fam == "adapprox":
                v = b2 * vs[i] + (1 - b2) * g * g
                u = g / (jnp.sqrt(v) + eps)
                rms = jnp.sqrt(jnp.mean(u * u, axis=(-2, -1), keepdims=True)
                               + 1e-30)
                u = u / jnp.maximum(1.0, rms / o["clip_d"])
                m = b1 * ms[i] + (1 - b1) * u
                d = m
            elif fam == "sketch":
                rows = g.shape[0]
                g2 = (g * g).reshape(rows, -1)
                idx = self.idx[i]
                depth = idx.shape[0]
                table = b2 * vs[i] + (1 - b2) * jnp.stack([
                    jax.ops.segment_sum(g2, idx[j], o["sketch_width"])
                    for j in range(depth)])
                q = jnp.min(jnp.stack([table[j][idx[j]]
                                       for j in range(depth)]), 0)
                v = table
                m = b1 * ms[i] + (1 - b1) * g
                d = (m / bc1) / (jnp.sqrt(q.reshape(g.shape) / bc2) + eps)
            else:
                v = b2 * vs[i] + (1 - b2) * g * g
                m = b1 * ms[i] + (1 - b1) * g
                d = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            new_m.append(m)
            new_v.append(v)
            out.append(p - lr * (d + o["weight_decay"] * p))
        return out, new_m, new_v

    def first_grad_numbers(self, grads: list):
        """``||G||_2`` of the first gradient, leaf by leaf."""
        return self._first(grads)

    @staticmethod
    def _pure_first(grads):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                          for g in grads])
