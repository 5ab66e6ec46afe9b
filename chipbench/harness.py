"""What every driver and reader of the benchmark shares: lookup by name,
the device guard, the compile cache, the result and check records."""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
from pathlib import Path
from typing import Any, Optional

GIB = 2 ** 30


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SystemExit(f"chipbench: no file {path}")
    return json.loads(path.read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r}; known: "
                     f"{[e['name'] for e in entries]}")


def end_to_end_for(bench: dict, workload: str) -> list:
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_for(bench: dict, workload: str) -> list:
    """A per-layer metric with ``workloads`` is read in those cells; one
    without is read in every cell that reports the metric it moves."""
    e2e = {m["name"] for m in end_to_end_for(bench, workload)}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in e2e
                                 else [])]


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(device: dict, chips: int) -> None:
    """No accelerator, or fewer chips than the cell asks for: fail, print
    no result.  A number taken on another backend is never reported."""
    if device["platform"] != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX sees {device}); "
                         f"refusing to measure on another backend")
    if device["count"] < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {device['count']}")


def one_chip(chips: int) -> None:
    """A driver that does not shard refuses a cell that asks for more
    than one chip: it would run on one device and report that chip's
    numbers under the cell's name."""
    if chips != 1:
        raise SystemExit(f"chipbench: the cell asks for {chips} chips; this "
                         f"driver runs on one and does not shard")


def enable_cache(jax, root: Path) -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or a fixed directory in the checkout), keeping every program: the
    small probe and reference programs too, so a second run in the same
    checkout compiles nothing."""
    import os
    import sys
    sys.path.insert(0, str(root / "src"))
    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return where


def peaks_for(table: dict, kind: str) -> dict:
    """The chip's published peaks by ``device_kind``; an unknown chip is
    an error, never a default."""
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[kind]


def workdir(base: Path, workload: str) -> Path:
    """A fixed, emptied scratch directory in the checkout for the run's
    trace (``chipbench/.work``, listed in .gitignore)."""
    d = base / ".work" / workload
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    return d


def prng_key(jax, seed: int):
    """A key from any whole seed, the bits above 32 folded in (a plain
    ``PRNGKey`` keeps only the low 32 bits of a large seed)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def quantile(xs, q: float) -> float:
    """Linear-interpolated quantile over all samples (numpy's default)."""
    s = sorted(xs)
    if not s:
        return math.nan
    pos = (len(s) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    if pos == lo or s[hi] == s[lo]:     # so that misses (inf) stay inf
        return s[lo]
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


class ListSink:
    """In-memory event sink for the program's tracer and engine (read
    after the run)."""

    def __init__(self, events: Optional[list] = None):
        self.events = [] if events is None else events

    def emit(self, event):
        self.events.append(event)

    def flush(self):
        pass


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit: the run
    is correct only if every check holds (value <= limit, or >= for a
    count that has to be reached)."""
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if not math.isfinite(self.value):
            return False
        return (self.value >= self.limit if self.at_least
                else self.value <= self.limit)


@dataclasses.dataclass
class RunResult:
    end_to_end: dict
    checks: list
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: Any = None            # trace_reduce.Reduced, with --trace 1
    context: Optional[dict] = None   # what metric readers read

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(c.ok for c in self.checks)


def memory_peak_bytes(jax) -> int:
    """Peak bytes in use on the fullest chip of this process."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def finite_or_none(x):
    """The result with every non-finite number (a missed request's
    latency, a reading that could not be taken) as null: JSON has no
    infinity."""
    if isinstance(x, dict):
        return {k: finite_or_none(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite_or_none(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x
