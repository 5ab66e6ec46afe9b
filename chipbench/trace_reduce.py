"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: device busy time, time per device operation, and
the device's idle gaps, each labelled by what the host was doing.

Device operations are the events of the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane.  The window is the host-side
``chipbench.window`` annotation the drivers open right after the trace
starts and close right before it stops, so every number is taken over the
same interval the host clock measured.  Busy time is the union of the
operation intervals inside the window, averaged over the chips used.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from pathlib import Path

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


@dataclasses.dataclass
class OpTotal:
    seconds: float
    count: int
    desc: str          # event name plus its HLO / kernel name stats


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    ops: dict                       # op name -> OpTotal (all chips)
    gaps: list                      # (start_s, dur_s) in window time, chip 0
    gap_labels: list = dataclasses.field(default_factory=list)

    def kernel_seconds(self, needle: str) -> tuple:
        """(device seconds, event count) of every operation whose name or
        kernel name contains ``needle``, summed over chips."""
        hits = [o for o in self.ops.values() if needle in o.desc]
        return sum(o.seconds for o in hits), sum(o.count for o in hits)

    def label_gaps(self, spans: list) -> None:
        """``spans``: (name, start_s, end_s) in window time, host side.
        Each gap gets the innermost span that covers its midpoint."""
        labels = []
        for start, dur in self.gaps:
            mid = start + dur / 2
            best = None
            for name, s0, s1 in spans:
                if s0 <= mid <= s1 and (best is None
                                        or s1 - s0 < best[2] - best[1]):
                    best = (name, s0, s1)
            labels.append(best[0] if best else "no host span")
        self.gap_labels = labels

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1].seconds)[:TOP]
        idx = sorted(range(len(self.gaps)),
                     key=lambda i: -self.gaps[i][1])[:TOP]
        labels = self.gap_labels or ["unlabelled"] * len(self.gaps)
        return {"device_ops": [[name, o.seconds] for name, o in ops],
                "idle_gaps": [[labels[i], self.gaps[i][1]] for i in idx]}


def _union(intervals: list) -> tuple:
    """Total covered length and the uncovered gaps of sorted intervals."""
    covered, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            covered += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        covered += cur_e - cur_s
    return covered, gaps


def _desc(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if k in ("long_name", "hlo_op", "tf_op", "kernel_details",
                 "hlo_category", "name", "custom_call_target"):
            parts.append(str(v))
    return " | ".join(parts)


def reduce_planes(planes) -> Reduced:
    """``planes``: objects with ``name`` and ``lines``; each line has
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``,
    ``stats``), as ``jax.profiler.ProfileData`` gives them."""
    planes = list(planes)
    win = None
    for p in planes:
        if p.name.startswith("/host"):
            for line in p.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if win is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    w0, w1 = win
    devices = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: p.name)
    if not devices:
        raise ValueError(f"trace has no {DEVICE_PREFIX}* plane; planes: "
                         f"{[p.name for p in planes]}")
    ops: dict = {}
    busy_total, gaps0 = 0.0, None
    for p in devices:
        lines = [ln for ln in p.lines if ln.name == OPS_LINE]
        if not lines:
            raise ValueError(f"{p.name} has no {OPS_LINE!r} line; lines: "
                             f"{[ln.name for ln in p.lines]}")
        intervals = []
        for ev in lines[0].events:
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            # TPU events are named by their whole HLO instruction; the
            # breakdown keys them by the instruction's name alone
            name = ev.name.split(" = ", 1)[0].lstrip("%")
            o = ops.get(name)
            if o is None:
                o = ops[name] = OpTotal(0.0, 0, _desc(ev))
            o.seconds += (e - s) * 1e-9
            o.count += 1
        covered, gaps = _union(intervals + [(w0, w0), (w1, w1)])
        busy_total += covered * 1e-9
        if gaps0 is None:
            gaps0 = [((g0 - w0) * 1e-9, d * 1e-9) for g0, d in gaps]
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total / len(devices),
                   n_devices=len(devices), ops=ops, gaps=gaps0)


def open_window(trace_dir: Path):
    """Start the profiler and open the window annotation; returns the
    handle :func:`close_window` takes.  The Python tracer stays off: it
    would slow the host code the window measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    ann = jax.profiler.TraceAnnotation(WINDOW)
    ann.__enter__()
    return ann


def close_window(ann) -> None:
    import jax
    ann.__exit__(None, None, None)
    jax.profiler.stop_trace()


def find_xplane(trace_dir: Path) -> Path:
    hits = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(hits[-1])


def reduce_dir(trace_dir: Path) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(find_xplane(trace_dir)))
                         .planes)
