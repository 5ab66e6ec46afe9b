"""Device time of the optimizer chain per train step, ms: the device time
in the traced window of every operation of the train step that the
program puts under its ``optimizer`` scope (``jax.named_scope`` in
``repro.train.steps``: gradient clipping, the optimizer's update and
``apply_updates``), over the traced steps.  None where no operation
carries the scope.

A TPU trace names each operation by its HLO instruction and carries no
scope in the operation's stats, so the scope is read from the HLO that
the profiler keeps with the trace: the ``Hlo Proto`` of each program on
the ``/host:metadata`` plane, whose instructions hold their ``op_name``.
The trace is the newest one under the benchmark's ``.work`` directory,
where the run has just written it.  The names are the program's own
because its compile cache keys include metadata (``repro.compile_cache``):
an executable loaded under a key without it would carry the names of
whichever program compiled that HLO first.

Leaf operations only: the event of a control-flow operation (``while``,
``conditional``, ``call``) encloses the events of its body, which are
counted themselves.
"""
import glob
import os
from pathlib import Path

SCOPE = "optimizer"
MODULE = "jit_train_step"          # the program the window runs
CONTROL = ("while", "conditional", "call")
WORK = Path(__file__).resolve().parents[1] / ".work"
METADATA_PLANE = b"/host:metadata"
HLO_STAT = b"Hlo Proto"


def in_scope(op_name: str) -> bool:
    """Whether ``SCOPE`` is one of the scope components of an op name."""
    return SCOPE in op_name.split("/")[:-1]


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message; a length-delimited
    value is a slice of ``buf``, a varint an int."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} not expected")
        yield key >> 3, value


def _one(buf, number):
    return next((v for n, v in _fields(buf) if n == number), None)


def hlo_modules(xspace: bytes) -> dict:
    """Serialized ``HloModuleProto`` of every program on the trace's
    metadata plane, by program name (``XSpace.planes`` -> ``XPlane``:
    ``event_metadata`` of ``XEventMetadata`` whose ``XStat`` named
    ``Hlo Proto`` holds an ``HloProto``, field 1 its module)."""
    buf = memoryview(xspace)
    for n, plane in _fields(buf):
        if n != 1 or bytes(_one(plane, 2) or b"") != METADATA_PLANE:
            continue
        stat_names = {}
        for m, entry in _fields(plane):
            if m == 5:
                meta = _one(entry, 2)
                stat_names[_one(meta, 1)] = bytes(_one(meta, 2) or b"")
        out = {}
        for m, entry in _fields(plane):
            if m != 4:
                continue
            meta = _one(entry, 2)
            name = bytes(_one(meta, 2) or b"").decode()
            for k, stat in _fields(meta):
                if k == 5 and stat_names.get(_one(stat, 1)) == HLO_STAT:
                    out[name] = bytes(_one(_one(stat, 6), 1))
        return out
    return {}


def scoped_instructions(module_proto: bytes) -> set:
    """Names of the instructions whose ``op_name`` lies in the scope
    (``HloModuleProto``: computations 3, their instructions 2, each with
    name 1 and ``OpMetadata`` 7, whose ``op_name`` is 2)."""
    out = set()
    for n, comp in _fields(memoryview(module_proto)):
        if n != 3:
            continue
        for m, instr in _fields(comp):
            meta = _one(instr, 7) if m == 2 else None
            op = _one(meta, 2) if meta is not None else None
            if op is not None and in_scope(bytes(op).decode()):
                out.add(bytes(_one(instr, 1)).decode())
    return out


def newest_trace():
    hits = glob.glob(str(WORK / "*" / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def scoped_names(path) -> set:
    """Instructions in the scope, over the trace's programs named
    ``MODULE``."""
    with open(path, "rb") as f:
        modules = hlo_modules(f.read())
    return set().union(*(scoped_instructions(proto)
                         for name, proto in modules.items()
                         if name.startswith(MODULE)))


def read(ctx):
    path = newest_trace()
    if path is None:
        return None
    scoped = scoped_names(path)
    seconds = sum(o.seconds for name, o in ctx["trace"].ops.items()
                  if name in scoped
                  and name.rsplit(".", 1)[0] not in CONTROL)
    if seconds <= 0:
        return None
    return 1e3 * seconds / ctx["steps_traced"]
