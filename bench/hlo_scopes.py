"""Device time of a traced window, split by the program's own names.

The profiler writes every compiled module's ``HloProto`` into the
``/host:metadata`` plane of its ``.xplane.pb``, as the bytes of an event
metadata's stat named ``Hlo Proto``; the proto holds each instruction's
``op_name`` (the ``jax.named_scope`` path it was traced under) and its
frontend attributes (a Pallas kernel's ``kernel_metadata``).  This module
reads those with the standard library alone (the protobuf wire format; the
field numbers are listed below), so the benchmark process never imports
TensorFlow.

Each top-level instruction of the module the window ran goes to one
bucket:

* a kernel whose ``kernel_metadata`` names one of ``KERNELS`` goes to that
  kernel's bucket, before any scope is looked at;
* any other instruction goes to the one program scope found among the
  ``op_name`` path components of it and, for a fusion, of every
  instruction it fuses; none, or more than one, goes to ``unattributed``.
  The scopes are the cell's family's (``SCOPES`` of `bench.families`);
  the kernel names are one table here, shared by every family.

The op self times of the window (`bench.trace.Summary.op_s`) are summed by
bucket, so each op counts once and the buckets sum to the window's total.
A program that names none of these (no kernel name, no scope) yields
nothing.
"""
from __future__ import annotations

import collections
import glob
import json
import os

from bench import families, trace

KERNELS = {"balanced_spmm_wide": "balanced_spmm",
           "balanced_spmm_skinny": "balanced_spmm",
           "balanced_spmm_batched": "balanced_spmm",
           "kv_cache_update": "kv_update"}
UNATTRIBUTED = "unattributed"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"

# field numbers (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto)
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
XEVENTMETA_NAME, XEVENTMETA_STATS = 2, 5
XSTAT_METADATA_ID, XSTAT_BYTES = 1, 6
XSTATMETA_NAME = 2
HLOPROTO_MODULE = 1
MODULE_COMPUTATIONS = 3
COMP_INSTRUCTIONS, COMP_ID = 2, 5
INSTR_NAME, INSTR_OPCODE, INSTR_METADATA = 1, 2, 7
INSTR_CALLED, INSTR_FRONTEND = 38, 68
OPMETA_OP_NAME = 2


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message's top-level fields: an int
    for a varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def hlo_protos(data: bytes, names=None) -> dict[str, memoryview]:
    """``{module event name: HloProto bytes}`` of an ``.xplane.pb``'s
    metadata plane, limited to ``names`` where given.  The name is the one
    the device plane's ``XLA Modules`` line gives its events
    (``jit_step(<fingerprint>)``)."""
    out = {}
    for f, plane in fields(memoryview(data)):
        if f != XSPACE_PLANES:
            continue
        parts = list(fields(plane))
        if not any(k == XPLANE_NAME and _text(v) == METADATA_PLANE
                   for k, v in parts):
            continue
        stat_ids = set()
        for k, v in parts:
            if k == XPLANE_STAT_METADATA:
                entry = dict(fields(v))
                meta = dict(fields(entry.get(MAP_VALUE, b"")))
                if _text(meta.get(XSTATMETA_NAME, b"")) == HLO_STAT:
                    stat_ids.add(entry[MAP_KEY])
        for k, v in parts:
            if k != XPLANE_EVENT_METADATA:
                continue
            meta = list(fields(dict(fields(v)).get(MAP_VALUE, b"")))
            name = next((_text(x) for kk, x in meta
                         if kk == XEVENTMETA_NAME), "")
            if names is not None and name not in names:
                continue
            for kk, x in meta:
                stat = dict(fields(x)) if kk == XEVENTMETA_STATS else {}
                if stat.get(XSTAT_METADATA_ID) in stat_ids:
                    out[name] = stat[XSTAT_BYTES]
    return out


def _kernel_name(frontend) -> str | None:
    for f, entry in fields(frontend):
        kv = dict(fields(entry))
        if f == 1 and _text(kv.get(MAP_KEY, b"")) == "kernel_metadata":
            try:
                meta = json.loads(_text(kv.get(MAP_VALUE, b"")))
            except ValueError:
                return None
            return meta.get("kernel") if isinstance(meta, dict) else None
    return None


def attribute(hlo_proto, scopes) -> dict[str, str]:
    """``{instruction name: bucket}`` for the top-level instructions (those
    no fusion holds) of one ``HloProto``, with ``scopes`` the program
    scopes that get a bucket of their own."""
    module = dict(fields(memoryview(hlo_proto)))[HLOPROTO_MODULE]
    # computation id -> [(name, opcode, op_name, called ids, kernel name)]
    comps = {}
    for f, comp in fields(module):
        if f != MODULE_COMPUTATIONS:
            continue
        cid, instrs = None, []
        for k, v in fields(comp):
            if k == COMP_ID:
                cid = v
            elif k == COMP_INSTRUCTIONS:
                name = opcode = op_name = ""
                called, kernel = [], None
                for kk, x in fields(v):
                    if kk == INSTR_NAME:
                        name = _text(x)
                    elif kk == INSTR_OPCODE:
                        opcode = _text(x)
                    elif kk == INSTR_METADATA:
                        op_name = _text(dict(fields(x)).get(OPMETA_OP_NAME,
                                                            b""))
                    elif kk == INSTR_CALLED:
                        called += _ints(x)
                    elif kk == INSTR_FRONTEND:
                        kernel = _kernel_name(x)
                instrs.append((name, opcode, op_name, called, kernel))
        comps[cid] = instrs
    fused = {c for instrs in comps.values()
             for _, opcode, _, called, _ in instrs if opcode == "fusion"
             for c in called}

    def reach(instr):
        """The instruction and all it fuses, through nested fusions."""
        yield instr
        stack, seen = list(instr[3]) if instr[1] == "fusion" else [], set()
        while stack:
            c = stack.pop()
            if c not in seen:
                seen.add(c)
                for sub in comps.get(c, ()):
                    yield sub
                    stack += sub[3]

    out = {}
    for cid, instrs in comps.items():
        if cid in fused:
            continue
        for instr in instrs:
            body = list(reach(instr))
            kernels = {KERNELS[i[4]] for i in body if i[4] in KERNELS}
            found = kernels or {s for i in body for s in i[2].split("/")
                                if s in scopes}
            out[instr[0]] = found.pop() if len(found) == 1 else UNATTRIBUTED
    return out


def split(op_s: dict, attribution: dict, scopes) -> dict[str, float]:
    """Sum op self seconds (keyed by `bench.trace.short_name`) by bucket:
    the kernels', then ``scopes``, then ``unattributed``, which also takes
    an op the attribution does not hold."""
    out = dict.fromkeys([*KERNELS.values(), *scopes, UNATTRIBUTED], 0.0)
    for op, s in op_s.items():
        name = op.split(" = ", 1)[0].lstrip("%")
        out[attribution.get(name, UNATTRIBUTED)] += s
    return out


def window_modules(planes) -> collections.Counter:
    """Runs, by module name, that start inside ``bench.window``."""
    win = [e for e in trace.host_spans(planes)
           if e.name == trace.WINDOW_SPAN][0]
    return collections.Counter(
        e.name for p in trace.device_planes(planes)
        for e in p.lines.get(MODULES_LINE, ())
        if win.start_ns <= e.start_ns < win.end_ns)


def newest_xplane(path: str) -> str | None:
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def window_split(ctx) -> dict[str, float] | None:
    """Seconds of the traced window's device ops by bucket, with the scopes
    of the cell's family, attributed by the module the window ran most:
    the step (a cache rewind runs once a pass, and its ops go to
    ``unattributed`` unless a step instruction has the same name).  None
    where no module ran or that module names nothing."""
    from bench.run import TRACE_DIR
    if ctx.summary is None:
        return None
    path = newest_xplane(str(TRACE_DIR))
    if path is None:
        return None
    modules = window_modules(trace.load(path)).most_common(1)
    if not modules:
        return None
    with open(path, "rb") as f:
        protos = hlo_protos(f.read(), {modules[0][0]})
    if len(protos) != 1:
        return None
    scopes = families.load(ctx.spec["model"]["family"]).SCOPES
    attribution = attribute(next(iter(protos.values())), scopes)
    if set(attribution.values()) <= {UNATTRIBUTED}:
        return None
    return split(ctx.summary.op_s, attribution, scopes)


def step_ms(ctx, bucket: str) -> float | None:
    """Device ms per step of the window in ``bucket``."""
    if not hasattr(ctx, "_window_split"):
        ctx._window_split = window_split(ctx)
    steps = ctx.counters.get("steps")
    if ctx._window_split is None or not steps \
            or bucket not in ctx._window_split:
        return None
    return 1e3 * ctx._window_split[bucket] / steps
