"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* device-op intervals: the events of each TPU plane's ``XLA Ops`` line;
* busy time: the union of those intervals inside the measured window
  (the benchmark's own ``bench.window`` host span), averaged over chips;
* per-op device self time (an op's time less the ops nested inside it,
  as a ``while`` holds its body's ops), summed by the op's HLO
  instruction name, each op counted whole in the window it starts in;
* idle gaps: the holes in the union, each attributed to the innermost
  ``bench.*`` host span that covers its midpoint (``untraced host`` where
  none does).

Reading the file needs only ``jax.profiler.ProfileData``, which does not
touch an accelerator.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict            # line name -> [Event]


def planes_from_profile(pd) -> list[Plane]:
    out = []
    for p in pd.planes:
        lines = defaultdict(list)
        for ln in p.lines:
            for e in ln.events:
                lines[ln.name].append(Event(e.name, e.start_ns, e.end_ns))
        out.append(Plane(p.name, dict(lines)))
    return out


def load(path: str) -> list[Plane]:
    """Planes of one ``.xplane.pb`` file, or of the newest one under a
    profiler log directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return planes_from_profile(ProfileData.from_file(path))


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def host_spans(planes: list[Plane]) -> list[Event]:
    return [e for p in planes if not p.name.startswith(DEVICE_PREFIX)
            for evs in p.lines.values() for e in evs
            if e.name.startswith(SPAN_PREFIX)]


def device_planes(planes: list[Plane]) -> list[Plane]:
    return [p for p in planes if p.name.startswith(DEVICE_PREFIX)
            and OPS_LINE in p.lines]


_HLO = re.compile(r"^(%\S+) = ([a-z0-9]+\[[0-9,]*\])\S* ([a-z-]+)\(")


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8,64]{1,0} fusion(...)`` ->
    ``%fusion.3 = f32[8,64] fusion``; other names stay as they are."""
    m = _HLO.match(name)
    if m:
        return f"{m[1]} = {m[2]} {m[3]}"
    return name.split(" = ", 1)[0]


def self_times(events: list[Event]) -> list[float]:
    """Each event's duration less that of the events nested directly
    inside it on the same line (ns).  Events that only overlap are
    siblings."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start_ns, -events[i].end_ns))
    own = [e.end_ns - e.start_ns for e in events]
    stack: list[int] = []
    for i in order:
        e = events[i]
        while stack and not (e.end_ns <= events[stack[-1]].end_ns):
            stack.pop()
        if stack:
            own[stack[-1]] -= e.end_ns - e.start_ns
        stack.append(i)
    return own


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # mean over chips
    n_devices: int
    op_s: dict                        # short op name -> self seconds
    op_text: dict                     # short op name -> full HLO text
    idle_by_span: dict                # host span -> idle seconds (mean)

    def seconds_of(self, match) -> float:
        """Self seconds of the ops whose full HLO text ``match`` accepts."""
        return sum(s for k, s in self.op_s.items() if match(self.op_text[k]))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def summarize(planes: list[Plane]) -> Summary:
    spans = host_spans(planes)
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    devs = device_planes(planes)
    if not devs:
        raise ValueError("trace holds no TPU device plane with XLA ops")
    op_s: dict = defaultdict(float)
    op_text: dict = {}
    idle: dict = defaultdict(float)
    busy = 0.0
    inner = [e for e in spans if e.name != WINDOW_SPAN]
    for p in devs:
        evs = p.lines[OPS_LINE]
        ivs = _clip([(e.start_ns, e.end_ns) for e in evs], lo, hi)
        for e, own in zip(evs, self_times(evs)):
            if lo <= e.start_ns < hi:
                k = short_name(e.name)
                op_s[k] += own * 1e-9
                op_text.setdefault(k, e.name)
        merged = union(ivs)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            cover = [sp for sp in inner if sp.start_ns <= mid <= sp.end_ns]
            name = min(cover, key=lambda sp: sp.end_ns - sp.start_ns).name \
                if cover else "untraced host"
            idle[name] += (e - s) * 1e-9 / len(devs)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / len(devs),
                   n_devices=len(devs), op_s=dict(op_s), op_text=op_text,
                   idle_by_span=dict(idle))
