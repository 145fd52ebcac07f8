"""Reduction from a JAX profiler trace to the numbers the per-layer metrics
read: device op intervals, their union (busy time) inside the traced
window, the summed device time of events by name, and the longest idle gaps
with what the host was doing in each.

The harness wraps the traced interval in a host annotation named
``WINDOW``; the window is that annotation's span on the profiler's clock,
which the device planes share. Device ops are the events of the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane; compiled programs are the events of
its ``XLA Modules`` line.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.trace_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_ANNOTATION = re.compile(r"^(serve/|train/|bench\.)")


@dataclass
class Trace:
    t0: float                                     # traced window, seconds
    t1: float
    ops: dict = field(default_factory=dict)       # device -> [(name, start, end)]
    modules: dict = field(default_factory=dict)   # device -> [(name, start, end)]
    host: list = field(default_factory=list)      # [(name, start, end)]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dst = (ops if line.name == OPS_LINE else modules).setdefault(
                    int(m.group(1)), [])
                dst.extend((e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9) for e in line.events)
            elif plane.name.startswith("/host:CPU"):
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events)
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if win:
        t0, t1 = win[0]
    else:  # a trace recorded without the harness: its whole extent
        every = [iv for evs in ops.values() for iv in evs] + host
        t0 = min(s for _, s, _ in every)
        t1 = max(e for _, _, e in every)
    return Trace(t0, t1, ops, modules, host)


def clip(events, t0: float, t1: float):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events if e > t0 and s < t1]


def union_s(events, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] covered by at least one event."""
    total, end = 0.0, t0
    for _, s, e in sorted(clip(events, t0, t1), key=lambda x: x[1]):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def busy_s(tr: Trace) -> float:
    """Device busy seconds in the window, averaged over the devices."""
    if not tr.ops:
        return 0.0
    return sum(union_s(evs, tr.t0, tr.t1) for evs in tr.ops.values()) / len(tr.ops)


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / tr.window_s


def matching(tr: Trace, pattern: str, *, modules: bool = False):
    """Events in the window (all devices) whose name matches ``pattern``."""
    rx = re.compile(pattern)
    src = tr.modules if modules else tr.ops
    return [ev for evs in src.values() for ev in clip(evs, tr.t0, tr.t1)
            if rx.search(ev[0])]


def time_s(events) -> float:
    return sum(e - s for _, s, e in events)


def op_label(name: str) -> str:
    """Short, stable label of an HLO op event: its instruction name without
    the leading ``%`` and the trailing ``.<n>`` (``%kernels.flare_packed.26
    = ...`` -> ``kernels.flare_packed``)."""
    m = re.match(r"%?([^\s=]+)", name)
    return re.sub(r"\.\d+$", "", m.group(1)) if m else name


def self_times(events):
    """[(name, self seconds)]: each event's duration less the time of the
    events nested inside it (a loop op holds its body's ops)."""
    out, stack = [], []  # stack of [name, start, end, child_time]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([n, s, e, 0.0])
    out.extend((t[0], t[2] - t[1] - t[3]) for t in stack)
    return out


def top_ops(tr: Trace, k: int = 10):
    """[[label, self seconds], ...] of the device ops that took most time,
    averaged over the devices."""
    agg: dict = {}
    for evs in tr.ops.values():
        for n, t in self_times(clip(evs, tr.t0, tr.t1)):
            agg[op_label(n)] = agg.get(op_label(n), 0.0) + t / len(tr.ops)
    return [[n, t] for n, t in sorted(agg.items(), key=lambda kv: -kv[1])[:k]]


def inside(events, outer):
    """The events whose start lies inside one of the ``outer`` intervals."""
    spans = sorted((s, e) for _, s, e in outer)
    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < spans[i][1]:
            out.append(ev)
    return out


def idle_gaps(tr: Trace, k: int = 10, device: int | None = None):
    """The ``k`` longest gaps between device ops in the window, each named
    by the innermost host annotation that covers the gap's middle (the
    program's ``serve/...`` and ``train/...``, the harness's ``bench....``):
    [[name, seconds], ...]."""
    if not tr.ops:
        return []
    dev = sorted(tr.ops)[0] if device is None else device
    evs = sorted(clip(tr.ops[dev], tr.t0, tr.t1), key=lambda x: x[1])
    gaps, end = [], tr.t0
    for _, s, e in evs:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if tr.t1 > end:
        gaps.append((end, tr.t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [h for h in tr.host if _ANNOTATION.match(h[0]) and h[0] != WINDOW]
    out = []
    for a, b in gaps[:k]:
        mid = 0.5 * (a + b)
        cover = [h for h in named if h[1] <= mid <= h[2]]
        name = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "host:unannotated"
        out.append([name, b - a])
    return out
