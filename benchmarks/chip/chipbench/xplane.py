"""Reduction of a profiler trace to device busy time, per-op and per-program
time, and idle gaps.

The traced window is the harness's own host span ``chipbench.traced_steps``,
opened and closed at engine-step boundaries (an ``on_token`` stamp), so the
steps inside it are known by count. On each device plane (``/device:TPU:n``)
the "XLA Ops" line holds every operation that ran, kernels included, named
by its HLO instruction, and the "XLA Modules" line every program execution. Busy time is the union of the op
intervals inside the window; the idle share is one minus busy over the
window. Each idle gap is labelled by the harness span it overlaps (or
"unattributed", the program's own host code) and by the program that ran
last before it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "chipbench.traced_steps"
HOST_SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")
# control-flow ops span the ops of their body: they count towards busy time
# but not towards any op's own time
_CONTAINERS = ("%while", "%conditional", "%call")


class TraceError(RuntimeError):
    """The trace lacks what the reduction needs."""


@dataclass
class Summary:
    window_s: float
    busy_s: float                     # mean over the chips used
    chips: int
    op_s: dict = field(default_factory=dict)        # op name -> seconds
    modules: list = field(default_factory=list)     # (program, seconds)
    gaps: list = field(default_factory=list)        # (label, seconds)

    def module_seconds(self, pattern: str) -> list[float]:
        return [s for name, s in self.modules if pattern in name]

    def op_seconds(self, prefixes) -> float:
        return sum(s for name, s in self.op_s.items()
                   if name.startswith(tuple(prefixes)))


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def program_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, w0: float, w1: float):
    a, b = max(a, w0), min(b, w1)
    return (a, b) if b > a else None


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def summarize(profile, top: int = 10) -> Summary:
    host_spans = []
    window = None
    devices = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append({ln.name: list(_events(ln)) for ln in plane.lines})
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name == WINDOW_SPAN:
                    window = (a, b)
                elif name.startswith(HOST_SPAN_PREFIX):
                    host_spans.append((a, b, name))
    if window is None:
        raise TraceError(f"no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise TraceError("no /device:TPU:n plane in the trace")
    w0, w1 = window
    busy, op_s, modules, gaps = [], {}, [], []
    for i, lines in enumerate(devices):
        if OPS_LINE not in lines:
            raise TraceError(f"device plane {i} has no {OPS_LINE!r} line; "
                             f"lines: {sorted(lines)}")
        ivs = []
        for name, a, b in lines[OPS_LINE]:
            c = _clip(a, b, w0, w1)
            if c is not None:
                ivs.append(c)
                if not name.startswith(_CONTAINERS):
                    op_s[name] = op_s.get(name, 0.0) + (c[1] - c[0]) * 1e-9
        merged = union(ivs)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        mods = [(program_name(n), a, b) for n, a, b in lines.get(MODULES_LINE, [])]
        for n, a, b in mods:
            if _clip(a, b, w0, w1) is not None:
                modules.append((n, (b - a) * 1e-9))
        if i == 0:
            gaps = _gaps(merged, (w0, w1), host_spans, mods, top)
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / len(busy), chips=len(devices),
                   op_s=op_s, modules=modules, gaps=gaps)


def _gaps(merged, window, host_spans, modules, top):
    """The ``top`` longest idle intervals of one chip, labelled."""
    w0, w1 = window
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    idle = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                   if b > a), reverse=True)[:top]
    out = []
    for _, a, b in idle:
        best, label = 0.0, "unattributed"
        for s0, s1, name in host_spans:
            ov = min(b, s1) - max(a, s0)
            if ov > best:
                best, label = ov, name
        before = [m for m in modules if m[2] <= a]
        after = max(before, key=lambda m: m[2])[0] if before else "window start"
        out.append((f"{label} after {after}", (b - a) * 1e-9))
    return out


def top_ops(summary: Summary, top: int = 10) -> list:
    return [[n, s] for n, s in
            sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]]
