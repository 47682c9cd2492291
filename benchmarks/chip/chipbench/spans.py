"""Device idle time put down to what the host loop was doing.

The program wraps each phase of its step loop in a profiler span
(``repro.serve.trace``), which the trace holds on the same clock as the
device's "XLA Ops". The device's idle intervals inside the traced window are
built as :func:`xplane.summarize` builds them: the window less the union of
each chip's op intervals. Each idle nanosecond goes to the innermost
``engine.*`` span covering it on the host, and through that span to a group;
idle time under no span, or under the caller's ``on_token``, is ``outside``
the engine. The groups sum to the chips' mean idle time, so per traced step
they sum to ``device_idle_share`` times the window over the steps.

The span names are copied here, as ``names.py`` copies the kernels', so a
renamed span fails the run rather than reading as zero. A program without
the tracing module (``repro.serve.trace``) has no spans: every reading is
then None.
"""
from __future__ import annotations

import importlib.util

from . import harness, names, xplane

PREFIX = "engine."
DISPATCH = "engine.dispatch"
OUTSIDE = "outside"
GROUPS = {
    "engine.guard": "sync",
    "engine.sample": "sync",
    "engine.pool.commit": "pool",
    "engine.pool.merge": "pool",
    "engine.pool.residency": "pool",
    "engine.schedule": "loop",
    "engine.dispatch": "loop",
    "engine.emit": "loop",
    "engine.admit_batch": "loop",
    "engine.spec": "loop",
    "engine.on_token": OUTSIDE,
}

def load_profile():
    return xplane.load(str(harness.TRACE_DIR))


def innermost(spans) -> list[tuple[float, float, str]]:
    """Pieces ``(start, end, name)`` of the time covered by nested spans,
    each under the innermost span covering it."""
    out: list = []
    stack: list = []     # (end, name), innermost last
    t = 0.0

    def close() -> float:
        end, name = stack.pop()
        if end > t:
            out.append((t, end, name))
        return max(t, end)

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            t = close()
        if stack and a > t:
            out.append((t, a, stack[-1][1]))
        stack.append((b, name))
        t = a
    while stack:
        t = close()
    return out


def _idle(busy, w0: float, w1: float) -> list[tuple[float, float]]:
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def split(profile, steps: int) -> dict[str, float]:
    """Device idle milliseconds per traced step, by group."""
    window, spans, chips = None, [], []
    for plane in profile.planes:
        if xplane._DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in plane.lines if ln.name == xplane.OPS_LINE]
            chips.append([ev for ln in ops for ev in xplane._events(ln)])
            continue
        for line in plane.lines:
            for name, a, b in xplane._events(line):
                if name == xplane.WINDOW_SPAN:
                    window = (a, b)
                elif name.startswith(PREFIX):
                    if name not in GROUPS:
                        raise names.NameMissing(
                            f"span {name!r} is in no group of {sorted(GROUPS)}")
                    spans.append((a, b, name))
    if window is None or not chips:
        raise xplane.TraceError("no traced window or no device plane")
    w0, w1 = window
    if steps and not any(n == DISPATCH and a < w1 and b > w0
                         for a, b, n in spans):
        raise names.NameMissing(
            f"{steps} engine steps in the traced window but no {DISPATCH!r} "
            f"span; engine spans seen: {sorted({n for _, _, n in spans})}")
    pieces = innermost(spans)
    ns = dict.fromkeys(sorted(set(GROUPS.values())), 0.0)
    for ops in chips:
        busy = xplane.union(c for _, a, b in ops
                            if (c := xplane._clip(a, b, w0, w1)) is not None)
        j = 0
        for a, b in _idle(busy, w0, w1):
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            covered, k = 0.0, j
            while k < len(pieces) and pieces[k][0] < b:
                s0, s1, name = pieces[k]
                ov = min(b, s1) - max(a, s0)
                ns[GROUPS[name]] += ov
                covered += ov
                k += 1
            ns[OUTSIDE] += (b - a) - covered
    return {g: v * 1e-6 / len(chips) / steps for g, v in ns.items()}


def idle_ms(ctx, group: str) -> float | None:
    """``group``'s device idle milliseconds per traced step of this run."""
    if importlib.util.find_spec("repro.serve.trace") is None or not ctx.steps:
        return None
    if not hasattr(ctx, "idle_split"):   # the trace is read once per run
        ctx.idle_split = split(load_profile(), len(ctx.steps))
    return ctx.idle_split[group]
