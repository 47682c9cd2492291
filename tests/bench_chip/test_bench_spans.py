"""The device's idle time put down to the program's engine spans, on a
synthesized trace: the innermost span takes each idle nanosecond, time in
no span (or in the caller's callback) is outside the engine, the four
readers sum to the idle time per step, and a window whose steps carry no
dispatch span fails loudly."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import harness, names, spans, xplane

WINDOW = NS(name=xplane.WINDOW_SPAN, start_ns=1000, duration_ns=10_000)
GROUPS = ("sync", "pool", "loop", "outside")


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def profile(host, chips=1):
    # busy 1000..2000, 4000..6000, 9000..11000: idle 2000..4000, 6000..9000
    ops = [ev("%fusion.1", 500, 2000), ev("%pim_gemv.3", 4000, 5000),
           ev("%copy.2", 4500, 6000), ev("%fusion.4", 9000, 12_000)]
    devices = [NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Ops", events=ops)])
               for i in range(chips)]
    return NS(planes=[NS(name="/host:CPU", lines=[
        NS(name="python", events=[WINDOW] + host)])] + devices)


def sample(chips=1):
    return profile([
        ev("engine.dispatch", 1000, 1500),
        ev("engine.guard", 1500, 2500),        # idle 2000..2500: sync 500
        ev("engine.emit", 2500, 3500),         # its own idle: loop 600
        ev("engine.on_token", 2800, 3200),     # inside emit: outside 400
        #                                        3500..4000 in no span: outside
        ev("engine.schedule", 6000, 8000),     # its own idle: loop 1000
        ev("engine.pool.merge", 6500, 7500),   # inside schedule: pool 1000
        ev("engine.dispatch", 8000, 8500),     # loop 500
        #                                        8500..9000 in no span: outside
        ev("chipbench.on_token", 8600, 8700),  # the harness's own: outside
    ], chips)


STEPS = 2
WANT_NS = {"sync": 500, "pool": 1000, "loop": 2100, "outside": 1400}


@pytest.mark.parametrize("chips", [1, 2])
def test_innermost_span_takes_the_idle_time(chips):
    got = spans.split(sample(chips), STEPS)
    assert got == pytest.approx({g: v * 1e-6 / STEPS for g, v in WANT_NS.items()})


def test_innermost_pieces_of_nested_spans():
    pieces = spans.innermost([(0, 10, "s"), (2, 5, "e"), (3, 4, "o"),
                              (12, 14, "d")])
    assert pieces == [(0, 2, "s"), (2, 3, "e"), (3, 4, "o"), (4, 5, "e"),
                      (5, 10, "s"), (12, 14, "d")]


def test_idle_in_no_span_is_outside():
    got = spans.split(profile([ev("engine.dispatch", 1000, 1500)]), 1)
    assert got["outside"] == pytest.approx(5000e-6)
    assert got["sync"] == got["pool"] == got["loop"] == 0


def read_all(monkeypatch, prof, steps):
    monkeypatch.setattr(spans, "load_profile", lambda: prof)
    ctx = NS(summary=xplane.summarize(prof), steps=[object()] * steps)
    return ctx, {g: harness.load_metric(f"device_idle_ms.{g}")(ctx)
                 for g in GROUPS}


def test_readers_sum_to_the_idle_time_per_step(monkeypatch):
    ctx, got = read_all(monkeypatch, sample(), STEPS)
    share = harness.load_metric("device_idle_share")(ctx)
    total = share / 100 * ctx.summary.window_s * 1e3 / STEPS
    assert sum(got.values()) == pytest.approx(total)
    assert got["pool"] == pytest.approx(1000e-6 / STEPS)


def test_steps_without_a_dispatch_span_fail_loudly(monkeypatch):
    prof = profile([ev("engine.guard", 1500, 2500)])
    with pytest.raises(names.NameMissing):
        read_all(monkeypatch, prof, STEPS)
    # no steps in the window: nothing to put the idle time down to
    assert set(read_all(monkeypatch, prof, 0)[1].values()) == {None}


def test_an_unknown_engine_span_fails_loudly():
    prof = profile([ev("engine.dispatch", 1000, 1500),
                    ev("engine.renamed", 2000, 3000)])
    with pytest.raises(names.NameMissing):
        spans.split(prof, 1)


def test_pool_copy_mb_reads_the_events_counters():
    read = harness.load_metric("pool_copy_mb")
    evs = [NS(pool_copy_bytes=b) for b in (16, 505_413_648, 16, 16)]
    assert read(NS(window_events=evs)) == pytest.approx(505_413_696 / 4 / 1e6)
    # a program whose events carry no counter reads nothing
    assert read(NS(window_events=[NS(decode_batch=4)])) is None
    assert read(NS(window_events=[])) is None
