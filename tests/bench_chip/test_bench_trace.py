"""The trace reduction on a synthesized trace: busy time as the union of
overlapping device ops, idle share, per-program and per-kernel time, and
the labelled idle gaps."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import names, xplane


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(device_ops, modules, host):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=device_ops)]),
    ])


WINDOW = ev(xplane.WINDOW_SPAN, 1000, 10_000)      # 1000 .. 11000 ns


def sample():
    attn = "%decode_attention_paged.9 = f32[4,4,8,128] custom-call(%b)"
    ops = [ev("fusion.1", 500, 1500),              # clipped to 1000..2000
           ev(attn, 1500, 1000),                   # overlaps: 1000..2500
           ev("%pim_gemv.69 = f32[4,4096] custom-call(%pad.38)", 4000, 2000),
           ev("%pim_gemv.70 = f32[4,11008] custom-call(%x)", 5000, 500),
           ev("%copy.3 = bf16[16,241] copy(%pim_gemv.69)", 9000, 4000),
           ev("%while.5 = (s32[], bf16[4,1,4096])", 1200, 1000)]  # container
    modules = [ev("jit_decode_only_step(7)", 1000, 1500),
               ev("jit_fused_step(9)", 4000, 2000),
               ev("jit_decode_only_step(7)", 9000, 2000)]
    host = [WINDOW, ev("chipbench.on_token", 6500, 1000)]
    return profile(ops, modules, host)


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    s = xplane.summarize(sample())
    assert s.window_s == pytest.approx(10_000e-9)
    # 1000..2500, 4000..6000, 9000..11000
    assert s.busy_s == pytest.approx(5500e-9)
    assert s.chips == 1


def test_op_and_program_time():
    s = xplane.summarize(sample())
    assert s.op_seconds(("%pim_gemv.",)) == pytest.approx(2500e-9)
    assert s.op_seconds(("fusion",)) == pytest.approx(1000e-9)
    # named among the operands of a copy: not the kernel's time
    assert s.op_seconds(("%copy.",)) == pytest.approx(2000e-9)
    assert not any(n.startswith("%while") for n in s.op_s)
    assert names.kernel_seconds(s, "pim_gemv") == pytest.approx(2500e-9)
    assert names.program_ms(s, "decode") == pytest.approx(1750e-6)
    assert names.program_ms(s, "fused") == pytest.approx(2000e-6)
    assert sorted(n[:7] for n, _ in xplane.top_ops(s, 2)) == ["%copy.3",
                                                               "%pim_ge"]


def test_idle_gaps_are_labelled_longest_first():
    s = xplane.summarize(sample())
    gaps = [(label, round(sec * 1e9)) for label, sec in s.gaps]
    assert gaps == [
        ("chipbench.on_token after jit_fused_step", 3000),
        ("unattributed after jit_decode_only_step", 1500)]


def test_idle_share_reader():
    from chipbench import harness

    read = harness.load_metric("device_idle_share")
    s = xplane.summarize(sample())
    assert read(NS(summary=s)) == pytest.approx(45.0)


def test_missing_names_fail_loudly():
    s = xplane.summarize(profile([ev("fusion", 1000, 10)], [], [WINDOW]))
    with pytest.raises(names.NameMissing):
        names.kernel_seconds(s, "decode_attention")
    with pytest.raises(names.NameMissing):
        names.program_ms(s, "decode")


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(xplane.TraceError):
        xplane.summarize(profile([], [], []))
    with pytest.raises(xplane.TraceError):
        xplane.summarize(NS(planes=[NS(name="/host:CPU", lines=[
            NS(name="python", events=[WINDOW])])]))
