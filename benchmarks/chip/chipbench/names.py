"""The names the device trace shows for the programs and kernels the
per-layer metrics read, in one place.

A program is matched by a substring of its XLA module name. A kernel is
matched by the start of its op's name in the "XLA Ops" line, which is the
HLO instruction (``%pim_gemv.69 = f32[4,4096] custom-call(...)``): a prefix,
because other ops name the kernel's result among their operands. A reader whose work ran inside the traced window
but whose name matches nothing fails loudly (:func:`kernel_seconds`), so a
renamed kernel cannot read as an idle one.
"""
from __future__ import annotations

PROGRAMS = {
    "decode": "decode_only_step",
    "fused": "fused_step",
    "prefill_chunk": "prefill_chunk_step",
}

KERNELS = {
    "decode_attention": ("%decode_attention_paged.",),
    "pim_gemv": ("%pim_gemv.",),
}


class NameMissing(LookupError):
    """Work ran in the traced window but no trace event carries its name."""


def kernel_seconds(summary, kernel: str) -> float:
    t = summary.op_seconds(KERNELS[kernel])
    if t <= 0:
        top = sorted(summary.op_s, key=lambda n: -summary.op_s[n])[:20]
        raise NameMissing(f"no op named like {KERNELS[kernel]} for {kernel}; "
                          f"the trace's longest ops: {top}")
    return t


def program_ms(summary, program: str) -> float:
    """Mean device milliseconds of one execution of ``program``."""
    times = summary.module_seconds(PROGRAMS[program])
    if not times:
        raise NameMissing(f"no module named like {PROGRAMS[program]!r}; the "
                          f"trace's modules: "
                          f"{sorted({n for n, _ in summary.modules})}")
    return 1e3 * sum(times) / len(times)
