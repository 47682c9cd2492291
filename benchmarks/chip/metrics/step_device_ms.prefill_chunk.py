"""Device milliseconds per execution of the admission-chunk program that
runs on its own (HBCEM's split step, or a chunk with no decode lane;
profiler trace). Moves tpot_p95_ms."""
from chipbench import names


def read(ctx):
    if not any(st.kind in ("split", "chunk") for st in ctx.steps):
        return None
    return names.program_ms(ctx.summary, "prefill_chunk")
