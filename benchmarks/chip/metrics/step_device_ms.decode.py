"""Device milliseconds per execution of the decode-only step program
(profiler trace). Moves tpot_p95_ms."""
from chipbench import names


def read(ctx):
    if not any(st.kind == "decode" for st in ctx.steps):
        return None
    return names.program_ms(ctx.summary, "decode")
