"""Device milliseconds per execution of the fused LBIM step program (decode
and an admission chunk in one program; profiler trace). Moves
tpot_p95_ms."""
from chipbench import names


def read(ctx):
    if not any(st.kind == "fused" for st in ctx.steps):
        return None
    return names.program_ms(ctx.summary, "fused")
