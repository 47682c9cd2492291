"""Device idle milliseconds per traced engine step while the host was in no
engine span or in the caller's ``on_token`` callback (the harness, Python
and the runtime; profiler trace). Moves tokens_per_s."""
from chipbench import spans


def read(ctx):
    return spans.idle_ms(ctx, "outside")
