"""Device idle milliseconds per traced engine step while the host was in
the NaN guard's pull of the logits or in sampling (innermost program span
``engine.guard`` or ``engine.sample``; profiler trace). Moves
tokens_per_s."""
from chipbench import spans


def read(ctx):
    return spans.idle_ms(ctx, "sync")
