"""Device idle milliseconds per traced engine step while the host was in
the page pool's commit, merge or page-in (innermost program span
``engine.pool.*``; profiler trace). Moves tokens_per_s."""
from chipbench import spans


def read(ctx):
    return spans.idle_ms(ctx, "pool")
