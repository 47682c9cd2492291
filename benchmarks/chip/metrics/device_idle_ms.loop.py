"""Device idle milliseconds per traced engine step while the host was in the
rest of the engine's step loop: scheduling, dispatch, emitting tokens
(outside the caller's callback), batch admission and speculation
(innermost program span ``engine.schedule``, ``engine.dispatch``,
``engine.emit``, ``engine.admit_batch`` or ``engine.spec``; profiler
trace). Moves tokens_per_s."""
from chipbench import spans


def read(ctx):
    return spans.idle_ms(ctx, "loop")
