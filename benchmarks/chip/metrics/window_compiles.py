"""Backend compiles inside the measured window (JAX monitoring events);
warm-up should leave none. Moves tpot_p95_ms."""


def read(ctx):
    return ctx.window_compiles
