"""Device idle share (%): one minus the union of the device's operation
intervals over the traced window (profiler trace). Moves tokens_per_s."""


def read(ctx):
    s = ctx.summary
    return 100.0 * (1.0 - s.busy_s / s.window_s)
