"""Blocking device-to-host reads per window step made by the engine's NaN
guard and token selection, the drained admission stream's first token
included (``ScheduleEvent.result_pulls``, a program counter). Moves
tokens_per_s."""


def read(ctx):
    counts = [getattr(e, "result_pulls", None) for e in ctx.window_events]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
