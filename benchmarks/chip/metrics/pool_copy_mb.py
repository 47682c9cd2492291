"""Megabytes written by the page pool's eager updates per window step
(``ScheduleEvent.pool_copy_bytes``, a program counter): an update outside a
jitted program writes its whole output array, so a merge of a few pages
writes both page arrays. Moves tpot_p95_ms."""


def read(ctx):
    counts = [getattr(e, "pool_copy_bytes", None) for e in ctx.window_events]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts) / 1e6
