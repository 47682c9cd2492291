"""Megabytes of page arrays that the step programs received without
donation, per window step (``ScheduleEvent.undonated_page_bytes``, a program
counter): such a program copies each whole array into its output before it
writes a token. Moves tokens_per_s."""


def read(ctx):
    counts = [getattr(e, "undonated_page_bytes", None)
              for e in ctx.window_events]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts) / 1e6
