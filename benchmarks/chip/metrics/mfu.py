"""Whole-step share of the chip's peak (%): the traced steps' least time,
each step the larger of its operations over the peak rates and its bytes
over the peak bandwidth (counts of what the algorithm needs at the lanes'
real contexts), over the traced window. At decode shapes the bandwidth side
binds. Moves tokens_per_s, and bounds every kernel roofline below it."""
from chipbench import counts


def read(ctx):
    if not ctx.steps:
        return None
    least = sum(counts.least_seconds(st.work(ctx.dims), ctx.peaks)
                for st in ctx.steps)
    return 100.0 * least / ctx.summary.window_s
