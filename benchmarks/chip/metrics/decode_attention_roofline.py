"""Paged decode-attention kernel's share of its roofline (%): the least time
for the bytes and operations the traced steps' decode attention needs at
every active lane's real context, one call per layer, over the kernel's
summed device time in the trace. Moves tokens_per_s."""
from chipbench import counts, names


def read(ctx):
    calls = [st.ctxs for st in ctx.steps if st.ctxs]
    if not calls:
        return None
    least = sum(ctx.dims.layers * counts.least_seconds(
        counts.decode_attention(ctx.dims, c), ctx.peaks) for c in calls)
    return 100.0 * least / names.kernel_seconds(ctx.summary, "decode_attention")
