"""W8A8 GEMV kernel's share of its roofline (%): the least time for the
int8 weight images, scales and activations of every single-token projection
the traced steps ran (seven per layer) over the kernel's summed device time
in the trace. Moves tokens_per_s."""
from chipbench import counts, names


def read(ctx):
    batches = [len(st.ctxs) for st in ctx.steps if st.ctxs]
    if not batches:
        return None
    shapes = counts.linear_shapes(ctx.dims)
    least = sum(ctx.dims.layers * counts.least_seconds(
        counts.pim_gemv(k, n, b), ctx.peaks) for b in batches for k, n in shapes)
    return 100.0 * least / names.kernel_seconds(ctx.summary, "pim_gemv")
