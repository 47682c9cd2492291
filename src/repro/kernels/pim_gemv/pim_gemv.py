"""PIM-style pipelined INT8 GEMV — the CD-PIM compute unit on TPU.

CD-PIM's CU receives weight data serially from the four Pbanks' sense amps
and MACs it against an input vector resident in a 64 B input buffer,
accumulating INT32 partial sums in a 128 B output buffer. The TPU analogue:

* the weight matrix is tiled into ``(block_n, block_k)`` "Pbank" tiles that
  the Pallas pipeline streams HBM→VMEM (double-buffered — the serial weight
  feed at 2× clock);
* the activation block stays VMEM-resident (the input buffer);
* an int32 VMEM scratch accumulates partials across the K grid (the output
  buffer), with the dequant epilogue applied once on the last K step.

The kernel is memory-bound by construction at int8 (arithmetic intensity
≈ 2·B MAC/byte for batch B) — the "compute-efficient" criterion from the
paper translated to TPU: the MXU never limits the HBM stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _gemv_kernel(x_ref, w_ref, wscale_ref, xscale_ref, out_ref, acc_ref, *, n_k: int):
    """Grid (n_tiles, k_tiles); k is the fast (sequential, pipelined) axis."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 MAC block (the CU datapath). Integer MACs are
    # exact at any precision; pinning DEFAULT keeps an ambient
    # default_matmul_precision("highest") (the f32 reference recipe) from
    # asking Mosaic for an fp32 contraction of int8 operands, which it refuses.
    x = x_ref[...]  # (B, BK) int8
    w = w_ref[...]  # (BN, BK) int8
    acc_ref[...] += jax.lax.dot_general(
        x, w,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out_ref[...] = acc * xscale_ref[...] * wscale_ref[...]  # (B,1), (1,BN)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def pim_gemv(
    w: jax.Array,        # (N, K) int8 — weight-stationary in the "banks"
    x: jax.Array,        # (B, K) int8 — the input-buffer operand
    w_scale: jax.Array,  # (N,) f32 per-channel
    x_scale: jax.Array,  # (B,) f32 per-row
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    n, k = w.shape
    b = x.shape[0]
    bn = min(block_n, n)
    bk = min(block_k, k)
    if n % bn or k % bk:
        raise ValueError(f"N={n} K={k} must divide block sizes ({bn},{bk})")
    n_n, n_k = n // bn, k // bk

    grid = (n_n, n_k)
    return pl.pallas_call(
        functools.partial(_gemv_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, bk), lambda i, j: (0, j)),      # x: resident per k
            pl.BlockSpec((bn, bk), lambda i, j: (i, j)),     # w: streamed tiles
            # scales ride as 2-D rows/columns: Mosaic tiles a 1-D f32 operand
            # differently from XLA's layout for it and refuses the kernel
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),      # w_scale (1, N)
            pl.BlockSpec((b, 1), lambda i, j: (0, 0)),       # x_scale (B, 1)
        ],
        out_specs=pl.BlockSpec((b, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, bn), jnp.int32)],
        interpret=interpret,
    )(x, w, w_scale.reshape(1, n), x_scale.reshape(b, 1))
