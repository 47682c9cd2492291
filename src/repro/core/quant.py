"""INT8 W8A8 quantization — the paper's precision regime (§III, no accuracy
loss claimed for 8-bit weights + activations).

Weights are quantized per output channel once (offline, weight-stationary in
the "banks"); activations per row at run time. The quantized linear either
dispatches to the Pallas ``pim_gemv`` kernel (TPU) or the exact jnp oracle
(CPU dry-run path).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.pim_gemv.ops import pim_gemv_int8
from repro.kernels.pim_gemv.ref import quantize_ref


class QuantizedLinear(NamedTuple):
    w_q: jax.Array      # (N, K) int8
    w_scale: jax.Array  # (N,) f32


class PreparedLinear(NamedTuple):
    """A weight leaf prepared at LOAD time for the serving decode path.

    Holds the float weight (the GEMM / prefill operand) alongside its
    weight-stationary int8 image and per-output-channel scales, so the decode
    hot loop feeds ``pim_gemv_int8`` directly instead of re-quantizing the
    float weights every step (the bandwidth bug the paper's weight-stationary
    banks exist to avoid). Built by :func:`prepare_decode_params`; consumed by
    ``core.dispatch.linear``. As a NamedTuple it is a pytree, so prepared
    leaves flow through ``lax.scan`` layer stacking and jit unchanged.
    """

    w: jax.Array        # (..., K, N) float — GEMM/prefill operand
    w_q: jax.Array      # (..., N, K) int8 — weight-stationary GEMV operand
    w_scale: jax.Array  # (..., N) f32 per-output-channel scales


# Leaves routed through the serving decode's dispatched linears
# (attention qkv/o + gated-MLP). MoE expert tables are (E, K, N) per layer —
# stacked 4-D — and RWKV reuses some of these names for leaves its decode
# consumes with raw matmuls; both are excluded by the ndim gate / family gate
# in `ServingModel.prepare`.
DECODE_LINEAR_SUFFIXES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_weight(w: jax.Array) -> QuantizedLinear:
    """w: (K, N) float (jnp layout) → weight-stationary (N, K) int8."""
    wq, ws = quantize_ref(w.T, axis=1)
    return QuantizedLinear(w_q=wq, w_scale=ws)


@jax.jit
def _quantize_leaf(w: jax.Array) -> QuantizedLinear:
    """One (K, N) or layer-stacked (nL, K, N) leaf, as ONE program: run
    eagerly, a stacked leaf materializes several float32 copies of itself,
    which at published widths outgrows the device memory left beside the
    weights."""
    return quantize_weight(w) if w.ndim == 2 else jax.vmap(quantize_weight)(w)


def quantize_params_tree(params, path_suffixes=DECODE_LINEAR_SUFFIXES,
                         exclude=None):
    """Quantize every matching weight leaf of a param tree to int8.

    Matches 2-D ``(K, N)`` leaves and layer-stacked 3-D ``(nL, K, N)`` leaves
    (the model zoo stacks layers for ``lax.scan``); stacked leaves quantize
    per layer per output channel via ``vmap`` — numerically identical to
    quantizing each layer's slice alone, which is what keeps the
    pre-quantized and on-the-fly decode paths token-identical. ``exclude``
    is an optional keystr predicate checked BEFORE any quantization work.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if exclude is not None and exclude(key):
            continue
        if leaf.ndim in (2, 3) and any(key.endswith(f"['{s}']") for s in path_suffixes):
            out[key] = _quantize_leaf(leaf)
    return out


def prepare_decode_params(params, path_suffixes=DECODE_LINEAR_SUFFIXES,
                          exclude=None):
    """Return ``params`` with every decode-linear leaf swapped for a
    :class:`PreparedLinear` (float weight + its load-time int8 image).

    The returned tree is structurally a superset of ``params``: unmatched
    leaves are shared (no copy), matched leaves carry the same float array
    plus the quantized pair, so the serving engine hands THIS tree to the
    decode/fused programs and keeps the plain float tree for full prefills.
    ``exclude`` (a keystr predicate) skips subtrees the caller knows never
    reach the dispatched decode linears — see ``ServingModel.prepare``.
    """
    qtree = quantize_params_tree(params, path_suffixes, exclude=exclude)

    def prep(path, leaf):
        ql = qtree.get(jax.tree_util.keystr(path))
        if ql is None:
            return leaf
        return PreparedLinear(w=leaf, w_q=ql.w_q, w_scale=ql.w_scale)

    return jax.tree_util.tree_map_with_path(prep, params)


def raw_weight(w) -> jax.Array:
    """Float view of a maybe-prepared weight leaf (GEMM/prefill operand)."""
    return w.w if isinstance(w, PreparedLinear) else w


def w8a8_linear(ql: QuantizedLinear, x: jax.Array, *, interpret: bool = False,
                use_kernel: bool = True) -> jax.Array:
    """x: (..., K) float → (..., N) f32 through the int8 CU datapath."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    x_q, x_s = quantize_ref(x2d, axis=1)
    y = pim_gemv_int8(ql.w_q, x_q, ql.w_scale, x_s,
                      interpret=interpret, use_kernel=use_kernel)
    return y.reshape(*shape[:-1], -1)


def quant_error(w: jax.Array, x: jax.Array) -> float:
    """Relative error of the W8A8 path vs fp32 matmul (accuracy audit)."""
    ql = quantize_weight(w)
    y_q = w8a8_linear(ql, x, use_kernel=False)
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return float(jnp.linalg.norm(y_q - y) / jnp.maximum(jnp.linalg.norm(y), 1e-9))
