"""Operations and bytes each unit of served work needs, at the cell's shapes.

What the algorithm needs, not what the program happens to move: the weights
a step must read once (int8 images and their scales for the single-token
W8A8 projections, bfloat16 for chunk and full prefill GEMMs, the bfloat16 LM
head), the KV a lane's attention must read at its real context, and the KV
the step writes. A copy of the page pool or a re-pad of a weight is not
work; it shows as device time the least time does not account for.

``least_seconds`` is the roofline bound of a unit: the larger of its
operations over the peak rate (bfloat16 FLOPs and int8 operations each at
their own peak) and its bytes over the peak bandwidth. At decode shapes the
bandwidth side binds.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

KV_ITEM = 2       # bfloat16 KV cache
W_ITEM = 2        # bfloat16 weights
SCALE_ITEM = 4    # float32 per-channel scales
OUT_ITEM = 4      # float32 kernel outputs

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of the chip; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


@dataclass
class Work:
    flops: float = 0.0     # bfloat16 MXU operations
    int_ops: float = 0.0   # int8 MXU operations
    bytes: float = 0.0     # HBM bytes

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.int_ops + o.int_ops,
                    self.bytes + o.bytes)


def least_seconds(w: Work, pk: dict) -> float:
    compute = (w.flops / pk["bf16_flops_per_s"]
               + w.int_ops / pk["int8_ops_per_s"])
    return max(compute, w.bytes / pk["hbm_bytes_per_s"])


def linear_shapes(dims) -> list[tuple[int, int]]:
    """(K, N) of one layer's projections: q, k, v, o, gate, up, down."""
    d, hd = dims.d, dims.head_dim
    return [(d, dims.heads * hd), (d, dims.kv_heads * hd),
            (d, dims.kv_heads * hd), (dims.heads * hd, d),
            (d, dims.f), (d, dims.f), (dims.f, d)]


def _kv_token_bytes(dims) -> int:
    """K and V of one token in one layer."""
    return 2 * dims.kv_heads * dims.head_dim * KV_ITEM


def pim_gemv(k: int, n: int, batch: int) -> Work:
    """One W8A8 GEMV call: int8 weight image and scales, int8 activations
    with their scales in, float32 out."""
    return Work(int_ops=2.0 * batch * n * k,
                bytes=float(n * k + n * SCALE_ITEM + batch * k
                            + batch * SCALE_ITEM + batch * n * OUT_ITEM))


def decode_attention(dims, ctxs) -> Work:
    """One paged decode-attention call (one layer) over the active lanes,
    each attending its real context (tokens, the new one included)."""
    b = len(ctxs)
    tok = sum(ctxs)
    q_out = b * dims.heads * dims.head_dim * (KV_ITEM + OUT_ITEM)
    return Work(flops=4.0 * dims.heads * dims.head_dim * tok,
                bytes=float(tok * _kv_token_bytes(dims) + q_out))


def lm_head(dims, rows: int) -> Work:
    return Work(flops=2.0 * rows * dims.d * dims.vocab,
                bytes=float(dims.d * dims.vocab * W_ITEM))


def decode_step(dims, ctxs) -> Work:
    """The decode half of a step: every layer's GEMVs and attention, the KV
    the active lanes append, and the LM head."""
    b = len(ctxs)
    if b == 0:
        return Work()
    per_layer = Work(bytes=float(b * _kv_token_bytes(dims)))
    for k, n in linear_shapes(dims):
        per_layer = per_layer + pim_gemv(k, n, b)
    per_layer = per_layer + decode_attention(dims, ctxs)
    total = Work(per_layer.flops * dims.layers, per_layer.int_ops * dims.layers,
                 per_layer.bytes * dims.layers)
    return total + lm_head(dims, b)


def _prefill_attention_flops(dims, start: int, n: int) -> float:
    """Causal attention of ``n`` new positions after ``start`` cached ones:
    position t attends start + t + 1 keys."""
    keys = n * start + n * (n + 1) // 2
    return 4.0 * dims.heads * dims.head_dim * keys


def chunk_prefill(dims, c: int, off: int, head_rows: int = 0) -> Work:
    """One admission chunk of ``c`` prompt tokens after ``off`` cached ones,
    on bfloat16 GEMMs; ``head_rows`` positions need logits (1 on a prompt's
    last chunk, whose last position seeds the first token)."""
    w_params = sum(k * n for k, n in linear_shapes(dims))
    per_layer = Work(
        flops=2.0 * c * w_params + _prefill_attention_flops(dims, off, c),
        bytes=float(w_params * W_ITEM
                    + (off + c) * _kv_token_bytes(dims)    # read, and
                    + c * _kv_token_bytes(dims)))          # write the chunk
    total = Work(per_layer.flops * dims.layers, 0.0,
                 per_layer.bytes * dims.layers)
    if head_rows:
        total = total + lm_head(dims, head_rows)
    return total


def full_prefill(dims, lens) -> Work:
    """One batched prefill of whole prompts (weights read once)."""
    w_params = sum(k * n for k, n in linear_shapes(dims))
    flops = sum(2.0 * n * w_params + _prefill_attention_flops(dims, 0, n)
                for n in lens)
    kv = sum(lens) * _kv_token_bytes(dims)
    total = Work(flops * dims.layers, 0.0,
                 (w_params * W_ITEM + kv) * dims.layers)
    return total + lm_head(dims, len(lens))
