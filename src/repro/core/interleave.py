"""LBIM fused step — decode (memory-bound) + prefill chunk (compute-bound)
in ONE XLA program.

On CD-PIM the three DRAM commands let two Pbanks serve the processor's GEMM
reads while the other two feed the CUs' GEMVs. On TPU the analogous overlap
is intra-program: when the decode batch's GEMV-class ops and the prefill
chunk's GEMM-class ops live in one jitted computation, XLA's scheduler can
hide the HBM-bound cache streaming under MXU-bound prefill tiles. The engine
invokes this for every LBIM step; HBCEM/BLOCKED call the two halves as
separate programs (the serialization the paper measures against).

The decode half and the prefill half carry INDEPENDENT caches with their own
batch widths, so the same fused program serves both the historic wave
handoff and slot-level continuous batching: the decode half is the
persistent `slots`-lane pool, the prefill half is whatever pending request
is currently being chunk-loaded into a freed slot (typically batch 1). The
final chunk of a prompt may be shorter than the admission chunk — chunks are
never padded, so state-carrying families (ssm/hybrid) stream through the
same path without corruption. Over a fully paged pool the two halves differ
only in their block tables and fill levels: they write disjoint pages of one
pair of page arrays, so admission never forks the pool.

Both halves use the same weights — the "two Pbanks each" split is a
scheduling statement, not a weight copy.

The decode half's attention and (under ``cfg.quantized_decode``) its linear
projections route through ``repro.core.dispatch`` — the Pallas flash-decode
kernel / W8A8 PIM-GEMV on TPU, jnp oracles elsewhere — while the prefill
half's multi-token chunks keep the dense GEMM path: inside one fused XLA
program that is exactly the paper's GEMV-class/GEMM-class Pbank split.
"""
from __future__ import annotations

import functools

import jax

from repro.configs.base import ModelConfig
from repro.models import model as M


@functools.partial(jax.jit, static_argnames=("cfg",))
def fused_step(
    params: dict,
    dec_cache: dict,
    dec_tokens: jax.Array,   # (Bd, 1)  decoding wave
    pre_cache: dict,
    pre_tokens: jax.Array,   # (Bp, C)  next wave's prefill chunk
    cfg: ModelConfig,
):
    """Returns (dec_logits, dec_cache', pre_logits, pre_cache').

    Over a paged pool both halves write one pair of page arrays: the chunk
    writes its fresh pages into the arrays the decode half returns (their
    pages are disjoint), ``dec_cache'`` carries the result, and
    ``pre_cache'`` only the stream's block table and fill level. No second
    copy of the pool exists."""
    dec_logits, dec_cache = M.decode_step(params, dec_cache, dec_tokens, cfg)
    shared = "k_pages" in dec_cache and "block_table" in pre_cache
    if shared:
        pre_cache = dict(pre_cache, k_pages=dec_cache["k_pages"],
                         v_pages=dec_cache["v_pages"])
    pre_logits, pre_cache = M.decode_step(params, pre_cache, pre_tokens, cfg)
    if shared:
        dec_cache = dict(dec_cache, k_pages=pre_cache.pop("k_pages"),
                         v_pages=pre_cache.pop("v_pages"))
    return dec_logits, dec_cache, pre_logits, pre_cache


@functools.partial(jax.jit, static_argnames=("cfg",))
def decode_only_step(params: dict, cache: dict, tokens: jax.Array, cfg: ModelConfig):
    return M.decode_step(params, cache, tokens, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_chunk_step(params: dict, cache: dict, tokens: jax.Array, cfg: ModelConfig):
    """Chunked prefill = multi-token decode step (cache-extending forward)."""
    return M.decode_step(params, cache, tokens, cfg)
