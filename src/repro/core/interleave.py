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

The page arrays are updated in place: each step program takes ``k_pages``
and ``v_pages`` by donation, so XLA writes the step's new KV into the input
buffers instead of first copying the whole pool into a fresh output. One
program per step kind and shape is reached two ways:

* the engine hands the pool's arrays over in a :class:`Handover` cache. The
  program consumes them, and the handover holds the program's output pages
  the moment it is dispatched, so no holder keeps a donated array;
* any other caller passes a plain cache, whose page arrays are copied
  first and so survive the call (the copy every step paid before).

The block table and the fill levels are never donated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M

PAGE_KEYS = ("k_pages", "v_pages")


class Handover(dict):
    """A step cache whose page arrays the caller gives up to the program.

    The program takes them by donation; when it has been dispatched this
    dict holds its output pages in their place."""


def without_pages(cache: dict) -> dict:
    """``cache`` without its page arrays (a plain dict)."""
    return {k: v for k, v in cache.items() if k not in PAGE_KEYS}


def _pages(cache: dict) -> dict:
    """The page arrays a step program takes from ``cache``: a handover's
    own, else copies, so that the caller's survive the donation."""
    pages = {k: cache[k] for k in PAGE_KEYS if k in cache}
    if isinstance(cache, Handover):
        return pages
    return {k: jnp.copy(v) for k, v in pages.items()}


def _run(program, params: dict, cache: dict, *args):
    """``program`` over the page arrays of ``cache`` (the first cache it
    takes) and the rest of its arguments; a handover then holds the output
    pages in place of its own."""
    out = program(params, _pages(cache), without_pages(cache), *args)
    if isinstance(cache, Handover):
        cache.update({k: out[1][k] for k in PAGE_KEYS if k in out[1]})
    return out


_program = functools.partial(jax.jit, static_argnames=("cfg",),
                             donate_argnames=("pages",))


@_program
def _fused_step(params, pages, dec_cache, dec_tokens, pre_cache, pre_tokens,
                cfg):
    dec_logits, dec_cache = M.decode_step(params, {**dec_cache, **pages},
                                          dec_tokens, cfg)
    shared = "k_pages" in dec_cache and "block_table" in pre_cache
    if shared:
        pre_cache = dict(pre_cache, k_pages=dec_cache["k_pages"],
                         v_pages=dec_cache["v_pages"])
    pre_logits, pre_cache = M.decode_step(params, pre_cache, pre_tokens, cfg)
    if shared:
        dec_cache = dict(dec_cache, k_pages=pre_cache.pop("k_pages"),
                         v_pages=pre_cache.pop("v_pages"))
    return dec_logits, dec_cache, pre_logits, pre_cache


@_program
def _decode_only_step(params, pages, cache, tokens, cfg):
    return M.decode_step(params, {**cache, **pages}, tokens, cfg)


@_program
def _prefill_chunk_step(params, pages, cache, tokens, cfg):
    return M.decode_step(params, {**cache, **pages}, tokens, cfg)


def fused_step(
    params: dict,
    dec_cache: dict,
    dec_tokens: jax.Array,   # (Bd, 1)  decoding wave
    pre_cache: dict,
    pre_tokens: jax.Array,   # (Bp, C)  next wave's prefill chunk
    cfg: ModelConfig,
):
    """Returns (dec_logits, dec_cache', pre_logits, pre_cache').

    Over a paged pool both halves write one pair of page arrays, those of
    ``dec_cache`` (any in ``pre_cache`` are the same pool's and are left
    out): the chunk writes its fresh pages into the arrays the decode half
    returns (their pages are disjoint), ``dec_cache'`` carries the result,
    and ``pre_cache'`` only the stream's block table and fill level. No
    second copy of the pool exists."""
    return _run(_fused_step, params, dec_cache, dec_tokens,
                without_pages(pre_cache), pre_tokens, cfg)


def decode_only_step(params: dict, cache: dict, tokens: jax.Array,
                     cfg: ModelConfig):
    return _run(_decode_only_step, params, cache, tokens, cfg)


def prefill_chunk_step(params: dict, cache: dict, tokens: jax.Array,
                       cfg: ModelConfig):
    """Chunked prefill = multi-token decode step (cache-extending forward)."""
    return _run(_prefill_chunk_step, params, cache, tokens, cfg)
