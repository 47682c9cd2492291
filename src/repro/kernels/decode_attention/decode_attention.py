"""Fused decode attention with the CD-PIM K-col / V-row cache mapping.

The paper's §III-C maps the K-cache column-wise so the score GEMV runs as an
outer-product flow, and the V-cache row-wise so the output GEMV runs as an
inner-product flow — keeping every CU busy for both phases. On TPU the same
layouts make both phases of flash-decoding stream the cache contiguously:

* grid = (batch, kv_head, L_tiles); the L axis is the sequential (pipelined)
  grid dim — each step streams one K tile (hd, BL) and one V tile (BL, hd)
  HBM→VMEM while q (G, hd) and the online-softmax state (m, l, acc) stay
  resident in VMEM scratch — exactly the CU input/output buffer roles.
* scores tile:  q (G, hd) @ K (hd, BL)   — contracts the minor hd axis
  (outer-product flow over K columns);
* output tile:  p (G, BL) @ V (BL, hd)   — contracts L (inner-product flow
  over V rows).

Per-sequence attention ranges (continuous batching)
---------------------------------------------------
``start``/``end`` are per-sequence ``(B,)`` int32 arrays delivered by scalar
prefetch: each sequence attends to cache positions ``[start[b], end[b])``.
``start > 0`` expresses sliding-window layers over a full-length cache; the
plain causal decode uses ``start = 0, end = pos + 1``.

Dead-tile skip (the Pbank-disable analogue): tiles entirely outside the live
range never execute (``@pl.when``), and — because the K/V BlockSpec index
maps clamp the L-tile index into the live range — the pipeline re-addresses
the last live block for dead grid steps, so Pallas' block-revisiting
optimization issues **no new HBM copy** for them. Decode-step cache traffic
therefore scales with the actual fill level, not ``Lmax``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.ref import merge_splits

NEG_INF = -2.3819763e38
DEFAULT_BLOCK_L = 512


def _decode_attn_kernel(start_ref, end_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, block_l: int, n_l: int,
                        scale: float, softcap: float | None):
    i = pl.program_id(0)
    li = pl.program_id(2)
    start = start_ref[i]
    end = end_ref[i]

    @pl.when(li == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # live tiles only: [start, end) ∩ [li·BL, (li+1)·BL) ≠ ∅ (dead Pbanks dark)
    @pl.when((li * block_l < end) & ((li + 1) * block_l > start))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (hd, BL) column-wise
        v = v_ref[0, 0].astype(jnp.float32)           # (BL, hd) row-wise
        s = jax.lax.dot_general(                      # outer-product flow
            q, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        idx = li * block_l + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((idx >= start) & (idx < end), s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(                     # inner-product flow
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(li == n_l - 1)
    def _finalize():
        # empty range (end <= start, e.g. pos == 0) -> defined zero output
        l = l_ref[...]
        denom = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def _clamp_tile(l, start, end, bl):
    """Clamp the L-tile index into the live range so dead grid steps re-address
    the previous live block (same index ⇒ Pallas skips the HBM copy)."""
    first = start // bl
    last = jnp.maximum((end + bl - 1) // bl - 1, first)
    return jnp.clip(l, first, last)


@functools.partial(jax.jit, static_argnames=("block_l", "scale", "softcap", "interpret"))
def decode_attention(
    q: jax.Array,        # (B, Hkv, G, hd)
    k_cache: jax.Array,  # (B, Hkv, hd, Lmax) column-wise
    v_cache: jax.Array,  # (B, Hkv, Lmax, hd) row-wise
    pos: jax.Array,      # (B,) int32 — end of the live range (exclusive)
    start: jax.Array,    # (B,) int32 — start of the live range (inclusive)
    *,
    scale: float,
    softcap: float | None = None,
    block_l: int = DEFAULT_BLOCK_L,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, hd = q.shape
    lmax = k_cache.shape[-1]
    bl = min(block_l, lmax)
    if lmax % bl:
        raise ValueError(
            f"block_l={bl} must divide Lmax={lmax} (ops.decode_attention_op "
            f"pads the cache to the tile grid for you)")
    n_l = lmax // bl
    grid = (b, hkv, n_l)

    kernel = functools.partial(
        _decode_attn_kernel, block_l=bl, n_l=n_l, scale=scale, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # start/end arrive in SMEM ahead of the pipeline
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, l, sr, er: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, hd, bl),
                         lambda i, j, l, sr, er: (i, j, 0, _clamp_tile(l, sr[i], er[i], bl))),
            pl.BlockSpec((1, 1, bl, hd),
                         lambda i, j, l, sr, er: (i, j, _clamp_tile(l, sr[i], er[i], bl), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda i, j, l, sr, er: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),      # m: running max
            pltpu.VMEM((g,), jnp.float32),      # l: running denominator
            pltpu.VMEM((g, hd), jnp.float32),   # acc: output buffer
        ],
    )
    start_b = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    end_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        interpret=interpret,
    )(start_b, end_b, q, k_cache, v_cache)


def _paged_kernel(start_ref, end_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block: int, n_blocks: int,
                  scale: float, softcap: float | None):
    # the block table is consumed entirely by the BlockSpec index maps — the
    # body itself is layout-blind and identical to the contiguous kernel
    del table_ref
    _decode_attn_kernel(start_ref, end_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, block_l=block, n_l=n_blocks,
                        scale=scale, softcap=softcap)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def decode_attention_paged(
    q: jax.Array,            # (B, Hkv, G, hd)
    k_pages: jax.Array,      # (P, Hkv, hd, Bsz) column-wise pages
    v_pages: jax.Array,      # (P, Hkv, Bsz, hd) row-wise pages
    block_table: jax.Array,  # (B, NB) int32 — physical page per logical block
    pos: jax.Array,          # (B,) int32 — end of the live range (exclusive)
    start: jax.Array,        # (B,) int32 — start of the live range (inclusive)
    *,
    scale: float,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over BLOCK-PAGED KV: same online-softmax body as
    :func:`decode_attention`, but the L-tile grid dim walks each sequence's
    *logical* blocks and the K/V BlockSpec index maps indirect through the
    scalar-prefetched block table to the *physical* page — the software
    analogue of CD-PIM's bank remapping staying out of the CU datapath.
    Pages are shared across sequences read-only (prefix reuse); logical tile
    order is preserved, so the accumulation order — and the output bits —
    match the contiguous kernel exactly. Dead-tile clamping works unchanged:
    tiles outside ``[start, end)`` re-address the last live page and issue no
    new HBM copy.
    """
    b, hkv, g, hd = q.shape
    bsz = k_pages.shape[-1]
    nb = block_table.shape[1]
    grid = (b, hkv, nb)

    kernel = functools.partial(
        _paged_kernel, block=bsz, n_blocks=nb, scale=scale, softcap=softcap)

    def _page(l, sr, er, tr, i):
        return tr[i, _clamp_tile(l, sr[i], er[i], bsz)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # start / end / block table ahead of the pipeline
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, l, sr, er, tr: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, hd, bsz),
                         lambda i, j, l, sr, er, tr: (_page(l, sr, er, tr, i), j, 0, 0)),
            pl.BlockSpec((1, 1, bsz, hd),
                         lambda i, j, l, sr, er, tr: (_page(l, sr, er, tr, i), j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda i, j, l, sr, er, tr: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    start_b = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    end_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        interpret=interpret,
    )(start_b, end_b, jnp.asarray(block_table, jnp.int32), q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Two-stage split-KV flash decoding (long-context L-axis parallelism)
# ---------------------------------------------------------------------------
#
# The single-pass kernels stream one sequential tile pipeline per (b, head) —
# fine at short context, but at long fill the L axis is the whole budget and
# it serializes. CD-PIM's HBCEM answer is splitting each bank into four
# pseudo-banks so the same GEMV runs on segmented bitlines in parallel; the
# kernel-space analogue (the Bullet/SGLang NUM_KV_SPLITS decode shape) adds a
# KV-split grid axis: stage 1 runs an independent flash-softmax accumulation
# per split and emits *unnormalized* per-split partials (acc, m, l); stage 2
# is a tiny associative merge across splits (ref.merge_splits). A split whose
# block range lies outside ``[start, end)`` emits the identity partial
# (m = NEG_INF, l = 0, acc = 0) and — like the single-pass dead tiles — its
# index map re-addresses a live page, so cache traffic still scales with the
# fill level, not with ``num_splits × Lmax``.


def _split_kernel(start_ref, end_ref, table_ref, q_ref, k_ref, v_ref,
                  acc_out_ref, m_out_ref, l_out_ref,
                  m_ref, l_ref, acc_ref, *, block: int, bps: int,
                  n_blocks: int, scale: float, softcap: float | None):
    del table_ref  # consumed by the BlockSpec index maps
    i = pl.program_id(0)
    si = pl.program_id(2)
    j = pl.program_id(3)
    start = start_ref[i]
    end = end_ref[i]
    blk = si * bps + j            # global logical block index

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((blk < n_blocks) & (blk * block < end)
             & ((blk + 1) * block > start))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (hd, Bsz) column-wise
        v = v_ref[0, 0].astype(jnp.float32)           # (Bsz, hd) row-wise
        s = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        idx = blk * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((idx >= start) & (idx < end), s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == bps - 1)
    def _finalize():
        # UNNORMALIZED partials: stage 2 owns the division. Dead splits pass
        # their init state through — the merge identity.
        acc_out_ref[0, 0, 0] = acc_ref[...]
        m_out_ref[0, 0, 0, 0] = m_ref[...]
        l_out_ref[0, 0, 0, 0] = l_ref[...]


def _clamp_split(blk, start, end, bsz, s_lo, s_hi):
    """Clamp a split-local fetch into the split's live block sub-range; a
    fully dead split re-addresses the last globally-live block instead (one
    revisited fetch per dead split, never a fresh HBM copy per tile)."""
    gfirst = start // bsz
    glast = jnp.maximum((end + bsz - 1) // bsz - 1, gfirst)
    first = jnp.maximum(gfirst, s_lo)
    last = jnp.minimum(glast, s_hi - 1)
    return jnp.where(first <= last, jnp.clip(blk, first, last), glast)


@functools.partial(jax.jit, static_argnames=(
    "num_splits", "scale", "softcap", "interpret"))
def decode_attention_paged_split(
    q: jax.Array,            # (B, Hkv, G, hd)
    k_pages: jax.Array,      # (P, Hkv, hd, Bsz) column-wise pages
    v_pages: jax.Array,      # (P, Hkv, Bsz, hd) row-wise pages
    block_table: jax.Array,  # (B, NB) int32
    pos: jax.Array,          # (B,) int32 — end of the live range (exclusive)
    start: jax.Array,        # (B,) int32 — start of the live range (inclusive)
    *,
    num_splits: int,
    scale: float,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash decode with a two-stage split-KV reduction.

    Grid ``(B, Hkv, S, blocks_per_split)``: the split axis parallelizes the
    L walk, the inner axis streams each split's pages sequentially through
    the same online-softmax body as the single-pass kernel. Stage 1 writes
    per-split ``(acc, m, l)`` partials to HBM; stage 2 merges them with
    :func:`ref.merge_splits` (associative — identical result to one pass up
    to float reassociation; ``num_splits == 1`` callers should use
    :func:`decode_attention_paged`, which is bit-identical to the contiguous
    kernel).
    """
    b, hkv, g, hd = q.shape
    bsz = k_pages.shape[-1]
    nb = block_table.shape[1]
    bps = -(-nb // max(int(num_splits), 1))   # blocks per split (ceil)
    n_splits = -(-nb // bps)                  # realized splits (<= requested)
    grid = (b, hkv, n_splits, bps)

    kernel = functools.partial(
        _split_kernel, block=bsz, bps=bps, n_blocks=nb,
        scale=scale, softcap=softcap)

    def _page(blk, si, sr, er, tr, i):
        s_lo = si * bps
        s_hi = jnp.minimum((si + 1) * bps, nb)
        return tr[i, _clamp_split(blk, sr[i], er[i], bsz, s_lo, s_hi)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda i, j, si, jj, sr, er, tr: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, hd, bsz),
                         lambda i, j, si, jj, sr, er, tr:
                         (_page(si * bps + jj, si, sr, er, tr, i), j, 0, 0)),
            pl.BlockSpec((1, 1, bsz, hd),
                         lambda i, j, si, jj, sr, er, tr:
                         (_page(si * bps + jj, si, sr, er, tr, i), j, 0, 0)),
        ],
        # m / l carry a unit second-minor axis: a (.., n_splits, g) block of
        # one split would tile n_splits by 1, which Mosaic refuses
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, hd),
                         lambda i, j, si, jj, sr, er, tr: (i, j, si, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, g),
                         lambda i, j, si, jj, sr, er, tr: (i, j, si, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, g),
                         lambda i, j, si, jj, sr, er, tr: (i, j, si, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    start_b = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    end_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, n_splits, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, n_splits, 1, g), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, n_splits, 1, g), jnp.float32),
        ],
        interpret=interpret,
    )(start_b, end_b, jnp.asarray(block_table, jnp.int32), q, k_pages, v_pages)
    return merge_splits(acc, m[:, :, :, 0], l[:, :, :, 0])
