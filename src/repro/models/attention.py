"""Attention: GQA + RoPE + softcap + sliding-window, train/prefill/decode.

Train/prefill use a query-chunked formulation (lax.scan over query blocks) so
the (S, S) score matrix never materializes — per chunk it is (B, H, C, S),
which keeps the dry-run memory analysis inside HBM at 32k context. The
optional *causal block skip* (beyond-paper optimization, see EXPERIMENTS.md
§Perf) computes only the non-masked KV prefix per chunk.

Decode consumes the CD-PIM dual-layout cache from ``repro.core.kv_mapping``:
K column-wise (outer-product score flow), V row-wise (inner-product output
flow) — the paper's §III-C mapping. Single-token decode steps route through
``repro.core.dispatch`` (Pallas flash-decode kernel on TPU, jnp oracle on
CPU, legacy dense einsum with ``attn_backend="dense"``); the dispatched path
takes per-sequence ``[start, end)`` attention ranges, so sliding-window and
ring-buffer layers hit the same kernel. With ``cfg.quantized_decode`` the
decode-time qkv/o projections run as W8A8 PIM GEMVs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import dispatch, kv_mapping
from repro.core.quant import raw_weight
from repro.models.layers import apply_rope, dense_init, softcap

NEG_INF = -2.3819763e38  # bf16-safe large negative


def init_attention(key, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": dense_init(k1, (d, cfg.n_heads * hd), dtype),
        "wk": dense_init(k2, (d, cfg.n_kv_heads * hd), dtype),
        "wv": dense_init(k3, (d, cfg.n_kv_heads * hd), dtype),
        "wo": dense_init(k4, (cfg.n_heads * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _project_qkv(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
                 linear_fn=None):
    """x (B,T,d) -> q (B,Hq,T,hd), k/v (B,Hkv,T,hd), RoPE applied.

    ``linear_fn`` overrides the matmul (decode injects the dispatched,
    possibly W8A8-quantized, GEMV from ``core.dispatch``)."""
    b, t, _ = x.shape
    hd = cfg.head_dim
    mm = linear_fn or _dense_matmul
    q = mm(p["wq"], x)
    k = mm(p["wk"], x)
    v = mm(p["wv"], x)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, t, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = apply_rope(q, positions[:, None, :], cfg.rope_theta)
    k = apply_rope(k, positions[:, None, :], cfg.rope_theta)
    return q, k, v


def _scale(cfg: ModelConfig) -> float:
    if cfg.attn_scale_override is not None:
        return cfg.attn_scale_override
    return cfg.head_dim ** -0.5


def _dense_matmul(w, x: jax.Array) -> jax.Array:
    # raw_weight: multi-token (GEMM-shaped) ops on a ServingModel's prepared
    # tree take the float operand — int8 buys nothing at MXU-bound shapes
    return x @ raw_weight(w)


def _decode_linear(cfg: ModelConfig):
    """Decode-time matmul: W8A8 PIM GEMV at quantized GEMV shapes, else dense."""
    if cfg.quantized_decode:
        return lambda w, xx: dispatch.linear(w, xx, cfg)
    return _dense_matmul


def _mask_bias(q_pos, k_pos, window: Optional[int], causal: bool) -> jax.Array:
    """(..., Tq, Tk) additive bias from causal + sliding-window constraints."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        ok = ok & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        ok = ok & (k_pos[None, :] > q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF)


def attention_dense(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    window: Optional[int] = None,
    causal: bool = True,
    positions: Optional[jax.Array] = None,
    return_kv: bool = False,
):
    """Full-sequence attention, query-chunked. Returns y [, (k, v)]."""
    b, t, d = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    q, k, v = _project_qkv(p, x, cfg, positions)
    g = cfg.q_per_kv
    qg = q.reshape(b, cfg.n_kv_heads, g, t, cfg.head_dim)
    scale = _scale(cfg)

    cq = min(cfg.q_chunk, t)
    n_chunks = -(-t // cq)   # a ragged tail is one shorter last chunk

    k_pos_full = jnp.arange(t)

    def chunk(i, skip: bool):
        n = min(cq, t - i * cq)
        qs = jax.lax.dynamic_slice_in_dim(qg, i * cq, n, axis=3)
        q_pos = i * cq + jnp.arange(n)
        if skip and causal:
            # beyond-paper: only the visible KV prefix for this chunk
            klen = i * cq + n
            ks = k[:, :, :klen, :]
            vs = v[:, :, :klen, :]
            k_pos = k_pos_full[:klen]
        else:
            ks, vs, k_pos = k, v, k_pos_full
        s = jnp.einsum("bkgtd,bksd->bkgts", qs, ks).astype(jnp.float32) * scale
        s = softcap(s, cfg.attn_softcap)
        s = s + _mask_bias(q_pos, k_pos, window, causal)
        pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bkgts,bksd->bkgtd", pr, vs)

    if n_chunks == 1:
        y = chunk(0, skip=False)
    elif cfg.causal_block_skip and causal:
        # static python loop: each chunk sees a different (static) KV length
        y = jnp.concatenate([chunk(i, skip=True) for i in range(n_chunks)], axis=3)
    else:
        # python loop (not lax.map): chunk counts are small, and unrolling
        # keeps HloCostAnalysis exact (loop bodies are counted once by XLA)
        y = jnp.concatenate([chunk(i, skip=False) for i in range(n_chunks)], axis=3)

    y = y.reshape(b, cfg.n_heads, t, cfg.head_dim).transpose(0, 2, 1, 3).reshape(b, t, -1)
    out = y @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def attention_cross(
    p: dict,
    x: jax.Array,
    memory_kv: tuple[jax.Array, jax.Array],
    cfg: ModelConfig,
) -> jax.Array:
    """Cross-attention against fixed encoder memory K/V (B, Hkv, S, hd)."""
    b, t, d = x.shape
    hd = cfg.head_dim
    q = (x @ raw_weight(p["wq"]))
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(b, t, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k, v = memory_kv
    g = cfg.q_per_kv
    qg = q.reshape(b, cfg.n_kv_heads, g, t, hd)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, k).astype(jnp.float32) * _scale(cfg)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    y = jnp.einsum("bkgts,bksd->bkgtd", pr, v)
    y = y.reshape(b, cfg.n_heads, t, hd).transpose(0, 2, 1, 3).reshape(b, t, -1)
    return y @ raw_weight(p["wo"])


def project_memory_kv(p: dict, mem: jax.Array, cfg: ModelConfig):
    """Encoder memory -> cross-attention K/V (computed once per request)."""
    b, s, _ = mem.shape
    hd = cfg.head_dim
    k = mem @ p["wk"]
    v = mem @ p["wv"]
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    return k, v


def attention_decode_ring(
    p: dict,
    x: jax.Array,   # (B, 1, d) — single new token
    k_ring: jax.Array,  # (B, Hkv, hd, W) col-wise ring buffer
    v_ring: jax.Array,  # (B, Hkv, W, hd) row-wise ring buffer
    pos: jax.Array,     # scalar int32 absolute position
    cfg: ModelConfig,
):
    """Sliding-window decode against a RING KV cache of exactly W slots.

    Beyond-paper optimization: a local (windowed) layer never attends past
    ``W = sliding_window`` tokens, so its cache needs W slots, not Lmax.
    Slot ``pos % W`` is overwritten each step; after the write, the ring
    holds exactly tokens (pos-W, pos], so the window mask degenerates to a
    fill check (softmax is permutation-invariant — slot order is irrelevant).
    RoPE uses absolute positions, so stored K vectors stay valid.
    """
    b, t, d = x.shape
    assert t == 1, "ring cache is a steady-state decode structure"
    w = k_ring.shape[-1]
    hd = cfg.head_dim
    pos_b = jnp.broadcast_to(jnp.asarray(pos).reshape(-1), (b,))
    positions = pos_b[:, None]
    lin = _decode_linear(cfg)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, linear_fn=lin)
    rpos = jnp.asarray(pos) % w
    k_ring, v_ring = kv_mapping.append_layer(k_ring, v_ring, k_new, v_new, rpos, "cdpim")

    if dispatch.use_dispatch(cfg):
        # after the append the ring's VALID slots are exactly the prefix
        # [0, min(pos+1, W)) — softmax is permutation-invariant, so the same
        # prefix-range kernel serves the ring layout (see module docstring).
        end = jnp.minimum(pos_b + 1, w).astype(jnp.int32)
        o = dispatch.decode_attention(
            q[:, :, 0, :], k_ring, v_ring, end,
            scale=_scale(cfg), softcap=cfg.attn_softcap, cfg=cfg)
        y = o.astype(x.dtype).reshape(b, 1, cfg.n_heads * hd)
        return lin(p["wo"], y), k_ring, v_ring

    g = cfg.q_per_kv
    qg = q.reshape(b, cfg.n_kv_heads, g, t, hd)
    s = kv_mapping.read_scores(qg, k_ring, "cdpim").astype(jnp.float32) * _scale(cfg)
    s = softcap(s, cfg.attn_softcap)
    # slot s holds token pos - ((rpos - s) mod W); valid iff that token >= 0
    slots = jnp.arange(w)
    offset = jnp.mod(rpos - slots, w)
    token_at = jnp.asarray(pos) - offset
    s = s + jnp.where(token_at >= 0, 0.0, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    y = kv_mapping.read_output(pr, v_ring, "cdpim")
    y = y.reshape(b, cfg.n_heads, t, hd).transpose(0, 2, 1, 3).reshape(b, t, -1)
    return lin(p["wo"], y), k_ring, v_ring


def attention_decode(
    p: dict,
    x: jax.Array,  # (B, T, d) — T new tokens (usually 1)
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,  # scalar int32: current cache fill
    cfg: ModelConfig,
    *,
    window=None,  # None | int | traced scalar (per-layer dynamic width)
    layout: kv_mapping.Layout = "cdpim",
):
    """One decode step against the CD-PIM dual-layout cache.

    ``pos`` may be a scalar (all sequences aligned) or (B,) for continuous
    batching with per-sequence fill levels. Returns (y, k_cache', v_cache').
    Score flow contracts hd against the column-wise K cache; output flow
    contracts L against the row-wise V cache.

    Single-token steps (T == 1) on the cdpim layout go through the backend
    dispatch (``core.dispatch``): the Pallas flash-decode kernel on TPU, the
    jnp oracle elsewhere, with per-sequence live range ``[end-window, end)``
    so work scales with actual fill, not Lmax. Multi-token steps (chunked
    prefill) and the ablation layouts keep the dense einsum.
    """
    b, t, d = x.shape
    hd = cfg.head_dim
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,)) if jnp.ndim(pos) <= 1 else pos
    positions = pos_b[:, None] + jnp.arange(t)[None, :]  # (B, T)
    lin = _decode_linear(cfg) if t == 1 else None
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, linear_fn=lin)

    k_cache, v_cache = kv_mapping.append_layer(k_cache, v_cache, k_new, v_new, pos, layout)

    if t == 1 and layout == "cdpim" and dispatch.use_dispatch(cfg):
        end = (pos_b + 1).astype(jnp.int32)  # the just-appended token is visible
        start = None if window is None else jnp.maximum(end - window, 0).astype(jnp.int32)
        o = dispatch.decode_attention(
            q[:, :, 0, :], k_cache, v_cache, end, start=start,
            scale=_scale(cfg), softcap=cfg.attn_softcap, cfg=cfg)
        y = o.astype(x.dtype).reshape(b, 1, cfg.n_heads * hd)
        return lin(p["wo"], y), k_cache, v_cache

    lmax = k_cache.shape[-1] if layout in ("cdpim", "col_col") else k_cache.shape[-2]
    g = cfg.q_per_kv
    qg = q.reshape(b, cfg.n_kv_heads, g, t, hd)

    s = kv_mapping.read_scores(qg, k_cache, layout).astype(jnp.float32) * _scale(cfg)
    s = softcap(s, cfg.attn_softcap)

    k_pos = jnp.arange(lmax)
    q_pos = positions  # (B, T)
    valid = k_pos[None, None, :] <= q_pos[:, :, None]       # (B, T, L)
    if window is not None:
        valid = valid & (k_pos[None, None, :] > q_pos[:, :, None] - window)
    s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :, :]

    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    y = kv_mapping.read_output(pr, v_cache, layout)
    y = y.reshape(b, cfg.n_heads, t, hd).transpose(0, 2, 1, 3).reshape(b, t, -1)
    proj = lin or _dense_matmul
    return proj(p["wo"], y), k_cache, v_cache


def attention_decode_paged(
    p: dict,
    x: jax.Array,            # (B, T, d) — T new tokens (usually 1)
    k_pages: jax.Array,      # (P, Hkv, hd, Bsz) column-wise pages (one layer)
    v_pages: jax.Array,      # (P, Hkv, Bsz, hd) row-wise pages (one layer)
    block_table: jax.Array,  # (B, NB) int32 — physical page per logical block
    pos: jax.Array,          # (B,) int32: per-lane cache fill
    cfg: ModelConfig,
    *,
    window=None,
):
    """One decode step against BLOCK-PAGED dual-layout KV — the fully paged
    sibling of :func:`attention_decode`, bit-identical to it per token.

    The new token's K/V is scattered into its page **in place**
    (:func:`kv_mapping.append_layer_paged`) — lanes never materialize
    contiguously. Single-token steps stream pages through the dispatched
    paged kernel (split-KV when ``cfg.decode_kv_splits > 1``); multi-token
    chunk-prefill steps (and the ``dense`` backend) gather the lanes in-XLA
    and run the exact dense masked einsum of the contiguous path, so garbage
    beyond each fill level is masked identically and the bits match.
    """
    b, t, d = x.shape
    hd = cfg.head_dim
    block = k_pages.shape[-1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,)).astype(jnp.int32)
    positions = pos_b[:, None] + jnp.arange(t)[None, :]  # (B, T)
    lin = _decode_linear(cfg) if t == 1 else None
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, linear_fn=lin)

    k_pages, v_pages = kv_mapping.append_layer_paged(
        k_pages, v_pages, k_new, v_new, pos_b, block_table, block)

    if t == 1 and dispatch.use_dispatch(cfg):
        end = (pos_b + 1).astype(jnp.int32)
        start = None if window is None else jnp.maximum(end - window, 0).astype(jnp.int32)
        o = dispatch.decode_attention_paged(
            q[:, :, 0, :], k_pages, v_pages, block_table, end, start=start,
            scale=_scale(cfg), softcap=cfg.attn_softcap, cfg=cfg)
        y = o.astype(x.dtype).reshape(b, 1, cfg.n_heads * hd)
        return lin(p["wo"], y), k_pages, v_pages

    k_cache, v_cache = kv_mapping.materialize_lanes(k_pages, v_pages, block_table)
    lmax = k_cache.shape[-1]
    g = cfg.q_per_kv
    qg = q.reshape(b, cfg.n_kv_heads, g, t, hd)

    s = kv_mapping.read_scores(qg, k_cache, "cdpim").astype(jnp.float32) * _scale(cfg)
    s = softcap(s, cfg.attn_softcap)

    k_pos = jnp.arange(lmax)
    q_pos = positions  # (B, T)
    valid = k_pos[None, None, :] <= q_pos[:, :, None]       # (B, T, L)
    if window is not None:
        valid = valid & (k_pos[None, None, :] > q_pos[:, :, None] - window)
    s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :, :]

    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    y = kv_mapping.read_output(pr, v_cache, "cdpim")
    y = y.reshape(b, cfg.n_heads, t, hd).transpose(0, 2, 1, 3).reshape(b, t, -1)
    proj = lin or _dense_matmul
    return proj(p["wo"], y), k_pages, v_pages
