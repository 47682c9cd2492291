"""Plain reference of the served model, and the gap check that decides
``correct``.

A dense pre-norm GQA transformer with RoPE and a SwiGLU MLP (Yi, InternLM2),
written here in plain ``jax.numpy``, float32 at ``Precision.HIGHEST``. It
imports nothing of the program and takes nothing the program made: it draws
its own weights from the run's seed, with the same initialiser the program's
models use (normal, std 1/sqrt(fan_in); embeddings std 0.02; norm scales 1),
so both sides hold the same bfloat16 numbers.

It follows the precision each configuration states. Prompt positions were
prefilled by bfloat16 GEMMs, so their projections are float here. Positions
from the first served token on were decoded one token at a time through
W8A8 projections (int8 weights per output channel, int8 activations per
token, int32 accumulation), so theirs are W8A8 here: per-token activation
scales make a full-sequence pass equal to one decode step per position. The
LM head is float. Attention, norms and residuals are float32 throughout.

The check runs the reference once over each sampled prompt with its served
tokens and reads, at every served token, how far that token's logit lies
below the reference's best (0 where the program chose the reference's
argmax). The widest such gap is compared with the cell's limit. The gap is
valid for greedy tokens only; every benchmark request is greedy.

A control is this same reference one precision step lower in some part, as
a later change might be tempted to serve (``CONTROLS``): the KV cache as
int8 (``kv8``); the bfloat16 prefill projections and the LM head as int8
W8A8 (``float8``); the W8A8 decode projections as int4 W4A4 (``w4a4``); or
all three at once (``all``). At each position the token a control ranks
first is read against the reference, as the served token is.

Work is done layer by layer, with each layer's weights drawn on the device
and dropped after it, and attention in blocks of query rows, so it fits
beside nothing else once the program's state is freed. Shapes are padded to
the cell's ``max_len`` and a fixed sample size, so it compiles once a cell.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


@dataclass(frozen=True)
class Dims:
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d=c["hidden_size"], f=c["intermediate_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   layers=c["num_hidden_layers"], vocab=c["vocab_size"],
                   theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


# ------------------------------------------------------------------ weights


def _dense(key, shape):
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / jnp.sqrt(fan_in))).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=1)
def top_weights(key, dims: Dims):
    """Embedding, LM head and the per-layer keys, from the run's raw key."""
    ks = jax.random.split(key, 8)
    embed = (jax.random.normal(ks[0], (dims.vocab, dims.d), jnp.float32)
             * 0.02).astype(jnp.bfloat16)
    lm_head = _dense(ks[3], (dims.d, dims.vocab))
    return embed, lm_head, jax.random.split(ks[1], dims.layers)


@functools.partial(jax.jit, static_argnums=1)
def layer_weights(key, dims: Dims) -> dict:
    k_attn, k_mlp = jax.random.split(key)
    a = jax.random.split(k_attn, 4)
    m = jax.random.split(k_mlp, 3)
    d, hd = dims.d, dims.head_dim
    return {
        "wq": _dense(a[0], (d, dims.heads * hd)),
        "wk": _dense(a[1], (d, dims.kv_heads * hd)),
        "wv": _dense(a[2], (d, dims.kv_heads * hd)),
        "wo": _dense(a[3], (dims.heads * hd, d)),
        "w_gate": _dense(m[0], (d, dims.f)),
        "w_up": _dense(m[1], (d, dims.f)),
        "w_down": _dense(m[2], (dims.f, d)),
    }


# --------------------------------------------------------------- arithmetic


def quantize_rows(a, bits: int):
    """Symmetric per-row quantization to ``bits`` (int8 container)."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) * jnp.float32(1.0 / qmax)
    q = jnp.clip(jnp.round(a.astype(jnp.float32) / scale), -qmax, qmax)
    return q.astype(jnp.int8), scale[..., 0]


def int_linear(x, w, bits: int):
    """x (R, K) f32 @ w (K, N) bf16 as W{bits}A{bits}: both sides quantized
    per row (w per output channel), exact int32 accumulation."""
    xq, xs = quantize_rows(x, bits)
    wq, ws = quantize_rows(w.astype(jnp.float32).T, bits)
    acc = jax.lax.dot_general(xq, wq, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs[:, None] * ws[None, :]


def float_linear(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def qdq_rows(a, bits: int):
    q, s = quantize_rows(a, bits)
    return q.astype(jnp.float32) * s[..., None]


def rmsnorm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta: float):
    """x (S, H, L, hd); rotate-half RoPE at positions 0..L-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """q (S, H, L, hd), k/v (S, Hkv, L, hd) -> (S, L, H*hd), in blocks of
    query rows so the score block stays small."""
    s_, h, length, hd = q.shape
    g = h // k.shape[1]
    scale = hd ** -0.5
    outs = []
    for b0 in range(0, length, QUERY_BLOCK):
        qb = q[:, :, b0:b0 + QUERY_BLOCK]
        nq = qb.shape[2]
        qg = qb.reshape(s_, k.shape[1], g, nq, hd)
        sc = jnp.einsum("skgqd,sktd->skgqt", qg, k, precision=HIGHEST) * scale
        ok = (jnp.arange(length)[None, :]
              <= (b0 + jnp.arange(nq))[:, None])
        sc = jnp.where(ok, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("skgqt,sktd->skgqd", p, v, precision=HIGHEST)
                    .reshape(s_, h, nq, hd))
    o = jnp.concatenate(outs, axis=2)
    return o.transpose(0, 2, 1, 3).reshape(s_, length, h * hd)


# ------------------------------------------------------------------ forward

# precision of (float projections and LM head, W8A8 projections, KV cache)
# per mode; None is float
MODES = {"reference": (None, 8, None),
         "kv8": (None, 8, 8), "float8": (8, 8, None), "w4a4": (None, 4, None),
         "all": (8, 4, 8)}
CONTROLS = ("kv8", "float8", "w4a4", "all")


def _proj(x, w, decoded, mode: str):
    """Projection of rows x (S, L, K): float rows (prompt) and decoded rows
    (served tokens) at the mode's precisions."""
    fbits, ibits, _ = MODES[mode]
    s_, length, k = x.shape
    x2 = x.reshape(s_ * length, k)
    yf = float_linear(x2, w) if fbits is None else int_linear(x2, w, fbits)
    yi = int_linear(x2, w, ibits)
    y = jnp.where(decoded.reshape(-1, 1), yi, yf)
    return y.reshape(s_, length, -1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def layer(h, w, decoded, dims: Dims, mode: str):
    """One transformer layer over padded sequences h (S, L, d) f32."""
    s_, length, _ = h.shape
    hd = dims.head_dim
    a = rmsnorm(h, dims.eps)
    q = _proj(a, w["wq"], decoded, mode)
    k = _proj(a, w["wk"], decoded, mode)
    v = _proj(a, w["wv"], decoded, mode)
    q = rope(q.reshape(s_, length, dims.heads, hd).transpose(0, 2, 1, 3),
             dims.theta)
    k = rope(k.reshape(s_, length, dims.kv_heads, hd).transpose(0, 2, 1, 3),
             dims.theta)
    v = v.reshape(s_, length, dims.kv_heads, hd).transpose(0, 2, 1, 3)
    kv_bits = MODES[mode][2]
    if kv_bits is not None:
        k, v = qdq_rows(k, kv_bits), qdq_rows(v, kv_bits)
    h = h + _proj(causal_attention(q, k, v), w["wo"], decoded, mode)
    a = rmsnorm(h, dims.eps)
    m = (jax.nn.silu(_proj(a, w["w_gate"], decoded, mode))
         * _proj(a, w["w_up"], decoded, mode))
    return h + _proj(m, w["w_down"], decoded, mode)


@functools.partial(jax.jit, static_argnums=(4,))
def head_logits(h, rows_s, rows_t, lm_head, eps_mode):
    """Logits at the gathered rows: h (S, L, d) -> (N, V) float32."""
    eps, mode = eps_mode
    x = rmsnorm(h[rows_s, rows_t], eps)
    fbits = MODES[mode][0]
    if fbits is None:
        return float_linear(x, lm_head)
    return int_linear(x, lm_head, fbits)


@dataclass
class Batch:
    """Sampled sequences padded to one shape: tokens (S, L), the first
    decoded position of each (its prompt length), and the rows at which the
    logits of served tokens are read, padded to a fixed count."""
    tokens: np.ndarray
    decoded: np.ndarray     # (S, L) bool: position processed by a decode step
    rows_s: np.ndarray      # (N,) sequence index of each compared row
    rows_t: np.ndarray      # (N,) position whose logits chose the token
    served: np.ndarray      # (N,) the token the program served there
    valid: np.ndarray       # (N,) bool: a real row, not padding


def make_batch(seqs, n_seqs: int, length: int, n_rows: int) -> Batch:
    """``seqs``: (prompt, served tokens) pairs; padded to ``n_seqs`` by
    repeating the first, to ``length`` positions, to ``n_rows`` rows."""
    seqs = list(seqs) + [seqs[0]] * (n_seqs - len(seqs))
    tokens = np.zeros((n_seqs, length), np.int32)
    decoded = np.zeros((n_seqs, length), bool)
    rs, rt, sv = [], [], []
    for s, (prompt, served) in enumerate(seqs):
        p = len(prompt)
        full = list(prompt) + list(served)
        if len(full) > length:
            raise ValueError(f"sequence of {len(full)} exceeds {length}")
        tokens[s, :len(full)] = full
        decoded[s, p:] = True
        for j, tok in enumerate(served):
            rs.append(s)
            rt.append(p - 1 + j)
            sv.append(tok)
    n = len(rs)
    if n > n_rows:
        raise ValueError(f"{n} served tokens exceed {n_rows} rows")
    pad = n_rows - n
    return Batch(tokens, decoded,
                 np.asarray(rs + [0] * pad, np.int32),
                 np.asarray(rt + [0] * pad, np.int32),
                 np.asarray(sv + [0] * pad, np.int32),
                 np.asarray([True] * n + [False] * pad))


def final_hidden(key, dims: Dims, batch: Batch, mode: str):
    embed, lm_head, layer_keys = top_weights(key, dims)
    h = jnp.take(embed, jnp.asarray(batch.tokens), axis=0).astype(jnp.float32)
    decoded = jnp.asarray(batch.decoded)
    for i in range(dims.layers):
        h = layer(h, layer_weights(layer_keys[i], dims), decoded, dims, mode)
    return h, lm_head


def logits(key, dims: Dims, batch: Batch, mode: str = "reference"):
    h, lm_head = final_hidden(key, dims, batch, mode)
    return head_logits(h, jnp.asarray(batch.rows_s), jnp.asarray(batch.rows_t),
                       lm_head, (dims.eps, mode))


def gaps(ref_logits, chosen, valid) -> np.ndarray:
    """How far each chosen token's reference logit lies below the best."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = np.asarray(chosen)
    g = ref.max(axis=-1) - ref[np.arange(len(chosen)), chosen]
    return g[np.asarray(valid)]


def served_gap(key, dims: Dims, batch: Batch) -> float:
    """Widest gap of a served token below the reference's best."""
    return float(gaps(logits(key, dims, batch), batch.served, batch.valid).max())


def control_gaps(key, dims: Dims, batch: Batch) -> dict[str, float]:
    """Widest gap of each control's first-ranked tokens, at the same rows."""
    ref = np.asarray(logits(key, dims, batch, "reference"), np.float64)
    out = {}
    for mode in CONTROLS:
        ctl = np.asarray(logits(key, dims, batch, mode)).argmax(axis=-1)
        out[mode] = float(gaps(ref, ctl, batch.valid).max())
    return out
