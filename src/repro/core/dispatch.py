"""Backend dispatch for the decode hot path.

This is the glue the paper's speedup actually lives in: the dual-layout
decode-attention kernel and the pipelined INT8 GEMV are only wins if the
*serving* path calls them. ``ModelConfig`` selects the backend:

``attn_backend``
    * ``"auto"``       — Pallas kernel on TPU, jnp oracle elsewhere (default)
    * ``"pallas"``     — force the compiled Pallas kernel
    * ``"interpret"``  — Pallas kernel in interpret mode (CPU tests exercise
      the real kernel lowering, not just the oracle)
    * ``"reference"``  — the pure-jnp oracle (float32, full-Lmax einsum)
    * ``"dense"``      — bypass dispatch entirely: the legacy dense-einsum
      path inside ``models.attention`` (the baseline the kernels are
      validated against at the token level)

``quantized_decode``
    Route decode-time linear projections (qkv / o / MLP) through the W8A8
    ``linear_w8a8`` PIM-GEMV path — the paper's INT8 CU datapath — whenever
    the activation is a low-batch single-token GEMV shape
    (``T == 1 and B <= quant_decode_max_batch``). Prefill and training are
    untouched: at GEMM shapes the MXU is compute-bound and int8 buys nothing.

Every routed op keeps a jnp reference fallback so CPU CI produces tokens
comparable with the TPU path.

**Degradation ladder.** A resilient serving engine must survive a kernel
that starts failing mid-run (a Pallas lowering regression, a numerics trip
on one shape) without taking down every in-flight request. The ladder is the
per-op fallback order

    pallas -> interpret -> reference

walked one rung at a time by :class:`DegradationLadder`: on a ``KernelFault``
(injected by a fault plan, or a NaN/Inf logit-guard trip) the engine demotes
the implicated op
(``"decode_attention"`` or ``"pim_gemv"`` — independently, via
``cfg.gemv_backend``), warns ONCE per transition, counts the event in its
health counters, and retries the step. Cross-backend token identity is a
tested property of every rung, so a degraded engine keeps emitting
bit-identical greedy tokens — only the schedule (and the honest pimsim
price of the retried, slower steps) changes. ``dense`` and ``reference``
have no rung below them: a fault there is terminal for the step and the
engine fails the in-flight requests instead of looping.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.quant import PreparedLinear, raw_weight
from repro.kernels.decode_attention.ops import (decode_attention_op,
                                                decode_attention_paged_op)
from repro.kernels.pim_gemv.ops import linear_w8a8, linear_w8a8_prequant

_KERNEL_BACKENDS = ("pallas", "interpret")
BACKENDS = ("auto", "pallas", "interpret", "reference", "dense")

# fallback order; backends outside the ladder ("dense") have no rung below
LADDER = ("pallas", "interpret", "reference")
LADDER_OPS = ("decode_attention", "pim_gemv")


def resolve_backend(cfg, op: str = "decode_attention") -> str:
    """Concrete backend for this process (``auto`` keys off the jax platform).

    ``op`` selects the per-op override: ``"pim_gemv"`` honors
    ``cfg.gemv_backend`` when set (the degradation ladder demotes the GEMV
    path independently of decode attention); every other op follows
    ``cfg.attn_backend``. Unknown names raise immediately — a typo'd backend
    must not silently serve from the fallback path while the operator
    believes the kernel ran.
    """
    name = cfg.attn_backend
    if op == "pim_gemv" and getattr(cfg, "gemv_backend", ""):
        name = cfg.gemv_backend
    if name not in BACKENDS:
        raise ValueError(
            f"attn_backend={name!r} unknown; expected one of {BACKENDS}")
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return name


class DegradationLadder:
    """Per-op fallback state + health counters for one engine.

    ``apply(cfg)`` pins the current rungs into a config for the next step's
    (statically-keyed) jit programs; ``degrade(op)`` moves one op down a
    rung (one-shot warning, counted) and returns False when there is no
    lower rung — the engine then fails the step's in-flight requests rather
    than retrying forever. ``record_nan`` / ``record_fault`` feed the health
    counters ``Engine.health()`` snapshots and ``schedule_report()``
    surfaces.
    """

    def __init__(self, cfg):
        self._base = {op: resolve_backend(cfg, op) for op in LADDER_OPS}
        self.rung = dict(self._base)
        self.counters = {op: {"fallbacks": 0, "nan_trips": 0,
                              "kernel_faults": 0} for op in LADDER_OPS}
        self._warned: set = set()

    # -------------------------------------------------------------- queries

    def backend(self, op: str) -> str:
        return self.rung[op]

    def kernel_live(self, op: str) -> bool:
        """True while the op still executes a kernel lowering (pallas /
        interpret) — the only rungs where a *kernel* fault can originate."""
        return self.rung[op] in _KERNEL_BACKENDS

    def is_degraded(self) -> bool:
        return self.rung != self._base

    def can_degrade(self) -> bool:
        """True while ANY op still has a rung below its current one."""
        return any(r in LADDER and r != LADDER[-1] for r in self.rung.values())

    def apply(self, cfg):
        """Config with the current rungs pinned (identity when undegraded,
        so the fault-free path keeps its exact jit cache keys)."""
        if not self.is_degraded():
            return cfg
        return cfg.replace(attn_backend=self.rung["decode_attention"],
                           gemv_backend=self.rung["pim_gemv"])

    # ----------------------------------------------------------- transitions

    def degrade(self, op: str, reason: str = "") -> bool:
        """Demote ``op`` one rung; False when already at the floor."""
        cur = self.rung[op]
        if cur not in LADDER or cur == LADDER[-1]:
            return False
        nxt = LADDER[LADDER.index(cur) + 1]
        self.rung[op] = nxt
        self.counters[op]["fallbacks"] += 1
        key = (op, cur, nxt)
        if key not in self._warned:  # one-shot per transition
            self._warned.add(key)
            warnings.warn(
                f"degrading {op}: {cur} -> {nxt}"
                f"{' (' + reason + ')' if reason else ''}; subsequent steps "
                f"run the fallback path (counted in Engine.health())",
                RuntimeWarning, stacklevel=3)
        return True

    def degrade_any(self, reason: str = "") -> bool:
        """The faulting op is at its floor: demote the first op that still
        has a rung below it (attention first — it dominates the decode
        step)."""
        return any(self.degrade(op, reason) for op in LADDER_OPS)

    def record_nan(self, op: str = "decode_attention") -> None:
        self.counters[op]["nan_trips"] += 1

    def record_fault(self, op: str) -> None:
        self.counters.setdefault(op, {"fallbacks": 0, "nan_trips": 0,
                                      "kernel_faults": 0})
        self.counters[op]["kernel_faults"] += 1

    def health(self) -> dict:
        """JSON-safe per-op snapshot for ``Engine.health()``."""
        return {op: {"backend": self.rung.get(op, "?"),
                     "base": self._base.get(op, "?"), **c}
                for op, c in self.counters.items()}


def use_dispatch(cfg) -> bool:
    """False only for the legacy dense-einsum baseline."""
    return resolve_backend(cfg) != "dense"


def decode_attention(
    q: jax.Array,        # (B, Hq, hd) single-token query heads
    k_cache: jax.Array,  # (B, Hkv, hd, Lmax) column-wise
    v_cache: jax.Array,  # (B, Hkv, Lmax, hd) row-wise
    end,                 # scalar or (B,) — live range [start, end) per sequence
    *,
    start=None,
    scale: float,
    softcap=None,
    cfg,
) -> jax.Array:
    """Dispatched decode-attention GEMV pair. Returns (B, Hq, hd) float32."""
    backend = resolve_backend(cfg)
    return decode_attention_op(
        q, k_cache, v_cache, end,
        start=start,
        scale=scale,
        softcap=softcap,
        block_l=cfg.decode_block_l,
        interpret=(backend == "interpret"),
        use_kernel=(backend in _KERNEL_BACKENDS),
    )


def decode_attention_paged(
    q: jax.Array,            # (B, Hq, hd) single-token query heads
    k_pages: jax.Array,      # (P, Hkv, hd, Bsz) column-wise pages
    v_pages: jax.Array,      # (P, Hkv, Bsz, hd) row-wise pages
    block_table: jax.Array,  # (B, NB) int32 — physical page per logical block
    end,                     # scalar or (B,) — live range [start, end)
    *,
    start=None,
    scale: float,
    softcap=None,
    cfg,
) -> jax.Array:
    """Dispatched BLOCK-PAGED decode attention: the block table indirects
    each sequence's logical blocks to shared physical pages (prefix reuse /
    CachePool storage) — scalar-prefetch index maps on the kernel backends,
    gather-materialize on the reference path. ``cfg.decode_kv_splits > 1``
    selects the two-stage split-KV reduction (long-context L parallelism).
    Returns (B, Hq, hd) float32."""
    backend = resolve_backend(cfg)
    return decode_attention_paged_op(
        q, k_pages, v_pages, block_table, end,
        start=start,
        scale=scale,
        softcap=softcap,
        interpret=(backend == "interpret"),
        use_kernel=(backend in _KERNEL_BACKENDS),
        num_splits=getattr(cfg, "decode_kv_splits", 1),
    )


def _gemv_shaped(cfg, x: jax.Array) -> bool:
    """Low-batch single-token decode activation (B, 1, d) — the paper's CU
    operating point (batch 1..8 GEMVs)."""
    return (cfg.quantized_decode and x.ndim == 3 and x.shape[1] == 1
            and x.shape[0] <= cfg.quant_decode_max_batch)


def quantizes_at(cfg, batch: int, t: int) -> bool:
    """Would :func:`linear` route a ``(batch, t, d)`` activation through the
    W8A8 PIM-GEMV path under ``cfg``?

    The shape gate made queryable: the CU datapath is single-token
    (``t == 1``) and low-batch only — anything else is the float GEMM.
    Speculative verify runs each score position through the same
    single-token decode shape, so a quantized-decode target quantizes its
    verify sub-steps exactly like plain decode and spec output stays
    bit-identical to the non-spec quantized engine (pinned by the spec
    suite)."""
    return bool(cfg.quantized_decode and t == 1
                and batch <= cfg.quant_decode_max_batch)


def linear(w, x: jax.Array, cfg) -> jax.Array:
    """``x @ w`` with the W8A8 PIM-GEMV path at quantized-decode GEMV shapes.

    ``w`` is either a raw (K, N) float array (the repo's row-major weight
    convention) or a :class:`repro.core.quant.PreparedLinear` built at load
    time by ``ServingModel.prepare``; ``x``: (..., K).

    Prepared leaves feed ``pim_gemv_int8`` their held weight-stationary int8
    image — only the activation is quantized per step, the deployment-shaped
    path (the paper's weight-stationary banks). Raw leaves quantize the
    weight on the fly (transpose + per-channel scale per step) — the
    accuracy-faithful FALLBACK that re-reads float weights every step, kept
    for ad-hoc engines constructed without a prepared artifact; both paths
    are token-identical (same quantizer, same operands).
    """
    if not _gemv_shaped(cfg, x):
        return x @ raw_weight(w)
    b, t, k = x.shape
    backend = resolve_backend(cfg, op="pim_gemv")
    interpret = backend == "interpret"
    use_kernel = backend in _KERNEL_BACKENDS
    if isinstance(w, PreparedLinear):
        y = linear_w8a8_prequant(w.w_q, w.w_scale, x.reshape(b * t, k),
                                 interpret=interpret, use_kernel=use_kernel)
    else:
        y = linear_w8a8(
            jnp.swapaxes(w, -1, -2),        # weight-stationary (N, K)
            x.reshape(b * t, k),
            interpret=interpret,
            use_kernel=use_kernel,
        )
    return y.reshape(b, t, -1).astype(x.dtype)


def mlp(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Gated-MLP through the dispatched (possibly W8A8) linears."""
    from repro.models import layers as L  # local import: avoid a cycle at init
    return L.mlp(p, x, linear_fn=lambda w, xx: linear(w, xx, cfg))


def projected_decode_attn_bytes(
    batch: int,
    n_kv_heads: int,
    head_dim: int,
    lmax: int,
    pos: int,
    *,
    block_l: int = 512,
    itemsize: int = 2,
    dispatched: bool = True,
) -> int:
    """Decode-step HBM cache traffic model for one attention layer.

    The dispatched kernel streams only live K/V tiles (dead tiles re-address
    the previous block and are skipped by the pipeline), so traffic scales
    with ``pos``; the dense path reads the full ``Lmax`` cache every step.
    """
    bl = min(block_l, lmax)
    if dispatched:
        live_tiles = -(-max(pos, 0) // bl)            # ceil(pos / BL)
        cols = min(live_tiles * bl, -(-lmax // bl) * bl)
    else:
        cols = lmax
    return 2 * batch * n_kv_heads * head_dim * cols * itemsize  # K + V streams
