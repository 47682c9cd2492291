#!/usr/bin/env python3
"""Bring-up smoke run of the serving hot path on one TPU chip.

Serves Yi-9B at its published widths, cut from 48 to 16 layers, through the
entry points a user calls: ``ServingModel.prepare`` -> ``CachePool`` ->
``Engine.serve``, with paged decode attention and the W8A8 GEMV as compiled
Pallas kernels. Eight greedy requests run under LBIM (fused step program),
then the same eight under HBCEM (split step programs), each once cold and
once on a fresh engine with every program compiled. Finally, on one pool
state, the paged attention kernel is compared with the jnp reference on every
layer's pages, and one decode step's logits on the Pallas kernels with those
on the reference backend.

    python chip_smoke.py        # from the repo root, on a machine with a TPU

Every phase runs in this one process (a chip belongs to one process at a
time). The run exits non-zero, and prints no result line, when JAX finds no
TPU, when a degradation-ladder op left the ``pallas`` rung or counted a
fallback, NaN trip or kernel fault, when a request did not finish, or when
a correctness check fails. The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The numbers it prints are bring-up observations, not benchmark metrics.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

LAYERS = 16          # of Yi-9B's 48: 16 fit one 16 GB v5e beside the int8
#                      decode images and the page pool (48 need 17.7 GB bf16)
MAX_LEN = 1024
SLOTS = 4
CHUNK = 64           # admission chunk == KV page size
PROMPT_LENS = (64, 128)
N_REQUESTS = 8
MAX_NEW = 32
SEED = 0

# Correctness, on one pool state: the first SLOTS prompts prefilled into the
# page pool. Gaps are relative L2 norms. Each check has a control, a
# reference that does what a wrong kernel would; it must land at least
# CONTROL_MARGIN x the tolerance away, or the check could not tell a wrong
# kernel from a right one.
#
# Kernel check. Every layer's pages, one seeded random query per layer: the
# paged kernel (single pass and split-KV) against the jnp reference, both
# with f32 contractions (default_matmul_precision "highest"). The inputs are
# the same bf16 pages and both sides compute in f32, so only the summation
# order and the exp implementation differ: about 1e-6 of the output. The
# control drops each lane's newest token (a mask off by one).
KERNEL_REL_L2_TOL = 1e-4
# Step check. One decode step of the served program, pallas vs reference
# backend, each at the served precision. The int8 GEMVs are exact on both
# sides, so the two differ only in attention's rounding (on the TPU an f32
# dot is by default one MXU pass on bf16-rounded operands, in a Pallas
# kernel as in XLA). The step is not continuous in that difference: every
# layer casts to bf16 and re-quantizes its activations to int8, so an
# attention output moved by 1e-5 re-draws that rounding noise and moves the
# logits by 1.4e-2 after one layer, saturating near 3.6e-2 (CPU, injected
# noise, Yi-9B widths). On a TPU v5e the gap is 3.6e-2 at 1 layer and
# 5.1e-2 at 16. The tolerance is 3x that; the kernel check above is the
# tight one. The control has every lane read its neighbour's pages (1.4 on
# the same chip). The gap between the reference at served and at f32
# precision is printed beside it: what rounding alone moves.
LOGITS_REL_L2_TOL = 0.15
CONTROL_MARGIN = 4.0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or degraded result."""


class CompileClock:
    """Sums JAX's backend-compile durations (compiles and persistent-cache
    loads) reported while it is registered."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.count += 1


def say(msg: str) -> None:
    print(msg, flush=True)


def make_requests(vocab: int):
    """N_REQUESTS greedy requests, prompts of PROMPT_LENS seeded-random
    tokens (alternating), MAX_NEW tokens each."""
    from repro.serve.api import GenerationRequest

    rng = np.random.default_rng(SEED)
    return [GenerationRequest(
        prompt=[int(t) for t in rng.integers(1, vocab,
                                             PROMPT_LENS[i % len(PROMPT_LENS)])],
        max_new_tokens=MAX_NEW) for i in range(N_REQUESTS)]


def check_health(health: dict, label: str, backend: str) -> None:
    """Every ladder op still on ``backend`` (``pallas`` on the chip), with
    zero fallbacks, NaN trips and kernel faults, and no step failure."""
    for op, h in health["ladder"].items():
        bad = {k: h[k] for k in ("fallbacks", "nan_trips", "kernel_faults")
               if h[k]}
        if h["backend"] != backend or bad:
            raise SmokeFailure(f"{label}: {op} ran on {h['backend']!r} "
                               f"with {bad or 'no faults'}")
    if health["degraded"] or health["counters"]["failures"]:
        raise SmokeFailure(f"{label}: degraded={health['degraded']} "
                           f"counters={health['counters']}")


def serve_once(sm, mode, reqs, clock: CompileClock, label: str):
    """One ``Engine.serve`` of ``reqs`` on a fresh engine; returns tokens."""
    from repro.serve.api import RequestState

    eng = sm.engine(mode=mode, chunk=CHUNK)
    c0, n0 = clock.seconds, clock.count
    t0 = time.perf_counter()
    results = eng.serve(reqs)
    wall = time.perf_counter() - t0
    health = eng.health()
    emitted = sum(len(r.tokens) for r in results)
    say(f"serve {label}: wall_s={wall:.3f} tokens={emitted} "
        f"compile_s={clock.seconds - c0:.3f} compiles={clock.count - n0} "
        f"steps={len(eng.events)}")
    say(f"health {label}: {json.dumps(health, sort_keys=True)}")
    states = [r.state for r in results]
    if any(s is not RequestState.FINISHED for s in states):
        raise SmokeFailure(f"{label}: request states "
                           f"{[s.value for s in states]}")
    if any(len(r.tokens) != q.max_new_tokens for r, q in zip(results, reqs)):
        raise SmokeFailure(f"{label}: a request emitted short of its budget")
    check_health(health, label, sm.backend)
    return [r.tokens for r in results]


def rel_l2(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_gap(label: str, gap: float, tol: float, control: float) -> None:
    if not (np.isfinite(gap) and gap <= tol):
        raise SmokeFailure(f"{label}: rel_l2 {gap:.3e} > {tol}")
    if control < CONTROL_MARGIN * tol:
        raise SmokeFailure(f"{label} cannot see a wrong kernel: control "
                           f"rel_l2 {control:.3e} < {CONTROL_MARGIN} x {tol}")


def pool_state(sm, reqs):
    """Page-pool views holding the first SLOTS prompts, and each lane's
    greedy next token (the feed of the first decode step)."""
    import jax.numpy as jnp

    from repro.models import model as M

    pool = sm.cache_pool(slots=SLOTS, block_size=CHUNK)
    group = reqs[:SLOTS]
    lens = [len(r.prompt) for r in group]
    toks = np.zeros((SLOTS, max(lens)), np.int32)
    for j, r in enumerate(group):
        toks[j, :lens[j]] = r.prompt
    seq_lens = jnp.asarray(lens, jnp.int32)
    pre_logits, pcache = M.prefill(sm.params, {"tokens": jnp.asarray(toks)},
                                   sm.cfg, sm.max_len, seq_lens=seq_lens)
    pcache["pos"] = seq_lens
    for j, r in enumerate(group):
        si = pool.alloc(r, j, ctx=lens[j])
        pool.insert(si, pcache, src_slot=j, prompt=list(r.prompt))
    feed = jnp.argmax(pre_logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    return pool.views(), feed


def kernel_check(cfg, views) -> None:
    """Paged decode attention over every layer's pages: the kernel, single
    pass and split-KV, against the reference, all at f32 contraction."""
    import jax
    import jax.numpy as jnp

    from repro.core import dispatch

    ref_cfg = cfg.replace(attn_backend="reference")
    kernels = {"single": cfg, "split4": cfg.replace(decode_kv_splits=4)}
    rng = np.random.default_rng(SEED)
    table, end = views["block_table"], views["pos"]
    gaps = dict.fromkeys(kernels, 0.0)
    control = np.inf
    with jax.default_matmul_precision("highest"):
        for layer in range(cfg.n_layers):
            q = jnp.asarray(rng.standard_normal(
                (SLOTS, cfg.n_heads, cfg.head_dim)), jnp.bfloat16)
            k_pages, v_pages = views["k_pages"][layer], views["v_pages"][layer]

            def attend(c, e):
                return np.asarray(dispatch.decode_attention_paged(
                    q, k_pages, v_pages, table, e,
                    scale=cfg.head_dim ** -0.5, cfg=c), np.float64)

            ref = attend(ref_cfg, end)
            for name, c in kernels.items():
                gaps[name] = max(gaps[name], rel_l2(attend(c, end), ref))
            control = min(control, rel_l2(attend(ref_cfg, end - 1), ref))
    say(f"kernel check (worst of {cfg.n_layers} layers): " + json.dumps(
        dict(gaps, control_rel_l2=control, tol=KERNEL_REL_L2_TOL),
        sort_keys=True))
    for name, gap in gaps.items():
        check_gap(f"kernel check {name}", gap, KERNEL_REL_L2_TOL, control)


def logits_check(sm, views, feed) -> None:
    """One decode step of the served program, pallas vs reference."""
    import jax
    import jax.numpy as jnp

    from repro.core import interleave

    ref_cfg = sm.cfg.replace(attn_backend="reference")

    def step(cfg, cache=views):
        logits, _ = interleave.decode_only_step(sm.decode_params, cache,
                                                feed, cfg)
        return np.asarray(logits[:, -1, :], np.float64)

    got, ref = step(sm.cfg), step(ref_cfg)
    with jax.default_matmul_precision("highest"):
        ref_f32 = step(ref_cfg)
    wrong = step(ref_cfg, dict(views, block_table=jnp.roll(
        views["block_table"], 1, axis=0)))
    out = {
        "rel_l2": rel_l2(got, ref),
        "max_abs": float(np.max(np.abs(got - ref))),
        "ref_max_abs": float(np.max(np.abs(ref))),
        "argmax_agree": int(np.sum(got.argmax(-1) == ref.argmax(-1))),
        "lanes": SLOTS,
        "rounding_rel_l2": rel_l2(ref, ref_f32),
        "control_rel_l2": rel_l2(wrong, ref),
        "tol": LOGITS_REL_L2_TOL,
    }
    say(f"logits check: {json.dumps(out, sort_keys=True)}")
    check_gap("logits check", out["rel_l2"], LOGITS_REL_L2_TOL,
              out["control_rel_l2"])


def smoke(cfg) -> None:
    """Every phase, on ``cfg``'s backend; raises SmokeFailure on a fault."""
    import jax

    from repro.core.pim_modes import Mode
    from repro.models import model as M
    from repro.serve.serving_model import ServingModel

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    say(f"config: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
        f"n_heads={cfg.n_heads} n_kv_heads={cfg.n_kv_heads} "
        f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"quantized_decode={cfg.quantized_decode} "
        f"attn_backend={cfg.attn_backend}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(M.init_params(jax.random.PRNGKey(SEED), cfg))
    sm = ServingModel.prepare(cfg, params, max_len=MAX_LEN, slots=SLOTS)
    jax.block_until_ready(sm.decode_params)
    say(f"setup: init+prepare_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.seconds:.3f} compiles={clock.count} "
        f"backend={sm.backend} prequantized={sm.prequantized}")
    if sm.backend != cfg.attn_backend:
        raise SmokeFailure(f"prepare pinned {sm.backend!r}, "
                           f"asked for {cfg.attn_backend!r}")

    reqs = make_requests(cfg.vocab_size)
    say(f"requests: {len(reqs)} greedy, prompt lens "
        f"{[len(r.prompt) for r in reqs]}, max_new_tokens={MAX_NEW}, "
        f"slots={SLOTS} chunk={CHUNK} max_len={MAX_LEN}")
    tokens = {}
    for mode in (Mode.LBIM, Mode.HBCEM):
        cold = serve_once(sm, mode, reqs, clock, f"{mode.value}/cold")
        warm = serve_once(sm, mode, reqs, clock, f"{mode.value}/warm")
        say(f"{mode.value}: warm tokens identical to cold: {warm == cold}")
        tokens[mode] = cold
    same = sum(a == b for a, b in zip(tokens[Mode.LBIM], tokens[Mode.HBCEM]))
    say(f"greedy tokens LBIM vs HBCEM (informational): {same}/{len(reqs)} "
        f"requests identical")
    views, feed = pool_state(sm, reqs)
    kernel_check(sm.cfg, views)
    logits_check(sm, views, feed)
    stats = jax.devices()[0].memory_stats() or {}
    say(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    say(f"compile total: compile_s={clock.seconds:.3f} "
        f"compiles={clock.count}")


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run", file=sys.stderr)
        return 2
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device: {json.dumps(device)}")
    say(f"compile cache: {enable_compile_cache()}")
    full = get_config("yi-9b")
    say(f"cut: {full.name} depth {full.n_layers} -> {LAYERS} layers; "
        f"every published width kept")
    cfg = full.replace(n_layers=LAYERS, quantized_decode=True,
                       attn_backend="pallas")
    try:
        smoke(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
