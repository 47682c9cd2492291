"""Token sampling: ONE masked-sampling path for the decode pool.

``sample_masked`` is the engine's only sampler: per-slot temperature /
top-k / top-p / RNG key vectors ride alongside the ``done`` mask, and
``temperature == 0`` lanes take the exact argmax branch — greedy is the zero
point of the sampled path, not a separate implementation, which is what
keeps a greedy request bit-identical whether its batch siblings sample or
not. ``greedy_masked`` survives as the all-greedy special case.

RNG lanes are per REQUEST, not per slot: :func:`request_key` derives a base
key from the request's own ``seed`` and a prompt checksum only, and
:func:`token_key` folds in the request's emitted-token index. Nothing
scheduling-dependent (slot index, admission order, sibling retirement,
global step count) enters the derivation, so the token stream of a request
is a pure function of (params, prompt, SamplingParams) — the property the
continuous-batching engine's determinism tests pin down.

:func:`check_and_sample` is what the engine runs each step: the NaN guard
and both rules in one device program, whose small result the host reads
once.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_FILL = -1e30  # filtered-out logit value (f32-safe)


def greedy(logits: jax.Array) -> jax.Array:
    """logits (B, 1, V) → (B,) int32."""
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)


def request_key(seed: int, prompt) -> jax.Array:
    """Per-request RNG lane base key.

    Derived from the request's ``SamplingParams.seed`` and a polynomial hash
    of its own prompt — and deliberately nothing else — so same-seed requests
    with different prompts decorrelate while the stream stays invariant to
    slot placement and admission order. (A polynomial rolling hash over a
    large prime, not a linear checksum: linear mixes collide on trivially
    different prompts like ``[3]`` vs ``[1, 1]``.)
    """
    mix = 0
    for t in prompt:
        mix = (mix * 1000003 + int(t) + 1) % (2**61 - 1)
    return jax.random.fold_in(jax.random.PRNGKey(seed), mix & 0xFFFFFFFF)


def token_key(base: jax.Array, index: int) -> jax.Array:
    """Key for the request's ``index``-th emitted token (0 = prefill-seeded)."""
    return jax.random.fold_in(base, index)


def _filter_top_k_top_p(lg: jax.Array, top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Per-lane (V,) logit filter: keep the top-k AND the nucleus top-p set.

    ``top_k == 0`` disables the k cutoff; ``top_p == 1`` keeps every token
    with non-zero residual mass. The highest-probability token is always
    kept, so the filtered categorical is never empty.

    Disabled cutoffs are EXACT no-ops, not near-misses: with ``top_p >= 1``
    and ``top_k`` disabled (0) or >= vocab, the input logits pass through
    untouched. The float-accumulated ``cumsum`` can reach 1.0 exactly at the
    tail, so without the explicit bypass ``prev_mass < 1.0`` would drop the
    last-ranked token — a silent distribution change rejection sampling
    (which composes on this path) would inherit.
    """
    v = lg.shape[-1]
    order = jnp.argsort(-lg)                      # descending, stable
    slg = lg[order]
    ranks = jnp.zeros((v,), jnp.int32).at[order].set(jnp.arange(v, dtype=jnp.int32))
    k_eff = jnp.where(top_k > 0, top_k, v)
    probs = jax.nn.softmax(slg)
    prev_mass = jnp.cumsum(probs) - probs         # mass strictly above each rank
    keep_sorted = (prev_mass < top_p) & (jnp.arange(v) < k_eff)
    exact_noop = (top_p >= 1.0) & ((top_k <= 0) | (top_k >= v))
    return jnp.where(exact_noop, lg, jnp.where(keep_sorted[ranks], lg, NEG_FILL))


@functools.partial(jax.jit, static_argnames=("pad_id",))
def sample_masked(
    logits: jax.Array,       # (B, 1, V)
    done: jax.Array,         # (B,) bool — retired/free lanes
    *,
    keys: jax.Array,         # (B, 2) uint32 — per-lane token keys
    temperature: jax.Array,  # (B,) f32 — 0 selects the exact argmax branch
    top_k: jax.Array,        # (B,) int32 — 0 disables
    top_p: jax.Array,        # (B,) f32 — 1 disables
    pad_id: int = 0,
) -> jax.Array:
    """The decode pool's single sampling path → (B,) int32 (jitted — the
    pool width and vocab are fixed per engine, so one compile serves the
    whole run).

    ``done`` lanes still flow through the fixed-shape batch but emit
    ``pad_id`` (their logits are garbage); ``temperature == 0`` lanes take
    the raw argmax — bit-identical to :func:`greedy` — and sampled lanes
    draw from the temperature-scaled, top-k/top-p-filtered categorical with
    their OWN key, so lanes never share randomness.
    """
    lg = logits[:, -1, :]
    gtok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    temp = jnp.asarray(temperature, jnp.float32)
    scaled = lg.astype(jnp.float32) / jnp.where(temp > 0, temp, 1.0)[:, None]
    filtered = jax.vmap(_filter_top_k_top_p)(
        scaled, jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32))
    stok = jax.vmap(jax.random.categorical)(keys, filtered).astype(jnp.int32)
    tok = jnp.where(temp > 0, stok, gtok)
    return jnp.where(jnp.asarray(done), jnp.int32(pad_id), tok)


def greedy_masked(logits: jax.Array, done: jax.Array, pad_id: int = 0) -> jax.Array:
    """All-greedy masked sampling — the ``temperature == 0`` point of
    :func:`sample_masked`, as a fast path.

    Emits EXACTLY what ``sample_masked`` emits when every lane's temperature
    is zero (pinned by a unit test) without paying the sampled branch's
    per-lane top-k/top-p filter, so the engine's default-greedy decode loop
    stays a single argmax per step. Retired/free lanes (``done``) keep
    feeding a stable ``pad_id`` token and never leak into results; active
    lanes are exact argmax — identical to :func:`greedy`, which keeps
    cross-mode token identity exact.
    """
    return jnp.where(jnp.asarray(done), jnp.int32(pad_id), greedy(logits))


class Lanes(NamedTuple):
    """Per-row inputs of :func:`check_and_sample`'s token rule.

    ``temperature`` None selects the all-greedy rule (:func:`greedy_masked`:
    no keys, no filter). Otherwise every row carries its sampling params and
    its request's base key, and position ``t`` of row ``b`` draws on
    ``token_key(keys[b], offsets[b] + t)``.
    """
    done: jax.Array                          # (B,) bool — rows that emit the pad token
    temperature: Optional[jax.Array] = None  # (B,) f32
    top_k: Optional[jax.Array] = None        # (B,) int32
    top_p: Optional[jax.Array] = None        # (B,) f32
    keys: Optional[jax.Array] = None         # (B, 2) uint32 — request base keys
    offsets: Optional[jax.Array] = None      # (B,) uint32 — emitted-token index


def _select(logits, lanes: Lanes, rules) -> jax.Array:
    """(B, T, V) logits → (B, T) int32: each position through the rows'
    rule as a (B, 1, V) step of its own, so a speculative verify position
    draws exactly what a plain decode step would."""
    greedy_rule, sampled_rule = rules
    cols = []
    for t in range(logits.shape[1]):
        lg = logits[:, t:t + 1, :]
        if lanes.temperature is None:
            cols.append(greedy_rule(lg, lanes.done))
            continue
        keys = jax.vmap(token_key)(lanes.keys, lanes.offsets + t)
        cols.append(sampled_rule(
            lg, lanes.done, keys=keys, temperature=lanes.temperature,
            top_k=lanes.top_k, top_p=lanes.top_p))
    return jnp.stack(cols, axis=1)


@functools.partial(jax.jit, static_argnames=("check", "rules"))
def _check_and_sample(groups, *, check: bool, rules) -> jax.Array:
    parts = []
    ok = jnp.bool_(True)
    for logits, lanes in groups:
        parts.append(_select(logits, lanes, rules).reshape(-1))
        if check:
            ok &= jnp.all(jnp.isfinite(logits) | lanes.done[:, None, None])
    parts.append(ok.astype(jnp.int32)[None])
    return jnp.concatenate(parts)


def check_and_sample(groups, *, check: bool = True) -> jax.Array:
    """The engine step's NaN guard and token selection as ONE device program.

    ``groups`` is a tuple of ``(logits, lanes)`` pairs: the step's decode
    logits ``(slots, T, V)`` (``T > 1`` for speculative verify positions) with
    one :class:`Lanes` row per slot, and/or the last positions ``(G, 1, V)``
    of prefill logits with one row per request they seed. Returns one
    ``(sum B*T + 1,)`` int32 vector for a single pull: each group's tokens
    row-major, in order, then a flag that is 1 iff every logit of every row
    not ``done`` is finite (always 1 with ``check`` False, which computes
    nothing for it).

    Which program runs is static — which groups the step holds, each group's
    rule (all-greedy or sampled), ``check`` — so the all-greedy step stays a
    plain masked argmax. The two rules are looked up here on every call and
    passed static, so a replaced :func:`greedy_masked` (a test's planted
    fault) compiles its own program and never lingers in the jit cache.
    """
    return _check_and_sample(tuple(groups), check=check,
                             rules=(greedy_masked, sample_masked))


def sample(logits: jax.Array, rng: jax.Array, temperature: float = 1.0,
           top_k: int = 0) -> jax.Array:
    """Single-policy batch sampling (legacy utility; the engine uses
    :func:`sample_masked`)."""
    lg = logits[:, -1, :].astype(jnp.float32)
    if temperature <= 0.0:
        return greedy(logits)
    lg = lg / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(lg, top_k)
        cutoff = vals[:, -1:]
        lg = jnp.where(lg >= cutoff, lg, -1e30)
    return jax.random.categorical(rng, lg, axis=-1).astype(jnp.int32)
