"""Typed ``CachePool``: slot table + per-family cache state + paged residency.

The serving engine used to plumb the decode cache around as a raw
dict-of-arrays: lane surgery lived in ``models.model`` (with a hardcoded
recurrent-key tuple in ``reset_slot``), and the engine special-cased cache
families at admission. That is exactly the software layout-management gap
PIM-SHERPA identifies for PIM deployments — the bank mapping was an
attribute of *call sites*, not of the deployed artifact. This module makes
the cache a typed object instead:

* :class:`CachePool` owns the slot table and one state object per cache
  *family* present in the config, all behind one protocol —
  ``alloc(request) -> slot``, ``insert(slot, prefilled)``, ``retire(slot)``,
  ``views()`` for the decode step, ``commit(new_cache)`` after it. The
  engine never touches a cache key or a family name.
* Dense/vlm/moe configs (KV is the whole cache state) run **fully paged**:
  :class:`PagedKVState` owns ONE physical page pool in the paper's §III-C
  dual layout — K pages column-wise ``(hd, Bsz)``, V pages row-wise
  ``(Bsz, hd)``, layer-stacked — shared by the live lanes, the in-flight
  admission stream, and the content-hashed prefix index. Lanes never
  materialize contiguously: per-slot block tables map logical blocks to
  physical pages, the decode step appends the new token IN PLACE
  (``kv_mapping.append_layer_paged``), and the split-KV flash kernel
  consumes the same tables through scalar-prefetch index maps. The step
  programs take the page arrays by donation (``views(handover=True)``) and
  the pool adopts their outputs at dispatch (:meth:`CachePool.adopt`), so
  one pair of page arrays exists.
* Pages are **refcounted**: an active lane's table row, the staging stream's
  handle, the prefix index, and any live :class:`LaneFork` each hold one
  reference per page, and a page returns to the free list exactly when its
  count reaches zero — the chaos suite audits this
  (:meth:`CachePool.check_invariants`) after every fault plan. Shared prefix
  pages are full blocks strictly below every owner's append point, so the
  natural flow never writes one; ``ensure_residency`` still carries a
  defensive copy-on-write for adversarial states.
* **Fork/rollback** (speculative decoding's verify branch):
  :meth:`CachePool.fork_lane` snapshots a slot as an O(1) refcounted copy of
  its block-table row — pages copy only when a branch writes (the fork's
  extra reference makes the write block shared, so ``ensure_residency``
  copies-on-write). A verify step appends k+1 candidate tokens, then either
  :meth:`CachePool.rollback_lane` truncates the lane to the accepted length
  and :meth:`CachePool.drop_fork` releases the snapshot, or
  :meth:`CachePool.restore_lane` reinstates the snapshot bit-identically
  (fault path). Each fork's references are released exactly once — a
  double ``drop``/``restore`` is an :class:`EngineStateError`, and live
  forks are part of the refcount audit.
* **Prefix reuse** is zero-copy now: at insert, full ``block_size``-token
  blocks of the prompt are *indexed in place* (content-hashed, refcount
  pinned — nothing is copied out); at admission, a matching prompt prefix
  enters the staging stream's block table read-only, so shared system
  prompts cost zero prefill tokens AND zero gather traffic after their
  first request.

The remaining families keep contiguous lanes: :class:`ContiguousKVState`
(mixed-family dense KV), :class:`RingKVState` (gemma2 W-slot rings),
:class:`RecurrentState` (RWKV wkv / Mamba ssd — zeroed on retire),
:class:`StaticKVState` (audio cross-attention memory). Which states exist is
DERIVED from the config's cache structure (:func:`derive_state_specs`), so a
new family's novel leaves are zero-on-retire by construction.

Admission *policy* is derived from the same specs (:class:`AdmissionPolicy`):
ring states cannot chunk-ingest (solo full prefills), recurrent states
cannot ride a right-padded ragged batch, and prefix reuse is only sound when
KV is the whole cache state (a recurrent family's prefix state snapshot is a
ROADMAP follow-up). The engine consults the policy — it has no family
branches of its own.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Any, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import interleave, kv_mapping
from repro.models import model as M
from repro.serve import trace
from repro.serve.errors import (EngineStateError, PoolExhausted,
                                PoolOccupancy)

FREE, ACTIVE = "free", "active"

# Physical page 0 is a permanently pinned dummy: free lanes' block tables
# resolve to it so their masked garbage decodes have somewhere harmless to
# land (the analogue of free lanes writing column 0 of a contiguous lane).
DUMMY_PAGE = 0

# Leaf names with positional masking or one-shot semantics: everything ELSE
# in a decode cache is recurrent state that must be zeroed when a lane is
# retired (no hardcoded per-family tuple — a new family's novel keys are
# zero-on-retire by default, so state can't silently leak across slot reuse).
KV_KEYS = ("k", "v")
RING_KEYS = ("k_loc", "v_loc")
STATIC_KEYS = ("cross_k", "cross_v")
NON_RECURRENT_KEYS = frozenset(KV_KEYS + RING_KEYS + STATIC_KEYS + ("pos",))


# ===========================================================================
# lane surgery primitives (moved here from models.model; shims remain there)
# ===========================================================================


def lane_count(cache: dict) -> int:
    """Batch-lane count of a stacked decode cache."""
    return jax.tree_util.tree_leaves(
        {k: v for k, v in cache.items() if k != "pos"})[0].shape[1]


def normalize_pos(cache: dict, batch: int) -> dict:
    """Return ``cache`` with ``pos`` broadcast to a per-lane (B,) vector."""
    out = dict(cache)
    out["pos"] = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(cache["pos"], jnp.int32), (-1,)), (batch,))
    return out


def _copy_lane(dst: jax.Array, src: jax.Array, slot: int,
               src_slot: int) -> jax.Array:
    lane = jax.lax.dynamic_slice_in_dim(src, src_slot, 1, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(
        dst, lane.astype(dst.dtype), slot, axis=1)


def _zero_lane(arr: jax.Array, slot: int) -> jax.Array:
    lane = jnp.zeros_like(jax.lax.dynamic_slice_in_dim(arr, slot, 1, axis=1))
    return jax.lax.dynamic_update_slice_in_dim(arr, lane, slot, axis=1)


def insert_lane(cache: dict, src_cache: dict, slot: int,
                src_slot: int = 0) -> dict:
    """Copy lane ``src_slot`` of ``src_cache`` into lane ``slot`` of ``cache``.

    ``src_cache`` is a freshly prefilled cache; its leaves and fill level
    replace whatever the freed slot held. Stale KV beyond the new fill level
    is left in place — decode attention masks strictly by ``[0, pos)``.
    """
    out = dict(cache)
    for key, dst in cache.items():
        if key == "pos":
            continue
        out[key] = _copy_lane(dst, src_cache[key], slot, src_slot)
    src_pos = normalize_pos(src_cache, lane_count(src_cache))["pos"][src_slot]
    out["pos"] = normalize_pos(cache, lane_count(cache))["pos"].at[slot].set(src_pos)
    return out


def reset_lane(cache: dict, slot: int) -> dict:
    """Retire lane ``slot``: zero its recurrent state and fill level.

    Zero-on-retire keys are DERIVED: every leaf not in
    :data:`NON_RECURRENT_KEYS` is recurrent state with no position masking,
    so it is zeroed to keep the free lane's dummy decode bounded. KV / ring /
    static lanes stay as dead weight behind ``pos == 0``.
    """
    out = dict(cache)
    for key in cache:
        if key not in NON_RECURRENT_KEYS:
            out[key] = _zero_lane(cache[key], slot)
    out["pos"] = normalize_pos(cache, lane_count(cache))["pos"].at[slot].set(0)
    return out


# ===========================================================================
# cache-state specs: derived, not declared per family
# ===========================================================================


@dataclass(frozen=True)
class StateSpec:
    """One cache family present in a config's decode cache."""

    kind: str                 # "paged_kv" | "ring" | "recurrent" | "static"
    keys: tuple[str, ...]
    zero_on_retire: bool


def derive_state_specs(cfg: ModelConfig) -> tuple[StateSpec, ...]:
    """Decompose a config's decode-cache structure into typed state specs.

    Derived from the abstract cache tree (``eval_shape`` — no allocation):
    known leaf groups map to their typed state; every leftover leaf is
    recurrent state, zeroed on retire. This replaces the old hardcoded
    ``("wkv", "att_tail", ...)`` tuple in ``model.reset_slot``.
    """
    struct = M.decode_cache_specs(cfg, 1, 8)
    keys = {k for k in struct if k != "pos"}
    specs: list[StateSpec] = []
    claimed: set[str] = set()
    if set(KV_KEYS) <= keys:
        specs.append(StateSpec("paged_kv", KV_KEYS, False))
        claimed |= set(KV_KEYS)
    if set(RING_KEYS) <= keys:
        specs.append(StateSpec("ring", RING_KEYS, False))
        claimed |= set(RING_KEYS)
    static = tuple(sorted(set(STATIC_KEYS) & keys))
    if static:
        specs.append(StateSpec("static", static, False))
        claimed |= set(static)
    recurrent = tuple(sorted(keys - claimed))
    if recurrent:
        specs.append(StateSpec("recurrent", recurrent, True))
    return tuple(specs)


@dataclass(frozen=True)
class AdmissionPolicy:
    """What the engine may do at admission — derived from the state specs,
    so the engine itself never branches on a cache family."""

    chunkable: bool        # False: ring states only load via full batch-1 prefill
    ragged_batch_ok: bool  # False: recurrent/ring states reject padded ragged batches
    prefix_capable: bool   # True: KV is the whole state -> prefix reuse is sound


def derive_policy(specs: tuple[StateSpec, ...]) -> AdmissionPolicy:
    kinds = {s.kind for s in specs}
    return AdmissionPolicy(
        chunkable="ring" not in kinds,
        ragged_batch_ok=kinds <= {"paged_kv", "static"},
        prefix_capable=kinds == {"paged_kv"},
    )


# ===========================================================================
# typed per-family states
# ===========================================================================


class CacheState(Protocol):
    """One cache family's slice of the slot pool, behind a uniform protocol."""

    spec: StateSpec
    copied: int   # bytes written by eager updates, until the pool takes them

    def insert(self, src_cache: dict, slot: int, src_slot: int) -> None: ...
    def retire(self, slot: int) -> None: ...
    def views(self) -> dict: ...
    def commit(self, new_cache: dict) -> None: ...


class _LaneState:
    """Shared plumbing: a dict of stacked lane leaves for this family."""

    def __init__(self, spec: StateSpec, leaves: dict):
        self.spec = spec
        self.leaves = {k: leaves[k] for k in spec.keys}
        self.copied = 0   # bytes written by eager lane updates

    def insert(self, src_cache: dict, slot: int, src_slot: int) -> None:
        for k in self.spec.keys:
            self.leaves[k] = _copy_lane(self.leaves[k], src_cache[k], slot, src_slot)
            self.copied += self.leaves[k].nbytes

    def retire(self, slot: int) -> None:
        if self.spec.zero_on_retire:
            for k in self.spec.keys:
                self.leaves[k] = _zero_lane(self.leaves[k], slot)
                self.copied += self.leaves[k].nbytes

    def views(self) -> dict:
        return dict(self.leaves)

    def commit(self, new_cache: dict) -> None:
        for k in self.spec.keys:
            self.leaves[k] = new_cache[k]


class ContiguousKVState(_LaneState):
    """Dense KV as contiguous dual-layout lanes — the non-paged fallback:
    mixed-family configs (hybrid/audio, where KV is not the whole state) and
    pools constructed with ``paged=False`` for A/B testing."""


class RingKVState(_LaneState):
    """gemma2 W-slot ring buffers (``k_loc``/``v_loc``): steady-state decode
    structures — admission only via full batch-1 prefill (policy-enforced)."""


class RecurrentState(_LaneState):
    """RWKV wkv / Mamba ssd leaves: no positional masking, so a retired
    lane's state is zeroed before reuse (spec-driven, not hardcoded)."""


class StaticKVState(_LaneState):
    """Per-request constant memory (audio cross-attention K/V): copied at
    insert, never appended to, never zeroed."""


class _PagesExhausted(Exception):
    """Internal: the physical page pool ran dry (the pool re-raises this as
    a :class:`PoolExhausted` carrying its occupancy snapshot)."""


@dataclass
class _StagingHandle:
    """The (single) in-flight admission stream's page residency: ``table``
    is its logical->physical map (matched prefix pages read-only, then the
    pages its chunks write in the pool arrays)."""

    table: np.ndarray


class PagedKVState:
    """Fully paged dense KV: one refcounted physical page pool (the paper's
    §III-C dual layout per page, layer-stacked) shared by live lanes, the
    admission stream, and the content-hashed prefix index.

    Steady-state decode runs ON the block tables: ``views()`` exposes
    ``k_pages``/``v_pages``/``block_table`` and the decode step scatters the
    new token into each lane's current write page in place — no lane is ever
    materialized contiguously, and admission never gathers (a prefix hit
    just enters the shared pages into the stream's table read-only).

    Reference counts per page: one per active-lane table entry, one per
    staging-handle entry, one per prefix-index pin, plus the permanent
    :data:`DUMMY_PAGE` pin. A page is free exactly when its count is zero.
    Shared pages are always FULL blocks strictly below every owner's append
    point (the prefix match is capped one token short of the prompt and the
    harvest takes full blocks only), so natural decode never writes a shared
    page; :meth:`ensure_residency` still copies-on-write defensively when a
    write block is shared (refcount > 1).
    """

    def __init__(self, spec: StateSpec, cfg: ModelConfig, n_slots: int,
                 max_len: int, block_size: int, *, store_pages: int,
                 prefix_cache: bool, dtype, spec_slack: int = 0):
        self.spec = spec
        self.block_size = int(block_size)
        # ceil: a ragged max_len just leaves the last block partially filled.
        # ``spec_slack`` buys each lane room for a speculative verify step's
        # TRANSIENT k+1 appends beyond max_len (rolled back before the lane
        # can be observed at that fill) — without it a verify near max_len
        # would clip into the lane's last real block.
        self.n_blocks = -(-(int(max_len) + max(int(spec_slack), 0))
                          // self.block_size)
        self.n_slots = int(n_slots)
        self.prefix_cache = bool(prefix_cache)
        self.store_capacity = int(store_pages) if self.prefix_cache else 0
        # worst-case distinct pages: every slot full + the staging stream
        # full + a saturated prefix index, all disjoint, + the dummy
        self.capacity = ((self.n_slots + 1) * self.n_blocks
                         + self.store_capacity + 1)
        self.pages = kv_mapping.init_paged_cache(
            cfg.n_layers, self.capacity, cfg.n_kv_heads, cfg.head_dim,
            self.block_size, dtype)
        self.refcount = np.zeros((self.capacity,), np.int64)
        self.refcount[DUMMY_PAGE] = 1
        self._free = list(range(self.capacity - 1, DUMMY_PAGE, -1))
        self.block_tables = np.full((self.n_slots, self.n_blocks), -1, np.int64)
        self._index: OrderedDict[bytes, int] = OrderedDict()
        self.staging: Optional[_StagingHandle] = None
        # live fork rows (identity-keyed): each holds one ref per page and
        # is part of the audit's expected-refcount reconstruction
        self._forks: list[np.ndarray] = []
        # bytes written by eager updates of the page arrays: each one
        # materialises both whole arrays, however few pages it touches
        self.copied = 0

    def _set_pages(self, pages: dict) -> None:
        """Take the output of an eager update of the page arrays."""
        self.pages = pages
        self.copied += pages["k_pages"].nbytes + pages["v_pages"].nbytes

    def __len__(self) -> int:
        """Indexed prefix entries (``prefix_report``'s ``stored_blocks``)."""
        return len(self._index)

    def pages_used(self) -> int:
        """Referenced pages, dummy excluded."""
        return int((self.refcount > 0).sum()) - 1

    # -------------------------------------------------------- page refcounts

    def _ref(self, p: int) -> None:
        self.refcount[p] += 1

    def _unref(self, p: int) -> None:
        self.refcount[p] -= 1
        if self.refcount[p] == 0:
            self._free.append(int(p))

    def _alloc_page(self) -> int:
        """A fresh page for the caller (refcount 1), evicting LRU
        store-only entries under pressure."""
        if self._free:
            p = self._free.pop()
            self.refcount[p] = 1
            return int(p)
        for key in list(self._index):  # LRU order
            p = self._index[key]
            if self.refcount[p] == 1:  # only the index pin holds it
                del self._index[key]
                self.refcount[p] = 1   # the pin transfers to the caller
                return int(p)
        raise _PagesExhausted(
            f"no free page among {self.capacity} (all lane- or "
            f"prefix-referenced)")

    def _drop_row(self, slot: int) -> None:
        for p in self.block_tables[slot]:
            if p >= 0:
                self._unref(int(p))
        self.block_tables[slot] = -1

    # ----------------------------------------------------------- prefix index

    def _key(self, prompt, i: int) -> bytes:
        return np.asarray(prompt[: (i + 1) * self.block_size],
                          np.int32).tobytes()

    def match_prefix(self, prompt) -> list[int]:
        """Longest indexed block-chain prefix of ``prompt`` — capped one
        token short of the full prompt (the final token must be prefilled to
        seed the first decode logits). Returns physical page ids in logical
        order."""
        if not self.prefix_cache:
            return []
        max_blocks = max(len(prompt) - 1, 0) // self.block_size
        pages: list[int] = []
        for i in range(max_blocks):
            phys = self._index.get(self._key(prompt, i))
            if phys is None:
                break
            self._index.move_to_end(self._key(prompt, i))  # LRU touch
            pages.append(phys)
        return pages

    def harvest(self, slot: int, prompt) -> None:
        """Index every full prompt block resident in ``slot``'s table —
        content-addressed and refcount-pinned IN PLACE, no copies. A key
        collision keeps the already-indexed page (the lane keeps its own
        bits); at capacity, LRU store-only entries are evicted first and the
        harvest truncates when nothing is evictable."""
        if not self.prefix_cache:
            return
        for i in range(len(prompt) // self.block_size):
            p = int(self.block_tables[slot, i])
            if p < 0:
                break
            key = self._key(prompt, i)
            if key in self._index:
                self._index.move_to_end(key)
            elif len(self._index) < self.store_capacity or self._evict_one():
                self._index[key] = p
                self._ref(p)

    def _evict_one(self) -> bool:
        for key in list(self._index):  # LRU order
            p = self._index[key]
            if self.refcount[p] == 1:
                del self._index[key]
                self._unref(p)
                return True
        return False

    # ------------------------------------------------------------- residency

    def ensure_residency(self, slot: int, pos: int, n_tokens: int = 1) -> None:
        """Page-in ``slot``'s write blocks for the next ``n_tokens`` appends
        starting at ``pos``; copy-on-write any such block that is shared
        (a forked lane's partial write block, or an adversarial state).
        This is the "pages copy only if the branch writes" half of the fork
        protocol — forking itself never copies a page."""
        last = min(pos + max(int(n_tokens), 1),
                   self.n_blocks * self.block_size) - 1
        if last < pos:
            return  # at capacity: the engine retires before appending
        for wb in range(pos // self.block_size, last // self.block_size + 1):
            p = int(self.block_tables[slot, wb])
            if p < 0:
                self.block_tables[slot, wb] = self._alloc_page()
            elif self.refcount[p] > 1:
                q = self._alloc_page()
                self._set_pages({
                    "k_pages": self.pages["k_pages"].at[:, q].set(
                        self.pages["k_pages"][:, p]),
                    "v_pages": self.pages["v_pages"].at[:, q].set(
                        self.pages["v_pages"][:, p]),
                })
                self.block_tables[slot, wb] = q
                self._unref(p)

    # -------------------------------------------------------- fork / rollback

    def fork_row(self, slot: int) -> np.ndarray:
        """Snapshot ``slot``'s table row: O(1) — one extra ref per page, no
        page content copied. The row is registered live for the audit."""
        row = self.block_tables[slot].copy()
        for p in row:
            if p >= 0:
                self._ref(int(p))
        self._forks.append(row)
        return row

    def _forget_fork(self, row: np.ndarray) -> None:
        for i, r in enumerate(self._forks):
            if r is row:
                del self._forks[i]
                return
        raise EngineStateError(
            "fork row is not registered (released twice?)")

    def restore_row(self, slot: int, row: np.ndarray) -> None:
        """Reinstate a fork: the lane's current row (including any pages the
        branch wrote) is released and the snapshot's row — and its refs —
        transfer back to the slot. Bit-identical: a shared write block was
        copied-on-write by the branch, so the snapshot's pages were never
        touched."""
        self._forget_fork(row)
        self._drop_row(slot)
        self.block_tables[slot] = row

    def drop_fork_row(self, row: np.ndarray) -> None:
        """Release a fork's references (the accept path, after rollback)."""
        self._forget_fork(row)
        for p in row:
            if p >= 0:
                self._unref(int(p))

    def rollback(self, slot: int, pos: int) -> None:
        """Truncate ``slot`` to fill level ``pos``: release every block at or
        beyond the first dead one. Exact for paged KV — attention masks
        strictly by ``[0, pos)``, so the kept write block's garbage tail is
        dead weight."""
        first = -(-int(pos) // self.block_size)
        for b in range(first, self.n_blocks):
            p = int(self.block_tables[slot, b])
            if p >= 0:
                self._unref(p)
                self.block_tables[slot, b] = -1

    def begin_staging(self, pages: list[int]) -> dict:
        """Open the admission stream: matched prefix pages enter its block
        table read-only — zero copies, no gather. Returns the stream's
        batch-1 cache dict over the POOL arrays, into which its chunks write
        their fresh pages."""
        self.release_staging()  # defensive: a stale handle leaks pages
        table = np.full((self.n_blocks,), -1, np.int64)
        for i, p in enumerate(pages):
            table[i] = p
            self._ref(p)
        self.staging = _StagingHandle(table=table)
        return {"k_pages": self.pages["k_pages"],
                "v_pages": self.pages["v_pages"],
                "block_table": self._staging_table(),
                "pos": jnp.asarray([len(pages) * self.block_size], jnp.int32)}

    def _staging_table(self) -> jax.Array:
        eff = np.where(self.staging.table >= 0, self.staging.table, DUMMY_PAGE)
        return jnp.asarray(eff[None, :], jnp.int32)

    def ensure_staging(self, cache: dict, n_tokens: int) -> dict:
        """Page-in the stream's next ``n_tokens`` write blocks; returns the
        stream cache with its block table refreshed."""
        h = self.staging
        if h is None:
            raise EngineStateError(
                "ensure_staging with no admission stream open")
        off = int(np.asarray(cache["pos"]).reshape(-1)[0])
        last = min(off + max(int(n_tokens), 1),
                   self.n_blocks * self.block_size) - 1
        for b in range(off // self.block_size, last // self.block_size + 1):
            if h.table[b] < 0:
                h.table[b] = self._alloc_page()
        out = dict(cache)
        out["block_table"] = self._staging_table()
        return out

    def release_staging(self) -> None:
        """Abort the admission stream: every page it references is unpinned
        (fresh pages return to the free list; shared pages drop one ref)."""
        h = self.staging
        if h is None:
            return
        for p in h.table:
            if p >= 0:
                self._unref(int(p))
        self.staging = None

    # -------------------------------------------------------------- protocol

    def insert(self, src_cache: dict, slot: int, src_slot: int) -> None:
        self._drop_row(slot)
        if "block_table" in src_cache:
            self._consume_staging(slot)
        else:
            self._pagify_lane(src_cache, slot, src_slot)

    def _consume_staging(self, slot: int) -> None:
        """Hand the drained stream's table row — and its refcounts — to lane
        ``slot``. Its chunks wrote their fresh pages into the pool arrays
        themselves, so no page moves."""
        h = self.staging
        if h is None:
            raise EngineStateError(
                "paged insert from a stream cache with no staging handle")
        self.block_tables[slot] = h.table
        self.staging = None

    def _pagify_lane(self, src_cache: dict, slot: int, src_slot: int) -> None:
        """Contiguous prefill source (batch-prefill admission, tests): cut
        the lane into freshly allocated pages block by block. The lane
        itself never enters the pool."""
        k_lane = src_cache["k"][:, src_slot]   # (nL, H, hd, Lmax)
        v_lane = src_cache["v"][:, src_slot]   # (nL, H, Lmax, hd)
        pos = int(np.asarray(
            normalize_pos(src_cache, lane_count(src_cache))["pos"])[src_slot])
        lpad = self.n_blocks * self.block_size - k_lane.shape[-1]
        if lpad > 0:  # ragged max_len: square the lane up to the block grid
            k_lane = jnp.pad(k_lane, ((0, 0), (0, 0), (0, 0), (0, lpad)))
            v_lane = jnp.pad(v_lane, ((0, 0), (0, 0), (0, lpad), (0, 0)))
        kd = self.pages["k_pages"].dtype
        for i in range(min(-(-pos // self.block_size), self.n_blocks)):
            p = self._alloc_page()
            kb, vb = kv_mapping.extract_block(k_lane, v_lane, i,
                                              self.block_size)
            self._set_pages(kv_mapping.store_block(
                self.pages, p, kb.astype(kd), vb.astype(kd)))
            self.block_tables[slot, i] = p

    def retire(self, slot: int) -> None:
        self._drop_row(slot)

    def views(self) -> dict:
        eff = np.where(self.block_tables >= 0, self.block_tables, DUMMY_PAGE)
        return {"k_pages": self.pages["k_pages"],
                "v_pages": self.pages["v_pages"],
                "block_table": jnp.asarray(eff, jnp.int32)}

    def commit(self, new_cache: dict) -> None:
        """Nothing to take: a step's page arrays were adopted when its
        program was dispatched (:meth:`CachePool.adopt`)."""

    def reset_lanes(self) -> None:
        """Drop every lane row, any staging stream, and any leaked fork; the
        prefix index and page CONTENT (the cross-drain asset) survive."""
        self.release_staging()
        for row in list(self._forks):
            self.drop_fork_row(row)
        for slot in range(self.n_slots):
            self._drop_row(slot)

    # ------------------------------------------------------------------ audit

    def audit(self) -> list[str]:
        """Refcount bookkeeping must be reconstructible from the references
        themselves — the chaos suite's page-leak detector."""
        bad: list[str] = []
        expect = np.zeros_like(self.refcount)
        expect[DUMMY_PAGE] += 1
        for row in self.block_tables:
            for p in row:
                if p >= 0:
                    expect[p] += 1
        if self.staging is not None:
            for p in self.staging.table:
                if p >= 0:
                    expect[p] += 1
        for row in self._forks:
            for p in row:
                if p >= 0:
                    expect[p] += 1
        for p in self._index.values():
            expect[p] += 1
        if not (expect == self.refcount).all():
            drift = np.nonzero(expect != self.refcount)[0].tolist()
            bad.append(f"page refcount drift on pages {drift[:8]} "
                       f"(expected from refs != stored)")
        if len(self._free) != len(set(self._free)):
            bad.append("free list contains duplicate pages")
        free = sorted(int(p) for p in self._free)
        zero = sorted(np.nonzero(self.refcount == 0)[0].tolist())
        if free != zero:
            bad.append(f"free list does not equal zero-refcount pages "
                       f"(free={len(free)}, zero-ref={len(zero)})")
        return bad


# ===========================================================================
# the pool
# ===========================================================================


@dataclass
class LaneFork:
    """A point-in-time snapshot of one slot's paged lane: the table row (one
    fork-held ref per page) plus the fill level. Spent exactly once — by
    :meth:`CachePool.drop_fork` (accept) or :meth:`CachePool.restore_lane`
    (fault); a second release raises :class:`EngineStateError`."""

    slot: int
    pos: int
    row: np.ndarray
    live: bool = True


@dataclass
class SlotInfo:
    """One decode lane's bookkeeping (owned by the pool, read by the engine)."""

    state: str = FREE
    req: int = -1
    budget: int = 0         # this request's max_new_tokens
    emitted: int = 0
    ctx: int = 0            # prompt length + generated tokens in cache
    reused_tokens: int = 0  # prompt tokens served from the prefix store
    priority: int = 0       # preemption order: lowest-priority slot evicts first


class CachePool:
    """The slot pool: table + typed per-family states + admission policy.

    One protocol for every family: ``alloc``/``insert``/``retire`` do the
    lane surgery, ``views()`` hands the decode step its cache dict (for
    paged pools: pages + block tables, with active lanes' write blocks
    paged-in), ``adopt()`` takes a dispatched program's page arrays,
    ``commit()`` the clean step's fill levels (pinning free lanes' fill to
    0 so their dummy decodes never overflow).
    ``stage_admission`` opens the chunk-prefill stream — on a prefix hit the
    shared pages enter its block table read-only, nothing is gathered or
    copied. The prefix index survives :meth:`reset`, so reuse works across
    drains of the same engine.
    """

    def __init__(self, cfg: ModelConfig, max_len: int, n_slots: int, *,
                 prefix_cache: bool = True, block_size: int = 8,
                 prefix_pages: Optional[int] = None,
                 paged: Optional[bool] = None, spec_slack: int = 0):
        self.cfg = cfg
        self.max_len = max_len
        self.n_slots = n_slots
        self.block_size = block_size
        self.prefix_pages = prefix_pages
        # extra per-lane physical blocks for speculative verify transients
        self.spec_slack = max(int(spec_slack), 0)
        self.specs = derive_state_specs(cfg)
        self.policy = derive_policy(self.specs)
        # fully paged residency requires KV to be the whole cache state;
        # `paged=False` forces the contiguous A/B path. A max_len off the
        # block grid is fine — the lane's last block stays partially filled.
        pageable = self.policy.prefix_capable and block_size > 0
        self.paged = pageable if paged is None else bool(paged) and pageable
        self.prefix_cache = bool(prefix_cache and self.paged)
        self.stats = {"prefix_lookups": 0, "prefix_hits": 0,
                      "reused_prefix_tokens": 0}
        self._build(keep_kv=None)

    # ------------------------------------------------------------- lifecycle

    def _make_state(self, spec: StateSpec, leaves: dict) -> CacheState:
        if spec.kind == "paged_kv" and self.paged:
            nb = -(-self.max_len // self.block_size)
            store_pages = (self.prefix_pages if self.prefix_pages is not None
                           else 4 * self.n_slots * nb)
            return PagedKVState(
                spec, self.cfg, self.n_slots, self.max_len, self.block_size,
                store_pages=store_pages, prefix_cache=self.prefix_cache,
                dtype=M.kv_cache_dtype(self.cfg), spec_slack=self.spec_slack)
        if spec.kind == "paged_kv":
            return ContiguousKVState(spec, leaves)
        cls = {"ring": RingKVState, "recurrent": RecurrentState,
               "static": StaticKVState}[spec.kind]
        return cls(spec, leaves)

    def _build(self, keep_kv: Optional[PagedKVState]) -> None:
        if self.paged:
            # KV is the whole state: no contiguous lane arrays exist at all
            if keep_kv is not None:
                keep_kv.reset_lanes()
                self.states: list[CacheState] = [keep_kv]
            else:
                self.states = [self._make_state(self.specs[0], {})]
            self._pos = jnp.zeros((self.n_slots,), jnp.int32)
        else:
            cache = normalize_pos(
                M.init_decode_cache(self.cfg, self.n_slots, self.max_len),
                self.n_slots)
            self.states = [self._make_state(s, cache) for s in self.specs]
            self._pos = cache["pos"]
        self.slots: list[SlotInfo] = [SlotInfo() for _ in range(self.n_slots)]
        self._copied = 0
        self._undonated = 0
        self.take_copy_bytes()   # a kept page pool's earlier copies are dropped

    def _set_pos(self, pos: jax.Array) -> None:
        """Take the output of an eager update of the lanes' fill levels."""
        self._pos = pos
        self._copied += pos.nbytes

    def take_copy_bytes(self) -> int:
        """Bytes written by the pool's eager updates (page arrays, lane
        leaves, fill levels) since the last call. An update outside a jitted
        program writes its whole output array, so each counts that array."""
        n, self._copied = self._copied, 0
        for st in self.states:
            n, st.copied = n + st.copied, 0
        return n

    def take_undonated_bytes(self) -> int:
        """Bytes of the pool's page arrays that step programs received
        without donation, and so copied whole into their outputs, since the
        last call (see :meth:`adopt`)."""
        n, self._undonated = self._undonated, 0
        return n

    def reset(self) -> None:
        """Fresh lanes, slot table, and per-drain stats; the prefix index
        and its page content (the cross-drain asset) are retained."""
        self._build(keep_kv=self._kv)
        # stats are per drain, like the engine's event stream — only the
        # index CONTENT outlives a serve() call
        self.stats = {"prefix_lookups": 0, "prefix_hits": 0,
                      "reused_prefix_tokens": 0}

    @property
    def _kv(self) -> Optional[PagedKVState]:
        for st in getattr(self, "states", []):
            if isinstance(st, PagedKVState):
                return st
        return None

    # ------------------------------------------------------------ slot table

    def get(self, slot: int) -> SlotInfo:
        return self.slots[slot]

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.state == FREE]

    def active_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.state == ACTIVE]

    def has_work(self) -> bool:
        return any(s.state == ACTIVE for s in self.slots)

    # ----------------------------------------------------------- accounting

    def occupancy(self) -> PoolOccupancy:
        """Point-in-time capacity snapshot (attached to every
        :class:`PoolExhausted`, surfaced by ``Engine.health()``)."""
        kv = self._kv
        if kv is None:
            return PoolOccupancy(
                slots_total=self.n_slots,
                slots_used=len(self.active_slots()),
                pages_total=0, pages_used=0, prefix_pins=0)
        indexed = set(kv._index.values())
        pinned = {p for p in indexed if kv.refcount[p] > 1}
        return PoolOccupancy(
            slots_total=self.n_slots,
            slots_used=len(self.active_slots()),
            pages_total=kv.capacity - 1,   # dummy excluded
            pages_used=kv.pages_used(),
            prefix_pins=len(pinned),
        )

    def check_invariants(self) -> list[str]:
        """Audit cache accounting; returns violation descriptions (empty =
        healthy). The chaos suite runs this after every fault plan: whatever
        was injected, retire/preempt paths must release every page exactly
        once — refcounts must be reconstructible from the live references,
        and the free list must equal the zero-refcount pages."""
        bad: list[str] = []
        pos = np.asarray(self._pos)
        for i, s in enumerate(self.slots):
            if s.state == FREE and int(pos[i]) != 0:
                bad.append(f"free slot {i} has pos={int(pos[i])} (expected 0)")
        kv = self._kv
        if kv is not None:
            bad += kv.audit()
            for i, s in enumerate(self.slots):
                if s.state == FREE and (kv.block_tables[i] >= 0).any():
                    bad.append(
                        f"free slot {i} still holds pages "
                        f"{sorted(int(p) for p in kv.block_tables[i] if p >= 0)}")
        return bad

    # -------------------------------------------------------------- protocol

    def alloc(self, request: Any, rid: int, *, reused_tokens: int = 0,
              ctx: Optional[int] = None, emitted: int = 0,
              priority: Optional[int] = None,
              slot: Optional[int] = None) -> int:
        """Claim the first free lane for ``request`` (a GenerationRequest).

        The keyword overrides exist for preemption resume: a requeued request
        re-enters with ``ctx`` covering prompt + already-emitted tokens and
        ``emitted`` at its absolute emitted-token count, so budget accounting
        and the per-request RNG lane (keys indexed by emitted position)
        continue exactly where eviction cut them off. ``slot`` claims that
        SPECIFIC free lane (a speculative draft pool mirrors the target
        pool's slot assignment, so first-free would be wrong).
        """
        free = self.free_slots()
        if slot is not None:
            if self.slots[slot].state != FREE:
                raise EngineStateError(
                    f"CachePool.alloc: slot {slot} is not free")
            si = slot
        elif not free:
            raise PoolExhausted("CachePool.alloc: no free slot",
                                self.occupancy())
        else:
            si = free[0]
        self.slots[si] = SlotInfo(
            state=ACTIVE, req=rid,
            budget=request.max_new_tokens,
            emitted=emitted,
            ctx=len(request.prompt) if ctx is None else ctx,
            reused_tokens=reused_tokens,
            priority=getattr(request, "priority", 0) if priority is None
            else priority)
        return si

    def insert(self, slot: int, prefilled: dict, *, src_slot: int = 0,
               prompt=None) -> None:
        """Drop lane ``src_slot`` of a prefilled cache into lane ``slot``;
        with ``prompt``, harvest its full blocks into the prefix index.
        For paged pools the source is either the drained admission stream
        (its table row transferred; its chunks already wrote the pool's
        pages) or a contiguous prefill (pagified block by block)."""
        with trace.span(trace.POOL_MERGE):
            kv = self._kv
            if kv is not None:
                try:
                    kv.insert(prefilled, slot, src_slot)
                except _PagesExhausted as e:
                    raise PoolExhausted(str(e), self.occupancy()) from None
                src_pos = jnp.reshape(
                    jnp.asarray(prefilled["pos"], jnp.int32), (-1,))
                src_pos = src_pos[src_slot if src_pos.shape[0] > 1 else 0]
                self._set_pos(self._pos.at[slot].set(src_pos))
                if self.prefix_cache and prompt is not None:
                    kv.harvest(slot, prompt)
                return
            for st in self.states:
                st.insert(prefilled, slot, src_slot)
            src_pos = normalize_pos(prefilled,
                                    lane_count(prefilled))["pos"][src_slot]
            self._set_pos(self._pos.at[slot].set(src_pos))

    def retire(self, slot: int) -> None:
        """Free lane ``slot``: release its pages (paged), zero spec-derived
        recurrent state, pin fill to 0."""
        for st in self.states:
            st.retire(slot)
        self._set_pos(self._pos.at[slot].set(0))
        self.slots[slot] = replace(self.slots[slot], state=FREE)

    def views(self, span: int = 1, handover: bool = False) -> dict:
        """The decode-step cache dict. Paged pools page-in every active
        lane's write blocks for the next ``span`` appends here (host-side
        residency, idempotent — a retried step re-ensures the same pages).
        A speculative verify step passes ``span = k + 1``. With
        ``handover`` the dict is an :class:`interleave.Handover`: the step
        program it is given to takes the page arrays by donation, and
        :meth:`adopt` takes its output pages back."""
        with trace.span(trace.POOL_RESIDENCY):
            kv = self._kv
            if kv is not None:
                pos = np.asarray(self._pos)
                try:
                    for i, s in enumerate(self.slots):
                        if s.state == ACTIVE:
                            kv.ensure_residency(i, int(pos[i]), span)
                except _PagesExhausted as e:
                    raise PoolExhausted(str(e), self.occupancy()) from None
            out: dict = {}
            for st in self.states:
                out.update(st.views())
            out["pos"] = self._pos
            return interleave.Handover(out) if handover else out

    def adopt(self, handover: dict, out: dict) -> dict:
        """Take the page arrays a dispatched step program left in
        ``handover`` as the pool's own; returns ``out``, the program's
        cache, without them.

        Called as soon as a program is dispatched, clean or not: the fill
        levels stay as they were until :meth:`commit`. A step writes only
        its lanes' (and the stream's) positions ``[pos, pos + T)``, and in
        every layer before attention reads them, so a retried attempt that
        starts from a failed one's pages, at the same fill levels, computes
        bit-identical results. Arrays the pool handed over that are still
        alive were not donated: the program copied them whole, and their
        bytes are counted (:meth:`take_undonated_bytes`)."""
        kv = self._kv
        if kv is None:
            return out
        new = {k: handover[k] for k in interleave.PAGE_KEYS}
        for k, old in kv.pages.items():
            if old is not new[k] and not old.is_deleted():
                self._undonated += old.nbytes
        kv.pages = new
        return interleave.without_pages(out)

    def commit(self, new_cache: dict) -> None:
        """Absorb a decode step's updated cache. Free lanes decode garbage
        each step; their fill level is pinned back to 0 here so the dummy KV
        write keeps landing at block 0 (the dummy page) and never overflows.
        A paged pool's page arrays are not taken here but at dispatch
        (:meth:`adopt`)."""
        with trace.span(trace.POOL_COMMIT):
            for st in self.states:
                st.commit(new_cache)
            free = np.zeros((self.n_slots,), bool)
            for i in self.free_slots():
                free[i] = True
            self._set_pos(jnp.where(jnp.asarray(free), 0, new_cache["pos"]))

    # -------------------------------------------------------- fork / rollback

    def fork_lane(self, slot: int) -> LaneFork:
        """Snapshot an active lane before a speculative verify branch writes
        into it: O(1) — the block-table row is copied and each page gains one
        fork-held reference; no page content moves. The branch's first append
        into the (now shared) partial write block copies-on-write in
        :meth:`views`, so the snapshot's pages are never mutated."""
        kv = self._kv
        if kv is None:
            raise EngineStateError("fork_lane requires a paged pool")
        if self.slots[slot].state != ACTIVE:
            raise EngineStateError(f"fork_lane of non-active slot {slot}")
        return LaneFork(slot=slot, pos=int(np.asarray(self._pos)[slot]),
                        row=kv.fork_row(slot))

    def restore_lane(self, fork: LaneFork) -> None:
        """Reinstate a fork bit-identically (the verify branch failed): the
        branch's pages are released and the snapshot's row + fill level
        transfer back to the slot. Spends the fork."""
        kv = self._kv
        if kv is None:
            raise EngineStateError("restore_lane requires a paged pool")
        if not fork.live:
            raise EngineStateError("restore_lane on a spent fork")
        kv.restore_row(fork.slot, fork.row)
        self._set_pos(self._pos.at[fork.slot].set(fork.pos))
        fork.live = False

    def drop_fork(self, fork: LaneFork) -> None:
        """Release a fork's page references (the accept path, after
        :meth:`rollback_lane` truncated the lane). Spends the fork."""
        kv = self._kv
        if kv is None:
            raise EngineStateError("drop_fork requires a paged pool")
        if not fork.live:
            raise EngineStateError("drop_fork on a spent fork")
        kv.drop_fork_row(fork.row)
        fork.live = False

    def rollback_lane(self, slot: int, pos: int) -> None:
        """Truncate an active lane to fill level ``pos``: blocks at or beyond
        the first dead one are released (exactly once — the audit holds
        mid-round because live forks are part of it). Exact for paged KV:
        attention masks strictly by ``[0, pos)``."""
        kv = self._kv
        if kv is None:
            raise EngineStateError("rollback_lane requires a paged pool")
        kv.rollback(slot, pos)
        self._set_pos(self._pos.at[slot].set(int(pos)))

    def extract_lane(self, slot: int) -> dict:
        """A batch-1 COPY-VIEW of one contiguous lane (slices of the pool
        arrays — functional updates downstream never touch the pool). The
        draft side of speculative decoding rolls candidates out on this
        without disturbing sibling lanes; paged pools fork instead."""
        kv = self._kv
        if kv is not None:
            raise EngineStateError("extract_lane requires a contiguous pool")
        out: dict = {}
        for st in self.states:
            for k, leaf in st.views().items():
                out[k] = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
        out["pos"] = jnp.reshape(self._pos[slot], (1,))
        return out

    # ----------------------------------------------------------- admission

    def init_staging(self, batch: int = 1) -> dict:
        """A fresh CONTIGUOUS admission staging cache (non-paged pools and
        batch-prefill admission; paged streams open via
        :meth:`stage_admission`)."""
        return normalize_pos(
            M.init_decode_cache(self.cfg, batch, self.max_len), batch)

    def peek_prefix(self, prompt) -> int:
        """Reusable prefix length in tokens — no staging, no stats."""
        kv = self._kv
        if not self.prefix_cache or kv is None:
            return 0
        return len(kv.match_prefix(prompt)) * kv.block_size

    def stage_admission(self, prompt) -> tuple[dict, int]:
        """Open the batch-1 admission stream for chunk-prefilling ``prompt``.

        Paged pools: the stream shares the pool's page arrays; on a prefix
        hit the matched pages enter its block table read-only and the fill
        level starts beyond them — the returned ``skip`` is the number of
        prompt tokens the engine must NOT prefill. No page is copied and
        nothing is gathered. Exactly one stream may be open at a time; the
        engine merges it via :meth:`insert` or aborts it via
        :meth:`release_staging`.
        """
        kv = self._kv
        if kv is None:
            return self.init_staging(1), 0
        if not self.prefix_cache:
            return kv.begin_staging([]), 0
        self.stats["prefix_lookups"] += 1
        pages = kv.match_prefix(prompt)
        skip = len(pages) * kv.block_size
        if pages:
            self.stats["prefix_hits"] += 1
            self.stats["reused_prefix_tokens"] += skip
        return kv.begin_staging(pages), skip

    def staging_step_prep(self, cache: dict, n_tokens: int) -> dict:
        """Page-in the admission stream's next ``n_tokens`` write blocks
        (paged pools; contiguous staging passes through untouched). Called
        by the engine before every chunk step; idempotent under retries."""
        kv = self._kv
        if kv is None or "block_table" not in cache:
            return cache
        with trace.span(trace.POOL_RESIDENCY):
            try:
                return kv.ensure_staging(cache, n_tokens)
            except _PagesExhausted as e:
                raise PoolExhausted(str(e), self.occupancy()) from None

    def stream_cache(self, cache: dict) -> dict:
        """The admission stream's input to a chunk step. Paged pools: a
        handover of the pool's page arrays with the stream's block table and
        fill level, so the chunk writes its fresh pages where the pool keeps
        them (after this step's decode half, when there is one) and the pool
        is never forked; :meth:`adopt` takes them back. Contiguous staging
        passes through."""
        kv = self._kv
        if kv is None or "block_table" not in cache:
            return cache
        return interleave.Handover(cache, **kv.pages)

    def release_staging(self) -> None:
        """Abort the in-flight admission stream, releasing its pages
        (no-op when none is open or the pool is contiguous)."""
        kv = self._kv
        if kv is not None:
            kv.release_staging()

    def prefix_report(self) -> dict:
        """Per-drain stats (reset with the slot table) + index occupancy."""
        kv = self._kv
        return {
            "enabled": self.prefix_cache,
            "block_size": self.block_size if self.prefix_cache else 0,
            "stored_blocks": len(kv) if kv is not None else 0,
            **self.stats,
        }
