"""Typed serving errors — the request plane's failure vocabulary.

The engine used to fail like a prototype: ``CachePool.alloc`` raised a bare
``RuntimeError`` and engine invariants were bare ``assert``s, so a caller
could not tell "the pool is full, shed load" apart from "the engine is in a
state it should never reach". These types make the distinction part of the
API:

* :class:`PoolExhausted` — a capacity condition. Carries a
  :class:`PoolOccupancy` snapshot (slots, prefix-store pages, pins) taken at
  the moment of failure, so admission control can decide to preempt, queue,
  or shed without re-querying a pool whose state may already have moved on.
* :class:`AdmissionRejected` — backpressure at the front door: the bounded
  admission queue is full and the submit is refused (reject-on-full, never
  silent unbounded buffering).
* :class:`EngineStateError` — an invariant violation: the engine was driven
  in an order its state machine does not allow (serving without a prepared
  pool, cancelling outside a serve, a request left non-terminal). These were
  ``assert``s before; they are real exceptions with actionable messages now,
  and they survive ``python -O``.

All inherit :class:`ServingError`, so a serving front can catch the whole
family at one boundary.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class PoolOccupancy:
    """Point-in-time capacity snapshot of a :class:`~repro.serve.cache.CachePool`.

    ``pages_*`` describe the prefix store's physical page pool (zero when the
    store is disabled or the family is not prefix-capable); ``prefix_pins``
    counts distinct store pages currently referenced by ACTIVE slots' block
    tables — pages an eviction policy must treat as hot.
    """

    slots_total: int
    slots_used: int
    pages_total: int
    pages_used: int
    prefix_pins: int

    @property
    def slots_free(self) -> int:
        return self.slots_total - self.slots_used

    @property
    def pages_free(self) -> int:
        return self.pages_total - self.pages_used

    def to_json(self) -> dict:
        return {
            "slots_total": self.slots_total, "slots_used": self.slots_used,
            "slots_free": self.slots_free, "pages_total": self.pages_total,
            "pages_used": self.pages_used, "pages_free": self.pages_free,
            "prefix_pins": self.prefix_pins,
        }


class ServingError(RuntimeError):
    """Base of every typed serving failure."""


class PoolExhausted(ServingError):
    """No lane (or page) could be claimed; carries the occupancy snapshot."""

    def __init__(self, message: str, occupancy: PoolOccupancy,
                 injected: bool = False):
        super().__init__(f"{message} [occupancy: slots {occupancy.slots_used}/"
                         f"{occupancy.slots_total} used, pages "
                         f"{occupancy.pages_used}/{occupancy.pages_total} used,"
                         f" {occupancy.prefix_pins} pinned]")
        self.occupancy = occupancy
        self.injected = injected  # raised by a FaultPlan, not real pressure


class AdmissionRejected(ServingError):
    """Bounded admission queue is full — the submit was refused."""

    def __init__(self, depth: int, max_queue: int):
        super().__init__(
            f"admission queue full ({depth}/{max_queue}); retry after a "
            f"drain or raise Scheduler.max_queue")
        self.depth = depth
        self.max_queue = max_queue


class EngineStateError(ServingError):
    """The engine was driven in an order its state machine does not allow."""


class KernelFault(ServingError):
    """A kernel-level failure attributed to one dispatched op (``op``) —
    raised by the engine's NaN/Inf logit guard or injected by a
    :class:`~repro.serve.faults.FaultPlan`; the engine answers it by walking
    that op down the degradation ladder and retrying the step. It is the
    only step exception the engine recovers from: a lowering, compile or
    device runtime error propagates out of ``Engine.serve``."""

    def __init__(self, op: str, message: str = "", injected: bool = False):
        super().__init__(message or f"kernel fault in {op!r}")
        self.op = op
        self.injected = injected
