"""Profiler spans of the engine's step loop.

Each phase of ``Engine.serve`` runs inside a ``jax.profiler.TraceAnnotation``
with one of the stable names below. A span costs under a microsecond when no
profiler is running; under ``jax.profiler.trace`` it lands in the same trace,
on the same clock, as the device's "XLA Ops" and "XLA Modules" events, so a
trace reader can put each idle stretch of the device down to what the host
loop was doing.

A span sits inside another only where the code nests the work:
``ON_TOKEN`` inside ``EMIT``; ``POOL_*`` inside ``SCHEDULE`` (placing a
parked stream merges it) and inside ``SPEC`` (verify sub-steps commit);
``SAMPLE`` and ``POOL_MERGE`` inside ``ADMIT_BATCH``. No other two spans
overlap. Keyword stats carry identifiers only: ``step`` (the index of the
step's ``ScheduleEvent``) on ``DISPATCH``, ``req`` (the request's index in
the ``serve`` call) on ``ADMIT_BATCH`` and ``ON_TOKEN``.
"""
from __future__ import annotations

import jax

SCHEDULE = "engine.schedule"              # loop head: arrivals, placement, staging, planning
DISPATCH = "engine.dispatch"              # enqueue the step's programs
SAMPLE = "engine.sample"                  # the one read of check-and-sample
EMIT = "engine.emit"                      # per-lane token bookkeeping
ON_TOKEN = "engine.on_token"              # the caller's callback
POOL_COMMIT = "engine.pool.commit"        # CachePool.commit
POOL_MERGE = "engine.pool.merge"          # CachePool.insert
POOL_RESIDENCY = "engine.pool.residency"  # page-in and copy-on-write
ADMIT_BATCH = "engine.admit_batch"        # full-prompt prefill admissions
SPEC = "engine.spec"                      # draft rollouts and verify sub-steps

SPANS = (SCHEDULE, DISPATCH, SAMPLE, EMIT, ON_TOKEN, POOL_COMMIT,
         POOL_MERGE, POOL_RESIDENCY, ADMIT_BATCH, SPEC)


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``name`` (one of :data:`SPANS`)."""
    return jax.profiler.TraceAnnotation(name, **stats)
