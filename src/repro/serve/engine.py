"""Inference engine: slot-level continuous batching with BLOCKED/HBCEM/LBIM.

The serving surface is request-level: ``Engine.serve(requests)`` takes
``GenerationRequest`` objects (per-request ``max_new_tokens`` / ``eos_id`` /
``SamplingParams`` / streaming ``on_token`` callback) and returns
index-aligned ``GenerationResult`` objects. Engines are cheap views over a
``ServingModel`` — the load-time artifact that pins the attention backend,
pre-quantizes the W8A8 decode weights, and lays out the dual-layout cache
specs once (``serve.serving_model``).

The decode cache is a typed :class:`repro.serve.cache.CachePool`: the pool
owns the slot table and one state object per cache family (paged dense KV,
gemma2 rings, RWKV/Mamba recurrent state, audio cross memory) behind ONE
protocol — ``alloc``/``insert``/``retire``/``views``/``commit`` — so this
engine contains no family-specific cache branches. Admission behaviour the
old engine special-cased per family (ring caches admit via full batch-1
prefills; recurrent state rejects padded ragged batches) is now an
:class:`~repro.serve.cache.AdmissionPolicy` the pool derives from its own
state specs.

Sequences retire mid-flight — per-slot ``max_new_tokens`` budgets and
per-request ``eos_id`` free a lane the step it finishes — and the head of
the pending queue is *chunk-prefilled ahead* into a staging cache, then
dropped into the next freed lane:

* **LBIM**    — the admission chunk is fused into the SAME XLA program as the
  running decode step (``core.interleave.fused_step``; the paper's
  MACT_LDB/MACB_LDT Pbank split), so prefill of ANY pending request overlaps
  with whatever is decoding, every step.
* **HBCEM**   — decode runs at full internal bandwidth (PIM_MAC_FM); the
  admission chunk executes as a separate program in the same engine step.
* **BLOCKED** — prior-PIM serialization: admission preempts and all decodes
  stall until the pending request is fully loaded.

**Prefix reuse** (``prefix_cache``, default on where the family supports
it): the pool content-hashes full ``chunk``-token blocks of every admitted
prompt; a later prompt sharing that block prefix is staged with the shared
pages *gathered* into its staging cache instead of prefilled, so the chunk
stream starts at the first un-shared token. Reused tokens are recorded per
``ScheduleEvent`` and priced by ``pimsim.scheduler.replay_events`` as
skipped processor prefill; ``schedule_report()`` exposes hit counts and the
strictly-lower ``prefill_tokens``. Reuse changes the schedule only — greedy
tokens stay identical to a cold prefill.

All modes emit identical tokens per request — a slot's decode depends only on
its own cache lane, and sampling randomness is a per-REQUEST RNG lane
(``sampling.request_key``) that never sees slot indices or admission order.
Free lanes keep flowing through the fixed-shape decode batch (their garbage
sample is pinned by ``sampling.sample_masked``'s done mask; the pool pins
their fill level to 0 at every ``commit``), and admission chunks are never
padded, so state-carrying families stream through the same path.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import interleave
from repro.core.dispatch import DegradationLadder
from repro.core.pim_modes import (Mode, StepChoice, StepPlan, StepPolicy,
                                  StepSignals, plan_step)
from repro.models import model as M
from repro.serve import sampling, trace
from repro.serve.api import (FINISH_CANCELLED, FINISH_EOS, FINISH_FAILED,
                             FINISH_LENGTH, FINISH_TIMEOUT, TERMINAL_STATES,
                             GenerationRequest, GenerationResult, RequestState)
from repro.serve.cache import ACTIVE, CachePool
from repro.serve.errors import EngineStateError, KernelFault, PoolExhausted
from repro.serve.faults import FaultPlan
from repro.serve.serving_model import ServingModel
from repro.serve.spec import SpecConfig, SpecDecoder


@dataclass
class ScheduleEvent:
    plan: StepPlan
    decode_batch: int       # active decode lanes this step
    prefill_tokens: int     # admission-prefill tokens consumed this step
    decode_ctx: int = 0     # max context (cache fill) among active lanes
    reused_tokens: int = 0  # prompt tokens served from the prefix store
    attempts: int = 1       # 1 + ladder retries this step (pimsim prices all)
    slow_penalty: int = 0   # injected slow-step clock penalty (engine steps)
    degraded: bool = False  # step ran below its base backend rungs
    kv_splits: int = 1      # paged decode KV-split fan-out (pimsim pricing)
    # --- traffic plane (arrival-driven serving telemetry) -----------------
    mode: str = ""          # governing Mode this step (step-policy choice)
    arrivals: int = 0       # requests that became visible at this boundary
    queue_depth: int = 0    # arrived-but-unadmitted requests after arrivals
    emitted_tokens: int = 0  # tokens emitted at this event's boundary
    first_tokens: int = 0    # requests whose FIRST token emitted here
    idle_steps: int = 0      # pure-idle clock jump to the next arrival
    #                          (idle events advance the clock by this gap
    #                           instead of 1 + slow_penalty)
    # --- speculative decoding (plan.spec steps; all 0 otherwise) ----------
    spec_drafted: int = 0         # draft tokens proposed this round
    spec_accepted: int = 0        # draft tokens accepted this round
    spec_draft_steps: int = 0     # draft-model GEMV steps (catch-up + chain)
    verify_tokens: int = 0        # target positions scored: lanes x (K+1)
    spec_max_emitted: int = 0     # most tokens any one lane emitted
    draft_prefill_tokens: int = 0  # draft-lane (re)sync prefill tokens
    # --- page pool --------------------------------------------------------
    pool_copy_bytes: int = 0  # bytes written by the pool's eager updates
    #                           from the previous step's end to this one's
    #                           (whole arrays: CachePool.take_copy_bytes)
    undonated_page_bytes: int = 0  # bytes of page arrays the step's programs
    #                                received without donation, and so
    #                                copied whole (take_undonated_bytes)
    # --- host syncs -------------------------------------------------------
    result_pulls: int = 0  # blocking device-to-host reads of the NaN guard
    #                        and token selection (retried attempts included)


class ScheduleReport(dict):
    """``schedule_report()``'s dict plus a machine-readable export — the
    benchmark trajectory (BENCH_serving.json) is diffed across PRs."""

    def to_json(self) -> dict:
        out = dict(self)
        out["modes"] = sorted(out["modes"])
        return out


@dataclass
class _Prefill:
    """One in-flight chunked admission (no lane reserved — it parks when
    loaded and drops into the next freed slot). ``off`` starts beyond the
    prefix-index hit: those blocks enter the stream's block table read-only,
    never prefilled and never copied."""
    req: int
    toks: np.ndarray        # (1, n) full prompt
    cache: dict             # batch-1 cache being filled chunk by chunk
    off: int = 0
    reused: int = 0

    @property
    def remaining(self) -> int:
        return self.toks.shape[1] - self.off


@dataclass
class _Ready:
    """A fully prefilled request parked until a lane frees. ``prompt``/``ctx``
    describe the *staged* token span — for a preempted request that is
    prompt + already-emitted tokens, so resume accounting (and the prefix
    harvest) covers everything actually in the lane."""
    req: int
    cache: dict
    first_tok: int
    reused: int = 0
    prompt: list = field(default_factory=list)
    ctx: int = 0


@dataclass
class Engine:
    cfg: ModelConfig
    params: dict
    max_len: int = 256
    slots: int = 4
    mode: Mode = Mode.HBCEM
    chunk: int = 8
    events: list = field(default_factory=list)
    serving: Optional[ServingModel] = None
    prefix_cache: bool = True
    pool: Optional[CachePool] = None
    # --- robustness knobs -------------------------------------------------
    fault_plan: Optional[FaultPlan] = None  # deterministic chaos injection
    nan_guard: bool = True                  # finite-logits check per step
    max_step_attempts: int = 4              # ladder retries before step fails
    step_limit: Optional[int] = None        # watchdog; None -> sized from work
    spec: Optional[SpecConfig] = None       # draft/verify speculative decoding
    # --- traffic plane ----------------------------------------------------
    step_policy: Optional[StepPolicy] = None  # per-step mode choice; None ->
    #                                           the static `mode` pin governs
    spec_refill: bool = True  # scale admission quantum with emitted tokens
    #                           (speculating lanes drain budgets (k+1)x
    #                            faster than retirements alone suggest)

    def __post_init__(self) -> None:
        if self.serving is None:
            self.serving = ServingModel.prepare(
                self.cfg, self.params, max_len=self.max_len, slots=self.slots)
        # the artifact is the source of truth for load-time decisions
        self.cfg = self.serving.cfg
        self.params = self.serving.params
        self.max_len = self.serving.max_len
        if self.spec is not None:
            self.spec.validate()
        if self.pool is None:
            # prefix blocks align with the admission chunk so a reuse run's
            # chunk boundaries match a cold run's exactly; spec_slack buys
            # each lane room for a verify round's transient k+1 appends
            self.pool = self.serving.cache_pool(
                slots=self.slots, prefix_cache=self.prefix_cache,
                block_size=self.chunk,
                spec_slack=self.spec.k if self.spec is not None else 0)
        elif self.pool.n_slots != self.slots:
            raise ValueError(
                f"pool has {self.pool.n_slots} slots, engine expects {self.slots}")
        elif self.pool.prefix_cache and self.pool.block_size != self.chunk:
            # reuse == cold-run token identity rests on shared chunk boundaries
            raise ValueError(
                f"pool block_size={self.pool.block_size} must equal engine "
                f"chunk={self.chunk} when prefix caching is on")
        self.prefix_cache = self.pool.prefix_cache
        self.spec_dec: Optional[SpecDecoder] = None
        if self.spec is not None:
            if not self.pool.paged:
                raise ValueError(
                    "speculative decoding requires a fully paged target pool "
                    "(verify branches fork/rollback block-table rows); this "
                    "pool is contiguous")
            if self.pool.spec_slack < self.spec.k:
                raise ValueError(
                    f"pool spec_slack={self.pool.spec_slack} < spec.k="
                    f"{self.spec.k}: a verify round near max_len would "
                    f"overflow the lane's block grid")
            self.spec_dec = SpecDecoder(
                self.spec.draft, self.serving, slots=self.slots,
                max_len=self.max_len, k=self.spec.k)
        # sticky across serve() calls: a kernel that faulted stays demoted,
        # and health counters accumulate for the engine's lifetime
        self.ladder = DegradationLadder(self.cfg)
        self._health = {"preemptions": 0, "timeouts": 0, "cancellations": 0,
                        "failures": 0, "retried_steps": 0, "injected_faults": 0}
        self._in_serve = False
        self._cancel: set = set()
        self.last_results: Optional[list[GenerationResult]] = None
        self.last_requests: Optional[list[GenerationRequest]] = None
        self._last_ev: Optional[ScheduleEvent] = None
        self._arrived_unstamped = 0
        self._queue_depth = 0

    def _require(self, cond: bool, msg: str) -> None:
        """Engine state-machine invariant (EngineStateError, not assert —
        survives ``python -O`` and tells the caller what was violated)."""
        if not cond:
            raise EngineStateError(msg)

    def _push_event(self, ev: ScheduleEvent) -> None:
        if not ev.mode:
            ev.mode = self.mode.value
        ev.arrivals = self._take_arrivals()
        ev.queue_depth = self._queue_depth
        ev.pool_copy_bytes = self.pool.take_copy_bytes()
        ev.undonated_page_bytes = self.pool.take_undonated_bytes()
        self.events.append(ev)
        # an idle event jumps the clock straight to the next arrival; every
        # other event is one engine step plus any injected slow penalty
        self._clock += ev.idle_steps if ev.idle_steps else 1 + ev.slow_penalty
        self._last_ev = ev

    def _take_arrivals(self) -> int:
        n, self._arrived_unstamped = self._arrived_unstamped, 0
        return n

    def _end_step(self) -> None:
        """Charge the pool's eager copies made since the step's event was
        pushed (its commit, retires and merges) to that event."""
        self._last_ev.pool_copy_bytes += self.pool.take_copy_bytes()

    # ------------------------------------------------------------------ API

    def serve(self, requests: Sequence[GenerationRequest]) -> list[GenerationResult]:
        """Serve ``requests`` through the persistent decode pool.

        Each request decodes to its OWN ``max_new_tokens`` budget, retires
        the step it emits its ``eos_id`` (defaulting to the config's; the
        EOS token is included in the output), samples on its private RNG
        lane, and — if ``on_token`` is set — streams every emitted token
        synchronously. Results are index-aligned with ``requests``; on
        return every result is in a TERMINAL state (engine contract — no
        request is ever left hanging, whatever faulted mid-run).

        Robustness semantics (see README "Failure semantics"):

        * deadlines (``ttft_deadline``/``deadline``, engine steps from serve
          start) and :meth:`cancel` are enforced at step boundaries;
        * lane pressure preempts the lowest-priority RUNNING slot — the
          victim requeues with its emitted tokens and resumes bit-identically
          (re-prefill of prompt + emitted tokens on the same RNG lane);
        * a :class:`KernelFault` (injected, or a NaN/Inf logit trip) demotes
          the implicated op down the dispatch ladder and retries the step;
          only ladder exhaustion fails the step's in-flight requests (others
          continue). Any other exception raised by a step propagates.
        """
        self._require(self.serving is not None and self.pool is not None,
                      "Engine not prepared: serving artifact / cache pool "
                      "missing (construct via Engine(cfg, params) or "
                      "ServingModel.engine())")
        reqs = list(requests)
        for r in reqs:
            r.validate(self.max_len)
        n = len(reqs)
        self._reqs = reqs
        self._eos = [r.eos_id if r.eos_id is not None else self.cfg.eos_id
                     for r in reqs]
        # host copies, made once: a step's sampled lanes upload theirs
        self._base_keys = [
            np.asarray(sampling.request_key(r.sampling.seed, r.prompt))
            if r.sampling.temperature > 0 else None for r in reqs]
        results = [GenerationResult(prompt_len=len(r.prompt),
                                    arrival_step=r.arrival_step)
                   for r in reqs]
        self._results = results

        self.events.clear()
        pool = self.pool
        ladder = self.ladder
        H = self._health
        faults = self.fault_plan
        if faults is not None:
            for f in faults.faults:  # a plan replays identically per serve
                f.fired = False
        pool.reset()  # fresh lanes + slot table; the prefix store survives
        spec_dec = self.spec_dec
        if spec_dec is not None:
            spec_dec.reset()
        # the ARRIVAL plane: a request is invisible to admission (and to the
        # step policy's queue-depth signals) until the engine-step clock
        # reaches its arrival_step. `pending` is arrival-ordered (FIFO ties
        # by submission index); `queue` holds only arrived requests.
        pending: list[int] = sorted(range(n),
                                    key=lambda r: (reqs[r].arrival_step, r))
        queue: list[int] = []
        cur_tok = np.zeros((self.slots,), np.int32)
        stream: Optional[_Prefill] = None
        ready: Optional[_Ready] = None
        self._pending_reuse = 0
        self._clock = 0
        self._arrived_unstamped = 0
        self._queue_depth = 0
        self._last_ev: Optional[ScheduleEvent] = None
        self._cancel.clear()
        self._in_serve = True
        iters = 0
        limit = self.step_limit if self.step_limit is not None else (
            64 + max((r.arrival_step for r in reqs), default=0)
            + 8 * sum(len(r.prompt) + r.max_new_tokens for r in reqs))

        def ext_prompt(r: int) -> list[int]:
            """Admission token span: prompt + already-emitted tokens, so a
            preempted request resumes exactly where eviction cut it off."""
            return list(reqs[r].prompt) + results[r].tokens

        def emit(si: int, tok: int) -> None:
            """Record one token for slot ``si``; retire the lane when done.

            Latency marks land here: tokens materialize at the boundary the
            step's event just advanced the clock to, so ``self._clock`` IS
            the token's engine-step timestamp (and the event the token is
            attributed to is the most recently pushed one).
            """
            s = pool.get(si)
            r = reqs[s.req]
            res = results[s.req]
            res.tokens.append(tok)
            if res.first_token_step is None:
                res.first_token_step = self._clock
                if self._last_ev is not None:
                    self._last_ev.first_tokens += 1
            if self._last_ev is not None:
                self._last_ev.emitted_tokens += 1
            if r.on_token is not None:
                with trace.span(trace.ON_TOKEN, req=s.req):
                    r.on_token(tok)
            s.emitted += 1
            s.ctx += 1
            eos = self._eos[s.req]
            if eos is not None and tok == eos:
                res.finish_reason = FINISH_EOS
            elif s.emitted >= s.budget:
                res.finish_reason = FINISH_LENGTH
            else:
                return
            res.state = RequestState.FINISHED
            res.finish_step = self._clock
            pool.retire(si)
            if spec_dec is not None:  # the draft mirror never outlives it
                spec_dec.retire_lane(si)

        def preempt(si: int) -> None:
            """Evict lane ``si`` under pressure: retire (pages released),
            requeue at the head with emitted tokens kept. Resumption is
            bit-identical by the per-request RNG-lane contract."""
            r = pool.get(si).req
            pool.retire(si)
            if spec_dec is not None:
                spec_dec.retire_lane(si)
            H["preemptions"] += 1
            results[r].preemptions += 1
            results[r].state = RequestState.QUEUED
            queue.insert(0, r)

        def alloc_guarded(rdy: _Ready) -> int:
            """``pool.alloc`` with injected-exhaustion + preemption healing."""
            r = rdy.req
            injected = (faults is not None and
                        faults.take(self._clock, "alloc_fail") is not None)
            if injected:
                H["injected_faults"] += 1  # models fragmentation/contention
            else:
                try:
                    return pool.alloc(reqs[r], r, reused_tokens=rdy.reused,
                                      ctx=rdy.ctx,
                                      emitted=len(results[r].tokens))
                except PoolExhausted:
                    pass
            # exhausted (injected or real): preempt the lowest-priority
            # active slot the incoming request outranks-or-ties
            victims = sorted(
                (pool.get(si).priority, si) for si in pool.active_slots()
                if pool.get(si).priority <= reqs[r].priority)
            if not victims:
                return -1  # stays parked; retried next boundary
            preempt(victims[0][1])
            return pool.alloc(reqs[r], r, reused_tokens=rdy.reused,
                              ctx=rdy.ctx, emitted=len(results[r].tokens))

        def place(rdy: _Ready) -> int:
            """Drop a fully prefilled request into a lane; returns the lane
            (its first token still to emit), or -1 while it stays parked."""
            si = alloc_guarded(rdy)
            if si < 0:
                return -1
            pool.insert(si, rdy.cache, prompt=rdy.prompt or None)
            results[rdy.req].reused_prefix_tokens += rdy.reused
            results[rdy.req].state = RequestState.RUNNING
            cur_tok[si] = rdy.first_tok
            return si

        def take_next() -> int:
            """Pop the queue's head for admission."""
            r = queue.pop(0)
            self._queue_depth = len(queue)
            results[r].state = RequestState.ADMITTED
            if results[r].admit_step is None:  # first admission only:
                results[r].admit_step = self._clock  # re-queues after
            #                          preemption never re-count waiting
            return r

        def evict(r: int, state: RequestState, reason: str,
                  error: Optional[str] = None) -> None:
            """Force request ``r`` terminal wherever it currently lives."""
            nonlocal stream, ready
            if results[r].state in TERMINAL_STATES:
                return
            if r in queue:
                queue.remove(r)
            if stream is not None and stream.req == r:
                stream = None
                pool.release_staging()  # the stream's pages go back
            if ready is not None and ready.req == r:
                ready = None
                pool.release_staging()  # its un-inserted handle too
            for si in pool.active_slots():
                if pool.get(si).req == r:
                    pool.retire(si)
                    if spec_dec is not None:
                        spec_dec.retire_lane(si)
            results[r].state = state
            results[r].finish_reason = reason
            results[r].error = error
            results[r].finish_step = self._clock

        def sweep() -> None:
            """Step-boundary enforcement: cancellations, then deadlines.

            Deadlines are measured from each request's ARRIVAL step (legacy
            arrival 0 == from serve() start), so a late arrival's budget
            starts when it becomes visible, not when the drain began.
            """
            for r in sorted(self._cancel):
                if results[r].state not in TERMINAL_STATES:
                    evict(r, RequestState.CANCELLED, FINISH_CANCELLED)
                    H["cancellations"] += 1
            self._cancel.clear()
            for r in range(n):
                if results[r].state in TERMINAL_STATES:
                    continue
                rq = reqs[r]
                arr = rq.arrival_step
                if (rq.ttft_deadline is not None and not results[r].tokens
                        and self._clock >= arr + rq.ttft_deadline):
                    evict(r, RequestState.TIMED_OUT, FINISH_TIMEOUT,
                          f"no first token by ttft_deadline="
                          f"{rq.ttft_deadline} steps after arrival {arr} "
                          f"(step {self._clock})")
                    H["timeouts"] += 1
                elif (rq.deadline is not None
                        and self._clock >= arr + rq.deadline):
                    evict(r, RequestState.TIMED_OUT, FINISH_TIMEOUT,
                          f"not finished by deadline={rq.deadline} steps "
                          f"after arrival {arr} (step {self._clock})")
                    H["timeouts"] += 1

        def admit_arrivals() -> None:
            """Move requests whose arrival step the clock has reached from
            the pending plane into the admission queue (arrival order)."""
            while pending and reqs[pending[0]].arrival_step <= self._clock:
                r = pending.pop(0)
                if results[r].state not in TERMINAL_STATES:
                    queue.append(r)
                    self._arrived_unstamped += 1

        def ttft_slack() -> Optional[int]:
            """Tightest TTFT slack among first-token-less live requests that
            declare a ttft_deadline (arrived or in admission); None if none
            do. The step policy reads this as deadline pressure."""
            slacks = [reqs[r].arrival_step + reqs[r].ttft_deadline - self._clock
                      for r in range(n)
                      if reqs[r].ttft_deadline is not None
                      and reqs[r].arrival_step <= self._clock
                      and results[r].state not in TERMINAL_STATES
                      and results[r].first_token_step is None]
            return min(slacks) if slacks else None

        while queue or pending or stream is not None or ready is not None \
                or pool.has_work():
            iters += 1
            if iters > limit:
                for r in range(n):  # watchdog: nothing hangs, ever
                    if results[r].state not in TERMINAL_STATES:
                        evict(r, RequestState.FAILED, FINISH_FAILED,
                              f"watchdog: step limit {limit} exceeded")
                        H["failures"] += 1
                break
            with trace.span(trace.SCHEDULE):
                admit_arrivals()
                sweep()
                self._queue_depth = len(queue)

                # -- nothing to run but arrivals still due: jump the clock
                # to the next arrival as ONE zero-work idle event (pimsim
                # prices it at zero busy time; the gap is recorded so
                # replays map the engine clock onto the simulated timeline
                # exactly)
                if (not queue and stream is None and ready is None
                        and not pool.has_work() and pending):
                    gap = reqs[pending[0]].arrival_step - self._clock
                    self._require(gap > 0,
                                  "idle jump planned with a due arrival")
                    self._push_event(ScheduleEvent(
                        plan_step(self.mode, False, False, 0), 0, 0,
                        idle_steps=gap))
                    continue

                # -- a parked request takes the first freed lane
                lane = None
                if ready is not None and pool.free_slots():
                    lane = place(ready)

                # -- priority preemption: a parked admission outranking a
                # running slot evicts the lowest-priority strict underdog
                elif ready is not None:
                    victims = sorted(
                        (pool.get(si).priority, si)
                        for si in pool.active_slots()
                        if pool.get(si).priority < reqs[ready.req].priority)
                    if victims:
                        preempt(victims[0][1])
                        continue
            if lane is not None:
                if lane >= 0:
                    with trace.span(trace.EMIT):
                        emit(lane, ready.first_tok)
                    ready = None
                continue

            active = pool.active_slots()
            if not (queue or stream is not None or ready is not None
                    or active):
                break  # sweep() emptied the engine

            # -- drained pool, nothing staged: batch-prefill straight into
            # lanes (prefix-hit requests fall through to the chunk-streaming
            # path below so their shared blocks are mapped, not recomputed)
            if not active and stream is None and ready is None and queue:
                if self._admit_batch(queue, cur_tok, emit):
                    continue

            # -- ring-cache configs: the W-slot ring is a steady-state
            # decode structure and cannot ingest multi-token chunks, so
            # admission is one full batch-1 prefill pass — a serialization
            # point in every mode.
            if (stream is None and ready is None and queue
                    and not pool.policy.chunkable):
                with trace.span(trace.ADMIT_BATCH, req=queue[0]):
                    ready = self._prefill_one(take_next())
                continue

            with trace.span(trace.SCHEDULE):
                # -- stage the next pending request (one admission in flight)
                if stream is None and ready is None and queue:
                    r = take_next()
                    p = ext_prompt(r)
                    staging, skip = pool.stage_admission(p)
                    self._pending_reuse += skip
                    # the stream keeps its table and fill level: its chunks
                    # take the pool's page arrays at each dispatch
                    stream = _Prefill(
                        req=r, toks=np.asarray([p], np.int32),
                        cache=interleave.without_pages(staging), off=skip,
                        reused=skip)

                # starvation-aware admission rate: each FREE lane is wasted
                # decode bandwidth, so the controller lets the processor run a
                # bigger prefill quantum per step the more lanes sit empty (1x
                # when the stream merely runs ahead of retirement, up to
                # `slots`x when the pool is starved). Under speculation lanes
                # drain budgets up to (k+1)x faster than retirements alone
                # suggest, so the quantum also scales with the EMITTED-token
                # rate of the last decode event (`spec_refill`) — refilling by
                # retirements only starves the very batch the verify GEMM win
                # depends on. Quanta stay whole multiples of `chunk` with at
                # most one sub-chunk tail per prompt, so the fused/prefill
                # program shapes — and the jit cache — stay bounded by (slots +
                # spec depth) x chunk.
                c = 0
                if stream is not None:
                    n_free = len(pool.free_slots())
                    boost = max(1, n_free)
                    if (self.spec_refill and spec_dec is not None
                            and self._last_ev is not None
                            and self._last_ev.decode_batch > 0):
                        e = self._last_ev
                        per_lane = -(-e.emitted_tokens // e.decode_batch)
                        boost = max(boost, per_lane)
                    if stream.remaining >= self.chunk:
                        c = self.chunk * min(boost,
                                             stream.remaining // self.chunk)
                    else:
                        c = stream.remaining
                # -- per-step mode: the step policy (when installed) resolves
                # LBIM-vs-HBCEM and speculative participation from the live
                # queue-depth / deadline-slack signals; otherwise the static
                # `mode` pin governs, with speculation always allowed.
                choice = StepChoice(self.mode)
                if self.step_policy is not None:
                    choice = self.step_policy.choose(StepSignals(
                        clock=self._clock, active=len(active),
                        free=len(pool.free_slots()),
                        queue_depth=len(queue), pending_arrivals=len(pending),
                        stream_remaining=(stream.remaining
                                          if stream is not None else 0),
                        backlog_prefill_tokens=sum(
                            len(ext_prompt(r)) for r in queue),
                        backlog_decode_tokens=sum(
                            reqs[r].max_new_tokens - len(results[r].tokens)
                            for r in queue),
                        min_ttft_slack=ttft_slack()))
                step_mode = choice.mode
                # -- speculative draft depth per lane: the engine-wide k,
                # capped by the request's own spec_k and by its remaining
                # budget (the verify round emits at most k+1 tokens; the last
                # budgeted token needs no speculation). Computed BEFORE
                # planning so a round where nothing drafts is a plain decode
                # step, not a mislabeled (and mispriced) SPEC_VERIFY. A policy
                # that withholds spec this step leaves spec_ks empty — draft
                # lanes stay synced through the plain path's note_emitted.
                spec_ks: dict[int, int] = {}
                if spec_dec is not None and choice.allow_spec:
                    for si in active:
                        s = pool.get(si)
                        rk = reqs[s.req].spec_k
                        k_eff = min(self.spec.k if rk is None else rk,
                                    self.spec.k, s.budget - s.emitted - 1)
                        if k_eff > 0:
                            spec_ks[si] = k_eff
                plan = plan_step(step_mode, bool(active), stream is not None,
                                 c, spec=bool(spec_ks))
                if stream is not None and c > 0:
                    # page-in the stream's write blocks for this quantum
                    # (host-side residency; idempotent under ladder retries)
                    stream.cache = pool.staging_step_prep(stream.cache, c)

            # ---- guarded step execution: on a KernelFault (injected or a
            # NaN/Inf trip), demote the implicated op down the dispatch ladder
            # and retry. The programs update the pool's page arrays in place
            # (donated), and the pool adopts their outputs at dispatch; fill
            # levels and the stream move only at a clean step's commit, so a
            # retry rewrites the failed attempt's positions before reading
            # them and never double-appends.
            dparams = self.serving.decode_params
            logits = pre_logits = new_cache = new_scache = None
            attempts, pulls, step_ok = 0, 0, False
            # -- each attempt forks every verify participant afresh: the
            # branch's appends copy-on-write against the snapshot, and each
            # fork is spent exactly once — restored (bit-identical row and
            # refcounts) the moment an attempt dies, dropped after accept
            drafts: dict[int, list[int]] = {}
            forks: dict = {}
            pos_before: dict[int, int] = {}
            span = 1
            if plan.spec:
                spec_dec.begin_round()
            while attempts < self.max_step_attempts:
                attempts += 1
                cfg_step = ladder.apply(self.cfg)
                try:
                    if faults is not None:
                        f = faults.take(self._clock, "kernel_exc",
                                        pred=lambda f: ladder.kernel_live(f.op))
                        if f is not None:
                            H["injected_faults"] += 1
                            raise KernelFault(f.op, injected=True)
                    logits = pre_logits = new_cache = new_scache = None
                    span = 1
                    if plan.spec:
                        # draft rollouts: functional w.r.t. the draft pool
                        # (only finish_round commits), so a retried attempt
                        # simply re-drafts; lane (re)sync is idempotent
                        with trace.span(trace.SPEC):
                            spec_dec.prune({si: pool.get(si).req
                                            for si in active})
                            dcfg = ladder.apply(spec_dec.draft_cfg)
                            drafts = {}
                            for si, k_eff in spec_ks.items():
                                s = pool.get(si)
                                spec_dec.ensure_lane(si, s.req, reqs[s.req],
                                                     ext_prompt(s.req), dcfg)
                                drafts[si] = spec_dec.rollout(si, k_eff,
                                                              dcfg)
                            span = 1 + max(len(d) for d in drafts.values())
                            forks = {si: pool.fork_lane(si) for si in active}
                            pos_before = {si: forks[si].pos for si in active}
                    views = (pool.views(span=1, handover=True)
                             if plan.decode else None)
                    with trace.span(trace.DISPATCH, step=len(self.events)):
                        feed = jnp.asarray(cur_tok)[:, None]
                        if plan.fused:
                            self._require(stream is not None,
                                          "fused step planned without an "
                                          "admission stream in flight")
                            chunk_toks = jnp.asarray(
                                stream.toks[:, stream.off:stream.off + c])
                            logits, new_cache, pre_logits, new_scache = \
                                interleave.fused_step(
                                    dparams, views, feed, stream.cache,
                                    chunk_toks, cfg_step)
                            new_cache = pool.adopt(views, new_cache)
                        else:
                            if plan.decode:
                                logits, new_cache = \
                                    interleave.decode_only_step(
                                        dparams, views, feed, cfg_step)
                                new_cache = pool.adopt(views, new_cache)
                            if plan.prefill_chunk:
                                self._require(
                                    stream is not None,
                                    "prefill chunk planned without an "
                                    "admission stream in flight")
                                chunk_toks = jnp.asarray(
                                    stream.toks[:, stream.off:stream.off + c])
                                # over the decode half's output pages
                                scache = pool.stream_cache(stream.cache)
                                pre_logits, new_scache = \
                                    interleave.prefill_chunk_step(
                                        dparams, scache, chunk_toks, cfg_step)
                                new_scache = pool.adopt(scache, new_scache)
                        # the token rule's inputs go up while the step runs:
                        # a decode lane keys on its request's emitted count,
                        # as does the admission stream's last position (its
                        # first token once the stream drains)
                        lanes = first = None
                        if plan.decode:
                            lanes = self._lanes([
                                (pool.get(si).req, pool.get(si).emitted)
                                if si in active else None
                                for si in range(self.slots)])
                        if plan.prefill_chunk:
                            first = self._lanes(
                                [(stream.req, len(results[stream.req].tokens))])
                        if span == 1:
                            out = self._check(logits, lanes, pre_logits,
                                              first)
                    views = None
                    if plan.spec and span > 1:
                        # Verify scores every span position through the SAME
                        # (slots, 1) decode program plain decode runs, each
                        # committed into the forked rows before the next —
                        # so both the verify logits AND the accepted tokens'
                        # KV are bit-identical to the non-spec path. (A
                        # T=K+1 batched forward rounds bf16 reductions
                        # differently, which flips near-tie argmaxes and
                        # poisons the cache ulp-by-ulp even at acceptance
                        # 1.0.) On hardware the K+1 scores fuse into one
                        # weights-resident GEMM; pimsim prices the event
                        # that way (`latency.verify_step_time`).
                        with trace.span(trace.SPEC):
                            vlogits = [logits]
                            pool.commit(new_cache)
                            new_cache = None
                            for j in range(1, span):
                                feed_j = np.zeros((self.slots, 1), np.int32)
                                for si, d in drafts.items():
                                    if j - 1 < len(d):
                                        feed_j[si, 0] = d[j - 1]
                                views = pool.views(span=1, handover=True)
                                lg_j, nc_j = interleave.decode_only_step(
                                    dparams, views, jnp.asarray(feed_j),
                                    cfg_step)
                                pool.commit(pool.adopt(views, nc_j))
                                vlogits.append(lg_j)
                            logits = jnp.concatenate(
                                [jnp.asarray(lg) for lg in vlogits], axis=1)
                            out = self._check(logits, lanes, pre_logits,
                                              first)
                    # the step's one blocking read: tokens and finite flag
                    with trace.span(trace.SAMPLE):
                        got = np.asarray(out)
                    pulls += 1
                    if not got[-1]:
                        ladder.record_nan()
                        raise KernelFault(
                            "decode_attention",
                            "non-finite logits (NaN/Inf guard trip)")
                    step_ok = True
                    break
                except KernelFault as e:
                    # Only a KernelFault (injected, or the NaN guard's) is a
                    # recoverable step fault. Any other exception -- a
                    # lowering, compile or runtime error of the device --
                    # propagates: demoting past it would keep serving on a
                    # path the operator never asked for.
                    # A dead attempt's forks are reinstated NOW — rows and
                    # refcounts bit-identical to pre-round — so the ladder
                    # retry (or the failure path below) starts clean.
                    for fk in forks.values():
                        if fk.live:
                            pool.restore_lane(fk)
                    H["retried_steps"] += 1
                    ladder.record_fault(e.op)
                    recovered = (ladder.degrade(e.op, str(e))
                                 or ladder.degrade_any(str(e)))
                    if not recovered:
                        break  # ladder exhausted: the step fails

            slow = 0
            if faults is not None:
                f = faults.take(self._clock, "slow_step")
                if f is not None:
                    H["injected_faults"] += 1
                    slow = f.penalty
            ev = ScheduleEvent(
                plan, len(active), c if plan.prefill_chunk else 0,
                max((pool.get(i).ctx for i in active), default=0),
                self._take_reuse(), attempts=attempts, slow_penalty=slow,
                degraded=ladder.is_degraded(), mode=step_mode.value,
                result_pulls=pulls,
                # a spec step is priced as one weights-resident verify GEMM,
                # not K+1 split-KV GEMV sweeps, so it doesn't fan out
                kv_splits=(max(1, self.cfg.decode_kv_splits)
                           if plan.decode and pool.paged and not plan.spec
                           else 1))
            if plan.spec:
                st = spec_dec.round_stats()
                ev.spec_drafted = st["drafted"]
                ev.spec_draft_steps = st["draft_steps"]
                ev.draft_prefill_tokens = st["draft_prefill_tokens"]
                ev.verify_tokens = len(active) * span
            self._push_event(ev)

            if not step_ok:
                # fail ONLY the step's participants; parked/queued requests
                # and the engine itself keep serving. Verify forks are
                # reinstated first — bit-identical rows — then retired with
                # their lanes, so every page is released exactly once.
                for fk in forks.values():
                    if fk.live:
                        pool.restore_lane(fk)
                if spec_dec is not None:
                    spec_dec.abort_round()
                H["failures"] += 1
                err = (f"step failed after {attempts} attempts "
                       f"(degradation ladder exhausted)")
                for si in list(pool.active_slots()):
                    r = pool.get(si).req
                    pool.retire(si)
                    if spec_dec is not None:
                        spec_dec.retire_lane(si)
                    results[r].state = RequestState.FAILED
                    results[r].finish_reason = FINISH_FAILED
                    results[r].error = err
                    results[r].finish_step = self._clock
                if stream is not None:
                    results[stream.req].state = RequestState.FAILED
                    results[stream.req].finish_reason = FINISH_FAILED
                    results[stream.req].error = err
                    results[stream.req].finish_step = self._clock
                    stream = None
                    pool.release_staging()
                self._end_step()
                continue

            if new_cache is not None:
                pool.commit(new_cache)
            if new_scache is not None:
                self._require(stream is not None, "stream vanished mid-step")
                stream.cache = new_scache
                stream.off += c

            if plan.decode:
                # the lanes' tokens lead the result, one row per slot
                samp = got[:logits.shape[0] * logits.shape[1]].reshape(
                    logits.shape[:2])
            if plan.spec:
                cur_tok = self._spec_accept(samp, active, drafts, forks,
                                            pos_before, cur_tok, ev, emit)
            elif plan.decode:
                tok = samp[:, 0]
                cur_tok = tok.astype(np.int32)
                with trace.span(trace.EMIT):
                    for si in active:
                        emit(si, int(tok[si]))
                        if spec_dec is not None:
                            # keep draft lanes in sync across plain decode
                            # steps (spec suppressed this round) without a
                            # resync
                            spec_dec.note_emitted(si, [int(tok[si])])

            if stream is not None and stream.remaining == 0:
                # chunks are unpadded, so the last chunk's final position IS
                # the last prompt token — its logits seed the slot's decode.
                # The loop head places it into the next freed lane.
                self._require(pre_logits is not None,
                              "admission stream drained without prefill "
                              "logits to seed its first token")
                r = stream.req
                # the stream's one token sits just before the flag
                ready = _Ready(r, stream.cache, int(got[-2]), stream.reused,
                               prompt=[int(t) for t in stream.toks[0]],
                               ctx=int(stream.toks.shape[1]))
                stream = None
            self._end_step()

        self._in_serve = False
        pool.release_staging()  # defensive: no handle outlives a serve()
        for r in range(n):  # terminal contract: nothing is left in flight
            if results[r].state not in TERMINAL_STATES:
                results[r].state = RequestState.FAILED
                results[r].finish_reason = FINISH_FAILED
                results[r].error = (results[r].error
                                    or "engine exited with request "
                                       "non-terminal")
                results[r].finish_step = self._clock
                H["failures"] += 1
        self.last_requests = reqs       # SLO telemetry (schedule_report)
        del self._reqs, self._eos, self._base_keys
        self.last_results = results     # latency telemetry (schedule_report)
        return results

    def cancel(self, request_index: int) -> None:
        """Cancel an in-flight request (index into the ``serve()`` request
        list). Valid only while ``serve()`` is running — call it from an
        ``on_token`` callback or another thread; it takes effect at the next
        step boundary and keeps already-emitted tokens."""
        if not self._in_serve:
            raise EngineStateError(
                "cancel() is only valid while serve() is running — request "
                "indices are scoped to the in-flight call")
        if not 0 <= request_index < len(self._reqs):
            raise EngineStateError(
                f"cancel({request_index}): no such request in the in-flight "
                f"serve ({len(self._reqs)} requests)")
        self._cancel.add(request_index)

    def _take_reuse(self) -> int:
        r, self._pending_reuse = self._pending_reuse, 0
        return r

    # --------------------------------------------------------------- sampling

    def _lanes(self, rows: Sequence[Optional[tuple[int, int]]]
               ) -> sampling.Lanes:
        """The token rule's inputs for ``rows``: each a ``(request, index)``
        pair — the request it serves and its emitted-token index, which keys
        the row's token — or None for a free row, which emits the pad token.

        When every row is greedy (the default) this is the done mask alone:
        ``check_and_sample`` then runs the plain masked argmax, with no keys
        and no top-k/top-p filter.
        """
        done = np.asarray([row is None for row in rows])
        sps = [None if row is None else self._reqs[row[0]].sampling
               for row in rows]
        if all(sp is None or sp.temperature <= 0 for sp in sps):
            return sampling.Lanes(jnp.asarray(done))
        n = len(rows)
        temps = np.zeros((n,), np.float32)
        tks = np.zeros((n,), np.int32)
        tps = np.ones((n,), np.float32)
        keys = np.zeros((n, 2), np.uint32)
        offs = np.zeros((n,), np.uint32)
        for i, (row, sp) in enumerate(zip(rows, sps)):
            if sp is None:
                continue
            temps[i], tks[i], tps[i] = sp.temperature, sp.top_k, sp.top_p
            if sp.temperature > 0:
                keys[i], offs[i] = self._base_keys[row[0]], row[1]
        return sampling.Lanes(
            jnp.asarray(done), temperature=jnp.asarray(temps),
            top_k=jnp.asarray(tks), top_p=jnp.asarray(tps),
            keys=jnp.asarray(keys), offsets=jnp.asarray(offs))

    def _check(self, logits, lanes, pre_logits, first) -> jax.Array:
        """Enqueue the step's check-and-sample program on its final logits:
        the decode lanes' (``lanes``) and the admission chunk's last
        position (``first``). Only rows that can become tokens are checked:
        active lanes (free lanes decode garbage by design) and that position.
        An injected ``nan_logits`` fault poisons the logits first."""
        faults = self.fault_plan
        if faults is not None:
            f = faults.take(self._clock, "nan_logits",
                            pred=lambda _: self.ladder.can_degrade())
            if f is not None:
                self._health["injected_faults"] += 1
                bad = jnp.float32(jnp.nan)
                if logits is not None:
                    logits = logits * bad
                elif pre_logits is not None:
                    pre_logits = pre_logits * bad
        groups = []
        if logits is not None:
            groups.append((logits, lanes))
        if pre_logits is not None:
            groups.append((pre_logits[:, -1:, :], first))
        return sampling.check_and_sample(groups, check=self.nan_guard)

    def _spec_accept(self, samp, active, drafts, forks, pos_before,
                     cur_tok, ev: ScheduleEvent, emit) -> np.ndarray:
        """Token-matching rejection acceptance for one verify round.

        ``samp`` (slots, K+1) holds the target's sample at EVERY position
        ``j`` of the verify logits, drawn on the request's own RNG lane at
        absolute index ``emitted + j`` — the key non-spec decode would use
        when it reached that token.
        Draft token ``d_j`` is accepted iff it equals the target's sample at
        the position that fed it. Verify positions run the plain decode
        program on an identical context, so the emitted stream ``s_0..s_a``
        (``s_a`` the corrected token, or the bonus token when the whole
        draft held) is bit-identical to the non-spec engine at every
        temperature, and acceptance is a pure function of the request seed.

        Surviving lanes roll back to their pre-round fill plus what they
        emitted (the lane's cache holds ``[cur, s_0..s_{a-1}]`` there — the
        accepted tokens' KV was written by the verify pass itself); each
        fork is spent exactly once.
        """
        pool = self.pool
        spec_dec = self.spec_dec
        results = self._results
        new_cur = cur_tok.copy()
        with trace.span(trace.EMIT):
            for si in active:
                d = drafts.get(si, [])
                a = 0
                while a < len(d) and int(d[a]) == int(samp[si, a]):
                    a += 1
                r = pool.get(si).req
                emitted: list[int] = []
                for j in range(a + 1):
                    emitted.append(int(samp[si, j]))
                    emit(si, int(samp[si, j]))
                    if pool.get(si).state != ACTIVE:
                        break  # eos/budget: exactly where non-spec would stop
                results[r].spec_proposed += len(d)
                results[r].spec_accepted += a
                ev.spec_accepted += a
                ev.spec_max_emitted = max(ev.spec_max_emitted, len(emitted))
                if pool.get(si).state == ACTIVE:
                    pool.rollback_lane(si, pos_before[si] + len(emitted))
                    new_cur[si] = emitted[-1]
                    spec_dec.finish_round(si, emitted)
                else:
                    # retire already released the lane's pages; the fork
                    # below still holds its own refs — dropped once, like
                    # every round
                    spec_dec.retire_lane(si)
                pool.drop_fork(forks[si])
        return new_cur

    def _first_tokens(self, logits, rids: list[int],
                      offsets: list[int]) -> list[int]:
        """Sample each request's prefill-seeded first token from its row of
        the prefill's last-position ``logits`` (G, 1, V), in one program and
        one pull.

        ``offsets`` are the requests' absolute emitted-token indices — 0 on
        first admission, the emitted count on a preemption resume, so the
        RNG-lane key stream continues exactly where eviction cut it off.
        """
        got = np.asarray(sampling.check_and_sample(
            [(logits, self._lanes(list(zip(rids, offsets))))], check=False))
        self._last_ev.result_pulls += 1
        return [int(t) for t in got[:len(rids)]]

    # ------------------------------------------------------- admission paths

    def _prefill_one(self, r: int) -> _Ready:
        """Full batch-1 prefill of request ``r`` -> a parked ``_Ready``.

        The prefilled span is prompt + already-emitted tokens, so a preempted
        ring-family request resumes through the same path it was admitted by.
        """
        p = list(self._reqs[r].prompt) + self._results[r].tokens
        toks = np.asarray([p], np.int32)
        logits, pcache = M.prefill(
            self.params, {"tokens": jnp.asarray(toks)}, self.cfg, self.max_len)
        pcache["pos"] = jnp.asarray([toks.shape[1]], jnp.int32)
        self._push_event(ScheduleEvent(
            plan_step(self.mode, False, True, toks.shape[1]), 0, toks.shape[1]))
        with trace.span(trace.SAMPLE):
            first = self._first_tokens(
                logits, [r], offsets=[len(self._results[r].tokens)])[0]
        return _Ready(r, pcache, first, prompt=p, ctx=len(p))

    def _admit_batch(self, queue, cur_tok, emit) -> bool:
        """Fill free lanes with one full (ragged) prefill pass.

        Used when nothing is decoding — there is no overlap to exploit, so a
        single batched prefill is strictly better than chunk streaming. The
        pool's admission policy replaces the old per-family branches: states
        that cannot ride a right-padded ragged batch (recurrent state, ring
        placement) fall back to per-request passes when lengths are ragged.
        Requests whose prompt hits the prefix store are NOT taken — they
        admit via the chunk-streaming path, which gathers the shared blocks.
        Returns False when no request was admissible here (including an
        injected alloc failure: the queue head then admits via the
        chunk-streaming path, the engine's recovery route).
        """
        self._require(self.pool is not None, "batch admission without a pool")
        reqs = self._reqs
        results = self._results
        pool = self.pool
        with trace.span(trace.ADMIT_BATCH):
            if (self.fault_plan is not None and self.fault_plan.take(
                    self._clock, "alloc_fail") is not None):
                self._health["injected_faults"] += 1
                return False
            free = pool.free_slots()
            ext = {r: list(reqs[r].prompt) + results[r].tokens for r in queue}
            take: list[int] = []
            while queue and len(take) < len(free):
                if pool.peek_prefix(ext[queue[0]]) > 0:
                    break
                take.append(queue.pop(0))
            if not take:
                return False
            lens = [len(ext[r]) for r in take]
            groups = ([[r] for r in take]
                      if not pool.policy.ragged_batch_ok and len(set(lens)) > 1
                      else [take])
        # each group's prefill is a step; a lane's first token is emitted
        # as soon as the lane holds it, outside the admission's span
        for group in groups:
            with trace.span(trace.ADMIT_BATCH, req=group[0]):
                for r in group:
                    results[r].state = RequestState.ADMITTED
                    if results[r].admit_step is None:  # set-once, as staged
                        results[r].admit_step = self._clock
                glens = [len(ext[r]) for r in group]
                toks = np.zeros((len(group), max(glens)), np.int32)
                for j, r in enumerate(group):
                    toks[j, : len(ext[r])] = ext[r]
                seq_lens = jnp.asarray(glens, jnp.int32)
                logits, pcache = M.prefill(
                    self.params, {"tokens": jnp.asarray(toks)}, self.cfg,
                    self.max_len,
                    seq_lens=seq_lens if len(set(glens)) > 1 else None)
                pcache["pos"] = seq_lens
                self._push_event(ScheduleEvent(
                    plan_step(self.mode, False, True, sum(glens)), 0,
                    sum(glens)))
                with trace.span(trace.SAMPLE):
                    first = self._first_tokens(
                        logits, group,
                        offsets=[len(results[r].tokens) for r in group])
            for j, r in enumerate(group):
                with trace.span(trace.ADMIT_BATCH, req=r):
                    si = pool.alloc(reqs[r], r, ctx=len(ext[r]),
                                    emitted=len(results[r].tokens))
                    pool.insert(si, pcache, src_slot=j, prompt=ext[r])
                    results[r].state = RequestState.RUNNING
                    cur_tok[si] = first[j]
                with trace.span(trace.EMIT):
                    emit(si, first[j])
            self._end_step()
        return True

    # ------------------------------------------------------------- reporting

    def health(self) -> dict:
        """Engine health snapshot: degradation-ladder rungs + per-op fault
        counters, lifecycle counters (cumulative for the engine's lifetime),
        pool occupancy, and the fault plan's consumption state (chaos runs).
        """
        self._require(self.pool is not None, "health() without a pool")
        return {
            "degraded": self.ladder.is_degraded(),
            "ladder": self.ladder.health(),
            "counters": dict(self._health),
            "occupancy": self.pool.occupancy().to_json(),
            "fault_plan": (self.fault_plan.to_json()
                           if self.fault_plan is not None else None),
        }

    def schedule_report(self) -> ScheduleReport:
        self._require(self.pool is not None, "schedule_report() without a pool")
        from repro.serve.traffic import latency_summary  # cycle-free (lazy)
        fused = sum(1 for e in self.events if e.plan.fused)
        decode_events = [e for e in self.events if e.plan.decode]
        mode_steps: dict[str, int] = {}
        for e in self.events:
            if e.idle_steps:
                continue  # idle jumps are clock bookkeeping, not mode picks
            mode_steps[e.mode] = mode_steps.get(e.mode, 0) + 1
        return ScheduleReport({
            "steps": len(self.events),
            "fused_steps": fused,
            "modes": {e.plan.label for e in self.events},
            "mode_steps": mode_steps,
            "decode_steps": len(decode_events),
            "decode_slot_steps": sum(e.decode_batch for e in decode_events),
            "idle_slot_steps": sum(self.slots - e.decode_batch
                                   for e in decode_events),
            "prefill_tokens": sum(e.prefill_tokens for e in self.events),
            "reused_prefix_tokens": sum(e.reused_tokens for e in self.events),
            "arrivals": sum(e.arrivals for e in self.events),
            "idle_steps": sum(e.idle_steps for e in self.events),
            "prefix": self.pool.prefix_report(),
            "retried_step_attempts": sum(e.attempts - 1 for e in self.events),
            "degraded_steps": sum(1 for e in self.events if e.degraded),
            "slow_penalty_steps": sum(e.slow_penalty for e in self.events),
            "spec": self._spec_report(),
            "latency": latency_summary(self.last_results or [],
                                       self.last_requests),
            "health": self.health(),
        })

    def _spec_report(self) -> dict:
        """Aggregate speculative-decoding stats over the event stream."""
        spec_events = [e for e in self.events if e.plan.spec]
        proposed = sum(e.spec_drafted for e in spec_events)
        accepted = sum(e.spec_accepted for e in spec_events)
        return {
            "enabled": self.spec_dec is not None,
            "rounds": len(spec_events),
            "proposed": proposed,
            "accepted": accepted,
            "acceptance_rate": (accepted / proposed) if proposed else 0.0,
            "draft_steps": sum(e.spec_draft_steps for e in spec_events),
            "draft_prefill_tokens": sum(e.draft_prefill_tokens
                                        for e in spec_events),
            "verify_tokens": sum(e.verify_tokens for e in spec_events),
        }


def wave_baseline_report(prompt_lens: Sequence[int], max_news: Sequence[int],
                         slots: int) -> dict:
    """Decode-step accounting of the OLD wave engine for the same request set.

    Waves of ``slots`` requests in submission order; every wave decodes to its
    batch-max ``max_new`` (first token comes from prefill, so a wave costs
    ``max(max_new) - 1`` decode steps) and per-request budgets are enforced by
    truncation only. ``idle_slot_steps`` counts slot-steps that produce no
    kept token: empty lanes plus lanes decoding past their own budget.
    """
    decode_steps = slot_steps = idle = 0
    reqs = list(zip(prompt_lens, max_news))
    for w0 in range(0, len(reqs), slots):
        wave = reqs[w0: w0 + slots]
        steps_w = max(mn for _, mn in wave) - 1
        decode_steps += steps_w
        slot_steps += len(wave) * steps_w
        idle += (slots - len(wave)) * steps_w
        idle += sum(steps_w - (mn - 1) for _, mn in wave)
    return {"decode_steps": decode_steps, "decode_slot_steps": slot_steps,
            "idle_slot_steps": idle}


def wave_baseline_events(prompt_lens: Sequence[int], max_news: Sequence[int],
                         slots: int, mode: Mode = Mode.HBCEM) -> list:
    """Synthesize the OLD wave engine's ``ScheduleEvent`` stream so
    ``pimsim.scheduler.replay_events`` can price the wave schedule against a
    continuous one. Every wave decodes its FULL width to the batch-max budget
    — the over-decoded slot-steps are exactly the work continuous batching
    reclaims by retiring lanes mid-flight.
    """
    events = []
    reqs = list(zip(prompt_lens, max_news))
    for w0 in range(0, len(reqs), slots):
        wave = reqs[w0: w0 + slots]
        ptoks = sum(pl for pl, _ in wave)
        events.append(ScheduleEvent(plan_step(mode, False, True, ptoks), 0, ptoks))
        for t in range(max(mn for _, mn in wave) - 1):
            ctx = max(pl + 1 + t for pl, _ in wave)
            events.append(ScheduleEvent(plan_step(mode, True, False, 0),
                                        len(wave), 0, ctx))
    return events
