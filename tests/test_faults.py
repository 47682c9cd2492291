"""Resilience suite: fault injection, degradation ladder, lifecycle, leaks.

Three layers of coverage:

* **chaos** (``@pytest.mark.chaos``) — seeded :class:`FaultPlan`s swept
  across BLOCKED / HBCEM / LBIM. After every run: all requests terminal, no
  stuck slots, zero leaked pages/blocks (``CachePool.check_invariants``),
  FINISHED requests' greedy tokens bit-identical to a fault-free run, and
  the same seed replays bit-identically.
* **surgical** — hand-built plans driving one mechanism each: kernel-fault
  -> ladder fallback, NaN logits -> finite guard, alloc failure ->
  preemption healing, slow steps -> deadline trips.
* **lifecycle / typed errors** — priority preemption with bit-identical
  resume, deadlines, cancellation, bounded-queue backpressure, and the
  PoolExhausted / EngineStateError / AdmissionRejected contracts.

The engine pins ``attn_backend="interpret"`` throughout: on CPU ``auto``
already resolves to the reference floor, and the ladder needs headroom above
the floor for injected kernel faults to be *recoverable* (interpret and
reference are token-bitwise identical, so baselines stay comparable).
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import interleave
from repro.core.pim_modes import Mode
from repro.models import model as M
from repro.pimsim import CDPIM, JETSON, LLAMA_1B, replay_events
from repro.serve import sampling
from repro.serve.api import (FINISH_CANCELLED, FINISH_FAILED, FINISH_TIMEOUT,
                             TERMINAL_STATES, GenerationRequest,
                             RequestState)
from repro.serve.engine import Engine
from repro.serve.errors import (AdmissionRejected, EngineStateError,
                                KernelFault, PoolExhausted)
from repro.serve.faults import KINDS, Fault, FaultPlan
from repro.serve.scheduler import Scheduler
from repro.serve.serving_model import ServingModel
from repro.serve.spec import SpecConfig
from serving_refs import BUDGETS, MAX_LEN, PROMPTS

CHAOS_SEEDS = [0, 1, 2, 3, 4]
MODES = [Mode.BLOCKED, Mode.HBCEM, Mode.LBIM]


@pytest.fixture(scope="module")
def setup():
    # interpret-pinned so the ladder has a live rung above the floor
    cfg = get_config("llama3-8b", smoke=True).replace(attn_backend="interpret")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """The chaos sweep compiles many one-off program variants (per-mode ×
    per-ladder-rung × interpret backend); drop them when the module ends so
    the full-suite process doesn't carry the peak compile-cache footprint
    into later modules."""
    yield
    jax.clear_caches()


def _engine(cfg, params, mode, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    return Engine(cfg, params, max_len=MAX_LEN, mode=mode, **kw)


def _reqs(prompts=PROMPTS, budgets=BUDGETS, **kw):
    return [GenerationRequest(prompt=list(p), max_new_tokens=b, **kw)
            for p, b in zip(prompts, budgets)]


@pytest.fixture(scope="module")
def baseline(setup):
    """Fault-free greedy tokens per mode — the bit-identity yardstick."""
    cfg, params = setup
    out = {}
    for mode in MODES:
        res = _engine(cfg, params, mode).serve(_reqs())
        out[mode] = [r.tokens for r in res]
    return out


def _assert_no_leaks(eng):
    violations = eng.pool.check_invariants()
    assert violations == [], violations
    occ = eng.pool.occupancy()
    assert occ.slots_used == 0, "stuck slot(s) after serve()"
    assert occ.prefix_pins == 0, "retired slots still pin prefix pages"


# ===========================================================================
# chaos sweep
# ===========================================================================


@pytest.mark.chaos
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_chaos_seeded_plan(setup, baseline, seed, mode):
    cfg, params = setup
    plan = FaultPlan.seeded(seed, horizon=20, n_faults=4)
    eng = _engine(cfg, params, mode, fault_plan=plan)
    res = eng.serve(_reqs())

    # every request reached a terminal state; nothing is stuck or leaked
    assert all(r.state in TERMINAL_STATES for r in res)
    assert all(r.done for r in res)
    _assert_no_leaks(eng)

    # unaffected (FINISHED) requests are bit-identical to the fault-free
    # run; requests the harness failed only ever hold a prefix of it
    for r, ref in zip(res, baseline[mode]):
        if r.state is RequestState.FINISHED:
            assert r.tokens == ref
        else:
            assert r.tokens == ref[:len(r.tokens)]

    # health counters surface through the schedule report
    rep = eng.schedule_report()
    for key in ("retried_step_attempts", "degraded_steps",
                "slow_penalty_steps", "health"):
        assert key in rep
    assert rep["health"]["counters"]["injected_faults"] == plan.fired()
    assert plan.fired() + plan.pending() == len(plan.faults)


@pytest.mark.chaos
def test_chaos_same_seed_replays_bit_identically(setup):
    cfg, params = setup

    def run():
        plan = FaultPlan.seeded(7, horizon=20, n_faults=4)
        eng = _engine(cfg, params, Mode.LBIM, fault_plan=plan)
        res = eng.serve(_reqs())
        return ([r.tokens for r in res], [r.state for r in res],
                plan.fired(), eng.schedule_report()["degraded_steps"])

    assert run() == run()


@pytest.mark.chaos
def test_chaos_faulted_run_priced_honestly(setup):
    """Replay prices retries and slow steps as real stall time — a faulted
    schedule is never cheaper than its fault-free twin."""
    cfg, params = setup
    clean = _engine(cfg, params, Mode.HBCEM)
    clean.serve(_reqs())
    clean_sim = replay_events(clean.events, LLAMA_1B, JETSON, CDPIM)

    plan = FaultPlan(faults=[Fault("kernel_exc", 1, op="decode_attention"),
                             Fault("slow_step", 3, penalty=2)])
    eng = _engine(cfg, params, Mode.HBCEM, fault_plan=plan)
    res = eng.serve(_reqs())
    assert all(r.state in TERMINAL_STATES for r in res)
    sim = replay_events(eng.events, LLAMA_1B, JETSON, CDPIM)
    assert sim.stall_s > 0
    assert sim.retried_attempts >= 1
    assert sim.degraded_steps >= 1
    assert sim.total_s > clean_sim.total_s


# ===========================================================================
# chaos sweep: speculative decoding mode
# ===========================================================================


@pytest.fixture(scope="module")
def spec_sm(setup):
    """Interpret-pinned serving artifact shared by spec chaos engines —
    self-draft keeps acceptance high so rounds actually fork/rollback."""
    cfg, params = setup
    return ServingModel.prepare(cfg, params, max_len=MAX_LEN, slots=2)


def _spec_engine(spec_sm, mode, k=2, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    return Engine(spec_sm.cfg, spec_sm.params, max_len=MAX_LEN, mode=mode,
                  serving=spec_sm, spec=SpecConfig(draft=spec_sm, k=k), **kw)


@pytest.mark.chaos
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", CHAOS_SEEDS[:3])
def test_chaos_spec_mode(setup, spec_sm, baseline, seed, mode):
    """The chaos contract survives draft/verify rounds: faults may land
    mid-verify, so every retry first restores the forked rows — terminal
    states, zero leaks in BOTH pools, FINISHED tokens bit-identical."""
    plan = FaultPlan.seeded(seed, horizon=20, n_faults=4)
    eng = _spec_engine(spec_sm, mode, fault_plan=plan)
    res = eng.serve(_reqs())
    assert all(r.state in TERMINAL_STATES for r in res)
    _assert_no_leaks(eng)
    assert eng.spec_dec.pool.check_invariants() == []
    for r, ref in zip(res, baseline[mode]):
        if r.state is RequestState.FINISHED:
            assert r.tokens == ref
        else:
            assert r.tokens == ref[:len(r.tokens)]
    assert eng.schedule_report()["health"]["counters"]["injected_faults"] \
        == plan.fired()


@pytest.mark.chaos
def test_chaos_spec_same_seed_replays_bit_identically(spec_sm):
    def run():
        plan = FaultPlan.seeded(7, horizon=20, n_faults=4)
        eng = _spec_engine(spec_sm, Mode.LBIM, fault_plan=plan)
        res = eng.serve(_reqs())
        return ([r.tokens for r in res], plan.fired(),
                eng.schedule_report()["spec"])

    assert run() == run()


def test_kernel_fault_during_verify_releases_forks_once(setup, spec_sm,
                                                        baseline):
    """A kernel fault inside a verify round: the handler restores every live
    fork (parent rows bit-identical, refcounts exactly once) before the
    ladder retry — proven by the retried spec step completing with baseline
    tokens and a clean refcount audit in both pools."""
    plan = FaultPlan(faults=[Fault("kernel_exc", 3, op="decode_attention")])
    eng = _spec_engine(spec_sm, Mode.HBCEM, fault_plan=plan)
    with pytest.warns(RuntimeWarning, match="decode_attention"):
        res = eng.serve(_reqs())
    assert plan.fired() == 1
    assert [r.state for r in res] == [RequestState.FINISHED] * len(res)
    assert [r.tokens for r in res] == baseline[Mode.HBCEM]
    # the faulted step WAS a spec step: it both retried and ran a rollout
    assert any(ev.attempts > 1 and ev.spec_drafted > 0 for ev in eng.events)
    assert eng.schedule_report()["retried_step_attempts"] >= 1
    _assert_no_leaks(eng)
    assert eng.spec_dec.pool.check_invariants() == []
    assert eng.spec_dec.pool.occupancy().slots_used == 0


# ===========================================================================
# degradation ladder
# ===========================================================================


def test_kernel_fault_walks_ladder_and_completes(setup, baseline):
    cfg, params = setup
    plan = FaultPlan(faults=[Fault("kernel_exc", 1, op="decode_attention")])
    eng = _engine(cfg, params, Mode.LBIM, fault_plan=plan)
    with pytest.warns(RuntimeWarning, match="decode_attention"):
        res = eng.serve(_reqs())
    assert [r.state for r in res] == [RequestState.FINISHED] * len(res)
    assert [r.tokens for r in res] == baseline[Mode.LBIM]
    assert eng.ladder.is_degraded()
    health = eng.health()
    assert health["degraded"]
    assert health["ladder"]["decode_attention"]["kernel_faults"] >= 1
    assert health["ladder"]["decode_attention"]["fallbacks"] >= 1
    rep = eng.schedule_report()
    assert rep["retried_step_attempts"] >= 1
    assert rep["degraded_steps"] >= 1
    _assert_no_leaks(eng)


def test_nan_logits_trip_finite_guard(setup, baseline):
    cfg, params = setup
    plan = FaultPlan(faults=[Fault("nan_logits", 2)])
    eng = _engine(cfg, params, Mode.HBCEM, fault_plan=plan)
    with pytest.warns(RuntimeWarning):
        res = eng.serve(_reqs())
    assert [r.tokens for r in res] == baseline[Mode.HBCEM]
    assert eng.health()["ladder"]["decode_attention"]["nan_trips"] >= 1
    _assert_no_leaks(eng)


@pytest.fixture(scope="module")
def checker(setup):
    cfg, params = setup
    return _engine(cfg, params, Mode.LBIM)


@pytest.mark.parametrize("nan_guard", [True, False], ids=["guard", "no_guard"])
@pytest.mark.parametrize("where,trips", [
    ("active_lane", True), ("free_lane", False),
    ("chunk_last", True), ("chunk_earlier", False), ("none", False)])
@pytest.mark.parametrize("bad", [float("nan"), float("inf")], ids=["nan", "inf"])
def test_check_flags_only_rows_that_become_tokens(checker, where, trips,
                                                  nan_guard, bad):
    """The step's check-and-sample program flags a non-finite logit in an
    active lane or in the admission chunk's last position — the rows that
    become tokens — and nothing else; with the guard off it never trips."""
    logits = np.zeros((2, 1, 11), np.float32)   # lane 0 active, lane 1 free
    pre = np.zeros((1, 3, 11), np.float32)      # a 3-token admission chunk
    at = {"active_lane": (logits, (0, 0, 4)), "free_lane": (logits, (1, 0, 4)),
          "chunk_last": (pre, (0, 2, 7)), "chunk_earlier": (pre, (0, 1, 7))}
    if where in at:
        arr, idx = at[where]
        arr[idx] = bad
    checker.nan_guard = nan_guard
    try:
        out = np.asarray(checker._check(
            jnp.asarray(logits), sampling.Lanes(jnp.asarray([False, True])),
            jnp.asarray(pre), sampling.Lanes(jnp.zeros((1,), bool))))
    finally:
        checker.nan_guard = True
    assert out.shape == (4,)
    assert out[-1] == int(not (trips and nan_guard))


def test_gemv_faults_degrade_independently_of_attention(setup, baseline):
    """The two ladder ops carry separate rungs: a pim_gemv fault must not
    demote decode_attention's backend."""
    cfg, params = setup
    plan = FaultPlan(faults=[Fault("kernel_exc", 1, op="pim_gemv")])
    eng = _engine(cfg, params, Mode.HBCEM, fault_plan=plan)
    with pytest.warns(RuntimeWarning, match="pim_gemv"):
        res = eng.serve(_reqs())
    assert [r.tokens for r in res] == baseline[Mode.HBCEM]
    ladder = eng.health()["ladder"]
    assert ladder["pim_gemv"]["backend"] != ladder["pim_gemv"]["base"]
    assert ladder["decode_attention"]["backend"] == "interpret"
    _assert_no_leaks(eng)


def test_ladder_is_sticky_across_serve_calls(setup, baseline):
    cfg, params = setup
    plan = FaultPlan(faults=[Fault("kernel_exc", 1, op="decode_attention")])
    eng = _engine(cfg, params, Mode.HBCEM, fault_plan=plan)
    with pytest.warns(RuntimeWarning):
        eng.serve(_reqs())
    assert eng.ladder.is_degraded()
    # second serve: no plan faults left, but the demotion persists (a kernel
    # that faulted once is not retried next call) and tokens still match
    res = eng.serve(_reqs())
    assert eng.ladder.is_degraded()
    assert [r.tokens for r in res] == baseline[Mode.HBCEM]


@pytest.mark.parametrize("mode", [Mode.HBCEM, Mode.LBIM])
def test_non_kernel_fault_propagates_without_demotion(setup, monkeypatch,
                                                      mode):
    """Only a KernelFault is recoverable. A device error raised inside a
    step -- here a Mosaic compile refusal -- leaves serve() at once and moves
    no ladder rung, so the engine never keeps serving on a fallback path
    (the Pallas interpreter, the jnp oracle) while reporting success."""
    cfg, params = setup
    eng = _engine(cfg, params, mode)

    def refuse(*_a, **_kw):
        raise jax.errors.JaxRuntimeError(
            "INVALID_ARGUMENT: Mosaic failed to compile TPU kernel")

    for step in ("decode_only_step", "fused_step", "prefill_chunk_step"):
        monkeypatch.setattr(interleave, step, refuse)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # a demotion warns
        with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
            eng.serve(_reqs())
    health = eng.health()
    assert not health["degraded"]
    assert health["counters"]["retried_steps"] == 0
    for op in health["ladder"].values():
        assert op["backend"] == op["base"] == "interpret"
        assert op["fallbacks"] == op["kernel_faults"] == 0


def test_ladder_exhaustion_fails_participants_not_engine(setup):
    """Unrecoverable numerics (NaN in the weights — every rung produces NaN
    logits) must fail the step's participants with a typed error, not hang
    the engine or leak their lanes."""
    cfg, params = setup
    bad = dict(params)
    bad["final_norm"] = jax.tree_util.tree_map(
        lambda x: x * jnp.float32(float("nan")), params["final_norm"])
    eng = _engine(cfg, bad, Mode.HBCEM)
    with pytest.warns(RuntimeWarning):
        res = eng.serve(_reqs(PROMPTS[:2], BUDGETS[:2]))
    assert all(r.state is RequestState.FAILED for r in res)
    assert all(r.finish_reason == FINISH_FAILED for r in res)
    assert all(r.error for r in res)
    _assert_no_leaks(eng)


# ===========================================================================
# backpressure, preemption, resume identity
# ===========================================================================


def test_priority_preemption_resumes_bit_identical(setup):
    cfg, params = setup
    prompts, budgets = PROMPTS[:3], [6, 6, 4]
    solo = [_engine(cfg, params, Mode.HBCEM, slots=1)
            .serve(_reqs([p], [b]))[0].tokens
            for p, b in zip(prompts, budgets)]
    reqs = _reqs(prompts, budgets)
    reqs[2] = dataclasses.replace(reqs[2], priority=5)
    eng = _engine(cfg, params, Mode.HBCEM, slots=2)
    res = eng.serve(reqs)
    assert all(r.state is RequestState.FINISHED for r in res)
    assert sum(r.preemptions for r in res) >= 1  # someone made way
    assert [r.tokens for r in res] == solo       # and resumed exactly
    assert eng.schedule_report()["health"]["counters"]["preemptions"] >= 1
    _assert_no_leaks(eng)


def test_injected_alloc_failure_heals_by_preemption(setup, baseline):
    cfg, params = setup
    plan = FaultPlan(faults=[Fault("alloc_fail", 1), Fault("alloc_fail", 4)])
    eng = _engine(cfg, params, Mode.HBCEM, fault_plan=plan)
    res = eng.serve(_reqs())
    assert all(r.state is RequestState.FINISHED for r in res)
    assert [r.tokens for r in res] == baseline[Mode.HBCEM]
    assert plan.fired() >= 1
    _assert_no_leaks(eng)


def test_pool_exhausted_carries_occupancy(setup):
    cfg, params = setup
    eng = _engine(cfg, params, Mode.HBCEM, slots=1)
    req = GenerationRequest(prompt=[1, 2, 3], max_new_tokens=4)
    eng.pool.alloc(req, 0)
    with pytest.raises(PoolExhausted) as ei:
        eng.pool.alloc(req, 1)
    occ = ei.value.occupancy
    assert occ.slots_used == occ.slots_total == 1
    assert occ.slots_free == 0
    assert not ei.value.injected
    assert "slots_used" in occ.to_json()
    eng.pool.retire(0)
    _assert_no_leaks(eng)


def test_bounded_queue_rejects_on_full(setup):
    cfg, params = setup
    sched = Scheduler(_engine(cfg, params, Mode.HBCEM), max_queue=2)
    sched.submit([1, 2], max_new=2)
    sched.submit([3, 4], max_new=2)
    with pytest.raises(AdmissionRejected) as ei:
        sched.submit([5, 6], max_new=2)
    assert ei.value.depth == 2 and ei.value.max_queue == 2
    # shedding one queued request reopens the front door
    assert sched.cancel(0)
    assert sched.results[0].state is RequestState.CANCELLED
    rid = sched.submit([5, 6], max_new=2)
    out = sched.drain()
    assert 0 not in out and rid in out


# ===========================================================================
# deadlines and cancellation
# ===========================================================================


def test_ttft_deadline_times_out_queued_request(setup):
    cfg, params = setup
    reqs = _reqs(PROMPTS[:3], [4, 4, 4])
    reqs[1] = dataclasses.replace(reqs[1], ttft_deadline=1)
    eng = _engine(cfg, params, Mode.BLOCKED, slots=1)
    res = eng.serve(reqs)
    assert res[1].state is RequestState.TIMED_OUT
    assert res[1].finish_reason == FINISH_TIMEOUT
    assert res[1].tokens == []
    assert res[0].state is res[2].state is RequestState.FINISHED
    assert eng.schedule_report()["health"]["counters"]["timeouts"] == 1
    _assert_no_leaks(eng)


def test_total_deadline_keeps_partial_tokens(setup, baseline):
    cfg, params = setup
    reqs = _reqs()
    reqs[1] = dataclasses.replace(reqs[1], deadline=4)
    eng = _engine(cfg, params, Mode.HBCEM, fault_plan=FaultPlan(
        faults=[Fault("slow_step", 1, penalty=6)]))
    res = eng.serve(reqs)
    assert res[1].state is RequestState.TIMED_OUT
    assert res[1].tokens == baseline[Mode.HBCEM][1][:len(res[1].tokens)]
    assert len(res[1].tokens) < len(baseline[Mode.HBCEM][1])
    _assert_no_leaks(eng)


def test_cancel_mid_stream_keeps_emitted_tokens(setup, baseline):
    cfg, params = setup
    eng = _engine(cfg, params, Mode.LBIM)
    seen = []

    def tap(tok):
        seen.append(tok)
        if len(seen) == 3:
            eng.cancel(1)

    reqs = _reqs()
    reqs[1] = dataclasses.replace(reqs[1], on_token=tap)
    res = eng.serve(reqs)
    assert res[1].state is RequestState.CANCELLED
    assert res[1].finish_reason == FINISH_CANCELLED
    assert res[1].tokens == baseline[Mode.LBIM][1][:len(res[1].tokens)]
    others = [r for i, r in enumerate(res) if i != 1]
    assert all(r.state is RequestState.FINISHED for r in others)
    assert [r.tokens for r in res if r.state is RequestState.FINISHED] == \
        [t for i, t in enumerate(baseline[Mode.LBIM]) if i != 1]
    assert eng.schedule_report()["health"]["counters"]["cancellations"] == 1
    _assert_no_leaks(eng)


def test_cancel_outside_serve_is_a_state_error(setup):
    cfg, params = setup
    eng = _engine(cfg, params, Mode.HBCEM)
    with pytest.raises(EngineStateError):
        eng.cancel(0)


# ===========================================================================
# cache accounting invariants
# ===========================================================================


def test_free_counts_return_to_baseline_across_serves(setup):
    cfg, params = setup
    eng = _engine(cfg, params, Mode.HBCEM)
    base = eng.pool.occupancy()
    eng.serve(_reqs())
    mid = eng.pool.occupancy()
    eng.serve(_reqs())  # second run reuses stored prefix pages
    end = eng.pool.occupancy()
    assert base.slots_used == mid.slots_used == end.slots_used == 0
    # prefix pages persist BY DESIGN (that's the cache); they may not grow
    # across identical runs, and every page stays accounted for
    assert end.pages_used == mid.pages_used
    assert eng.pool.check_invariants() == []


def test_preempt_heavy_run_leaves_no_dangling_blocks(setup):
    cfg, params = setup
    plan = FaultPlan(faults=[Fault("alloc_fail", s) for s in (1, 2, 3, 5, 8)])
    eng = _engine(cfg, params, Mode.LBIM, fault_plan=plan)
    res = eng.serve(_reqs())
    assert all(r.done for r in res)
    _assert_no_leaks(eng)


def test_typed_faults_expose_injection_provenance():
    f = KernelFault("decode_attention", "boom", injected=True)
    assert f.injected and f.op == "decode_attention"
    assert set(KINDS) == {"alloc_fail", "kernel_exc", "nan_logits",
                          "slow_step"}
    plan = FaultPlan.seeded(3, horizon=10)
    j = plan.to_json()
    assert j["seed"] == 3 and len(j["faults"]) == len(plan.faults)
