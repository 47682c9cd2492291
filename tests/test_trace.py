"""The engine's profiler spans and the page pool's copy counter, read back
from a real profiler trace of a tiny LBIM serve (CPU): one batch admission,
then one chunked admission fused into running decode and merged into the
lane its predecessor frees."""
from __future__ import annotations

import glob
import os
import warnings

import jax
import pytest

from repro.configs import get_config
from repro.core.pim_modes import Mode
from repro.models import model as M
from repro.serve import trace
from repro.serve.api import GenerationRequest
from repro.serve.engine import Engine

SLOTS, CHUNK, MAX_LEN = 2, 4, 64
# two lanes admitted together, then a 10-token prompt streamed in chunks
PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8], [7, 9, 3, 2, 3, 8, 4, 6, 2, 6]]
BUDGETS = [6, 9, 4]

# (inner, outer): the only ways two spans may overlap
POOL = (trace.POOL_COMMIT, trace.POOL_MERGE, trace.POOL_RESIDENCY)
NESTED = ({(trace.ON_TOKEN, trace.EMIT), (trace.SAMPLE, trace.ADMIT_BATCH),
           (trace.POOL_MERGE, trace.ADMIT_BATCH)}
          | {(p, trace.SCHEDULE) for p in POOL} | {(p, trace.SPEC) for p in POOL})


@pytest.fixture(scope="module")
def model():
    cfg = get_config("llama3-8b", smoke=True)
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def serve(model):
    cfg, params = model
    eng = Engine(cfg, params, max_len=MAX_LEN, slots=SLOTS, mode=Mode.LBIM,
                 chunk=CHUNK)
    seen = []
    reqs = [GenerationRequest(prompt=p, max_new_tokens=b, on_token=seen.append)
            for p, b in zip(PROMPTS, BUDGETS)]
    return eng, eng.serve(reqs)


def engine_spans(log_dir: str) -> list[tuple[float, float, str, dict]]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    # jaxlib's stats type warns that it has no __module__;
                    # this file's DeprecationWarnings are errors, and one
                    # raised inside the binding aborts the process
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                    out.append((e.start_ns, e.start_ns + e.duration_ns, e.name,
                                stats))
    return sorted(out, key=lambda s: (s[0], -s[1]))   # outer span first


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        eng, results = serve(model)
    finally:
        jax.profiler.stop_trace()
    return eng, results, engine_spans(log_dir)


def test_one_dispatch_per_step_named_by_its_event(traced):
    eng, results, spans = traced
    steps = [st["step"] for _, _, name, st in spans if name == trace.DISPATCH]
    # event 0 is the batch admission of the first two requests; every later
    # event is a step of the loop, dispatched once
    first = eng.events[0]
    assert not first.plan.decode
    assert first.prefill_tokens == len(PROMPTS[0]) + len(PROMPTS[1])
    assert steps == list(range(1, len(eng.events)))
    assert any(e.plan.fused for e in eng.events)
    assert {name for _, _, name, _ in spans} >= {
        trace.SCHEDULE, trace.DISPATCH, trace.SAMPLE, trace.EMIT,
        trace.ON_TOKEN, trace.ADMIT_BATCH, *POOL}


def test_span_names_and_stats(traced):
    _, results, spans = traced
    assert {name for _, _, name, _ in spans} <= set(trace.SPANS)
    n_tokens = sum(len(r.tokens) for r in results)
    on_token = [st for _, _, name, st in spans if name == trace.ON_TOKEN]
    assert len(on_token) == n_tokens
    assert sorted(st["req"] for st in on_token) == sorted(
        i for i, r in enumerate(results) for _ in r.tokens)


def test_spans_nest_only_where_the_work_nests(traced):
    _, _, spans = traced
    for i, (a0, a1, outer, _) in enumerate(spans):
        for b0, b1, inner, _ in spans[i + 1:]:
            if b0 >= a1:
                break
            assert b1 <= a1, (inner, "straddles", outer)
            assert (inner, outer) in NESTED, (inner, "inside", outer)


def test_pool_copy_bytes_count_whole_arrays(traced):
    eng, results, _ = traced
    pages = eng.pool.views()
    merge = 2 * pages["k_pages"].nbytes
    assert pages["v_pages"].nbytes == pages["k_pages"].nbytes
    pos = pages["pos"].nbytes
    # a lane's fill level is written once by each commit, insert and retire;
    # a request retires in the step whose event precedes its finish_step
    retires = [0] * len(eng.events)
    for r in results:
        retires[r.finish_step - 1] += 1
    # the third request's stream merges at the boundary before the step
    # that follows its first token
    merged = results[2].first_token_step
    assert 0 < merged < len(eng.events) and eng.events[merged].plan.decode
    blocks = sum(-(-len(p) // CHUNK) for p in PROMPTS[:2])
    assert eng.events[0].pool_copy_bytes == (blocks * merge
                                            + pos * (2 + retires[0]))
    for k, ev in enumerate(eng.events[1:], start=1):
        # the drained stream's pages are already the pool's: its merge
        # writes only the lane's fill level
        want = pos * (1 + retires[k]) + pos * (k == merged)
        assert ev.pool_copy_bytes == want, (k, ev.plan.label)


def test_tokens_identical_without_profiler(model, traced):
    _, traced_results, _ = traced
    _, plain = serve(model)
    assert [r.tokens for r in plain] == [r.tokens for r in traced_results]
