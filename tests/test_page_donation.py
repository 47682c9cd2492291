"""The step programs update the pool's page arrays in place.

The engine hands the pool's ``k_pages``/``v_pages`` to every step program
by donation and the pool adopts the outputs at dispatch, so no step copies
the whole pool. These tests hold that contract on the CPU (which donates):

* a served step consumes the arrays the pool held before it, and counts no
  undonated page bytes;
* tokens equal those of the same engine whose programs receive copies (what
  every step did before donation), in every mode, greedy and sampled, with
  and without speculation;
* a caller that passes a plain cache, as the chip benchmark's warm-up does,
  keeps its arrays, and reaches the very programs the engine runs: a later
  serve at a quantum the warm-up called compiles no step program.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import interleave
from repro.core.pim_modes import Mode
from repro.models import model as M
from repro.serve.api import GenerationRequest, SamplingParams
from repro.serve.engine import Engine
from repro.serve.serving_model import ServingModel
from repro.serve.spec import SpecConfig

MODES = [Mode.BLOCKED, Mode.HBCEM, Mode.LBIM]
MAX_LEN = 64
PROMPTS = [[1, 2, 3], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], [5, 5], [9],
           [2, 4, 6, 8, 1], [3, 1, 4, 1, 5, 9, 2, 6, 5]]
BUDGETS = [2, 9, 3, 6, 4, 7]


@pytest.fixture(scope="module")
def sm():
    cfg = get_config("llama3-8b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return ServingModel.prepare(cfg, params, max_len=MAX_LEN, slots=2)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    yield
    jax.clear_caches()


def _engine(sm, mode, spec=False, **kw):
    return Engine(sm.cfg, sm.params, max_len=MAX_LEN, mode=mode, slots=2,
                  chunk=4, serving=sm,
                  spec=SpecConfig(draft=sm, k=2) if spec else None, **kw)


def _reqs(temperature=0.0, **kw):
    return [GenerationRequest(
        prompt=list(p), max_new_tokens=b,
        sampling=SamplingParams(temperature=temperature, seed=i), **kw)
        for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS))]


def _copying(monkeypatch):
    """Programs receive copies of the handed-over pages, as before donation."""
    pages = interleave._pages
    monkeypatch.setattr(interleave, "_pages",
                        lambda cache: pages(dict(cache)))


@pytest.mark.parametrize("mode", MODES)
def test_served_steps_consume_the_pools_pages(sm, mode):
    """The page arrays the pool holds when a step starts are gone once its
    programs have run: they took them by donation."""
    eng = _engine(sm, mode)
    pool = eng.pool
    handed = []
    views, stream_cache = pool.views, pool.stream_cache

    def spy(fn):
        def wrapped(*a, **kw):
            out = fn(*a, **kw)
            if isinstance(out, interleave.Handover):
                handed.extend(out[k] for k in interleave.PAGE_KEYS)
            return out
        return wrapped
    pool.views, pool.stream_cache = spy(views), spy(stream_cache)
    res = eng.serve(_reqs())
    assert all(r.finish_reason for r in res)
    assert len(handed) >= 2 * len(eng.events) - 4
    assert all(a.is_deleted() for a in handed)
    assert not any(a.is_deleted() for a in pool._kv.pages.values())
    assert [e.undonated_page_bytes for e in eng.events] == [0] * len(eng.events)
    assert pool.check_invariants() == []


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("mode", MODES)
def test_tokens_equal_the_copying_steps(sm, monkeypatch, mode, temperature,
                                        spec):
    donated = _engine(sm, mode, spec).serve(_reqs(temperature))
    _copying(monkeypatch)
    eng = _engine(sm, mode, spec)
    copied = eng.serve(_reqs(temperature))
    assert [r.tokens for r in donated] == [r.tokens for r in copied]
    # the counter sees the copies: both page arrays on every stepped event
    pages = eng.pool.views()
    both = pages["k_pages"].nbytes + pages["v_pages"].nbytes
    counts = [e.undonated_page_bytes for e in eng.events]
    assert max(counts) >= both and all(n % both == 0 for n in counts)


@pytest.mark.parametrize("mode", [Mode.HBCEM, Mode.LBIM])
def test_warm_up_keeps_its_pages_and_compiles_the_engines_programs(sm, mode):
    """The chip benchmark's warm-up calls the public step functions on the
    pool's own views and staging, once per admission quantum: the pool's
    arrays must survive, and the engine must then find those programs
    compiled."""
    jax.clear_caches()   # what this test compiles, it compiles itself
    compiled = []

    def listen(event, _secs, fun_name="", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        eng = _engine(sm, mode)
        # one batch admission, then decode steps: no chunk program runs
        eng.serve([GenerationRequest(prompt=[7, 1, 2, 3], max_new_tokens=3),
                   GenerationRequest(prompt=[7, 2, 2, 3], max_new_tokens=3)])
        pool = eng.pool
        held = dict(pool._kv.pages)
        feed = jnp.zeros((2, 1), jnp.int32)
        del compiled[:]
        for q in (4, 8):
            staging, _ = pool.stage_admission([0] * (q + 1))
            staging = pool.staging_step_prep(staging, q)
            views = pool.views(span=1)
            toks = jnp.zeros((1, q), jnp.int32)
            if mode is Mode.LBIM:
                interleave.fused_step(sm.decode_params, views, feed, staging,
                                      toks, sm.cfg)
            interleave.prefill_chunk_step(sm.decode_params, staging, toks,
                                          sm.cfg)
            pool.release_staging()
        assert all(pool._kv.pages[k] is held[k] for k in held)
        assert not any(a.is_deleted() for a in held.values())
        assert any("prefill_chunk_step" in n for n in compiled)

        del compiled[:]
        # a prefix hit keeps the drained pool off the batch admission, so
        # the prompt's 8 fresh tokens stream in as one quantum of 8 (both
        # lanes free), then decode
        eng.serve([GenerationRequest(prompt=[7, 1, 2, 3] + list(range(8)),
                                     max_new_tokens=3)])
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert [e.prefill_tokens for e in eng.events if e.plan.prefill_chunk] == [8]
    assert [n for n in compiled if "_step" in n] == []
