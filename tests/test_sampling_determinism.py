"""Per-slot RNG determinism + the unified masked-sampling path.

The RNG lane of a request is derived from its own SamplingParams.seed and
prompt only — never from slot index, admission order, or sibling lifetime —
so the same seed + the same request set must emit identical tokens no matter
how the scheduler interleaves them (different submission orders, different
pool widths, different modes, solo vs batched). Covered for the dense family
and one state-carrying family (rwkv6: recurrent state, per-request
admission).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.pim_modes import Mode
from repro.models import model as M
from repro.serve import sampling
from repro.serve.api import GenerationRequest, SamplingParams
from repro.serve.serving_model import ServingModel

MAX_LEN = 48


# ------------------------------------------------------ sample_masked (unit)


def _logits(b=3, v=17, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, 1, v))


def _params(b, temps, ks=None, ps=None, seeds=None):
    return dict(
        keys=jnp.stack([jax.random.PRNGKey(s)
                        for s in (seeds or list(range(b)))]),
        temperature=jnp.asarray(temps, jnp.float32),
        top_k=jnp.asarray(ks or [0] * b, jnp.int32),
        top_p=jnp.asarray(ps or [1.0] * b, jnp.float32),
    )


def test_temperature_zero_is_exact_greedy():
    lg = _logits()
    done = jnp.zeros((3,), bool)
    out = sampling.sample_masked(lg, done, **_params(3, [0.0, 0.0, 0.0]))
    assert (np.asarray(out) == np.asarray(sampling.greedy(lg))).all()
    # and greedy_masked IS the temperature=0 case of the same path
    assert (np.asarray(sampling.greedy_masked(lg, done))
            == np.asarray(sampling.greedy(lg))).all()


def test_done_lanes_emit_pad():
    lg = _logits()
    done = jnp.asarray([True, False, True])
    out = np.asarray(sampling.sample_masked(lg, done, **_params(3, [0.9] * 3)))
    assert out[0] == 0 and out[2] == 0


def test_top_k_one_and_tiny_top_p_collapse_to_argmax():
    lg = _logits(b=4, v=33, seed=3)
    done = jnp.zeros((4,), bool)
    gd = np.asarray(sampling.greedy(lg))
    k1 = sampling.sample_masked(lg, done, **_params(4, [1.3] * 4, ks=[1] * 4))
    assert (np.asarray(k1) == gd).all()
    p0 = sampling.sample_masked(lg, done, **_params(4, [1.3] * 4, ps=[1e-9] * 4))
    assert (np.asarray(p0) == gd).all()


def test_top_k_geq_vocab_is_exact_noop():
    """``top_k >= vocab`` keeps every token — bit-identical to disabled (0),
    including the filtered logits themselves (the explicit bypass, not a
    near-miss through the sort/cumsum path)."""
    lg = _logits(b=4, v=17, seed=9)
    done = jnp.zeros((4,), bool)
    base = np.asarray(sampling.sample_masked(
        lg, done, **_params(4, [1.1] * 4, ks=[0] * 4)))
    for k in (17, 18, 1000):
        out = np.asarray(sampling.sample_masked(
            lg, done, **_params(4, [1.1] * 4, ks=[k] * 4)))
        assert (out == base).all(), f"top_k={k} changed the draw"
    # k >= vocab composes with an ACTIVE top_p exactly like k disabled
    row = lg[0, 0, :]
    withp = sampling._filter_top_k_top_p(row, jnp.int32(17), jnp.float32(0.6))
    nop = sampling._filter_top_k_top_p(row, jnp.int32(0), jnp.float32(0.6))
    assert (np.asarray(withp) == np.asarray(nop)).all()


def test_top_p_one_is_exact_noop():
    """``top_p == 1.0`` passes logits through UNTOUCHED. The cumsum tail can
    reach 1.0 exactly in f32, so without the explicit bypass the last-ranked
    token would be silently dropped — a distribution change rejection
    sampling (speculative verify) would inherit."""
    lg = _logits(b=3, v=33, seed=4)
    done = jnp.zeros((3,), bool)
    base = np.asarray(sampling.sample_masked(
        lg, done, **_params(3, [0.9] * 3, ps=[1.0] * 3)))
    free = np.asarray(sampling.sample_masked(
        lg, done, **_params(3, [0.9] * 3)))  # defaults: p=1, k=0
    assert (base == free).all()
    for row in np.asarray(lg[:, 0, :]):
        filt = sampling._filter_top_k_top_p(
            jnp.asarray(row), jnp.int32(0), jnp.float32(1.0))
        # bitwise passthrough: every logit survives, none clamped to NEG_FILL
        assert (np.asarray(filt) == row).all()
    # and the combined disabled-cutoff case (p=1, k>=vocab) is also exact
    row = lg[0, 0, :]
    filt = sampling._filter_top_k_top_p(row, jnp.int32(33), jnp.float32(1.0))
    assert (np.asarray(filt) == np.asarray(row)).all()


def test_mixed_greedy_and_sampled_lanes_do_not_interact():
    """A greedy lane inside a sampled batch is bit-identical to greedy."""
    lg = _logits(b=3, v=29, seed=5)
    done = jnp.zeros((3,), bool)
    mixed = np.asarray(sampling.sample_masked(
        lg, done, **_params(3, [0.0, 0.8, 1.5])))
    assert mixed[0] == np.asarray(sampling.greedy(lg))[0]
    # the sampled lanes are a function of their OWN key only
    again = np.asarray(sampling.sample_masked(
        lg, done, **_params(3, [0.0, 0.8, 1.5])))
    assert (mixed == again).all()


def test_request_key_ignores_scheduling_but_not_prompt():
    a = sampling.request_key(7, [1, 2, 3])
    assert np.asarray(a).tolist() == np.asarray(
        sampling.request_key(7, [1, 2, 3])).tolist()
    assert np.asarray(a).tolist() != np.asarray(
        sampling.request_key(7, [3, 2, 1])).tolist()
    assert np.asarray(a).tolist() != np.asarray(
        sampling.request_key(8, [1, 2, 3])).tolist()
    # linear-checksum collision class ([3] vs [1, 1]) must not alias lanes
    assert np.asarray(sampling.request_key(7, [3])).tolist() != np.asarray(
        sampling.request_key(7, [1, 1])).tolist()


# ---------------------------------------------- check_and_sample (unit)


@pytest.mark.parametrize("positions", [1, 3])
@pytest.mark.parametrize("temps,ks,ps", [
    ([0.0, 0.0, 0.0, 0.0], None, None),                   # all-greedy rule
    ([0.0, 0.8, 1.5, 0.9], None, None),                   # greedy rider
    ([1.1, 0.6, 0.7, 1.3], [8, 0, 3, 40], [1.0, 0.9, 0.7, 0.5]),
], ids=["greedy", "mixed", "filtered"])
def test_check_and_sample_tokens_equal_the_rules(temps, ks, ps, positions):
    """The one-program path emits exactly what the per-position rules emit:
    ``greedy_masked`` when every lane is greedy, else ``sample_masked`` on
    each position's token key (``emitted + j``), with done lanes padded."""
    lg = jax.random.normal(jax.random.PRNGKey(7), (4, positions, 41))
    done = jnp.asarray([False, True, False, False])
    p = _params(4, temps, ks=ks, ps=ps)
    offs = jnp.asarray([0, 3, 5, 11], jnp.uint32)
    greedy = all(t == 0 for t in temps)
    lanes = (sampling.Lanes(done) if greedy else sampling.Lanes(
        done, temperature=p["temperature"], top_k=p["top_k"],
        top_p=p["top_p"], keys=p["keys"], offsets=offs))
    out = np.asarray(sampling.check_and_sample([(lg, lanes)]))
    assert out.shape == (4 * positions + 1,) and out[-1] == 1
    got = out[:-1].reshape(4, positions)
    for j in range(positions):
        row = lg[:, j:j + 1, :]
        if greedy:
            want = sampling.greedy_masked(row, done)
        else:
            keys = jax.vmap(jax.random.fold_in)(p["keys"], offs + j)
            want = sampling.sample_masked(
                row, done, keys=keys, temperature=p["temperature"],
                top_k=p["top_k"], top_p=p["top_p"])
        assert (got[:, j] == np.asarray(want)).all(), j
    assert (got[1] == 0).all()  # the done lane emits pad


@pytest.mark.parametrize("temp", [0.0, 0.9])
def test_check_and_sample_packs_a_first_token_after_the_lanes(temp):
    """A prefill row's first token follows the lanes' tokens, drawn as a
    batch-1 ``sample_masked`` on its request's token key would draw it."""
    lg = _logits(b=3, v=23, seed=8)
    pre = _logits(b=1, v=23, seed=9)
    lanes = sampling.Lanes(jnp.zeros((3,), bool))
    key = jax.random.PRNGKey(5)
    first = (sampling.Lanes(jnp.zeros((1,), bool)) if temp == 0 else
             sampling.Lanes(jnp.zeros((1,), bool),
                            temperature=jnp.asarray([temp], jnp.float32),
                            top_k=jnp.zeros((1,), jnp.int32),
                            top_p=jnp.ones((1,), jnp.float32),
                            keys=key[None], offsets=jnp.asarray([4], jnp.uint32)))
    out = np.asarray(sampling.check_and_sample([(lg, lanes), (pre, first)]))
    assert out.shape == (5,) and out[-1] == 1
    assert (out[:3] == np.asarray(sampling.greedy(lg))).all()
    want = sampling.sample_masked(
        pre, jnp.zeros((1,), bool), keys=sampling.token_key(key, 4)[None],
        temperature=jnp.asarray([temp], jnp.float32),
        top_k=jnp.zeros((1,), jnp.int32), top_p=jnp.ones((1,), jnp.float32))
    assert out[3] == int(np.asarray(want)[0])


# -------------------------------------------- engine-level determinism (e2e)


def _requests(vocab):
    rng = np.random.default_rng(11)
    samplers = [
        SamplingParams(temperature=0.8, seed=1),
        SamplingParams(temperature=1.1, top_k=8, seed=2),
        SamplingParams(),  # greedy rider in a sampled pool
        SamplingParams(temperature=0.9, top_p=0.7, seed=3),
        SamplingParams(temperature=0.7, top_k=16, top_p=0.9, seed=4),
    ]
    return [GenerationRequest(
                prompt=list(map(int, rng.integers(1, vocab,
                                                  int(rng.integers(2, 7))))),
                max_new_tokens=int(rng.integers(2, 6)),
                sampling=sp)
            for sp in samplers]


def _serve_permuted(sm, reqs, order, slots, mode):
    out = sm.engine(slots=slots, mode=mode, chunk=2).serve(
        [reqs[i] for i in order])
    return {order[j]: out[j].tokens for j in range(len(order))}


@pytest.mark.parametrize("arch,family", [("llama3-8b", "dense"),
                                         ("rwkv6-1.6b", "ssm")])
def test_same_seed_same_requests_any_admission_order(arch, family):
    """same seed + same request set => identical tokens regardless of
    admission order, pool width, mode, or sibling retirement."""
    cfg = get_config(arch, smoke=True)
    assert cfg.family == family
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    sm = ServingModel.prepare(cfg, params, max_len=MAX_LEN, slots=3)
    reqs = _requests(cfg.vocab_size)
    n = len(reqs)

    base = _serve_permuted(sm, reqs, list(range(n)), slots=2, mode=Mode.HBCEM)
    shuffled = _serve_permuted(sm, reqs, [2, 0, 4, 1, 3], slots=3,
                               mode=Mode.LBIM)
    assert shuffled == base
    # solo pool: every sibling interaction removed entirely
    solo = {}
    for i in range(n):
        solo.update(_serve_permuted(sm, reqs, [i], slots=1, mode=Mode.HBCEM))
    assert solo == base


def test_rerun_is_deterministic():
    cfg = get_config("llama3-8b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    sm = ServingModel.prepare(cfg, params, max_len=MAX_LEN, slots=2)
    reqs = _requests(cfg.vocab_size)
    a = _serve_permuted(sm, reqs, list(range(len(reqs))), 2, Mode.LBIM)
    b = _serve_permuted(sm, reqs, list(range(len(reqs))), 2, Mode.LBIM)
    assert a == b
