"""Beyond-paper optimizations must preserve exact model semantics."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import model as M


@pytest.fixture(scope="module")
def gemma():
    cfg = get_config("gemma2-27b", smoke=True).replace(
        dtype="float32", param_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 14), 0, cfg.vocab_size)
    return cfg, params, toks


@pytest.mark.parametrize("split", [6, 12])  # prompt < W and prompt > W (W=8)
def test_ring_kv_cache_matches_full(gemma, split):
    """W-slot ring cache for local layers == full cache, both fill regimes."""
    cfg, params, toks = gemma
    cfg_ring = cfg.replace(windowed_kv_cache=True)
    lf, cf = M.prefill(params, {"tokens": toks[:, :split]}, cfg, max_len=32)
    lr, cr = M.prefill(params, {"tokens": toks[:, :split]}, cfg_ring, max_len=32)
    assert cr["k_loc"].shape[-1] == cfg.sliding_window  # W slots, not max_len
    errs = [float(jnp.max(jnp.abs(lf - lr)))]
    for i in range(split, 14):
        lf, cf = M.decode_step(params, cf, toks[:, i:i + 1], cfg)
        lr, cr = M.decode_step(params, cr, toks[:, i:i + 1], cfg_ring)
        errs.append(float(jnp.max(jnp.abs(lf - lr))))
    assert max(errs) < 1e-4


def test_f8_kv_cache_close_to_bf16():
    """f8 KV (int8-KV analogue): logits drift stays small (accuracy audit)."""
    cfg = get_config("llama3-8b", smoke=True)
    cfg8 = cfg.replace(kv_dtype="float8_e4m3fn")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab_size)
    l1, c1 = M.prefill(params, {"tokens": toks[:, :6]}, cfg, max_len=16)
    l2, c2 = M.prefill(params, {"tokens": toks[:, :6]}, cfg8, max_len=16)
    assert c2["k"].dtype == jnp.float8_e4m3fn
    for i in range(6, 10):
        l1, c1 = M.decode_step(params, c1, toks[:, i:i + 1], cfg)
        l2, c2 = M.decode_step(params, c2, toks[:, i:i + 1], cfg8)
    # greedy decisions should agree on a smoke model
    assert jnp.array_equal(jnp.argmax(l1, -1), jnp.argmax(l2, -1))


def test_seq_parallel_is_semantics_preserving():
    """with_sharding_constraint changes layout only — identical outputs."""
    cfg = get_config("internvl2-2b", smoke=True).replace(
        dtype="float32", param_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size),
        "prefix_embeds": 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (2, cfg.n_prefix_tokens, cfg.d_model)),
    }
    x1 = M.forward(params, batch, cfg)
    x2 = M.forward(params, batch, cfg.replace(seq_parallel=True))
    assert float(jnp.max(jnp.abs(x1 - x2))) < 1e-5


def test_causal_block_skip_matches_full():
    """Triangular KV-block skipping == full computation (masked anyway)."""
    cfg = get_config("llama3-8b", smoke=True).replace(
        dtype="float32", param_dtype="float32", q_chunk=4)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)}
    x1 = M.forward(params, batch, cfg.replace(causal_block_skip=True))
    x2 = M.forward(params, batch, cfg.replace(causal_block_skip=False))
    assert float(jnp.max(jnp.abs(x1 - x2))) < 1e-5


@pytest.mark.parametrize("skip", [True, False])
def test_ragged_tail_chunks_match_one_chunk(skip):
    """A sequence that is not a whole number of query chunks is chunked with
    a shorter last chunk, and matches attention over one chunk of it all."""
    cfg = get_config("llama3-8b", smoke=True).replace(
        dtype="float32", param_dtype="float32", causal_block_skip=skip)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 14), 0, cfg.vocab_size)}
    x1 = M.forward(params, batch, cfg.replace(q_chunk=4))
    x2 = M.forward(params, batch, cfg.replace(q_chunk=16))
    assert float(jnp.max(jnp.abs(x1 - x2))) < 1e-5
