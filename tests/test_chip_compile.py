"""Main-path Pallas kernels compile for a TPU v5e at the widths the
benchmark serves.

Interpret-mode tests cannot see what the chip's compiler refuses (tile
alignment, block shapes, VMEM), so these compile each kernel for a v5e
that is described, not attached, and assert the kernel survived lowering
as a ``tpu_custom_call``. Nothing runs; no chip is needed. The topology is
described only inside a fixture: loading the TPU compiler at import time
would make pytest-xdist workers collect different tests.

Widths are Yi-9B's (d_model 4096, d_ff 11008, 32 query / 4 KV heads of 128)
at 4 decode slots, max_len 1024, pages of 8 (the default admission chunk)
and 64 tokens; and InternLM2-1.8B's, the language model of InternVL2-2B
(d_model 2048, d_ff 8192, 16 query / 8 KV heads of 128, so a GQA group of
2 and a ``(1, 1, 2, 128)`` query block), at 4 slots, max_len 4608 and pages
of 64, as its long-context cell serves them. One more compiles the batch
prefill's layer scan at InternLM2 widths and bounds its scratch memory.
"""
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import (decode_attention_op,
                                                decode_attention_paged_op)
from repro.kernels.pim_gemv.ops import pim_gemv_int8

SLOTS, HD = 4, 128


@dataclass(frozen=True)
class Widths:
    n_kv: int
    group: int
    max_len: int
    prefix_lanes: int = 0   # lanes of pages the pool keeps for the prefix store


YI = Widths(n_kv=4, group=8, max_len=1024)
INTERNLM2 = Widths(n_kv=8, group=2, max_len=4608, prefix_lanes=1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described-chip compile is written to the persistent cache but cannot
    # be read back without a chip: keep the cache off around these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            jax.config.update("jax_enable_compilation_cache", prev)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on the described chip 0."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("n,k", [
    (4096, 4096),    # o projection (and q)
    (11008, 4096),   # gate / up projections
    (4096, 11008),   # down projection (K off the 512 block grid: padded)
    (2048, 2048),    # InternLM2: o projection (and q)
    (1024, 2048),    # InternLM2: k / v projections
    (8192, 2048),    # InternLM2: gate / up projections
    (2048, 8192),    # InternLM2: down projection
])
def test_pim_gemv_compiles(spec, n, k, precision):
    """``"highest"``: the f32 reference recipe wraps a step in
    ``default_matmul_precision("highest")``; the int8 kernel inside it must
    not inherit an fp32 contraction, which Mosaic refuses for int8."""
    with jax.default_matmul_precision(precision):
        _assert_kernel(pim_gemv_int8,
                       spec((n, k), jnp.int8), spec((SLOTS, k), jnp.int8),
                       spec((n,), jnp.float32), spec((SLOTS,), jnp.float32))


def test_decode_attention_contiguous_compiles(spec):
    _assert_kernel(
        lambda q, k, v, pos: decode_attention_op(q, k, v, pos, scale=HD ** -0.5),
        spec((SLOTS, YI.n_kv * YI.group, HD), jnp.bfloat16),
        spec((SLOTS, YI.n_kv, HD, YI.max_len), jnp.bfloat16),
        spec((SLOTS, YI.n_kv, YI.max_len, HD), jnp.bfloat16),
        spec((SLOTS,), jnp.int32))


@pytest.mark.parametrize("w,page,num_splits", [
    pytest.param(YI, 8, 1, id="8-1"),
    pytest.param(YI, 8, 4, id="8-4"),
    pytest.param(YI, 64, 1, id="64-1"),
    pytest.param(YI, 64, 4, id="64-4"),
    pytest.param(INTERNLM2, 64, 1, id="internlm2-64-1"),
    pytest.param(INTERNLM2, 64, 4, id="internlm2-64-4"),
])
def test_decode_attention_paged_compiles(spec, w, page, num_splits):
    nb = w.max_len // page
    n_pages = (SLOTS + 1 + w.prefix_lanes) * nb + 1

    def paged(q, kp, vp, table, pos):
        return decode_attention_paged_op(q, kp, vp, table, pos,
                                         scale=HD ** -0.5,
                                         num_splits=num_splits)

    _assert_kernel(paged,
                   spec((SLOTS, w.n_kv * w.group, HD), jnp.bfloat16),
                   spec((n_pages, w.n_kv, HD, page), jnp.bfloat16),
                   spec((n_pages, w.n_kv, page, HD), jnp.bfloat16),
                   spec((SLOTS, nb), jnp.int32),
                   spec((SLOTS,), jnp.int32))


def test_long_prompt_batch_prefill_fits_beside_the_pool(topo):
    """The engine admits a drained pool's lanes by one batch prefill. At
    InternLM2-1.8B's widths, four prompts of 3776 tokens (not a whole number
    of 1024-row query chunks) keep the layer scan's scratch near 1 GB: a
    ragged tail is one shorter last chunk, not a 3776 x 3776 score matrix
    per head (7.4 GB), which does not fit beside the weights and page pool
    on one chip."""
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config("internvl2-2b").replace(attn_backend="pallas")
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        M.param_specs(cfg))
    x = jax.ShapeDtypeStruct((SLOTS, 3776, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda p, h: M._scan_layers(p, h, cfg, collect_kv=True)
    ).lower(params, x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
