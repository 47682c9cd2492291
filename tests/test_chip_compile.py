"""Main-path Pallas kernels compile for a TPU v5e at Yi-9B widths.

Interpret-mode tests cannot see what the chip's compiler refuses (tile
alignment, block shapes, VMEM), so these compile each kernel for a v5e
that is described, not attached, and assert the kernel survived lowering
as a ``tpu_custom_call``. Nothing runs; no chip is needed. The topology is
described only inside a fixture: loading the TPU compiler at import time
would make pytest-xdist workers collect different tests.

Widths are Yi-9B's (d_model 4096, d_ff 11008, 32 query / 4 KV heads of 128)
at the serving shapes: 4 decode slots, max_len 1024, pages of 8 (the
default admission chunk) and 64 tokens.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import (decode_attention_op,
                                                decode_attention_paged_op)
from repro.kernels.pim_gemv.ops import pim_gemv_int8

SLOTS, N_KV, GROUP, HD, MAX_LEN = 4, 4, 8, 128, 1024


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
        spec((SLOTS, N_KV * GROUP, HD), jnp.bfloat16),
        spec((SLOTS, N_KV, HD, MAX_LEN), jnp.bfloat16),
        spec((SLOTS, N_KV, MAX_LEN, HD), jnp.bfloat16),
        spec((SLOTS,), jnp.int32))


@pytest.mark.parametrize("num_splits", [1, 4])
@pytest.mark.parametrize("page", [8, 64])
def test_decode_attention_paged_compiles(spec, page, num_splits):
    nb = MAX_LEN // page
    n_pages = (SLOTS + 1) * nb + 1

    def paged(q, kp, vp, table, pos):
        return decode_attention_paged_op(q, kp, vp, table, pos,
                                         scale=HD ** -0.5,
                                         num_splits=num_splits)

    _assert_kernel(paged,
                   spec((SLOTS, N_KV * GROUP, HD), jnp.bfloat16),
                   spec((n_pages, N_KV, HD, page), jnp.bfloat16),
                   spec((n_pages, N_KV, page, HD), jnp.bfloat16),
                   spec((SLOTS, nb), jnp.int32),
                   spec((SLOTS,), jnp.int32))
