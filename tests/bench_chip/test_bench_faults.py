"""Runs of the harness with the timed path broken underneath: each planted
fault must turn ``correct`` false. The faults a serving cell can have: a
token altered where it is produced, a step that leaves its state (the KV
pages) unchanged, and half of the batch left out (its lanes given the other
half's logits)."""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest
from conftest import tiny_traffic

from chipbench import harness


def _alter_tokens(monkeypatch):
    from repro.serve import sampling

    orig = sampling.greedy_masked

    def altered(logits, done, pad_id=0):
        tok = orig(logits, done, pad_id)
        return jnp.where(done, tok, (tok + 1) % logits.shape[-1])
    monkeypatch.setattr(sampling, "greedy_masked", altered)


def _stale_state(monkeypatch):
    from repro.core import interleave

    dec, fused = interleave.decode_only_step, interleave.fused_step

    def stale_decode(params, cache, tokens, cfg):
        return dec(params, cache, tokens, cfg)[0], cache

    def stale_fused(params, dcache, dtok, pcache, ptok, cfg):
        dl, _, pl, pc = fused(params, dcache, dtok, pcache, ptok, cfg)
        return dl, dcache, pl, pc
    monkeypatch.setattr(interleave, "decode_only_step", stale_decode)
    monkeypatch.setattr(interleave, "fused_step", stale_fused)


def _half_batch(monkeypatch):
    from repro.core import interleave

    dec, fused = interleave.decode_only_step, interleave.fused_step

    def skip(logits):
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:logits.shape[0] - h])

    def half_decode(params, cache, tokens, cfg):
        lg, c = dec(params, cache, tokens, cfg)
        return skip(lg), c

    def half_fused(params, dcache, dtok, pcache, ptok, cfg):
        dl, dc, pl, pc = fused(params, dcache, dtok, pcache, ptok, cfg)
        return skip(dl), dc, pl, pc
    monkeypatch.setattr(interleave, "decode_only_step", half_decode)
    monkeypatch.setattr(interleave, "fused_step", half_fused)


@pytest.mark.parametrize("plant", [_alter_tokens, _stale_state, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
def test_planted_fault_reads_incorrect(tiny_cell, monkeypatch, plant):
    plant(monkeypatch)
    res = harness.run(tiny_cell(tiny_traffic("lbim")), 3_000_000_011, 1.5,
                      False, t_start=time.perf_counter(), backend="reference")
    assert not res["correct"], res["checks"]
