"""The roofline counts against hand counts at the published Yi-9B and
InternLM2-1.8B widths, and the rebuilding of per-step work from the
engine's schedule events and the token stamps."""
from __future__ import annotations

import json
from types import SimpleNamespace as NS

import pytest

from chipbench import counts, reference, steps, workload

YI = reference.Dims.from_config(
    workload.load_json(workload.CHIP_DIR / "configs" / "yi9b-16l.json"))
# the InternLM2-1.8B language model of InternVL2-2B, at its published widths
IVL = reference.Dims(d=2048, f=8192, heads=16, kv_heads=8, head_dim=128,
                     layers=24, vocab=92553, theta=1e6, eps=1e-5)


def test_weight_and_kv_sizes():
    per_layer = sum(k * n for k, n in counts.linear_shapes(YI))
    # q, o 4096x4096; k, v 4096x512; gate, up, down 4096x11008
    assert per_layer == 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert per_layer * YI.layers == 2_768_240_640          # 2.77 GB int8
    ivl = sum(k * n for k, n in counts.linear_shapes(IVL))
    assert ivl * IVL.layers == 1_509_949_440               # 1.51 GB int8
    # K and V of one token over all layers: 32 KB (Yi-16l), 96 KB (InternLM2)
    assert counts._kv_token_bytes(YI) * YI.layers == 32 * 1024
    assert counts._kv_token_bytes(IVL) * IVL.layers == 96 * 1024


def test_pim_gemv_call():
    w = counts.pim_gemv(4096, 4096, 4)
    assert w.int_ops == 2 * 4 * 4096 * 4096
    assert w.bytes == 4096 * 4096 + 4096 * 4 + 4 * 4096 + 4 * 4 + 4 * 4096 * 4
    assert w.flops == 0


def test_decode_attention_call():
    w = counts.decode_attention(YI, [1000, 1000, 1000, 1000])
    assert w.bytes == 4000 * (2 * 4 * 128 * 2) + 4 * 32 * 128 * (2 + 4)
    assert w.flops == 4 * 32 * 128 * 4000
    w = counts.decode_attention(IVL, [5000])
    assert w.bytes == 5000 * (2 * 8 * 128 * 2) + 16 * 128 * 6


def test_decode_step_by_hand():
    ctxs = [300, 700]
    w = counts.decode_step(YI, ctxs)
    gemv_bytes = sum(n * k + 4 * n + 2 * k + 2 * 4 + 2 * n * 4
                     for k, n in counts.linear_shapes(YI))
    attn_bytes = 1000 * 2048 + 2 * 32 * 128 * 6
    append = 2 * 2048
    lm = 4096 * 64000 * 2
    assert w.bytes == YI.layers * (gemv_bytes + attn_bytes + append) + lm
    assert w.int_ops == YI.layers * 2 * 2 * 173_015_040
    assert w.flops == YI.layers * 4 * 32 * 128 * 1000 + 2 * 2 * 4096 * 64000
    assert counts.decode_step(YI, []).bytes == 0


def test_chunk_prefill_by_hand():
    w = counts.chunk_prefill(YI, 64, 128, head_rows=1)
    keys = 64 * 128 + 64 * 65 // 2
    assert w.flops == (YI.layers * (2 * 64 * 173_015_040 + 4 * 32 * 128 * keys)
                       + 2 * 4096 * 64000)
    assert w.bytes == (YI.layers * (173_015_040 * 2 + 192 * 2048 + 64 * 2048)
                       + 4096 * 64000 * 2)
    full = counts.full_prefill(YI, [64])
    assert full.flops == counts.chunk_prefill(YI, 64, 0, 1).flops


def test_least_seconds_takes_the_binding_side():
    pk = counts.peaks("TPU v5 lite")
    w = counts.decode_step(YI, [1000] * 4)
    assert counts.least_seconds(w, pk) == pytest.approx(w.bytes / 819e9)
    big = counts.Work(flops=197e12, int_ops=393e12, bytes=1.0)
    assert counts.least_seconds(big, pk) == pytest.approx(2.0)
    with pytest.raises(KeyError):
        counts.peaks("TPU v99")


def _ev(decode_batch, prefill, decode=True, fused=False):
    return NS(plan=NS(decode=decode, fused=fused), decode_batch=decode_batch,
              prefill_tokens=prefill, idle_steps=0)


def test_rebuild_steps():
    # requests 0-3 batch-prefilled at step 0 (prompts 64, 128, 64, 64);
    # request 4 (prompt 128) streamed in two fused chunks at steps 1-2,
    # placed at count 3 after request 0 retired
    events = [_ev(0, 320, decode=False), _ev(4, 64, fused=True),
              _ev(4, 64, fused=True), _ev(3, 0)]
    plens = [64, 128, 64, 64, 128]
    stamps = [[(1, 0), (2, 1), (3, 2)],
              [(1, 0), (2, 1), (3, 2), (4, 3)],
              [(1, 0), (2, 1), (3, 2), (4, 3)],
              [(1, 0), (2, 1), (3, 2), (4, 3)],
              [(3, 0)]]
    admit = [0, 0, 0, 0, 1]
    got = steps.rebuild(events, stamps, admit, plens, [0] * 5)
    assert got[0].kind == "prefill" and got[0].prefill_lens == [64, 128, 64, 64]
    assert got[1].kind == "fused" and (got[1].chunk, got[1].chunk_off) == (64, 0)
    assert got[1].ctxs == [65, 129, 65, 65] and not got[1].chunk_last
    assert got[2].chunk_off == 64 and got[2].chunk_last
    assert got[3].kind == "decode" and got[3].ctxs == [131, 67, 67]
    fused = got[2].work(YI)
    assert fused.flops == (counts.decode_step(YI, got[2].ctxs).flops
                           + counts.chunk_prefill(YI, 64, 64).flops
                           + 2 * 4096 * 64000)


def test_benchmark_file_names_exactly_its_metrics():
    bench = json.loads((workload.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (workload.CHIP_DIR / "metrics" / f"{m['name']}.py").exists()
    for w in bench["workloads"]:
        cell = workload.load_cell(w["name"])
        assert cell.limits["max_logit_gap"]["limit"] > 0
