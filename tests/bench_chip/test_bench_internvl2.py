"""The InternVL2-2B cell: its configuration file gives the registered
InternLM2-1.8B widths whole, the harness serves an InternLM2-shaped model
(GQA group 2, a vocabulary off the 128 grid, RoPE base 1e6) through HBCEM's
split steps on the CPU and the plain reference passes it and fails the W4A4
controls, and the admission-chunk program's reader reads the trace only
where a step ran that program on its own."""
from __future__ import annotations

import time
from types import SimpleNamespace as NS

import pytest
from conftest import TINY_CONFIG, TINY_LIMIT, tiny_traffic

from chipbench import harness, names, reference, steps, workload, xplane

CELL = "internvl2-2b.longctx-hbcem-b4"

# InternLM2-shaped at a CPU test's size: 4 query heads over 2 KV heads,
# a vocabulary that is not a multiple of 128, RoPE base 1e6
TINY_INTERNLM2 = dict(
    TINY_CONFIG, name="tiny-internlm2", registered="internvl2-2b",
    family="vlm", num_attention_heads=4, num_key_value_heads=2,
    vocab_size=250, rope_theta=1e6)


def test_cell_loads_with_the_registered_widths_whole():
    from repro.configs import get_config

    cell = workload.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["mode"] == "hbcem"
    c = cell.config
    assert c["reduced"] == []
    assert all(c[k] == v for k, v in c["published"].items())
    cfg = harness.model_config(c, "pallas")
    reg = get_config("internvl2-2b")
    for f in ("family", "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab_size", "rope_theta", "head_dim", "tie_embeddings"):
        assert getattr(cfg, f) == getattr(reg, f), f
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.head_dim) == (2, 128)
    assert cfg.quantized_decode and cfg.attn_backend == "pallas"
    dims = reference.Dims.from_config(c)
    assert (dims.d, dims.f, dims.layers, dims.vocab) == (2048, 8192, 24, 92553)


def test_cell_reports_the_admission_chunk_program():
    cell = workload.load_cell(CELL)
    per_layer = {m["name"] for m in cell.per_layer}
    assert "step_device_ms.prefill_chunk" in per_layer
    # the Yi cell's LBIM-only readers stay out of this cell
    assert "step_device_ms.fused" not in per_layer
    yi = workload.load_cell("yi9b-16l.chat-lbim-b4")
    assert "step_device_ms.prefill_chunk" not in {m["name"] for m in yi.per_layer}


def test_internlm2_shape_through_hbcem_matches_the_reference(tiny_cell,
                                                             monkeypatch):
    rebuilt = []
    orig = steps.rebuild

    def keep(*a, **kw):
        rebuilt.append(orig(*a, **kw))
        return rebuilt[-1]
    monkeypatch.setattr(steps, "rebuild", keep)
    cell = tiny_cell(tiny_traffic("hbcem"))
    cell.config = TINY_INTERNLM2
    res = harness.run(cell, 3_000_000_017, 1.5, False,
                      t_start=time.perf_counter(), backend="reference",
                      with_control=True)
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert res["correct"], checks
    assert checks["requests_not_terminal"] == 0
    assert checks["pool_invariant_violations"] == 0
    assert res["window"]["compiles"] == 0
    assert res["window"]["compared_tokens"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0
    kinds = {st.kind for st in rebuilt[0]}
    assert "split" in kinds and "fused" not in kinds, kinds
    for name in ("w4a4", "all"):
        c = res["controls"][name]
        assert c["max_logit_gap"] > TINY_LIMIT, (name, c)
        assert c["correct"] is False, (name, c)


def _summary():
    window = NS(name=xplane.WINDOW_SPAN, start_ns=1000, duration_ns=10_000)
    modules = [NS(name="jit_decode_only_step(7)", start_ns=1000, duration_ns=3000),
               NS(name="jit_prefill_chunk_step(8)", start_ns=4000,
                  duration_ns=1000),
               NS(name="jit_prefill_chunk_step(8)", start_ns=6000,
                  duration_ns=3000)]
    return xplane.summarize(NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[window])]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=[NS(name="fusion.1", start_ns=1000,
                                          duration_ns=8000)])])]))


@pytest.mark.parametrize("kinds,want", [
    (("decode", "split", "decode"), 2.0e-3),
    (("chunk",), 2.0e-3),
    (("decode", "fused", "idle"), None),
    ((), None),
])
def test_prefill_chunk_reader(kinds, want):
    read = harness.load_metric("step_device_ms.prefill_chunk")
    ctx = NS(summary=_summary(), steps=[NS(kind=k) for k in kinds])
    got = read(ctx)
    assert got == (None if want is None else pytest.approx(want))


def test_prefill_chunk_reader_fails_loudly_when_its_program_is_unnamed():
    s = xplane.summarize(NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            NS(name=xplane.WINDOW_SPAN, start_ns=0, duration_ns=100)])]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[]),
            NS(name="XLA Ops", events=[NS(name="fusion", start_ns=0,
                                          duration_ns=10)])])]))
    read = harness.load_metric("step_device_ms.prefill_chunk")
    with pytest.raises(names.NameMissing):
        read(NS(summary=s, steps=[NS(kind="split")]))
