"""Shared set-up of the chip benchmark's CPU tests: the benchmark package on
the import path, and a tiny configuration and mixes that exercise the
harness's real paths at a size a test run holds."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP_DIR = ROOT / "benchmarks" / "chip"
if str(CHIP_DIR) not in sys.path:
    sys.path.insert(0, str(CHIP_DIR))

TINY_CONFIG = {
    "name": "tiny", "registered": "yi-9b", "family": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "serving": {"chunk": 8, "prefix_lanes": 1, "attn_backend": "reference",
                "quantized_decode": True},
}


def tiny_traffic(mode: str = "lbim", slots: int = 4) -> dict:
    return {"mode": mode, "slots": slots, "max_len": 96, "arrival": "backlog",
            "prompt_pages": {"dist": "lognormal", "median": 4, "sigma": 0.4,
                             "lo": 2, "hi": 6},
            "output_tokens": {"dist": "lognormal", "median": 14, "sigma": 0.5,
                              "lo": 8, "hi": 24},
            "size_seed": 1, "shuffle_block": 8,
            "requests_per_s_ceiling": 400, "check_requests": 6}


# The tiny model's widest served-token gap reads about 0.08 and its
# control's about 2 (CPU); the faults planted by the tests read far above.
TINY_LIMIT = 0.5


@pytest.fixture
def tiny_cell():
    from chipbench import workload

    def make(traffic: dict) -> "workload.Cell":
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        return workload.Cell(
            name="tiny", chips=1, config=TINY_CONFIG, traffic=traffic,
            limits={"max_logit_gap": {"limit": TINY_LIMIT}},
            end_to_end=bench["end_to_end"],
            per_layer=bench["per_layer"])
    return make
