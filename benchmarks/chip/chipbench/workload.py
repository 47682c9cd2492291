"""Cells, configurations and traffic mixes, found by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. Each is
one data file: ``configs/<config>.json`` holds the model as it is run (sizes
under the published config's keys, what was cut, what was assumed, the stated
precision and the serving knobs), ``traffic/<traffic>.json`` the mix (length
distributions, arrival rule, mode pin, pool width, request-count rule).
``limits/<cell>.json`` holds the limit of each number the correctness check
compares, with the readings it was set from. Adding a cell adds files; no
code here names one.

Every mix is a backlog: all of its requests are submitted at once, the
first ``slots`` fill the lanes before the window opens, and every later
step has a queue behind it.

The generator gives every seed the same multiset of request sizes, drawn once
from the mix's ``size_seed``; ``--seed`` only reorders them (within blocks of
``shuffle_block``, so every window sees the same mix) and draws the prompt
tokens. So two seeds do the same work in another order, and every program
shape a seed can reach is known before the window opens.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]

# published config.json keys -> repro ModelConfig fields
_MODEL_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "family": "family",
}


class WorkloadError(RuntimeError):
    """A cell, configuration or mix that cannot be run as written."""


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise WorkloadError(f"missing benchmark file {path}") from None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise WorkloadError(f"no workload named {name!r} in BENCHMARK.json")
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=load_json(CHIP_DIR / "configs" / f"{entry['config']}.json"),
        traffic=load_json(CHIP_DIR / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(CHIP_DIR / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def model_overrides(config: dict) -> dict:
    """ModelConfig fields the configuration file sets."""
    return {field_: config[key] for key, field_ in _MODEL_KEYS.items()
            if key in config}


# --------------------------------------------------------------- traffic


@dataclass
class Request:
    prompt: np.ndarray      # (prompt_len,) int32 token ids
    max_new: int


def _draw(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    """``n`` integers in ``[lo, hi]``, log-normal around ``median`` with
    log-spread ``sigma``, clipped to the range."""
    if dist["dist"] != "lognormal":
        raise WorkloadError(f"unknown length distribution {dist['dist']!r}")
    x = float(dist["median"]) * np.exp(float(dist["sigma"])
                                       * rng.standard_normal(n))
    return np.clip(np.rint(x), int(dist["lo"]), int(dist["hi"])).astype(np.int64)


def request_count(traffic: dict, seconds: float) -> int:
    """Requests submitted: the lanes filled before the window opens plus a
    ceiling on what the window can finish. The engine's per-step sweep is
    linear in this count, so it stays a margin above, not far beyond."""
    return traffic["slots"] + max(
        1, math.ceil(traffic["requests_per_s_ceiling"] * seconds))


def sizes(traffic: dict, n: int, chunk: int) -> list[tuple[int, int]]:
    """(prompt_len, max_new) of ``n`` requests, the same for every seed."""
    rng = np.random.default_rng(traffic["size_seed"])
    pages = _draw(rng, traffic["prompt_pages"], n)
    outs = _draw(rng, traffic["output_tokens"], n)
    out = [(int(p) * chunk, int(o)) for p, o in zip(pages, outs)]
    for p, o in out:
        if p + o - 1 > traffic["max_len"]:
            raise WorkloadError(f"prompt {p} + output {o} exceeds max_len "
                                f"{traffic['max_len']}")
    return out


def prompt_lengths(traffic: dict, chunk: int) -> list[int]:
    """Every prompt length the mix can send, whatever the seed or window."""
    d = traffic["prompt_pages"]
    return [p * chunk for p in range(int(d["lo"]), int(d["hi"]) + 1)]


def order(traffic: dict, n: int, seed: int) -> list[int]:
    """The seed's order of the size list: the first ``slots`` entries
    (filled before the window opens) stay in place, the rest is permuted
    within consecutive blocks of ``shuffle_block``."""
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(2)[1])
    head = traffic["slots"]
    idx = list(range(head))
    blk = int(traffic["shuffle_block"])
    for s in range(head, n, blk):
        block = list(range(s, min(s + blk, n)))
        idx += [block[i] for i in rng.permutation(len(block))]
    return idx


def generate(traffic: dict, seed: int, seconds: float, vocab: int,
             chunk: int) -> list[Request]:
    """The seed's request list for a window of ``seconds``."""
    if traffic["arrival"] != "backlog":
        raise WorkloadError(f"unknown arrival rule {traffic['arrival']!r}")
    n = request_count(traffic, seconds)
    size = sizes(traffic, n, chunk)
    tok_rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(2)[0])
    reqs = []
    for i in order(traffic, n, seed):
        plen, out = size[i]
        prompt = tok_rng.integers(1, vocab, plen).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new=out))
    return reqs


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words from any non-negative seed: the raw key that both
    the program's initialiser and the reference's draw the weights from."""
    if seed < 0:
        raise WorkloadError(f"--seed must be >= 0, got {seed}")
    return np.random.SeedSequence(seed).generate_state(2, np.uint32)
