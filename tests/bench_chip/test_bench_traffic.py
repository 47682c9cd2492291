"""Traffic draws: deterministic per seed, whole pages, the same sizes for
every seed in another order, and the backlog arrival rule."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import workload

BIG_SEED = 2**31 + 12345
MIXES = sorted(p.stem for p in (workload.CHIP_DIR / "traffic").glob("*.json"))


def mix(name):
    return workload.load_json(workload.CHIP_DIR / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_draws_are_deterministic_whole_pages_and_fit(name):
    tr = mix(name)
    a = workload.generate(tr, BIG_SEED, 30, 64000, 64)
    b = workload.generate(tr, BIG_SEED, 30, 64000, 64)
    assert [(r.prompt.tolist(), r.max_new) for r in a] == \
           [(r.prompt.tolist(), r.max_new) for r in b]
    lo, hi = tr["prompt_pages"]["lo"] * 64, tr["prompt_pages"]["hi"] * 64
    for r in a:
        assert len(r.prompt) % 64 == 0 and lo <= len(r.prompt) <= hi
        assert tr["output_tokens"]["lo"] <= r.max_new <= tr["output_tokens"]["hi"]
        assert len(r.prompt) + r.max_new - 1 <= tr["max_len"]
        assert r.prompt.min() >= 1 and r.prompt.max() < 64000
    assert {len(r.prompt) for r in a} <= set(workload.prompt_lengths(tr, 64))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    tr = mix(name)
    a = workload.generate(tr, 1, 30, 64000, 64)
    b = workload.generate(tr, BIG_SEED, 30, 64000, 64)
    sa = [(len(r.prompt), r.max_new) for r in a]
    sb = [(len(r.prompt), r.max_new) for r in b]
    assert sorted(sa) == sorted(sb) and sa != sb
    blk = tr["shuffle_block"]
    head = tr["slots"]
    assert sa[:head] == sb[:head]          # the backlog's first lanes
    for s in range(head, len(sa), blk):    # each block holds the same sizes
        assert sorted(sa[s:s + blk]) == sorted(sb[s:s + blk])
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_arrival_rules():
    """Every mix is a backlog; another rule is refused, not run."""
    for name in MIXES:
        assert mix(name)["arrival"] == "backlog"
    with pytest.raises(workload.WorkloadError):
        workload.generate(dict(mix("chat-lbim-b4"), arrival="poisson"), 5, 30,
                          64000, 64)


def test_lognormal_draws_follow_their_median_and_clip():
    rng = np.random.default_rng(0)
    d = {"dist": "lognormal", "median": 129, "sigma": 0.9, "lo": 8, "hi": 512}
    x = workload._draw(rng, d, 20000)
    assert x.min() >= 8 and x.max() <= 512
    assert abs(np.median(x) - 129) <= 4
    assert (x == 512).mean() > 0.03   # the tail past the cap lands on it


def test_request_count_follows_the_window():
    tr = dict(mix("chat-lbim-b4"), requests_per_s_ceiling=8.0)
    assert workload.request_count(tr, 30) == 4 + 240
    assert workload.request_count(tr, 10) == 4 + 80


def test_seed_words_cover_large_seeds():
    assert workload.seed_words(BIG_SEED).dtype == np.uint32
    assert not np.array_equal(workload.seed_words(1), workload.seed_words(2))
    with pytest.raises(workload.WorkloadError):
        workload.seed_words(-1)
