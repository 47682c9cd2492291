"""The ``undonated_page_mb`` reader: the mean of the engine's per-step count
of page-array bytes its programs copied for want of donation, in MB, and
nothing for a program whose events carry no such count."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import harness


@pytest.mark.parametrize("counts,want", [((0, 0, 0), 0.0),
                                         ((2_724_000_000, 0), 1362.0),
                                         ((505_000_000,), 505.0)])
def test_undonated_page_mb_reads_the_events_counters(counts, want):
    read = harness.load_metric("undonated_page_mb")
    evs = [NS(undonated_page_bytes=n) for n in counts]
    assert read(NS(window_events=evs)) == pytest.approx(want)


@pytest.mark.parametrize("events", [[NS(decode_batch=4)],
                                    [NS(undonated_page_bytes=0),
                                     NS(decode_batch=4)],
                                    []],
                         ids=["no_counter", "counter_on_some", "no_events"])
def test_undonated_page_mb_reads_nothing_without_the_counter(events):
    read = harness.load_metric("undonated_page_mb")
    assert read(NS(window_events=events)) is None
