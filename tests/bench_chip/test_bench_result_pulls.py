"""The ``result_pulls`` reader: the mean of the engine's per-step count of
blocking result reads over the window's events, and nothing for a program
whose events carry no such count."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import harness


@pytest.mark.parametrize("pulls,want", [((1, 1, 1, 1), 1.0),
                                        ((1, 3, 1, 2), 1.75),
                                        ((2,), 2.0)])
def test_result_pulls_reads_the_events_counters(pulls, want):
    read = harness.load_metric("result_pulls")
    evs = [NS(result_pulls=p) for p in pulls]
    assert read(NS(window_events=evs)) == pytest.approx(want)


@pytest.mark.parametrize("events", [[NS(decode_batch=4)],
                                    [NS(result_pulls=1), NS(decode_batch=4)],
                                    []],
                         ids=["no_counter", "counter_on_some", "no_events"])
def test_result_pulls_reads_nothing_without_the_counter(events):
    read = harness.load_metric("result_pulls")
    assert read(NS(window_events=events)) is None
