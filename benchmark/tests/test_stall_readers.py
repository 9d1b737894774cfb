"""The four readers of the host's stalls: each is the share of the
traced window in which the device sat idle under one span the program
opens (the collector, the wait for a finished step), on hand-made
summaries; 0.0, never None, when no gap falls under its span. And what
the reduction makes of the spans nested inside a fetch."""

import os

import pytest

import harness
import reduce
import span_math

METRICS = os.path.join(harness.HERE, "metrics")
PAUSES = span_math.SHORT_PAUSES
READERS = {
    "host.gc_idle_share_train": "executor/gc",
    "host.gc_idle_share_serve": "executor/gc",
    "executor.fetch_ready_idle_share_train": "executor/fetch_ready",
    "engine.fetch_ready_idle_share_serve": "generation/fetch_ready",
}


def _read(name, gaps, window_s=10.0):
    reader = harness.load_module(os.path.join(METRICS, name + ".py"))
    return reader.read({"trace": {"idle_gaps": gaps, "window_s": window_s}})


@pytest.mark.parametrize("name,span", sorted(READERS.items()))
def test_the_share_of_the_window_idle_under_the_span(name, span):
    gaps = [["executor/fetch", 1.0], [span, 0.25], ["bench/window", 0.5],
            [PAUSES, 0.004]]
    assert _read(name, gaps) == pytest.approx(2.5)
    assert _read(name, gaps, window_s=8.0) == pytest.approx(3.125)
    # the reduction names each span once; a second entry would add up
    assert _read(name, gaps + [[span, 0.05]]) == pytest.approx(3.0)


@pytest.mark.parametrize("name,span", sorted(READERS.items()))
@pytest.mark.parametrize("gaps", [
    [],
    [[PAUSES, 0.0003]],
    [["executor/fetch", 1.615], ["executor/step", 2.571],
     ["generation/fetch", 0.17], ["no_host_span", 0.1], [PAUSES, 0.01]],
    # a name that only begins like the span is another span
    [["executor/gc_other", 0.2], ["generation/fetch_ready_x", 0.2]],
])
def test_zero_and_never_none_where_nothing_is_idle_under_it(name, span, gaps):
    value = _read(name, gaps)
    assert value is not None
    assert value == 0.0


def test_a_gap_goes_to_the_innermost_of_the_spans_nested_in_a_fetch():
    """A fetch of 30 ms holds the wait for the finished step (the first
    20 ms) and the copy; a collection of 4 ms runs inside the wait. The
    device is idle over three gaps of 2 ms: in the collection, in the
    wait, in the copy."""
    ms = 1_000_000
    host = [("bench/window", 0, 100 * ms),
            ("executor/fetch", 10 * ms, 30 * ms),
            ("executor/fetch_ready", 10 * ms, 20 * ms),
            ("executor/gc", 12 * ms, 4 * ms)]
    gaps = [[13 * ms, 15 * ms], [25 * ms, 27 * ms], [33 * ms, 35 * ms]]
    named = dict(reduce.attribute_gaps(gaps, host))
    assert named == pytest.approx({"executor/gc": 0.002,
                                   "executor/fetch_ready": 0.002,
                                   "executor/fetch": 0.002})
    idle = [[k, v] for k, v in named.items()]
    assert _read("host.gc_idle_share_train", idle, 0.1) == pytest.approx(2.0)
    assert _read("executor.fetch_ready_idle_share_train", idle,
                 0.1) == pytest.approx(2.0)
