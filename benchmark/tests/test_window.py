"""The window arithmetic, on a scripted clock: no chip, no jax."""

import pytest

import harness

train_steps = harness.load_module(
    harness.os.path.join(harness.HERE, "kinds", "train_steps.py"))
serve = harness.load_module(
    harness.os.path.join(harness.HERE, "kinds", "serve_closed_loop.py"))


class Clock:
    """Time moves only when a step runs: step i takes durations[i]."""

    def __init__(self, durations):
        self.now, self.durations, self.i = 100.0, durations, 0

    def __call__(self):
        return self.now

    def step(self):
        self.now += self.durations[min(self.i, len(self.durations) - 1)]
        self.i += 1


def rate(durations, seconds, tokens_per_step=12288):
    clock = Clock(durations)
    steps, elapsed, ends = train_steps.run_window(clock.step, clock, seconds)
    return steps, elapsed, steps * tokens_per_step / elapsed, ends


def test_window_of_n_steps_divides_by_the_time_of_n_steps():
    # 0.209 s a step, 2.0 s asked: ten steps are started (the tenth
    # begins at 1.881 s), and the clock stops when the tenth returns
    steps, elapsed, tokens_per_s, ends = rate([0.209], 2.0)
    assert steps == 10
    assert elapsed == pytest.approx(10 * 0.209)
    assert tokens_per_s == pytest.approx(12288 / 0.209)
    assert ends[-1] == pytest.approx(elapsed)


def test_rate_does_not_depend_on_where_the_window_is_cut():
    for seconds in (1.0, 1.04, 1.05, 1.2, 2.0, 2.09, 2.1):
        assert rate([0.209], seconds)[2] == pytest.approx(12288 / 0.209)


def test_one_stalled_step_lowers_the_rate_by_exactly_that_stall():
    # the stall lies well inside a window of the same number of steps
    stall = 0.150
    steady = rate([0.2] * 10, 1.96)
    stalled = rate([0.2] * 3 + [0.2 + stall] + [0.2] * 6, 1.96)
    assert steady[0] == stalled[0] == 10
    assert stalled[1] - steady[1] == pytest.approx(stall)
    assert stalled[2] == pytest.approx(10 * 12288 / (10 * 0.2 + stall))


class Stream:
    error, finish_reason = None, "length"


def request(submit_t, times):
    r = serve.Request(0, [1] * 10, len(times))
    r.submit_t, r.times, r.stream = submit_t, times, Stream()
    return r


def test_serving_metrics_are_over_all_tokens_and_requests_of_the_window():
    reqs = [request(9.0, [9.5, 10.5, 11.0, 12.5]),      # began before
            request(10.2, [11.2, 11.3, 11.5]),           # inside
            request(11.9, [13.0, 13.1])]                 # first token late
    m = serve.window_metrics(reqs, 10.0, 12.0)
    assert m["tokens"] == 5                 # 10.5 11.0 | 11.2 11.3 11.5
    assert m["serve_out_tokens_per_s"] == pytest.approx(5 / 2.0)
    # gaps whose later token lies in the window: 1000, 500, 100, 200 ms
    assert m["gaps"] == 4
    assert m["itl_p95_ms"] == pytest.approx(1000 * 0.85 + 500 * 0.15)
    # requests submitted in the window, the late first token counted
    assert m["ttft_samples"] == 2
    assert m["ttft_p50_ms"] == pytest.approx((1000 + 1100) / 2)


def test_the_seed_draws_token_ids_and_never_the_lengths_or_their_order():
    import types

    import numpy as np

    def requests(seed):
        ctx = types.SimpleNamespace(
            seed=seed, config={"model": "gpt", "vocab_size": 1000},
            traffic={"prompt_lens": [5, 9, 7], "answer_lens": [11, 13]})
        kind = serve.Kind(ctx)
        return [kind.next_request(0) for _ in range(7)]

    a, b = requests(1), requests(2 ** 31 + 5)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b] \
        == [5, 9, 7, 5, 9, 7, 5]
    assert [r.max_new for r in a] == [r.max_new for r in b] \
        == [11, 13, 11, 13, 11, 13, 11]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    want = {"a": 1.0, "b": 10.0, "c": 1e-9}
    got = {"a": 1.1, "b": 10.0, "c": 0.5}
    gap, leaf = train_steps.worst_leaf_gap(got, want)
    assert leaf == "c" and gap == pytest.approx(0.5 / 1.0)   # median is 1.0
    assert train_steps.moving_leaves({"a": 1.0, "b": 2.0, "k": 1e-9}) == ["a", "b"]
