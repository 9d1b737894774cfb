"""The two readers of idle time under the program's own spans, on
hand-made `idle_gaps` lists, and what the reduction makes of a named
Mosaic kernel and of loop phases laid over a gap."""

import os

import pytest

import harness
import reduce
import span_math

METRICS = os.path.join(harness.HERE, "metrics")
SERVE = harness.load_module(
    os.path.join(METRICS, "engine.idle_in_spans_share_serve.py"))
TRAIN = harness.load_module(
    os.path.join(METRICS, "executor.idle_in_spans_share_train.py"))
PAUSES = span_math.SHORT_PAUSES


def _x(gaps):
    return {"trace": {"idle_gaps": gaps}}


@pytest.mark.parametrize("gaps,serve,train", [
    # the parent: all idle time under the benchmark's own spans
    ([["bench/window", 0.594], [PAUSES, 0.0002]], 0.0, 0.0),
    ([["bench/exe.run", 0.152], ["bench/window", 0.010], [PAUSES, 0.006]],
     0.0, 0.0),
    # split 3:1 between the program's spans and the benchmark's
    ([["generation/emit", 0.2], ["executor/step", 0.1],
      ["bench/window", 0.1], [PAUSES, 0.5]], 75.0, 25.0),
    ([["executor/feed", 0.06], ["executor/step", 0.03],
      ["bench/exe.run", 0.03]], 75.0, 75.0),
    # a span of another layer is not the program's loop
    ([["serving/batch_execute", 0.1], ["no_host_span", 0.1]], 0.0, 0.0),
    # only short pauses: nothing to share out
    ([[PAUSES, 0.0003]], None, None),
    ([], None, None),
])
def test_idle_in_spans_share(gaps, serve, train):
    assert SERVE.read(_x(gaps)) == (
        None if serve is None else pytest.approx(serve))
    assert TRAIN.read(_x(gaps)) == (
        None if train is None else pytest.approx(train))


def test_pool_name_is_the_reductions():
    assert reduce.attribute_gaps([(0, reduce.SHORT_PAUSE_NS - 1)], []) == [
        [PAUSES, pytest.approx((reduce.SHORT_PAUSE_NS - 1) / 1e9)]]


def test_a_gap_goes_to_the_loop_phase_over_its_midpoint():
    """One gap a step, 1000..6000 ns... scaled to microseconds: the
    step's tail, emit, admit .. bind and the next step's dispatch lie
    over it; the midpoint decides, and the benchmark's window, which
    covers everything, gets nothing."""
    us = 1000
    host = [("bench/window", 0, 10_000 * us),
            ("generation/step", 0, 1100 * us),
            ("generation/emit", 1100 * us, 900 * us),
            ("generation/admit", 2000 * us, 100 * us),
            ("generation/assemble", 2100 * us, 1900 * us),
            ("generation/step", 4000 * us, 5000 * us),
            ("executor/feed", 4000 * us, 500 * us),
            ("executor/step", 4500 * us, 2000 * us)]
    gaps = reduce.attribute_gaps([(1000 * us, 6000 * us)], host)
    assert gaps == [["generation/assemble", pytest.approx(5e-3)]]
    assert SERVE.read(_x(gaps)) == pytest.approx(100.0)
    assert TRAIN.read(_x(gaps)) == pytest.approx(0.0)


@pytest.mark.parametrize("hlo,want", [
    # as the v5e compiler prints a pallas_call(name=...): the custom
    # call's instruction is named after the kernel
    ('%flash_attention_bwd_dq_panel.3 = f32[24,12,512,64]{3,2,1,0:T(8,128)} '
     'custom-call(%a, %b), custom_call_target="tpu_custom_call", '
     'metadata={op_name="jit(step_fn)/transpose(jvp(x))/'
     'flash_attention_bwd_dq_panel/pallas_call"}',
     "mosaic:flash_attention_bwd_dq_panel f32[24,12,512,64]"),
    ('%ragged_paged_attention = f32[8,16,16,128]{3,2,1,0} custom-call(%q), '
     'custom_call_target="tpu_custom_call"',
     "mosaic:ragged_paged_attention f32[8,16,16,128]"),
    # and unnamed, as the parent's kernels were: the transform's name
    ('%transpose_jvp___.7 = f32[24,12,512,64]{3,2,1,0} custom-call(%a), '
     'custom_call_target="tpu_custom_call"',
     "mosaic:transpose_jvp___ f32[24,12,512,64]"),
])
def test_short_name_of_a_named_kernel(hlo, want):
    assert reduce.short_name(hlo) == want
