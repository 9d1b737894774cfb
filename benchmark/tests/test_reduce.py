"""The trace reduction against hand counts."""

import os

import pytest

import reduce

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def test_union_counts_overlapping_events_once():
    # two overlapping events: a per-lane sum (tools/trace_summary.py)
    # would say 10 + 10 = 20
    assert reduce.total(reduce.union([(0, 10), (5, 15)])) == 15
    assert reduce.total(reduce.union([(0, 10), (2, 3), (20, 30)])) == 20
    assert reduce.subtract([[0, 10]], [[2, 3], [8, 12]]) == [[0, 2], [3, 8]]


def test_hand_made_perfetto_fixture():
    """fixtures/hand.perfetto.json, in microseconds:
    window bench/window 100..1100 (1000 us).
    device 0 ops: fusion 100..300, fusion 250..400 (overlaps: union 300),
      custom-call (Mosaic) 500..600, all-reduce 700..800 of which
      fusion 750..800 hides half, nothing else -> busy 100..400 +
      500..600 + 700..800 = 500 us; mosaic 100; collective 100,
      exposed 50.
    modules: step 100..400 whole, step 500..800 whole, step 1050..1200
      cut by the window's edge -> two steps of 300 us.
    gaps: 400..500 under bench/exe.run (innermost), 600..700 under
      bench/window only, 800..1100 under bench/exe.run 900..1100? its
      midpoint 950 lies in it -> exe.run 100 + 300, window 100."""
    trace = reduce.load_perfetto(os.path.join(FIX, "hand.perfetto.json"))
    s = reduce.summarize(trace)
    assert s["window_s"] == pytest.approx(1000e-6)
    assert s["busy_s"] == pytest.approx(500e-6)
    assert s["mosaic_s"] == pytest.approx(100e-6)
    assert s["collective_s"] == pytest.approx(100e-6)
    assert s["exposed_collective_s"] == pytest.approx(50e-6)
    assert s["step_device_s"] == pytest.approx([300e-6, 300e-6])
    gaps = dict(s["idle_gaps"])
    assert gaps["bench/exe.run"] == pytest.approx(400e-6)
    assert gaps["bench/window"] == pytest.approx(100e-6)
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_recorded_xplane_from_the_chip():
    """fixtures/tiny_v5e.xplane.pb: five runs of one jitted program of
    three fusions (~91 us each) on a v5e (PR 25's probe run)."""
    trace = reduce.load_xplane(os.path.join(FIX, "tiny_v5e.xplane.pb"))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    dev = trace["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 5 and len(dev["ops"]) == 25
    s = reduce.summarize(trace)
    # by hand from the dump: 5 x (90.8 + 90.0 + 91.4 us) of fusions
    assert s["busy_s"] == pytest.approx(5 * 272.2e-6, rel=0.01)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["mosaic_s"] == 0.0 and s["collective_s"] == 0.0
    assert s["top_ops"][0][0].startswith("convolution_tanh_fusion")


def test_names():
    hlo = ('%all-reduce.3 = f32[768,3072]{1,0} all-reduce(f32[768,3072]{1,0} '
           '%p), replica_groups={{0,1,2,3}}, to_apply=%add')
    assert reduce.is_collective(hlo)
    assert reduce.short_name(hlo) == "all-reduce f32[768,3072]"
    assert not reduce.is_collective(
        '%fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), kind=kLoop')
    assert reduce.quantile([1, 2, 3, 4], 0.5) == 2.5
