"""Rehearsal of the four-chip training cell on 4 virtual CPU devices:
`bert_base_s512_dp4` is `pretrain_s512_dp4` (the numbers of
`pretrain_s512`, in a file of its own because a pair of configuration and
traffic names one cell) with `chips` 4, which makes
`train_steps` drive the step under `with_data_parallel` with the batch
scaled by the number of devices. A process of its own, because the
device count is fixed when jax starts. And the reader of its metric
against a hand count."""

import json
import os
import subprocess
import sys

import harness

CELL = "bert_base_s512_dp4"

CHILD = """
import json, os, sys
sys.path[:0] = [{bench!r}, {root!r}, {tests!r}]
import harness
from tiny import tiny_ctx
ctx = tiny_ctx({cell!r})
assert len(ctx.devices) == 4, ctx.devices
r = harness.drive(ctx)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"],
                  "metrics": sorted(r["metrics"]), "failed": r["failed"],
                  "attempted": r["attempted"],
                  "device_count": r["device"]["count"]}}))
"""


def test_sound_run_on_four_devices_is_correct():
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_KERNEL_INTERPRET="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", CHILD.format(
            bench=harness.HERE, root=harness.ROOT, tests=tests, cell=CELL)],
        capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["device_count"] == 4
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"] == ["setup_s", "train_tokens_per_s"]


def test_cell_reports_the_exposed_collective_share():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, CELL)
    assert cell.chips == 4
    assert "collectives.exposed_share_dp4" in {m["name"]
                                               for m in cell.per_layer}
    reader = harness.load_module(os.path.join(
        harness.HERE, "metrics", "collectives.exposed_share_dp4.py"))
    # 0.33 s of 10 s a chip with nothing else running on that chip
    assert abs(reader.read({"trace": {"exposed_collective_s": 0.33,
                                      "window_s": 10.0}}) - 3.3) < 1e-9
    assert reader.read({"trace": {"window_s": 10.0}}) is None
    # the one-chip cell does not list it
    one = harness.Cell(bench, "bert_base_s512_1chip")
    assert "collectives.exposed_share_dp4" not in {m["name"]
                                                   for m in one.per_layer}


def test_traffic_is_pretrain_s512_number_for_number():
    # a pair of configuration and traffic names one cell, so the four-chip
    # cell has a traffic file of its own: it may differ only in its `why`
    def load(name):
        t = harness.load_json(os.path.join(harness.HERE, "traffic", name))
        t.pop("why")
        return t
    assert load("pretrain_s512_dp4.json") == load("pretrain_s512.json")
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
