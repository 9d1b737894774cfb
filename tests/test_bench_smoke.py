"""Bench-harness smoke tests.

A harness bug that any CPU invocation would catch (`from
paddle_tpu.kernels import flash_attention` binds the function, so every
`fa._flash_fwd_pallas` lookup is an AttributeError) must never cost a
chip call. These tests import and INVOKE every bench.py stage and every
tools/kernel_bench.py row-builder on CPU with tiny shapes: if it
imports and runs here, what is left to go wrong on the chip is the
chip's own compiler and numerics (tools/aot_check.py, chip_smoke.py).

Reference analogue: the reference benchmarks its ops through the same
op-registry path its tests use (op_tester.cc shares the op registry
with op_test.py), so a bench-only binding bug cannot exist there.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def _unique_stage_paths():
    """One representative per (kind, model, flash) — batch/seq/steps are
    overridden to tiny values, so stages differing only in those share
    a code path."""
    seen, out = set(), []
    for st in bench.STAGES:
        key = (st["kind"], st["model"], st["flash"])
        if key not in seen:
            seen.add(key)
            out.append(st)
    return out


STAGES = _unique_stage_paths()


@pytest.fixture()
def _interpret_kernels(monkeypatch):
    # flash stages run their Pallas kernels in interpreter mode on CPU
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")


@pytest.mark.parametrize(
    "stage", STAGES,
    ids=[f"{s['kind']}-{s['model']}-flash{int(s['flash'])}" for s in STAGES])
def test_every_bench_stage_runs_on_cpu(stage, _interpret_kernels):
    """Each STAGES code path builds, compiles, and steps."""
    seq = 32 if stage["kind"] != "resnet" else 32
    rec = bench.run_stage_inproc(
        stage["kind"], stage["model"], batch=2, seq=seq, steps=2,
        warmup=1, flash=stage["flash"])
    assert rec["metric"] in ("tokens_per_sec_per_chip",
                             "images_per_sec_per_chip")
    assert rec["value"] > 0
    assert rec["final_loss"] == rec["final_loss"]  # finite (non-NaN)
    # rows must be self-describing
    assert "timing" in rec and "config" in rec
    if stage["kind"] == "resnet":
        assert rec["config"].get("data_format") in ("NCHW", "NHWC")
    assert rec["config"]["flash"] is stage["flash"]


def test_device_loop_path_runs_on_cpu(_interpret_kernels):
    """The lax.fori_loop device-side timing loop compiles and runs (it
    defaults on only on a TPU, so only this test exercises it in CI)."""
    rec = bench.run_stage_inproc("bert", "tiny", batch=2, seq=32,
                                 steps=2, warmup=1, flash=False,
                                 device_loop=True)
    assert rec["s_per_step_device_loop"] is not None
    assert rec["value"] > 0


def test_kernel_bench_smoke_zero_errors(tmp_path):
    """tools/kernel_bench.py walks EVERY row-builder in smoke mode;
    a single errored row fails CI."""
    out = tmp_path / "kernel_smoke.json"
    env = {**os.environ,
           "PT_KERNEL_BENCH_SMOKE": "1",
           "PT_KERNEL_BENCH_OUT": str(out),
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kernel_bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(out.read_text())
    rows = data["runs"][-1]["rows"]
    assert rows, "smoke run produced no rows"
    errored = [r for r in rows if "error" in r]
    assert not errored, f"kernel bench rows errored: {errored}"
    by_name = {r["name"] for r in rows}
    # every benchmark family must be present — a silently skipped
    # builder is as dangerous as an errored one
    for fam in ("xla_attention_fwd", "flash_fwd", "flash_fwd_numerics",
                "flash_train", "xla_attention_train",
                "layer_norm_pallas", "layer_norm_xla",
                "softmax_xent_pallas", "softmax_xent_xla",
                "mm_bf16_8192", "conv3x3_nchw_bf16", "conv3x3_nhwc_bf16",
                "bert_block_dots_bf16"):
        assert fam in by_name, f"missing benchmark family {fam}"
    numerics = [r for r in rows if r["name"] == "flash_fwd_numerics"]
    assert all(r.get("ok") for r in numerics), numerics


def test_profile_trace_path_runs_on_cpu(tmp_path, _interpret_kernels):
    """The jax-profiler hook (main() passes PT_BENCH_TRACE_DIR) runs a
    stage and writes a trace."""
    rec = bench.run_stage_inproc("bert", "tiny", batch=2, seq=32,
                                 steps=2, warmup=1, flash=False,
                                 trace_dir=str(tmp_path))
    assert rec["value"] > 0
    # a trace FILE actually landed (the stage dir alone is created by
    # makedirs before the profiler starts, so directories don't count)
    files = [p for p in tmp_path.rglob("*") if p.is_file()]
    assert files, "profiler produced no trace files"


def test_bench_refuses_cpu():
    """`python bench.py` without a TPU exits non-zero and prints no row:
    no CPU stage, no cached row."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    class _Dev:
        device_kind = "TPU v99"

    class _Jax:
        @staticmethod
        def devices():
            return [_Dev()]

    with pytest.raises(ValueError, match="not in TPU_PEAKS"):
        bench._device_peak(_Jax)
