"""The examples/ scripts run end to end (smoke: few steps, tiny
shapes). They are user-facing documentation — a broken example is a
broken promise."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=HERE)
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags +
                            " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "examples", script), *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=HERE)
    assert proc.returncode == 0, (script, proc.stdout[-800:],
                                  proc.stderr[-1500:])
    return proc.stdout


@pytest.mark.parametrize("script,args", [
    ("train_mnist.py", ["--steps", "3", "--batch", "16"]),
    ("train_gpt_moe.py", ["--steps", "2", "--batch", "4", "--seq", "16"]),
    ("train_resnet_nhwc.py",
     ["--steps", "2", "--batch", "2", "--image-size", "32"]),
    ("train_long_context.py",
     ["--steps", "1", "--batch", "2", "--seq", "256"]),
    ("train_bert.py", ["--steps", "2", "--batch", "4", "--seq", "32"]),
])
def test_example_runs(script, args):
    out = _run(script, *args)
    assert "loss=" in out or "acc=" in out, out[-400:]


def test_train_pipeline_dp():
    out = _run("train_pipeline_dp.py")
    assert "pipeline x dp training OK" in out


def test_serve_bucketed():
    out = _run("serve_bucketed.py")
    assert "bucketed serving OK" in out


@pytest.mark.slow  # tier-1 runs `-m 'not slow'`; tests/test_serving.py
def test_serve_engine():  # covers the subsystem itself in-process
    out = _run("serve_engine.py")
    assert "engine serving OK" in out


@pytest.mark.slow  # tier-1 runs `-m 'not slow'`; tests/test_resilience.py
def test_chaos_resume():  # covers the subsystem itself in-process
    out = _run("chaos_resume.py", "--steps", "12")
    assert "chaos resume OK" in out


@pytest.mark.slow  # tier-1 runs `-m 'not slow'`; tests/test_generation.py
def test_generate_stream():  # covers the subsystem itself in-process
    out = _run("generate_stream.py")
    assert "streamed generation OK" in out
