"""chip_smoke.py without a chip: it refuses the CPU, and its leg
functions run at toy size with interpret-mode kernels, so a typo never
costs a chip call. Plus the two rules the chip path rests on: where the
compile cache lives, and that a failed Pallas kernel raises."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run_py(code_or_path, env_extra, is_file=False, drop=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    for k in drop:
        env.pop(k, None)
    cmd = [sys.executable] + ([code_or_path] if is_file
                              else ["-c", code_or_path])
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=300)


def test_refuses_cpu_naming_the_platform():
    proc = _run_py(os.path.join(REPO, "chip_smoke.py"), {}, is_file=True)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout        # no result line without a chip


@pytest.fixture()
def interpret_kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")


def _kernel_module(name):
    # the package rebinds some module names to functions of the same name
    import importlib

    return importlib.import_module(f"paddle_tpu.kernels.{name}")


def _passed(report, result):
    assert not report.failed, report.failed
    return result


def test_kernel_phase_toy(interpret_kernels):
    report = chip_smoke.Report()
    out = _passed(report, chip_smoke.kernel_phase(
        report, flash_shape=(2, 2, 128, 32), rows=64, hidden=128, vocab=1000,
        adam_shapes=((1000, 128), (128,)),
        ragged=dict(lanes=5, chunk=8, heads=2, head_dim=32, num_pages=32,
                    page_size=8, max_pages=8)))
    assert out["flash_o_rel_err"] > 0       # bf16 output: never exact


def test_leg_a_toy(interpret_kernels):
    import paddle_tpu as fluid
    from paddle_tpu.models import BertConfig

    old = fluid.get_flags(["optimizer_fuse"])
    fluid.set_flags({"optimizer_fuse": "on"})   # auto fuses on a TPU only
    try:
        report = chip_smoke.Report()
        out = _passed(report, chip_smoke.leg_a(
            report, cfg=BertConfig.tiny(), seq=64, batch=4, steps=4))
    finally:
        fluid.set_flags(old)
    assert out["fused_adam_ops"] > 0 and len(out["losses"]) == 4


def test_leg_b_toy(interpret_kernels):
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=151, hidden_size=48, num_layers=2,
                    num_heads=4, ffn_size=96, max_position=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    report = chip_smoke.Report()
    out = _passed(report, chip_smoke.leg_b(
        report, cfg=cfg, prompt_lens=(3, 6, 11, 19), new_tokens=6,
        export_seq=32,
        engine_kwargs=dict(page_size=8, num_pages=64, max_decode_batch=4,
                           chunk_tokens=8)))
    assert out["mode"] == "ragged" and out["mean_active_lanes"] > 1
    assert out["layers"] == 2


def test_leg_b_export_is_cut_in_depth_when_the_disk_is_short(
        tmp_path, monkeypatch):
    """The driver's chip machine refused a 5.4 GB file: the export is in
    bounded parts, and depth (never a width) gives way to the disk."""
    import collections

    import paddle_tpu as fluid
    from paddle_tpu.generation.model import build_lm_program
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=151, hidden_size=48, num_layers=4,
                    num_heads=4, ffn_size=96, max_position=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    fixed, per_layer = chip_smoke._export_bytes(
        build_lm_program(cfg, 32)[0], cfg.num_layers)
    usage = collections.namedtuple("usage", "total used free")
    free = int((fixed + 2.5 * per_layer) / 0.9)
    monkeypatch.setattr(chip_smoke.shutil, "disk_usage",
                        lambda _d: usage(free, 0, free))
    monkeypatch.setattr(fluid.io, "_PART_BYTES", per_layer)
    out = {}
    got = chip_smoke._export_lm(cfg, 32, str(tmp_path), out)
    assert got.num_layers == 2 and got.hidden_size == cfg.hidden_size
    assert out["export_bytes"] == fixed + 4 * per_layer
    files = sorted(os.listdir(tmp_path))
    assert "__params__.1.npz" in files, files
    assert max(os.path.getsize(tmp_path / f) for f in files) < 2 * per_layer
    stored = {}
    for f in files:
        if f.endswith(".npz"):
            with np.load(tmp_path / f) as z:
                stored.update({k: z[k].nbytes for k in z.files})
    assert sum(stored.values()) == fixed + 2 * per_layer
    from paddle_tpu.inference import Config, create_predictor

    pred = create_predictor(Config(str(tmp_path)))
    (logits,) = pred.run([np.zeros((1, 32), np.int64)])
    assert logits.shape == (1, 32, 151) and np.isfinite(logits).all()


def test_leg_c_toy(interpret_kernels):
    from paddle_tpu.models import BertConfig
    from paddle_tpu.models.gpt import GPTConfig

    report = chip_smoke.Report()
    out = _passed(report, chip_smoke.leg_c(
        report, bert_cfg=BertConfig.tiny(), bert_seq=64, bert_batch=8,
        gpt_cfg=GPTConfig.tiny(), gpt_seq=64, gpt_batch=4,
        steps_c1=3, steps_c2=3))
    assert len(out["c1"]["losses"]) == 3


def test_a_failed_check_fails_the_run():
    report = chip_smoke.Report()

    def boom(_report):
        raise RuntimeError("kernel did not compile")

    report.phase("leg X", boom)
    assert report.failed == ["leg X ran to its end"]


_BIND = """
import numpy as np, jax, paddle_tpu as fluid
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data("x", [4])
    y = fluid.layers.fc(x, 2)
exe = fluid.Executor(fluid.TPUPlace()); exe.run(startup)
exe.run(main, feed={"x": np.zeros((1, 4), "f")}, fetch_list=[y])
print("CACHE_DIR=" + str(jax.config.jax_compilation_cache_dir))
print("REPORTED=" + str(exe.cache_stats()["process"]["persistent_cache_dir"]))
"""


def _cache_dirs(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    found = dict(ln.split("=", 1) for ln in proc.stdout.splitlines()
                 if ln.startswith(("CACHE_DIR=", "REPORTED=")))
    return found["CACHE_DIR"], found["REPORTED"]


def test_compile_cache_follows_the_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: a bind leaves jax's setting equal
    to it (nothing in the program updates it) and writes there."""
    want = str(tmp_path / "from_env")
    got, reported = _cache_dirs(_run_py(_BIND, {
        "JAX_COMPILATION_CACHE_DIR": want}))
    assert got == want and reported == want
    assert os.listdir(want)


def test_compile_cache_defaults_to_fixed_in_checkout_path():
    from paddle_tpu.runtime import dispatch

    assert dispatch.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    got, reported = _cache_dirs(_run_py(
        _BIND, {}, drop=("JAX_COMPILATION_CACHE_DIR",)))
    assert got == dispatch.DEFAULT_CACHE_DIR == reported


def test_one_call_site_sets_the_cache_directory():
    """`grep -rn jax_compilation_cache_dir --include=*.py .`, tests
    aside: one jax.config.update, in runtime/dispatch.py."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "chiprun_out")]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    for ln in fh:
                        if ('update("jax_compilation_cache_dir"' in ln
                                or "update('jax_compilation_cache_dir'" in ln):
                            hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("paddle_tpu", "runtime", "dispatch.py")]


@pytest.mark.parametrize("kernel", ["flash", "ragged", "quant_matmul",
                                    "lora", "paged"])
def test_pallas_failure_propagates_without_force_flag(kernel, monkeypatch):
    """On a non-interpret Pallas path a kernel exception reaches the
    caller: no retry on the reference, with PADDLE_TPU_FORCE_PALLAS
    unset."""
    import jax.numpy as jnp

    fa, lora, pa, qm, rpa = (_kernel_module(n) for n in (
        "flash_attention", "lora", "paged_attention", "quant_matmul",
        "ragged_paged_attention"))

    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FLASH_INTERPRET", raising=False)
    # what a tpu backend selects
    monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")

    def boom(*a, **k):
        raise RuntimeError("mosaic said no")

    f32 = jnp.float32
    if kernel == "flash":
        monkeypatch.setattr(fa, "_flash_fwd_pallas", boom)
        q = jnp.zeros((1, 2, 128, 32), f32)
        call = lambda: fa.flash_attention(q, q, q)  # noqa: E731
    elif kernel == "ragged":
        monkeypatch.setattr(rpa, "_ragged_pallas", boom)
        pages = jnp.zeros((2, 4, 8, 32), f32)
        iv = jnp.zeros((2,), jnp.int32)
        call = lambda: rpa.ragged_paged_attention(  # noqa: E731
            jnp.zeros((2, 8, 2, 32), f32), pages, pages, iv, iv,
            jnp.zeros((2, 2), jnp.int32))
    elif kernel == "quant_matmul":
        monkeypatch.setattr(qm, "_quant_matmul_pallas", boom)
        call = lambda: qm.quantized_matmul(  # noqa: E731
            jnp.zeros((4, 256), f32), jnp.zeros((256, 128), jnp.int8),
            jnp.ones((128,), f32), mode="int8")
    elif kernel == "lora":
        monkeypatch.setattr(lora, "_lora_delta_pallas", boom)
        call = lambda: lora.batched_lora_delta(  # noqa: E731
            jnp.zeros((4, 64), f32), jnp.zeros((2, 64, 8), f32),
            jnp.zeros((2, 8, 128), f32), jnp.ones((2,), f32),
            jnp.zeros((4,), jnp.int32))
    else:
        import jax.experimental.pallas.ops.tpu.paged_attention as jpa

        monkeypatch.setattr(jpa, "paged_attention", boom)
        pages = jnp.zeros((2, 4, 8, 32), f32)
        call = lambda: pa.paged_attention(  # noqa: E731
            jnp.zeros((2, 2, 32), f32), pages, pages,
            jnp.ones((2,), jnp.int32), jnp.zeros((2, 2), jnp.int32))
    with pytest.raises(RuntimeError, match="mosaic said no"):
        call()


def test_declared_geometry_rule_still_selects_the_reference(monkeypatch):
    """A shape the kernel does not support (kernels/constraints.py) is a
    selection, not a fallback: the reference runs and the Pallas entry
    is never tried."""
    import jax.numpy as jnp

    fa, qm = _kernel_module("flash_attention"), _kernel_module("quant_matmul")
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")

    def boom(*a, **k):
        raise AssertionError("the Pallas entry must not be tried")

    monkeypatch.setattr(qm, "_quant_matmul_pallas", boom)
    K, N, block = 1000, 64, 250        # 250 is not Mosaic-tileable
    out = qm.quantized_matmul(
        jnp.ones((4, K), jnp.float32), jnp.ones((K, N), jnp.int8),
        jnp.ones((K // block, N), jnp.float32), mode="int8_block",
        block=block)
    np.testing.assert_allclose(np.asarray(out), K)


def test_executor_refuses_a_device_it_would_ignore():
    import paddle_tpu as fluid

    with pytest.raises(NotImplementedError, match="device 0"):
        fluid.Executor(fluid.TPUPlace(2))
    with pytest.raises(ValueError, match="device"):
        fluid.TPUPlace(64).jax_device()
    assert fluid.TPUPlace(3).jax_device().id == 3   # 8 virtual devices
