"""The zoo models as a trainer runs them: bf16 AMP around Adam, flash
attention where the model has it, steps through ``Executor.run``. Every
full-width config builds, compiles and moves its parameters; whether the
loss falls is each model's own test file."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.contrib.mixed_precision import decorate
from paddle_tpu.models import BertConfig, build_bert_pretrain
from paddle_tpu.models.bert import synthetic_batch
from paddle_tpu.models.gpt import GPTConfig, build_gpt_lm
from paddle_tpu.models.resnet import build_resnet50

BATCH, SIZE = 2, 32  # rows; tokens a row, or pixels a side


def _bert(name, flash, opt, rng):
    cfg = getattr(BertConfig, name)()
    cfg.use_flash_attention = flash
    main, startup, _, fetches = build_bert_pretrain(cfg, SIZE, optimizer=opt)
    return main, startup, fetches["loss"], synthetic_batch(
        rng, BATCH, SIZE, cfg.vocab_size)


def _gpt(name, flash, opt, rng):
    cfg = getattr(GPTConfig, name)()
    cfg.use_flash_attention = flash
    main, startup, _, fetches = build_gpt_lm(cfg, SIZE, optimizer=opt)
    toks = rng.randint(0, cfg.vocab_size, (BATCH, SIZE)).astype("int64")
    return main, startup, fetches["loss"], {
        "tokens": toks, "labels": np.roll(toks, -1, 1)}


def _resnet(fmt, _flash, opt, rng):
    main, startup, _, fetches = build_resnet50(
        num_classes=1000, image_size=SIZE, optimizer=opt, data_format=fmt)
    return main, startup, fetches["loss"], {
        "image": rng.randn(BATCH, 3, SIZE, SIZE).astype("float32"),
        "label": rng.randint(0, 1000, (BATCH, 1)).astype("int64")}


@pytest.mark.parametrize("build,name,flash", [
    pytest.param(_bert, "tiny", False, id="bert-tiny-flash0"),
    pytest.param(_bert, "base", True, id="bert-base-flash1"),
    pytest.param(_bert, "large", True, id="bert-large-flash1"),
    pytest.param(_gpt, "small", True, id="gpt-small-flash1"),
    pytest.param(_resnet, "NCHW", False, id="resnet50-nchw"),
    pytest.param(_resnet, "NHWC", False, id="resnet50-nhwc"),
])
def test_zoo_model_takes_amp_train_steps(build, name, flash, monkeypatch):
    # the flash cases run their Pallas kernels under the interpreter
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    opt = decorate(fluid.optimizer.Adam(1e-4), init_loss_scaling=1.0,
                   use_dynamic_loss_scaling=False, dest_dtype="bfloat16")
    main, startup, loss, feed = build(name, flash, opt,
                                      np.random.RandomState(0))
    param = main.global_block().all_parameters()[0].name
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        before = scope.get_numpy(param).copy()
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                  for _ in range(2)]
        after = scope.get_numpy(param)
    assert np.all(np.isfinite(losses)), losses
    assert not np.array_equal(before, after), param
