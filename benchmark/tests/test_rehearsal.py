"""The rest of a run, without the harness's look for a chip: the
harness's own functions at the tiny presets on the CPU (interpret-mode
kernels). A sound run comes out correct;
with the timed path broken underneath, `correct` comes out false: once
for each fault a cell can have. No number printed here is a device
metric."""

import numpy as np
import pytest

import harness
from tiny import tiny_ctx

TRAIN = ["bert_base_s512_1chip"]
SERVE = ["gpt3_xl_chat_decode", "gpt3_xl_doc_prefill"]


def drive(workload, break_kind=None, **kw):
    ctx = tiny_ctx(workload, **kw)
    if break_kind is not None:
        mod = ctx.cell.kind()
        broken = break_kind(mod.Kind)
        ctx.cell.kind = lambda: type("m", (), {"Kind": broken})
    return harness.drive(ctx)


@pytest.mark.parametrize("workload", TRAIN + SERVE)
def test_sound_run_is_correct(workload):
    r = drive(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in
                                 tiny_ctx(workload).cell.end_to_end}
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert list(r)[-1] == "checks"          # the numbers compared come last


def failing(r):
    return {k for k, v in r["checks"].items()
            if v["value"] is None or not v["value"] <= v["limit"]}


def state_unchanged(Kind):
    """A step that returns its state unchanged: every parameter and
    optimizer moment is put back after the step."""
    class Broken(Kind):
        def step(self):
            names = [row[0] for row in self.spec]
            names += [self.moment1[n] for n in names]
            keep = {n: np.asarray(self.scope.find_var(n)) for n in names}
            value = Kind.step(self)
            import jax.numpy as jnp

            for n, v in keep.items():
                self.scope.set_var(n, jnp.asarray(v))
            return value
    return Broken


def half_of_batch(Kind):
    """Half of the batch left out, the mean taken over the rest: the
    rows kept are fed twice over, so the step's mean is theirs."""
    class Broken(Kind):
        def step(self):
            feed = self.feeds[self.next_feed % len(self.feeds)]
            keep = self.batch // 2
            cut = {k: np.concatenate([v[:keep]] * 2) for k, v in feed.items()}
            self.feeds[self.next_feed % len(self.feeds)] = cut
            try:
                return Kind.step(self)
            finally:
                self.feeds[(self.next_feed - 1) % len(self.feeds)] = feed
    return Broken


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("bert_base_s512_1chip", state_unchanged, {"delta_norm_gap"}),
    ("bert_base_s512_1chip", half_of_batch, {"grad_norm_gap"}),
])
def test_broken_train_path_is_not_correct(workload, fault, caught_by):
    r = drive(workload, fault)
    assert not r["correct"]
    assert caught_by <= failing(r), r["checks"]


def altered_token(Kind):
    """A token altered where it is produced: the engine emits another
    id than the step program chose, once in every seven."""
    class Broken(Kind):
        def setup(self):
            Kind.setup(self)

        def submit(self, client, stagger=1.0):
            eng = self.eng
            if not getattr(eng, "_bench_broken", False):
                emit, n = eng._emit, [0]

                def bad_emit(req, token, now):
                    n[0] += 1
                    if n[0] % 7 == 0:
                        token = (token + 1) % self.ctx.config["vocab_size"]
                    return emit(req, token, now)

                eng._emit = bad_emit
                eng._bench_broken = True
            return Kind.submit(self, client, stagger)
    return Broken


@pytest.mark.parametrize("workload", SERVE)
def test_altered_token_is_not_correct(workload):
    r = drive(workload, altered_token, seconds=2.0)
    assert not r["correct"]
    assert "served_logit_gap" in failing(r), r["checks"]


def test_control_fails_a_number_at_tiny_size():
    """The training cell's control and planted faults, as control.py
    reads them on the chip, kept at a size a test run can hold."""
    ctx = tiny_ctx("bert_base_s512_1chip", seconds=2.0)
    kind = ctx.cell.kind().Kind(ctx)
    kind.setup()
    kind.window()
    kind.release()
    assert harness.judge(kind.check())
    for name, checks in kind.control().items():
        assert not harness.judge(checks), (name, checks)


@pytest.mark.parametrize("workload", SERVE)
def test_program_with_bf16_pages_is_not_correct(workload):
    """The serving cells' control: the program with its own bfloat16
    KV pages switched on (control.py --set engine.kv_dtype=bfloat16)
    where the configuration states float32. The tokens cannot tell;
    the bytes the step program takes as arguments do."""
    ctx = tiny_ctx(workload, seconds=2.0)
    ctx.config["engine"]["kv_dtype"] = "bfloat16"
    r = harness.drive(ctx)
    assert not r["correct"]
    assert failing(r) == {"step_argument_bytes_gap"}, r["checks"]


def test_no_chip_no_result(capsys):
    """run.py on this machine (the CPU): another exit code than 0 and
    no result line."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, harness.os.path.join(harness.HERE, "run.py"),
         "--workload", "bert_base_s512_1chip", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True,
        env=dict(harness.os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
