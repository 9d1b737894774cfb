"""Chaos training driver: run a supervised training loop under
injected faults, and benchmark supervision overhead.

Run mode (one training run; used as the subprocess under chaos tests)::

    python tools/chaos_train.py --steps 40 --ckpt-dir /tmp/ck \\
        --ckpt-every 8 --fault kill@17 --loss-out /tmp/losses.json

The model is a small deterministic MLP WITH dropout — the dropout mask
depends on the per-step PRNG fold, so a resumed run only matches an
uninterrupted one bitwise if the supervisor restored the RNG state
correctly (the property this driver exists to prove). Feeds derive
from the step index, so any step is re-runnable. The process exits
with code 43 (resilience.KILL_EXIT_CODE) when a kill fault fires.

Smoke mode (the CI `chaos` job)::

    python tools/chaos_train.py --smoke --out chaos_bench.json

measures supervision overhead (supervised vs bare Executor.run loop,
gated at <5% steps/s), checkpoint write/restore latency, verifies a
truncated checkpoint is never selected for resume, and drives the full
kill -> auto-resume round trip through THREE child processes
(uninterrupted reference, killed run, resumed run), asserting the
recovered loss trajectory is bitwise identical to the reference.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_model(seed=41):
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [12])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        h = fluid.layers.dropout(h, dropout_prob=0.1)  # consumes step PRNG
        logits = fluid.layers.fc(h, 4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(5e-3).minimize(loss)
    return main, startup, loss


def feed_fn(step, batch=8):
    """Deterministic feed for any step index (re-runnable after
    rollback/resume)."""
    rng = np.random.RandomState(10_000 + step)
    x = rng.randn(batch, 12).astype("float32")
    y = (np.abs(x).sum(1, keepdims=True) > 9.5).astype("int64") \
        + (x[:, :1] > 0).astype("int64")
    return {"x": x, "y": y}


def run_supervised(steps, ckpt_dir, ckpt_every=8, keep_last=3, fault="",
                   watchdog_s=0.0, final_checkpoint=True, seed=41):
    """One supervised run; returns (losses_by_step, stats)."""
    import paddle_tpu as fluid
    from paddle_tpu import resilience

    main, startup, loss = build_model(seed)
    scope = fluid.Scope()
    losses = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ckpt_dir,
            feed_fn=feed_fn, fetch_list=[loss],
            policy=resilience.CheckpointPolicy(
                ckpt_dir, every_steps=ckpt_every, keep_last=keep_last),
            watchdog_timeout_s=watchdog_s,
            fault_injector=resilience.FaultInjector(fault),
            on_step=lambda s, f: losses.__setitem__(
                s, float(np.asarray(f[0]))))
        stats = sup.run_loop(steps, final_checkpoint=final_checkpoint)
    return losses, stats


def _child(args):
    losses, stats = run_supervised(
        args.steps, args.ckpt_dir, ckpt_every=args.ckpt_every,
        fault=args.fault, seed=args.seed,
        final_checkpoint=not args.no_final_checkpoint)
    out = {"losses": {str(s): v for s, v in losses.items()}, "stats": stats}
    if args.loss_out:
        with open(args.loss_out, "w") as f:
            json.dump(out, f)
    print(f"chaos_train: {stats['steps_completed']} steps, "
          f"resumed_from={stats['resumed_from']} "
          f"ckpts={stats['checkpoints_written']} "
          f"retries={stats['retries']} rollbacks={stats['rollbacks']}")
    return 0


def spawn_run(tmp, name, steps, ckpt_dir, ckpt_every, fault=""):
    """Run this script as a CPU child process; returns
    (CompletedProcess, losses_json_or_None). Shared with
    tests/test_resilience.py so the spawn environment is maintained
    once."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    loss_out = os.path.join(str(tmp), f"{name}.json")
    cmd = [sys.executable, os.path.abspath(__file__),
           "--steps", str(steps), "--ckpt-dir", str(ckpt_dir),
           "--ckpt-every", str(ckpt_every), "--loss-out", loss_out]
    if fault:
        cmd += ["--fault", fault]
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env=env, cwd=repo)
    data = None
    if os.path.exists(loss_out):
        with open(loss_out) as f:
            data = json.load(f)
    return proc, data


def smoke(out_path=None):
    import paddle_tpu as fluid
    from paddle_tpu import io, resilience

    report = {"bench": "chaos_train", "mode": "smoke"}

    # -- 1. supervision overhead: bare Executor.run loop vs Supervisor ----
    # Two measurements, because jax CPU dispatch noise on a ~0.5-1ms
    # step (+-30% rep to rep) swamps the supervisor's tens-of-us cost:
    #   (a) end-to-end steps/s for both loops (reported, informational);
    #   (b) the supervision MACHINERY cost per step, isolated with a
    #       stub executor (pure python, deterministic), which is the
    #       gated number: machinery_us / bare_step_us < 5%.
    reps, timed = 5, 200
    feeds = [feed_fn(s) for s in range(64)]
    cheap_feed = lambda s: feeds[s % 64]  # noqa: E731
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731

    ckroot = tempfile.mkdtemp(prefix="chaos_smoke_")
    main, startup, loss = build_model()
    scope = fluid.Scope()
    bare_t, sup_t = [], []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        # cadence disabled: measure the supervisor machinery (fault
        # hooks, nan guard, stats, feed plumbing), not checkpoint IO
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=os.path.join(ckroot, "overhead"),
            feed_fn=cheap_feed, fetch_list=[loss],
            policy=resilience.CheckpointPolicy(
                os.path.join(ckroot, "overhead"), every_steps=0,
                every_secs=0, keep_last=2))

        def bare_loop():
            for s in range(timed):
                exe.run(main, feed=cheap_feed(s), fetch_list=[loss])

        def supervised_loop():
            sup.run_loop(timed, resume=False, final_checkpoint=False)

        bare_loop()
        supervised_loop()  # warm both paths
        for _ in range(reps):
            t0 = time.perf_counter()
            bare_loop()
            bare_t.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            supervised_loop()
            sup_t.append(time.perf_counter() - t0)

        # -- 2. checkpoint write / restore latency --------------------
        t0 = time.perf_counter()
        sup._save(timed, reason="bench")
        ckpt_write_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        sup.policy.restore(main_program=main, scope=scope)
        ckpt_restore_s = time.perf_counter() - t0

    bare_per_step = med(bare_t) / timed
    supervised_per_step = med(sup_t) / timed

    # (b) machinery cost, jax noise excluded: same Supervisor code path
    # over a stub executor whose run() is a constant
    class _StubExe:
        _run_counter = 0

        @staticmethod
        def run(program, feed=None, fetch_list=None, scope=None):
            return [np.float32(0.5)]

    stub_steps = 3000
    stub_sup = resilience.Supervisor(
        _StubExe(), main,
        checkpoint_dir=os.path.join(ckroot, "stub"),
        feed_fn=cheap_feed, fetch_list=[loss],
        policy=resilience.CheckpointPolicy(
            os.path.join(ckroot, "stub"), every_steps=0, every_secs=0,
            keep_last=2))
    stub_sup.run_loop(stub_steps, resume=False, final_checkpoint=False)
    machinery_t, stub_bare_t = [], []
    stub = _StubExe()
    for _ in range(reps):
        t0 = time.perf_counter()
        stub_sup.run_loop(stub_steps, resume=False, final_checkpoint=False)
        machinery_t.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for s in range(stub_steps):
            stub.run(main, feed=cheap_feed(s), fetch_list=[loss])
        stub_bare_t.append(time.perf_counter() - t0)
    machinery_per_step = (med(machinery_t) - med(stub_bare_t)) / stub_steps
    overhead_pct = machinery_per_step / bare_per_step * 100.0
    report.update(
        bare_steps_per_s=1.0 / bare_per_step,
        supervised_steps_per_s=1.0 / supervised_per_step,
        end_to_end_delta_pct=(supervised_per_step / bare_per_step - 1) * 100,
        supervision_machinery_us_per_step=machinery_per_step * 1e6,
        supervision_overhead_pct=overhead_pct,
        ckpt_write_s=ckpt_write_s,
        ckpt_restore_s=ckpt_restore_s,
    )
    print(f"bare: {report['bare_steps_per_s']:.0f} steps/s | supervised: "
          f"{report['supervised_steps_per_s']:.0f} steps/s | machinery "
          f"{machinery_per_step*1e6:.1f}us/step = {overhead_pct:.2f}% of a "
          f"bare step | ckpt write {ckpt_write_s*1e3:.0f}ms "
          f"restore {ckpt_restore_s*1e3:.0f}ms")

    # -- 3. truncated checkpoint is never selected for resume ---------
    trunc_dir = os.path.join(ckroot, "trunc")
    losses, _ = run_supervised(8, trunc_dir, ckpt_every=4)
    latest = io.latest_checkpoint(trunc_dir)
    victim = os.path.join(trunc_dir, str(latest))
    marker = io.read_commit_marker(victim)
    rel = sorted(marker["manifest"])[-1]
    with open(os.path.join(victim, rel), "r+b") as f:
        f.truncate(max(0, os.path.getsize(os.path.join(victim, rel)) - 1))
    after = io.latest_checkpoint(trunc_dir)
    assert after != latest, (
        f"truncated checkpoint {latest} still selected for resume")
    report["truncation_skipped"] = {"truncated": latest, "selected": after}
    print(f"truncation: step-{latest} corrupted -> resume selects "
          f"{after} (OK)")

    # -- 4. kill -> auto-resume round trip, bitwise --------------------
    steps, every, kill_at = 12, 3, 8
    tmp = tempfile.mkdtemp(prefix="chaos_kill_")
    ck = os.path.join(tmp, "ck")
    ref_proc, ref = spawn_run(tmp, "ref", steps,
                              os.path.join(tmp, "ref_ck"), every)
    assert ref_proc.returncode == 0, ref_proc.stderr[-2000:]
    kill_proc, _ = spawn_run(tmp, "killed", steps, ck, every,
                             fault=f"kill@{kill_at}")
    assert kill_proc.returncode == resilience.KILL_EXIT_CODE, (
        kill_proc.returncode, kill_proc.stderr[-2000:])
    res_proc, res = spawn_run(tmp, "resumed", steps, ck, every)
    assert res_proc.returncode == 0, res_proc.stderr[-2000:]
    resumed_from = res["stats"]["resumed_from"]
    assert resumed_from and 0 < resumed_from <= kill_at, resumed_from
    tail = {s: res["losses"][s] for s in res["losses"]}
    mismatch = {s: (v, ref["losses"][s]) for s, v in tail.items()
                if ref["losses"][s] != v}
    assert not mismatch, f"resumed trajectory diverged: {mismatch}"
    report["chaos_round_trip"] = {
        "steps": steps, "killed_at": kill_at, "resumed_from": resumed_from,
        "bitwise_identical": True,
    }
    print(f"kill@{kill_at}: resumed from {resumed_from}, "
          f"{len(tail)} post-resume losses bitwise-identical (OK)")

    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {out_path}")

    # the acceptance gate — generous step count keeps CPU CI noise down
    assert overhead_pct < 5.0, (
        f"supervision overhead {overhead_pct:.2f}% >= 5% budget")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="overhead + latency + chaos round-trip bench")
    p.add_argument("--out", default=None, help="smoke: JSON report path")
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=8)
    p.add_argument("--fault", default="",
                   help="e.g. 'raise@3,nan@12,hang@20:2,kill@30'")
    p.add_argument("--seed", type=int, default=41)
    p.add_argument("--loss-out", default=None,
                   help="write {losses, stats} JSON here")
    p.add_argument("--no-final-checkpoint", action="store_true")
    args = p.parse_args(argv)
    if args.smoke:
        return smoke(args.out)
    if not args.ckpt_dir:
        args.ckpt_dir = tempfile.mkdtemp(prefix="chaos_train_")
        print(f"checkpoints -> {args.ckpt_dir}")
    return _child(args)


if __name__ == "__main__":
    sys.exit(main())
