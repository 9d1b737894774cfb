"""Chaos suite for paddle_tpu.resilience: every leg of the fault
lifecycle is exercised deterministically through the flag-gated fault
injector, and the headline property — a run killed at an arbitrary
step auto-resumes from the last COMMITTED checkpoint with a loss
trajectory bitwise identical to an uninterrupted run — is proven
across real process boundaries (os._exit kill, fresh interpreter
resume)."""

import os
import signal
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, resilience
from paddle_tpu.fs import HDFSClient, LocalFS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "tools"))

import chaos_train  # noqa: E402  (the driver doubles as the test model zoo)


def _run(steps, ckpt_dir, **kw):
    return chaos_train.run_supervised(steps, str(ckpt_dir), **kw)


# -- atomic commit / corrupt-checkpoint handling ----------------------------


def test_latest_checkpoint_skips_uncommitted_and_truncated(tmp_path):
    ck = str(tmp_path / "ck")
    _run(8, ck, ckpt_every=2, keep_last=10)
    committed = io.committed_checkpoint_steps(ck)
    assert committed == [2, 4, 6, 8], committed

    # a crash mid-save: numeric dir with data but NO commit marker
    fake = os.path.join(ck, "12")
    os.makedirs(fake)
    with open(os.path.join(fake, "array_data"), "w") as f:
        f.write("partial write")
    assert io.latest_checkpoint(ck) == 8

    # truncation AFTER commit: manifest sizes no longer match
    victim = os.path.join(ck, "8")
    marker = io.read_commit_marker(victim)
    rel = sorted(marker["manifest"])[-1]
    path = os.path.join(victim, rel)
    with open(path, "r+b") as f:
        f.truncate(max(0, os.path.getsize(path) - 1))
    assert not io.is_committed_checkpoint(victim)
    assert io.latest_checkpoint(ck) == 6

    # a deleted manifest file is also detected
    victim = os.path.join(ck, "6")
    marker = io.read_commit_marker(victim)
    os.remove(os.path.join(victim, sorted(marker["manifest"])[0]))
    assert io.latest_checkpoint(ck) == 4

    # load_checkpoint refuses the corrupt dir with a clear error
    with pytest.raises(ValueError, match="uncommitted or corrupt"):
        io.load_checkpoint(ck, main_program=fluid.Program(), step=6)


def test_resume_skips_corrupt_dir_end_to_end(tmp_path):
    """Kill -> truncate the newest commit -> resume must pick the
    previous one and still complete."""
    ck = str(tmp_path / "ck")
    _run(9, ck, ckpt_every=3, keep_last=10, final_checkpoint=False)
    assert io.latest_checkpoint(ck) == 9 or io.latest_checkpoint(ck) == 6
    latest = io.latest_checkpoint(ck)
    victim = os.path.join(ck, str(latest))
    marker = io.read_commit_marker(victim)
    rel = sorted(marker["manifest"])[-1]
    with open(os.path.join(victim, rel), "r+b") as f:
        f.truncate(0)
    losses, stats = _run(12, ck, ckpt_every=3)
    assert stats["resumed_from"] == latest - 3
    assert stats["steps_completed"] == 12 - (latest - 3)


def test_atomic_rename_local_and_hdfs_stub(tmp_path):
    fs = LocalFS()
    src, dst = str(tmp_path / "src"), str(tmp_path / "dst")
    os.makedirs(src)
    with open(os.path.join(src, "f"), "w") as f:
        f.write("new")
    # dst exists non-empty: plain os.replace would raise ENOTEMPTY
    os.makedirs(dst)
    with open(os.path.join(dst, "stale"), "w") as f:
        f.write("old")
    fs.atomic_rename(src, dst)
    assert sorted(os.listdir(dst)) == ["f"]
    assert not os.path.exists(src)
    with pytest.raises(Exception):
        fs.atomic_rename(str(tmp_path / "missing"), dst)
    with pytest.raises(NotImplementedError, match="LocalFS staging"):
        HDFSClient(hadoop_home="/nonexistent").atomic_rename("a", "b")


# -- fault spec -------------------------------------------------------------


def test_fault_spec_parse_and_one_shot():
    spec = resilience.FaultSpec.parse("raise@3, nan@5, hang@7:0.01, kill@9")
    # actions are (kind, step, arg, rank) — rank None = every rank
    assert [(a[0], a[1]) for a in spec.actions] == [
        ("raise", 3), ("nan", 5), ("hang", 7), ("kill", 9)]
    assert all(a[3] is None for a in spec.actions)
    inj = resilience.FaultInjector(
        resilience.FaultSpec([("raise", 3, None)]))
    with pytest.raises(resilience.InjectedFault):
        inj.before_step(3)
    inj.before_step(3)  # one-shot: second pass is clean
    assert inj.fired() == [("raise", 3)]
    # an explicit :0 arg means a ~0s hang, not the hang-forever default
    inj0 = resilience.FaultInjector("hang@1:0")
    t0 = time.time()
    inj0.before_step(1)
    assert time.time() - t0 < 5.0
    assert inj0.fired() == [("hang", 1)]
    with pytest.raises(ValueError, match="fault"):
        resilience.FaultSpec.parse("explode@3")
    with pytest.raises(ValueError, match="bad fault spec"):
        resilience.FaultSpec.parse("raise3")


# -- supervisor lifecycle ---------------------------------------------------


def test_retry_then_success_and_stats(tmp_path):
    losses, stats = _run(10, tmp_path / "ck", ckpt_every=4,
                         fault="raise@5")
    assert stats["retries"] == 1
    assert stats["rollbacks"] == 0
    assert stats["steps_completed"] == 10
    assert stats["faults_injected"] == 1
    assert sorted(losses) == list(range(10))


def test_retry_budget_exhausts(tmp_path):
    with pytest.raises(resilience.InjectedFault):
        _run(10, tmp_path / "ck", ckpt_every=4,
             fault="raise@5,raise@5,raise@5,raise@5,raise@5,raise@5")


def test_nan_rollback_fires_hook_and_recovers(tmp_path):
    ck = str(tmp_path / "ck")
    nan_seen = []
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    losses = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ck,
            feed_fn=chaos_train.feed_fn, fetch_list=[loss],
            policy=resilience.CheckpointPolicy(ck, every_steps=4,
                                               keep_last=3),
            fault_injector=resilience.FaultInjector("nan@6"),
            on_nan=lambda step, val: nan_seen.append((step, val)),
            on_step=lambda s, f: losses.__setitem__(
                s, float(np.asarray(f[0]))))
        stats = sup.run_loop(10)
    assert nan_seen and nan_seen[0][0] == 6 and np.isnan(nan_seen[0][1])
    assert stats["nan_events"] == 1
    assert stats["rollbacks"] == 1
    assert stats["steps_completed"] == 10 + (6 - 4)  # replayed 4,5 post-rollback
    assert all(np.isfinite(v) for v in losses.values())
    # the rolled-back trajectory matches a clean run bitwise (state AND
    # rng counter were restored from the step-4 commit)
    ref, _ = _run(10, tmp_path / "ref", ckpt_every=4)
    assert losses == ref


def test_nan_without_checkpoint_raises(tmp_path):
    with pytest.raises(resilience.NonFiniteLossError, match="no committed"):
        _run(10, tmp_path / "ck", ckpt_every=0, fault="nan@1",
             final_checkpoint=False)


def test_hang_trips_watchdog_then_recovers(tmp_path):
    losses, stats = _run(8, tmp_path / "ck", ckpt_every=4,
                         fault="hang@5:30", watchdog_s=0.3)
    assert stats["watchdog_fires"] == 1
    assert stats["retries"] == 1  # the watchdog timeout fed the retry path
    assert stats["steps_completed"] == 8
    assert sorted(losses) == list(range(8))


def test_zombie_step_detected_and_rolled_back(tmp_path):
    """A watchdog-abandoned step that later UNWEDGES and completes
    (mutating scope + run counter behind the retry's back) is detected
    and the corruption is discarded by rolling back to the last commit
    — the recovered trajectory still matches a clean run bitwise."""
    ck = str(tmp_path / "ck")
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    losses = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        real_run = exe.run
        hang = {"armed": True}

        def slow_run(*a, **kw):
            if hang["armed"] and sup._stats["steps_completed"] >= 5:
                hang["armed"] = False
                time.sleep(0.5)  # a hang INSIDE the step, then completes
            return real_run(*a, **kw)

        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ck,
            feed_fn=chaos_train.feed_fn, fetch_list=[loss],
            watchdog_timeout_s=0.2,
            policy=resilience.CheckpointPolicy(ck, every_steps=4,
                                               keep_last=3),
            # slow the loop so it is still running when the zombie wakes
            on_step=lambda s, f: (
                losses.__setitem__(s, float(np.asarray(f[0]))),
                time.sleep(0.05)))
        exe.run = slow_run
        stats = sup.run_loop(16)
    assert stats["watchdog_fires"] == 1
    assert stats["zombie_steps"] == 1
    assert stats["rollbacks"] >= 1
    assert stats["steps_completed"] >= 16
    ref, _ = _run(16, tmp_path / "ref", ckpt_every=4)
    assert losses == ref, "zombie corruption leaked into the trajectory"


def test_cancelled_hang_is_not_a_zombie(tmp_path):
    """An abandoned attempt that wakes from its (injected) hang AFTER
    cancellation parks before exe.run — it never touched the scope and
    must NOT be absorbed as a zombie (no spurious rollback, no bogus
    'no committed checkpoint' abort)."""
    ck = str(tmp_path / "ck")
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    losses = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ck,
            feed_fn=chaos_train.feed_fn, fetch_list=[loss],
            watchdog_timeout_s=0.25,
            fault_injector=resilience.FaultInjector("hang@2:1.0"),
            policy=resilience.CheckpointPolicy(ck, every_steps=4,
                                               keep_last=3),
            # keep the loop alive past the hang's wake-up at ~1.0s
            on_step=lambda s, f: (
                losses.__setitem__(s, float(np.asarray(f[0]))),
                time.sleep(0.12)))
        stats = sup.run_loop(10)
    assert stats["watchdog_fires"] == 1
    assert stats["zombie_steps"] == 0
    assert stats["rollbacks"] == 0
    assert stats["steps_completed"] == 10
    ref, _ = _run(10, tmp_path / "ref", ckpt_every=4)
    assert losses == ref


def test_async_save_handle_waits_for_commit(tmp_path):
    ck = str(tmp_path / "ck")
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        h = io.save_checkpoint(ck, main_program=main, scope=scope, step=3,
                               async_save=True, extra={"run_counter": 7})
        h.wait_until_finished()  # must cover the COMMIT, not just data
    path = os.path.join(ck, "3")
    marker = io.read_commit_marker(path)
    assert marker is not None and marker["extra"]["run_counter"] == 7
    assert io.is_committed_checkpoint(path)


def test_policy_save_same_step_is_idempotent(tmp_path):
    """Re-committing a step that already has a committed dir (post-
    rollback replay re-reaching a cadence point) skips the publish —
    never moves a live committed checkpoint aside."""
    ck = str(tmp_path / "ck")
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        pol = resilience.CheckpointPolicy(ck, every_steps=4, keep_last=3)
        first = pol.save(5, main_program=main, scope=scope)
        mtime = os.path.getmtime(os.path.join(first, io._COMMIT_MARKER))
        again = pol.save(5, main_program=main, scope=scope)
    assert again == first
    assert os.path.getmtime(os.path.join(first, io._COMMIT_MARKER)) == mtime


def test_fresh_run_never_adopts_foreign_commits(tmp_path):
    """A fresh run (resume=False) pointed at a dir holding a previous
    run's commits must neither roll back into that foreign state nor
    skip publishing its own checkpoints over it."""
    ck = str(tmp_path / "ck")
    _run(8, ck, ckpt_every=4)  # run A (seed 41): commits 4 and 8
    marker_a = io.read_commit_marker(os.path.join(ck, "4"))

    def fresh_run(fault=""):
        main, startup, loss = chaos_train.build_model(seed=99)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            sup = resilience.Supervisor(
                exe, main, checkpoint_dir=ck,
                feed_fn=chaos_train.feed_fn, fetch_list=[loss],
                policy=resilience.CheckpointPolicy(ck, every_steps=4,
                                                   keep_last=3),
                fault_injector=resilience.FaultInjector(fault))
            return sup.run_loop(8, resume=False, final_checkpoint=False)

    # a NaN before run B's own first commit has nothing OF RUN B's to
    # roll back to — run A's step-4/8 commits must not be adopted
    with pytest.raises(resilience.NonFiniteLossError, match="no committed"):
        fresh_run(fault="nan@2")

    # and run B's cadence save REPLACES run A's step-4 commit (the
    # skip-if-committed shortcut only applies to this run's own replay)
    fresh_run()
    marker_b = io.read_commit_marker(os.path.join(ck, "4"))
    assert marker_b["extra"]["random_seed"] == 99
    assert marker_b["extra"] != marker_a["extra"]


def test_gc_never_drops_own_latest_commit(tmp_path):
    """In a reused dir, foreign higher-step commits must not make
    retention GC collect the commit this run just wrote."""
    ck = str(tmp_path / "ck")
    _run(12, ck, ckpt_every=4, keep_last=10)  # foreign commits: 4, 8, 12
    main, startup, loss = chaos_train.build_model(seed=99)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        pol = resilience.CheckpointPolicy(ck, every_steps=4, keep_last=3)
        own = pol.save(2, main_program=main, scope=scope)
    # [2, 4, 8, 12] with keep_last=3 would rank 2 as oldest — but it is
    # this policy's newest own commit and must survive its own gc()
    assert io.is_committed_checkpoint(own)
    assert 2 in io.committed_checkpoint_steps(ck)


def test_retention_gc_keeps_exactly_keep_last(tmp_path):
    ck = str(tmp_path / "ck")
    _run(20, ck, ckpt_every=2, keep_last=3, final_checkpoint=False)
    assert io.committed_checkpoint_steps(ck) == [16, 18, 20]
    numeric = [d for d in os.listdir(ck) if d.isdigit()]
    assert sorted(int(d) for d in numeric) == [16, 18, 20]
    # stale staging debris from a "crashed" foreign writer is collected
    # — but only once old enough that it cannot be a live writer's
    debris = os.path.join(ck, ".staging.99.1")
    aside = os.path.join(ck, "7.old.1")  # atomic_rename aside, stranded
    os.makedirs(debris)
    os.makedirs(aside)
    pol = resilience.CheckpointPolicy(ck, every_steps=2, keep_last=3)
    pol.gc()
    assert os.path.exists(debris), "fresh foreign staging must survive gc"
    old = time.time() - 3600
    os.utime(debris, (old, old))
    os.utime(aside, (old, old))
    pol.gc()
    assert not os.path.exists(debris)
    assert not os.path.exists(aside)


def test_sigterm_flushes_final_checkpoint(tmp_path):
    ck = str(tmp_path / "ck")
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        # pre-compile the step so the timer races pure stepping, not
        # the first-call XLA compile (loaded CI boxes take seconds)
        exe.run(main, feed=chaos_train.feed_fn(0), fetch_list=[loss])
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ck,
            feed_fn=chaos_train.feed_fn, fetch_list=[loss],
            policy=resilience.CheckpointPolicy(ck, every_steps=0,
                                               keep_last=2))
        timer = threading.Timer(
            1.0, lambda: os.kill(os.getpid(), signal.SIGTERM))
        timer.start()
        try:
            stats = sup.run_loop(10_000_000)
        finally:
            timer.cancel()
    assert stats["preempted"]
    assert 0 < stats["steps_completed"] < 10_000_000
    # the flush committed exactly the completed-step count, so a
    # follow-up run continues where the preempted one stopped
    assert io.latest_checkpoint(ck) == stats["steps_completed"]
    losses, stats2 = _run(stats["steps_completed"] + 3, ck, ckpt_every=0)
    assert stats2["resumed_from"] == stats["steps_completed"]
    assert stats2["steps_completed"] == 3


def test_reader_position_checkpoint_roundtrip(tmp_path):
    """GeneratorLoader's resumable position: a supervised run feeding
    from a loader records the position in the commit marker and a
    resumed run fast-forwards to it."""
    from paddle_tpu.reader import GeneratorLoader

    def make_loader():
        loader = GeneratorLoader(feed_list=[], use_double_buffer=False)
        loader.set_batch_generator(
            lambda: (chaos_train.feed_fn(s) for s in range(64)))
        return loader

    ck = str(tmp_path / "ck")
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ck, data=make_loader(),
            fetch_list=[loss],
            policy=resilience.CheckpointPolicy(ck, every_steps=3,
                                               keep_last=2))
        sup.run_loop(7, final_checkpoint=False)
    marker = io.read_commit_marker(os.path.join(ck, "6"))
    assert marker["extra"]["reader_position"] == 6

    main2, startup2, loss2 = chaos_train.build_model()
    scope2 = fluid.Scope()
    loader2 = make_loader()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.TPUPlace())
        exe2.run(startup2)
        sup2 = resilience.Supervisor(
            exe2, main2, checkpoint_dir=ck, data=loader2,
            fetch_list=[loss2],
            policy=resilience.CheckpointPolicy(ck, every_steps=3,
                                               keep_last=2))
        stats = sup2.run_loop(10, final_checkpoint=False)
    assert stats["resumed_from"] == 6
    assert stats["steps_completed"] == 4
    assert loader2.position() == 10


# -- the headline: kill -> auto-resume, bitwise across processes ------------


# child processes go through the driver's own spawn helper
# (chaos_train.spawn_run) so the CPU-pinned spawn environment is
# maintained in one place
_spawn_driver = chaos_train.spawn_run


def test_kill_then_auto_resume_bitwise_identical(tmp_path):
    """A supervised run hard-killed (os._exit, no cleanup) at step 8
    auto-resumes in a FRESH PROCESS from the last committed checkpoint
    and reproduces the uninterrupted run's loss trajectory bitwise —
    dropout makes every step consume the PRNG, so this proves the
    step/RNG counter round-trips through the commit marker."""
    steps, every, kill_at = 12, 3, 8
    ck = tmp_path / "ck"

    ref_proc, ref = _spawn_driver(tmp_path, "ref", steps,
                                  tmp_path / "ref_ck", every)
    assert ref_proc.returncode == 0, ref_proc.stderr[-2000:]

    kill_proc, _ = _spawn_driver(tmp_path, "killed", steps, ck, every,
                                 fault=f"kill@{kill_at}")
    assert kill_proc.returncode == resilience.KILL_EXIT_CODE, (
        kill_proc.returncode, kill_proc.stderr[-2000:])
    # the kill landed between commits: some steps exist only in memory
    assert io.latest_checkpoint(str(ck)) == 6

    res_proc, res = _spawn_driver(tmp_path, "resumed", steps, ck, every)
    assert res_proc.returncode == 0, res_proc.stderr[-2000:]
    assert res["stats"]["resumed_from"] == 6
    mismatch = {s: (v, ref["losses"][s]) for s, v in res["losses"].items()
                if ref["losses"][s] != v}
    assert not mismatch, f"resumed trajectory diverged: {mismatch}"
    assert io.latest_checkpoint(str(ck)) == steps  # final flush committed
