"""Multi-host chaos driver: kill one rank of N mid-step, restart the
world, resume bit-exactly.

This is the proof the whole multi-host fault-tolerance layer hangs on:
an N-process CPU-backend training run (jax.distributed rendezvous,
per-rank LOCAL batches through a rank-sharded GeneratorLoader, data
parallelism over the coordination-service host wire, the Supervisor's
checkpoint cadence riding the TWO-PHASE cross-host commit) where
``faults.py`` kills EXACTLY ONE rank mid-step. The elastic launcher
detects the death, SIGTERM->SIGKILLs the survivors stalled on the dead
peer, re-rendezvouses on a fresh port, and the world auto-resumes from
the last committed checkpoint — with final parameters
BITWISE-IDENTICAL to an unkilled control run.

The DP wire on CPU: XLA's CPU backend refuses cross-process device
computations outright (pmap and GSPMD both), so the harness averages
the model state across ranks after each local step through
``Coordinator.host_allreduce`` (the coordination-service KV wire).
With a MOMENTUM optimizer the update is linear in the gradient, so
per-step state averaging is mathematically identical to training on
the averaged gradient — the same trajectory an in-graph dp all-reduce
(the TPU path) produces, which ``tests/test_multihost.py`` checks
allclose against a single-process partitioned dp2 run.

Worker mode (one rank; run under paddle_tpu.distributed.launch)::

    python -m paddle_tpu.distributed.launch --nproc_per_node=4 \\
        --max_restarts=2 tools/chaos_multihost.py --worker \\
        --steps 12 --every 3 --ckpt-dir /shared/ck --stats-dir /shared/st

Smoke mode (the CI ``chaos-multihost`` job)::

    python tools/chaos_multihost.py --smoke --out chaos_multihost.json

drives three launches: (1) an unkilled N-rank control run, (2) the same
run with ``r<K>:kill@<step>`` killing one rank mid-step — gated on the
launcher restarting the world exactly once and the resumed run's final
params matching the control bitwise — and (3) a ``killsave`` run where
one rank dies MID-SAVE, after its shards but before its shard-done
file — gated on the torn checkpoint never acquiring a commit marker.
The worker also snapshots the ``paddle_dist_*`` gauges so the report
shows the world's health metrics existed and moved.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

BATCH = 8
FEATS = 12


# -- worker ------------------------------------------------------------------


def build_model(seed=41, dropout=True):
    """Small MLP trained with MOMENTUM: the update is linear in the
    gradient, so the harness's per-step cross-rank state averaging is
    exactly averaged-gradient DP (Adam's second moment would break the
    linearity). Dropout consumes the per-step PRNG fold, so a resumed
    run only matches the control bitwise if the run counter was
    restored."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [FEATS])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.1)
        logits = fluid.layers.fc(h, 4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Momentum(5e-3, momentum=0.9).minimize(loss)
    return main, startup, loss


def _sample_reader(total):
    """Deterministic per-GLOBAL-index samples: every world size/rank
    carves the same stream, so control and chaos runs see identical
    data."""

    def reader():
        for i in range(total):
            rng = np.random.RandomState(10_000 + i)
            x = rng.randn(FEATS).astype("float32")
            y = np.asarray(
                [int(np.abs(x).sum() > 9.0) + int(x[0] > 0)], dtype="int64")
            yield (x, y)

    return reader


def run_worker(args) -> int:
    import paddle_tpu as fluid
    from paddle_tpu import distributed, observability, resilience

    coord = distributed.initialize()
    gen = coord.restart_count
    # the injected fault models ONE spot reclaim: only the first
    # incarnation of the world arms it — the restarted world must run
    # clean or the resume proof would kill itself forever
    fault = args.fault if gen == 0 else ""

    main, startup, loss = build_model(args.seed,
                                      dropout=not args.no_dropout)

    scope = fluid.Scope()
    losses = {}
    sync_names = sorted(
        v.name for v in main.global_block().vars.values()
        if v.persistable and not v.is_data)

    def sync_state(step):
        """The DP wire: average every float persistable across ranks
        (momentum makes this == averaged-gradient DP; see module doc).
        Runs after each step, BEFORE any checkpoint save, so committed
        state is the globally-averaged trajectory on every rank."""
        if coord.world_size <= 1:
            return
        arrays = {}
        for n in sync_names:
            val = scope.find_var(n)
            if val is not None:
                a = np.asarray(val)
                if a.dtype.kind == "f":
                    arrays[n] = a
        for n, a in coord.host_allreduce(
                arrays, tag=f"sync:{step}",
                timeout_s=args.sync_timeout_s).items():
            scope.set_var(n, a)

    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        # per-process LOCAL batches: the loader's rank sharding
        # (trainer_id/num_trainers from the launcher env) carves the
        # global sample stream; total covers steps * batch * world
        block = main.global_block()
        from paddle_tpu.reader import GeneratorLoader

        loader = GeneratorLoader([block.var("x"), block.var("y")],
                                 capacity=8)
        loader.set_sample_generator(
            _sample_reader(args.steps * BATCH * coord.world_size),
            batch_size=BATCH, drop_last=True)
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=args.ckpt_dir,
            data=loader, fetch_list=[loss],
            policy=resilience.CheckpointPolicy(
                args.ckpt_dir, every_steps=args.every, keep_last=3),
            max_retries=1, retry_backoff_s=0.1,
            watchdog_timeout_s=args.watchdog_s,
            fault_injector=resilience.FaultInjector(fault),
            on_step=lambda s, f: (
                losses.__setitem__(s, float(np.asarray(f[0]))),
                sync_state(s)))
        # progress-based heartbeat: a rank wedged in a dead peer's
        # collective stops beating and the launcher declares it hung
        coord.attach_progress(
            lambda: sup._stats["steps_completed"],
            stall_after_s=max(30.0, 4 * args.watchdog_s))
        stats = sup.run_loop(args.steps)

    scrape = observability.to_prometheus_text()
    dist_gauges = sorted({line.split("{")[0].split()[0]
                          for line in scrape.splitlines()
                          if line.startswith("paddle_dist_")})
    if args.stats_dir:
        os.makedirs(args.stats_dir, exist_ok=True)
        out = {
            "rank": coord.rank, "world": coord.world_size,
            "generation": gen, "stats": stats,
            "losses": {str(s): v for s, v in losses.items()},
            "dist_gauges": dist_gauges,
        }
        path = os.path.join(args.stats_dir,
                            f"stats.rank{coord.rank}.gen{gen}.json")
        with open(path, "w") as f:
            json.dump(out, f)
    print(f"chaos_multihost worker rank={coord.rank}/{coord.world_size} "
          f"gen={gen}: {stats['steps_completed']} steps, "
          f"resumed_from={stats['resumed_from']} "
          f"ckpts={stats['checkpoints_written']}")
    return 0


# -- smoke -------------------------------------------------------------------


def _free_port() -> int:
    from paddle_tpu.parallel.env import free_port

    return free_port()


def _scrubbed_env():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="",  # one device per process
        PYTHONPATH=REPO,
        # a torn save must fail in seconds, not the production 120
        FLAGS_dist_commit_timeout_s="15",
        FLAGS_dist_barrier_timeout_s="30",
    )
    return env


def _launch(tmp, name, nproc, steps, every, ckpt_dir, stats_dir,
            fault="", max_restarts=0, timeout=420):
    cmd = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        f"--nproc_per_node={nproc}", f"--started_port={_free_port()}",
        f"--max_restarts={max_restarts}", "--kill_grace_s=8",
        "--heartbeat_timeout_s=45", "--heartbeat_interval_s=1.0",
        f"--run_dir={os.path.join(tmp, name + '.run')}",
        os.path.abspath(__file__), "--worker",
        "--steps", str(steps), "--every", str(every),
        "--ckpt-dir", ckpt_dir, "--stats-dir", stats_dir,
        "--watchdog-s", "15",
    ]
    if fault:
        cmd += ["--fault", fault]
    t0 = time.time()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=_scrubbed_env(), cwd=REPO)
    return proc, time.time() - t0


def _read_stats(stats_dir, rank, gen):
    path = os.path.join(stats_dir, f"stats.rank{rank}.gen{gen}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def smoke(out_path=None, nproc=4, steps=12, every=3):
    from paddle_tpu import io, resilience

    assert nproc >= 4, "the kill-one-of-N proof needs N >= 4 ranks"
    tmp = tempfile.mkdtemp(prefix="chaos_multihost_")
    report = {"bench": "chaos_multihost", "mode": "smoke",
              "nproc": nproc, "steps": steps, "ckpt_every": every}
    kill_rank, kill_step = 2, steps // 2 + 1  # mid-step, mid-run

    # -- 1. control: unkilled N-rank run --------------------------------
    ck_control = os.path.join(tmp, "ck_control")
    st_control = os.path.join(tmp, "st_control")
    proc, dt = _launch(tmp, "control", nproc, steps, every,
                       ck_control, st_control)
    assert proc.returncode == 0, (
        f"control run failed rc={proc.returncode}\n{proc.stderr[-3000:]}")
    control = io.load_checkpoint_arrays(os.path.join(ck_control, str(steps)))
    st0 = _read_stats(st_control, 0, 0)
    assert st0 and st0["stats"]["steps_completed"] == steps, st0
    report["control"] = {"wall_s": round(dt, 1),
                         "vars": len(control),
                         "world": st0["world"]}
    for g in ("paddle_dist_world_size", "paddle_dist_live_ranks",
              "paddle_dist_heartbeat_age_s", "paddle_dist_restarts",
              "paddle_dist_barriers_total"):
        assert g in st0["dist_gauges"], (g, st0["dist_gauges"])
    print(f"control: {nproc} ranks x {steps} steps in {dt:.0f}s, "
          f"{len(control)} persistables committed, "
          f"{len(st0['dist_gauges'])} paddle_dist_* gauges live")

    # -- 2. chaos: kill exactly one rank mid-step, world restarts -------
    ck_chaos = os.path.join(tmp, "ck_chaos")
    st_chaos = os.path.join(tmp, "st_chaos")
    proc, dt = _launch(tmp, "chaos", nproc, steps, every,
                       ck_chaos, st_chaos,
                       fault=f"r{kill_rank}:kill@{kill_step}",
                       max_restarts=2)
    assert proc.returncode == 0, (
        f"chaos run failed rc={proc.returncode}\n{proc.stderr[-3000:]}")
    assert f"rank {kill_rank} exited with code " \
        f"{resilience.KILL_EXIT_CODE}" in proc.stderr, proc.stderr[-2000:]
    assert "restarting world (restart 1/" in proc.stderr, \
        proc.stderr[-2000:]
    # EXACTLY one: a second restart means generation 1 crashed too —
    # the resume itself is broken even if generation 2 limps home
    assert "restarting world (restart 2/" not in proc.stderr, \
        proc.stderr[-2000:]
    st1 = _read_stats(st_chaos, 0, 1)
    assert st1 is not None, "no generation-1 stats — the world never " \
        f"restarted? launcher stderr:\n{proc.stderr[-2000:]}"
    resumed_from = st1["stats"]["resumed_from"]
    last_commit = (kill_step // every) * every
    assert resumed_from == last_commit, (
        f"resumed from {resumed_from}, wanted the last pre-kill commit "
        f"{last_commit}")
    chaos = io.load_checkpoint_arrays(os.path.join(ck_chaos, str(steps)))
    mismatch = [k for k in control
                if not np.array_equal(control[k], np.asarray(chaos[k]))]
    assert not mismatch, (
        f"final params diverged after kill+restart+resume: {mismatch}")
    # and the LOSS trajectory (rank 0's local stream) replays bitwise
    c0 = _read_stats(st_control, 0, 0)["losses"]
    r0 = st1["losses"]
    diverged = {s: (r0[s], c0[s]) for s in r0 if c0.get(s) != r0[s]}
    assert not diverged, f"post-resume losses diverged: {diverged}"
    report["chaos_round_trip"] = {
        "wall_s": round(dt, 1), "killed_rank": kill_rank,
        "killed_at_step": kill_step, "resumed_from": resumed_from,
        "restarts": 1, "params_bitwise_identical": True,
        "post_resume_losses_bitwise": len(r0),
    }
    print(f"chaos: r{kill_rank}:kill@{kill_step} -> world restarted, "
          f"resumed from {resumed_from}, {len(control)} final params + "
          f"{len(r0)} post-resume losses bitwise-identical in {dt:.0f}s")

    # -- 3. torn save: a rank killed mid-save never yields a marker ------
    ck_torn = os.path.join(tmp, "ck_torn")
    st_torn = os.path.join(tmp, "st_torn")
    # killsave@(every-1) arms during the step BEFORE the first cadence
    # save, so the very first save(every) is the one rank 1 dies in —
    # no earlier commit exists and latest_checkpoint must stay None
    proc, dt = _launch(tmp, "torn", nproc, steps, every,
                       ck_torn, st_torn,
                       fault=f"r1:killsave@{every - 1}", max_restarts=0)
    assert proc.returncode != 0, (
        "torn-save run exited 0 — the dead-in-save rank went unnoticed")
    assert io.latest_checkpoint(ck_torn) is None, (
        f"a checkpoint committed despite rank 1 dying mid-save: "
        f"{io.latest_checkpoint(ck_torn)}")
    # walk EVERYTHING including the dot-named staging dir — the marker
    # must not exist anywhere, published or staged
    markers, done_files = [], []
    for root, _dirs, files in os.walk(ck_torn):
        for fn in files:
            if fn == "_PT_COMMIT.json":
                markers.append(os.path.join(root, fn))
            elif fn.startswith("_PT_SHARD_DONE."):
                done_files.append(os.path.join(root, fn))
    assert not markers, f"torn save left commit marker(s): {markers}"
    report["torn_save"] = {
        "wall_s": round(dt, 1), "exit_code": proc.returncode,
        "committed_marker": False,
        "partial_done_files": len(done_files),
    }
    print(f"torn save: rank 1 killed mid-save -> rc={proc.returncode}, "
          f"{len(done_files)} partial done-file(s), NO commit marker (OK)")

    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {out_path}")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="control + kill-one-of-N + torn-save gates")
    p.add_argument("--out", default=None, help="smoke: JSON report path")
    p.add_argument("--nproc", type=int, default=4)
    p.add_argument("--worker", action="store_true",
                   help="run as one rank under distributed.launch")
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--every", type=int, default=3)
    p.add_argument("--seed", type=int, default=41)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--stats-dir", default=None)
    p.add_argument("--watchdog-s", type=float, default=15.0)
    p.add_argument("--sync-timeout-s", type=float, default=30.0,
                   help="host_allreduce wait before declaring a peer "
                        "dead (-> restartable exit)")
    p.add_argument("--no-dropout", action="store_true",
                   help="drop the dropout layer (the dp-parity test "
                        "needs a PRNG-free model to compare against a "
                        "single-process partitioned run)")
    p.add_argument("--fault", default="",
                   help="e.g. 'r2:kill@7' or 'r1:killsave@3'")
    args = p.parse_args(argv)
    if args.smoke:
        return smoke(args.out, nproc=args.nproc, steps=args.steps,
                     every=args.every)
    if not args.worker:
        p.error("pick --smoke or --worker")
    if not args.ckpt_dir:
        args.ckpt_dir = tempfile.mkdtemp(prefix="chaos_mh_ck_")
    return run_worker(args)


if __name__ == "__main__":
    sys.exit(main())
