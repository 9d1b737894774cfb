"""TestDistBase-grade multi-process TRAINING parity (reference
tests/unittests/test_dist_base.py:506,586,696: spawn real subprocess
trainers/pservers on localhost, train the same model as a single
process, assert per-step loss deltas).

Collective mode: 2 subprocess trainers via distributed.launch +
jax.distributed; grads cross processes through c_allreduce_sum lowered
onto a pmap axis (executor multi-process path).
PS mode: 2 subprocess pservers + 2 subprocess trainers over the socket
PS; sync barrier averages grads.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 5
BATCH = 16

_MODEL = textwrap.dedent(
    """
    def build_model(seed=5):
        import paddle_tpu as fluid

        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", [8])
            y = fluid.layers.data("y", [1], dtype="int64")
            h = fluid.layers.fc(x, 16, act="relu")
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(fluid.layers.fc(h, 4), y)
            )
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss


    def batches(steps, batch):
        import numpy as np

        rng = np.random.RandomState(7)
        out = []
        for _ in range(steps):
            xb = rng.randn(batch, 8).astype("float32")
            yb = (np.abs(xb[:, :1]) * 2).astype("int64") % 4
            out.append({"x": xb, "y": yb})
        return out
    """
)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _scrubbed_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # one device per process
    return env


def _single_process_losses():
    ns = {}
    exec(compile(_MODEL, "<model>", "exec"), ns)
    main, startup, loss = ns["build_model"]()
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for b in ns["batches"](STEPS, BATCH):
            (l,) = exe.run(main, feed=b, fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
    return losses


_COLLECTIVE_WORKER = textwrap.dedent(
    """
    import os, sys, json
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from paddle_tpu.parallel.env import init_parallel_env

    env = init_parallel_env()
    import paddle_tpu as fluid
    from paddle_tpu.transpiler.collective import GradAllReduce

    {model}

    main, startup, loss = build_model()
    t = GradAllReduce()
    t.transpile(startup, main, rank=env.rank,
                endpoints=list(env.trainer_endpoints),
                current_endpoint=env.current_endpoint)
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        half = {batch!r} // 2
        for b in batches({steps!r}, {batch!r}):
            lo, hi = env.rank * half, (env.rank + 1) * half
            feed = {{k: v[lo:hi] for k, v in b.items()}}
            (l,) = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
    with open({outdir!r} + f"/collective_rank{{env.rank}}.json", "w") as f:
        json.dump(losses, f)
    """
)


def test_two_process_collective_training_parity(tmp_path):
    """2 subprocess trainers, half batch each, c_allreduce grads ->
    every step must match single-process full-batch training to 1e-5
    (reference test_dist_base.py:506 delta)."""
    worker = tmp_path / "collective_worker.py"
    worker.write_text(
        _COLLECTIVE_WORKER.format(
            repo=REPO, model=_MODEL, outdir=str(tmp_path),
            steps=STEPS, batch=BATCH,
        )
    )
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", f"--started_port={_free_port()}", str(worker)],
        cwd=REPO, env=_scrubbed_env(), capture_output=True, text=True, timeout=240,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    ranks = []
    for r in (0, 1):
        p = tmp_path / f"collective_rank{r}.json"
        assert p.exists(), out[-3000:]
        ranks.append(json.loads(p.read_text()))
    dist_losses = np.mean(ranks, axis=0)  # mean of half-batch means
    local_losses = _single_process_losses()
    np.testing.assert_allclose(dist_losses, local_losses, atol=1e-5, rtol=1e-5)


_PSERVER_WORKER = textwrap.dedent(
    """
    import os, sys, json
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.transpiler import DistributeTranspiler, DistributeTranspilerConfig
    from paddle_tpu.ps.server import ParameterServer

    {model}

    endpoint = sys.argv[1]
    endpoints = sys.argv[2].split(",")
    main, startup, loss = build_model()
    cfg = DistributeTranspilerConfig(); cfg.mode = "pserver"
    t = DistributeTranspiler(cfg)
    t.transpile(0, program=main, pservers=",".join(endpoints), trainers=2,
                sync_mode=True, startup_program=startup)
    art = t._ps_artifacts
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        shards, specs = {{}}, {{}}
        for shard_name, (pname, lo, hi) in art.pserver_programs[endpoint].items():
            shards[shard_name] = np.asarray(scope.find_var(pname))[lo:hi].copy()
            spec = dict(art.optimizer_specs.get(pname, {{"type": "sgd"}}))
            lr_var = spec.pop("lr_var", None)
            if lr_var is not None and scope.find_var(lr_var) is not None:
                spec["lr"] = float(np.asarray(scope.find_var(lr_var)).reshape(-1)[0])
            specs[shard_name] = spec
    ps = ParameterServer(endpoint, shards, specs, art.trainers, art.sync_mode)
    t = ps.start_background()
    print("PSERVER_READY", flush=True)
    t.join()  # parent kills the process when the trainers finish
    """
)

_PS_TRAINER_WORKER = textwrap.dedent(
    """
    import os, sys, json
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.transpiler import DistributeTranspiler, DistributeTranspilerConfig
    from paddle_tpu.ps.transpile import PSTrainer

    {model}

    trainer_id = int(sys.argv[1])
    endpoints = sys.argv[2].split(",")
    main, startup, loss = build_model()
    cfg = DistributeTranspilerConfig(); cfg.mode = "pserver"
    t = DistributeTranspiler(cfg)
    t.transpile(trainer_id, program=main, pservers=",".join(endpoints),
                trainers=2, sync_mode=True, startup_program=startup)
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        trainer = PSTrainer(t._ps_artifacts, exe, scope, trainer_id=trainer_id)
        half = {batch!r} // 2
        for b in batches({steps!r}, {batch!r}):
            lo, hi = trainer_id * half, (trainer_id + 1) * half
            feed = {{k: v[lo:hi] for k, v in b.items()}}
            (l,) = trainer.run_step(feed, [loss])
            losses.append(float(np.asarray(l).reshape(())))
    with open({outdir!r} + f"/ps_rank{{trainer_id}}.json", "w") as f:
        json.dump(losses, f)
    """
)


def test_two_trainer_two_pserver_training_parity(tmp_path):
    """2 pserver processes + 2 trainer processes, sync barrier; per-step
    losses (averaged over trainers) must match single-process training
    (reference test_dist_base.py:586 pserver path)."""
    eps = [f"127.0.0.1:{p}" for p in _free_ports(2)]
    env = _scrubbed_env()
    ps_src = _PSERVER_WORKER.format(repo=REPO, model=_MODEL)
    tr_src = _PS_TRAINER_WORKER.format(
        repo=REPO, model=_MODEL, outdir=str(tmp_path), steps=STEPS, batch=BATCH,
    )
    (tmp_path / "ps.py").write_text(ps_src)
    (tmp_path / "tr.py").write_text(tr_src)

    servers = [
        subprocess.Popen(
            [sys.executable, str(tmp_path / "ps.py"), ep, ",".join(eps)],
            cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for ep in eps
    ]
    try:
        for s in servers:  # wait until both listen
            # stderr rides the same pipe: skip whatever XLA logs while
            # it loads cached executables
            seen = []
            for line in iter(s.stdout.readline, ""):
                seen.append(line)
                if "PSERVER_READY" in line:
                    break
            assert seen and "PSERVER_READY" in seen[-1], "".join(seen)[-2000:]
        trainers = [
            subprocess.Popen(
                [sys.executable, str(tmp_path / "tr.py"), str(tid), ",".join(eps)],
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
            for tid in (0, 1)
        ]
        outs = []
        for t in trainers:
            out, _ = t.communicate(timeout=180)
            outs.append(out)
            assert t.returncode == 0, out[-3000:]
    finally:
        for s in servers:
            s.kill()
    ranks = []
    for r in (0, 1):
        p = tmp_path / f"ps_rank{r}.json"
        assert p.exists(), outs
        ranks.append(json.loads(p.read_text()))
    dist_losses = np.mean(ranks, axis=0)
    local_losses = _single_process_losses()
    np.testing.assert_allclose(dist_losses, local_losses, atol=1e-5, rtol=1e-5)
