"""Real multi-process distributed test (reference TestDistBase,
test_dist_base.py:506: spawn subprocesses on localhost, check parity).

Spawns 2 worker processes through paddle_tpu.distributed.launch; each
initializes jax.distributed from the PADDLE_* env contract and runs a
cross-process psum. Validates launcher -> env contract -> coordination
service -> gloo collectives end to end.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from paddle_tpu.parallel.env import init_parallel_env
    env = init_parallel_env()
    import jax, jax.numpy as jnp
    x = jnp.ones((jax.local_device_count(), 2)) * (env.rank + 1)
    y = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(x)
    # per-rank result file: concurrent stdout writes interleave mid-line
    with open({outdir!r} + f"/rank{{env.rank}}.txt", "w") as f:
        f.write(str(float(np.asarray(y)[0, 0])))
    """
)


def test_two_process_psum(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=repo, outdir=str(tmp_path)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # drop the 8-device virtualization for the children: 1 device/proc
    env["XLA_FLAGS"] = ""
    import socket

    with socket.socket() as s:  # free port: fixed ports flake on reruns
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", f"--started_port={port}", str(worker)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=150,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-2000:]
    results = {
        r: float((tmp_path / f"rank{r}.txt").read_text())
        for r in (0, 1)
        if (tmp_path / f"rank{r}.txt").exists()
    }
    # psum over both processes: 1 + 2 = 3 everywhere
    assert results == {0: 3.0, 1: 3.0}, (results, out[-1000:])
