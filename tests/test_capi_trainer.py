"""Native trainer C API (paddle_tpu/capi/ PD_Trainer*): a python
script AUTHORS and serializes the program pair, then a REAL C program
drives the whole training loop — no Python driver in the loop — and
the loss must fall. Reference: paddle/fluid/train/demo/demo_trainer.cc
(+ demo_network.py authoring split)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid



def test_c_trainer_trains_saved_program(tmp_path):
    # -- python authoring side: the EXAMPLE script (so it can't rot) ---
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": here}
    author = subprocess.run(
        [sys.executable, os.path.join(here, "examples",
                                      "author_trainer_program.py"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=300, check=True)
    out_dir, loss_name = author.stdout.split()
    main_p = os.path.join(out_dir, "main.json")
    startup_p = os.path.join(out_dir, "startup.json")

    # -- native side: the EXAMPLE C driver -----------------------------
    from paddle_tpu.capi.build import build, embed_flags

    so = build()
    csrc = os.path.join(here, "examples", "native_trainer.c")
    exe_path = str(tmp_path / "ctrainer")
    cflags, ldflags = embed_flags()
    subprocess.run(
        ["gcc", csrc, "-o", exe_path, f"-L{os.path.dirname(so)}",
         "-lpaddle_capi", f"-Wl,-rpath,{os.path.dirname(so)}"] + ldflags,
        check=True, capture_output=True)

    save_dir = str(tmp_path / "persist")
    proc = subprocess.run([exe_path, main_p, startup_p, loss_name, save_dir],
                          capture_output=True, text=True, env=env,
                          timeout=420)
    assert proc.returncode == 0, (proc.returncode, proc.stdout, proc.stderr)
    assert "first=" in proc.stdout and "last=" in proc.stdout
    # persistables landed on disk (combined npz w/ fc weight + bias)
    params = np.load(os.path.join(save_dir, "__params__.npz"))
    assert len(params.files) >= 2, params.files
