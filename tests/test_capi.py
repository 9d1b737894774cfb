"""C inference API test (paddle_tpu/capi/): save an inference model,
then drive it from a REAL C consumer — a small C program compiled
against libpaddle_capi.so — and compare with the in-process predictor.

Reference: inference/capi/ tested by inference/tests/capi/ (C
consumers over a saved model).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers

C_MAIN = r"""
#include <stdio.h>
#include <stdint.h>
#include <stdlib.h>

extern int PD_Init();
extern void *PD_NewPredictor(const char *model_dir);
extern void PD_DeletePredictor(void *);
extern int PD_GetInputNum(void *);
extern int PD_GetOutputNum(void *);
extern int PD_GetInputName(void *, int, char *, int);
extern int PD_GetOutputName(void *, int, char *, int);
extern int PD_SetInputFloat(void *, const char *, const float *,
                            const int64_t *, int);
extern int PD_PredictorRun(void *);
extern int64_t PD_GetOutputFloat(void *, const char *, float *, int64_t,
                                 int64_t *, int, int *);

int main(int argc, char **argv) {
  if (PD_Init() != 0) return 1;
  void *pred = PD_NewPredictor(argv[1]);
  if (!pred) return 2;
  if (PD_GetInputNum(pred) != 1) return 3;
  char in_name[256], out_name[256];
  if (PD_GetInputName(pred, 0, in_name, sizeof in_name) != 0) return 4;
  if (PD_GetOutputName(pred, 0, out_name, sizeof out_name) != 0) return 5;

  float x[2 * 4];
  for (int i = 0; i < 8; ++i) x[i] = (float)i * 0.25f - 1.0f;
  int64_t shape[2] = {2, 4};
  if (PD_SetInputFloat(pred, in_name, x, shape, 2) != 0) return 6;
  if (PD_PredictorRun(pred) != 0) return 7;

  float out[64];
  int64_t oshape[8];
  int ndim = 0;
  int64_t n = PD_GetOutputFloat(pred, out_name, out, 64, oshape, 8, &ndim);
  if (n <= 0) return 8;
  printf("ndim=%d numel=%lld\n", ndim, (long long)n);
  for (int64_t i = 0; i < n; ++i) printf("%.6f\n", out[i]);
  PD_DeletePredictor(pred);
  return 0;
}
"""


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("capi_model"))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", [4])
        y = layers.fc(x, 3, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [y], exe, main_program=main)
        xv = (np.arange(8, dtype="float32") * 0.25 - 1.0).reshape(2, 4)
        (expect,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    return d, np.asarray(expect)


def test_c_consumer_runs_model(saved_model, tmp_path):
    model_dir, expect = saved_model
    from paddle_tpu.capi.build import build

    so = build()
    csrc = tmp_path / "main.c"
    csrc.write_text(C_MAIN)
    exe_path = tmp_path / "capi_main"
    subprocess.run(
        ["gcc", str(csrc), "-o", str(exe_path), f"-L{os.path.dirname(so)}",
         "-lpaddle_capi", f"-Wl,-rpath,{os.path.dirname(so)}"],
        check=True, capture_output=True,
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo  # embedded interpreter must find paddle_tpu
    proc = subprocess.run(
        [str(exe_path), model_dir], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode == 0, f"C consumer rc={proc.returncode}: {proc.stderr[-800:]}"
    lines = proc.stdout.strip().splitlines()
    assert lines[0] == "ndim=2 numel=6", lines[0]
    got = np.array([float(v) for v in lines[1:]], "float32").reshape(2, 3)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
