"""The benchmark's own tests: CPU, tiny presets, interpret-mode
kernels. Run from the repo's root:

    python3 -m pytest benchmark/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_KERNEL_INTERPRET", "1")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
