"""Op lowerings: each module registers op types into the core registry.

Reference: paddle/fluid/operators/ (~500 op types, C++/CUDA kernels).
Here each op is a JAX lowering; XLA supplies the per-backend kernels,
fusion, and layout assignment that the reference hand-writes.
"""

from . import math  # noqa: F401
from . import tensor  # noqa: F401
from . import random  # noqa: F401
from . import nn  # noqa: F401
from . import optim  # noqa: F401
from . import collective  # noqa: F401
from . import quant  # noqa: F401
from . import loss_ext  # noqa: F401
from . import control  # noqa: F401
from . import rnn  # noqa: F401
from . import sequence  # noqa: F401
from . import detection  # noqa: F401
from . import metrics  # noqa: F401
from . import beam  # noqa: F401
from . import lod  # noqa: F401
from . import fused  # noqa: F401
from . import vision3d  # noqa: F401
from . import dist_compute  # noqa: F401
from . import misc  # noqa: F401
from . import detection2  # noqa: F401
from . import persist  # noqa: F401
from . import moe  # noqa: F401
from . import decoder  # noqa: F401
from . import ssm  # noqa: F401
