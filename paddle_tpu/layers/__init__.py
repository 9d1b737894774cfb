"""Layer library: functions that append ops to the default main program.

Reference: python/paddle/fluid/layers/ (~32k LoC: nn.py,
control_flow.py, tensor.py, loss ops inside nn.py,
learning_rate_scheduler.py, collective.py, detection.py, io.py).
"""

from .io import data
from .nn import *  # noqa: F401,F403
from .tensor import *  # noqa: F401,F403
from .control_flow import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .learning_rate_scheduler import *  # noqa: F401,F403
from .metric_op import accuracy, auc
from .collective import (
    _c_allreduce,
    _c_broadcast,
    _c_allgather,
    _c_reducescatter,
)
from .detection import iou_similarity, box_coder, prior_box
from .sequence import *  # noqa: F401,F403
from .py_func_registry import py_func
from .extras import *  # noqa: F401,F403
from .decoder import *  # noqa: F401,F403

# auto-generated wrappers fill remaining reference layer names; hand-
# written layers above always win on name conflicts
from . import auto as _auto

for _n in _auto.__all__:
    if _n not in globals():
        globals()[_n] = getattr(_auto, _n)
del _auto, _n
from .rnn import (
    dynamic_lstm,
    dynamic_gru,
    lstm_unit,
    gru_unit,
    beam_search,
    beam_search_decode,
)
from . import ops  # noqa: F401
from . import distributions  # noqa: F401
