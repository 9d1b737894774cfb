"""Model zoo built on the layers API.

Reference analogue: the "book"/dist test model definitions
(tests/book/, tests/unittests/dist_mnist.py, dist_se_resnext.py,
dist_transformer.py) — canonical models exercising the stack, also used
by benchmark/, chip_smoke.py and __graft_entry__.py.
"""

from .bert import BertConfig, build_bert_pretrain, apply_megatron_sharding
from .resnet import build_resnet50
from .mnist import build_lenet
from .gpt import (
    GPTConfig,
    build_gpt_lm,
    apply_gpt_megatron_sharding,
    synthetic_lm_batch,
)
from .seq2seq import build_seq2seq, beam_search_infer
from .ctr import build_deepfm, build_wide_deep, synthetic_ctr_batch
from .vision import build_vgg, build_se_resnext
from .ssd import build_ssd, multi_box_head, ssd_loss, detection_output
