"""Pallas TPU kernels for the fused hot ops.

Reference: operators/fused/ (multihead_matmul_op.cu — inference-only
fused attention; fused_fc_elementwise_layernorm_op.cu; ...). Here the
fused set is implemented as Pallas kernels (BASELINE north star names
attention/ffn/layer_norm/adam/softmax-ce):

  * flash_attention — blockwise attention, no [B,H,S,S] materialization
  * fused layer_norm — single-pass row kernel, fwd + bwd
    (kernels/layer_norm.py), wired into the layer_norm lowering
  * fused softmax cross-entropy — loss+lse row kernel, fused backward
    (kernels/softmax_xent.py), wired into softmax_with_cross_entropy
  * paged attention + kv_cache_write — decode-step attention over
    paged K/V with block tables (kernels/paged_attention.py, wrapping
    jax.experimental.pallas.ops.tpu.paged_attention on TPU), the
    kernel layer under paddle_tpu.generation's two-lane engine
  * ragged paged attention + quantized KV write — ONE kernel serving
    mixed prefill chunks and decode rows side by side over the paged
    pool (kernels/ragged_paged_attention.py, custom Pallas lowering),
    with an int8-page variant reusing the kernels/quant.py blockwise
    machinery — the kernel under the ragged GenerationEngine
  * quantized weight matmul — int8 / blockwise-int8 / fp8 weights with
    per-channel or blockwise fp32 scale tracking
    (kernels/quant_matmul.py): dequantize-in-registers inside the
    matmul tile loop, the kernel layer under
    paddle_tpu.quantize.rewrite_for_inference's quantized serving path
  * batched LoRA matmul — per-row adapter deltas over rank-bucketed,
    device-resident (A, B) factor pools indexed by a per-row slot
    vector fed like a block table (kernels/lora.py): slot-masked
    small-rank matmuls accumulated in VMEM, composing with the
    dense OR quantized base — the kernel layer under
    paddle_tpu.adapters' multi-adapter serving
  * Mamba-2 state step — read a chunk out of every lane's carried
    state [H, P, N] and advance it, one pass over the state
    (kernels/mamba2_state.py): the kernel under ops/ssm.py's
    ``mamba2_mixer`` in the hybrid serving step
  * grouped expert feed-forward — the held experts' gated feed-forward
    for pairs sorted by expert, each visited expert's weights streamed
    once in whole-row tiles of 2-4 MB (kernels/moe_ffn.py): the kernel
    under ops/moe.py's ``topk_moe`` in the hybrid and MiMo serving steps
  * fused optimizer — one-pass Adam/AdamW/Momentum over donated
    buffers (kernels/fused_optim.py): the whole m/v/param update is a
    single Pallas pass per parameter with the global-norm-clip scale
    folded in as a scalar operand, wired into optimizer.Adam/Momentum
    under the ``optimizer_fuse`` flag (this supersedes the seed's
    "adam is deliberately not a kernel" stance — the lowered HLO of a
    ZeRO-sharded step showed the optimizer tail as a CHAIN of fusions
    re-reading state, not one)

Kernels degrade gracefully: on non-TPU backends (CPU tests) they fall
back to the pure-XLA implementation with identical numerics
(flash fallback uses the same stable-softmax algorithm).
"""

from .flash_attention import flash_attention, flash_attention_layer
from .fused_optim import (fused_adam_update, fused_momentum_update,
                          optimizer_fuse_enabled)
from .layer_norm import fused_layer_norm, layer_norm_pallas
from .mamba2_state import state_step
from .lora import (batched_lora_delta, batched_lora_matmul,
                   lora_pool_shapes, lora_rank_geometry_issue,
                   lora_slot_bytes)
from .quant_matmul import (dequantize_weight, quantize_weight,
                           quantized_matmul, quantized_weight_bytes)
from .paged_attention import (kv_cache_write, kv_cache_write_layer,
                              paged_attention, paged_attention_layer)
from .ragged_paged_attention import (quantized_kv_cache_write,
                                     quantized_kv_cache_write_layer,
                                     ragged_paged_attention,
                                     ragged_paged_attention_layer,
                                     split_kv_cache_write,
                                     split_kv_cache_write_layer)
from .softmax_xent import fused_softmax_xent
