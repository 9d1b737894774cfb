"""Hybrid decoder: a list of layers, each a Mamba-2 mixer, a
grouped-query attention mixer or both side by side on one input
(``parallel``), followed by dropless top-k experts plus a shared expert
or by a dense gated feed-forward; RMS norms, no bias. The defaults are
the `granitemoehybrid` family's (no position embedding, tied head, the
embedding, residual, attention and logits multipliers); the `falcon_h1`
family adds rotary positions, a head size of its own, an untied head and
the muP multipliers (Mamba input, the five parts of its projection and
its output; attention input, keys and output; the gate inside the SiLU
and the down projection). One chip may hold a share of it: the experts
``moe_first .. moe_first + moe_held`` of the ``moe_experts`` the router
ranks, and the first ``vocab_size`` rows of the embedding.

    h = E[token] * embedding_multiplier
    u = rms_norm(h)
    h = h + residual_multiplier * (ssm_out_multiplier * Mamba_i(u)
                                   + attention_out_multiplier * Attn_i(u))
        (either term alone where the layer has one mixer)
    u = rms_norm(h)
    h = h + residual_multiplier * (MoE_i(u) + Shared_i(u))    experts
    h = h + residual_multiplier * FFN_i(u)                     dense
    logits = rms_norm(h) E^T / logits_scaling    (W_head, untied)

``hybrid_decoder`` writes the stack once; ``build_hybrid_lm_program`` is
the exportable full causal forward (Mamba from zero state over the whole
sequence), ``generation.model.build_hybrid_step_program`` the serving
step (pages for the attention layers, a per-lane recurrent state for the
Mamba layers; a parallel layer has both). Parameter names:
``hyb_tok_emb``, ``hyb{i}_ln1.scale``, ``hyb{i}_mamba_*`` and/or
``hyb{i}_attn_{q,k,v,o}.w``, ``hyb{i}_ln2.scale``, ``hyb{i}_router.w``,
``hyb{i}_experts_{in,out}.w``, ``hyb{i}_shared_{in,out}.w`` or
``hyb{i}_ffn_{in,out}.w``, ``hyb_lnf.scale``, ``hyb_head.w`` (untied).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import numpy as np

from .. import layers, nets
from ..core.framework import Program, program_guard, unique_name
from .gpt import _attr

__all__ = ["HybridConfig", "hybrid_decoder", "build_hybrid_lm_program"]


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]        # "mamba" | "attention" | "parallel"
    num_heads: int
    num_kv_heads: int
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    moe_experts: int                    # what the router ranks
    moe_top_k: int
    moe_expert_size: int
    shared_size: int
    max_position: int
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    moe_held: Optional[int] = None      # experts held here (None: all)
    moe_first: int = 0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None    # None: 1/sqrt(head_dim)
    logits_scaling: float = 1.0
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    param_dtype: str = "float32"
    state_dtype: str = "float32"
    head_dim: Optional[int] = None      # None: hidden_size / num_heads
    ffn_types: Tuple[str, ...] = ()     # "experts" | "dense"; (): experts
    dense_size: int = 0
    rope_base: Optional[float] = None   # None: no position embedding
    tie_word_embeddings: bool = True
    # muP multipliers (falcon_h1); 1 / None: absent
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Optional[Tuple[float, ...]] = None  # z, x, B, C, dt
    ssm_out_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)    # gate, down

    def __post_init__(self):
        bad = (set(self.layer_types) - {"mamba", "attention", "parallel"}
               | set(self.ffn_types) - {"experts", "dense"})
        if bad:
            raise ValueError(f"layer_types / ffn_types: unknown kinds "
                             f"{sorted(bad)}")
        if self.ffn_types and len(self.ffn_types) != self.num_layers:
            raise ValueError("ffn_types: one kind a layer")
        if self.num_heads % self.num_kv_heads or \
                self.mamba_heads % self.mamba_groups:
            raise ValueError("query heads must be a multiple of KV heads, "
                             "Mamba heads of Mamba groups")
        if self.ssm_multipliers is not None and len(self.ssm_multipliers) != 5:
            raise ValueError("ssm_multipliers: one a part of [z|x|B|C|dt]")
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_heads)

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k != "mamba")

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k != "attention")

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        return self.ffn_types or ("experts",) * self.num_layers

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.ffn_kinds)
                     if k == "experts")

    @property
    def held_experts(self) -> int:
        return self.moe_experts if self.moe_held is None else self.moe_held

    def state_shapes(self, rows: int):
        """The recurrent state a step over ``rows`` lanes carries, in
        feed order: name -> (shape, dtype). Per Mamba layer the SSM state
        and the conv's last inputs; last, the expert layers' load
        counts, if any."""
        ch = (self.mamba_heads * self.mamba_head_dim
              + 2 * self.mamba_groups * self.mamba_state)
        out = {}
        for i in self.mamba_layers:
            out[f"gen_state_ssm_{i}"] = (
                (rows, self.mamba_heads, self.mamba_head_dim,
                 self.mamba_state), self.state_dtype)
            out[f"gen_state_conv_{i}"] = (
                (rows, self.mamba_conv - 1, ch), self.state_dtype)
        if self.expert_layers:
            out["gen_state_moe_loads"] = (
                (len(self.expert_layers), self.held_experts), "int32")
        return out


def hybrid_decoder(cfg: HybridConfig, tokens, attention: Callable,
                   num_valid=None, positions=None, state=None,
                   head_at=None, pos_ids=None):
    """The stack on ``tokens`` [rows, chunk] -> (logits [rows, chunk, V],
    state_out). ``attention(i, q, k, v)`` -> ctx is the caller's (full
    causal, or pages); q arrives scaled so that a 1/sqrt(head_dim)
    kernel applies ``attention_multiplier``, q and k rotated at
    ``pos_ids`` [rows, chunk] where the config has a rotary base, k
    times ``key_multiplier``. ``state``: {name: Variable}
    of ``cfg.state_shapes`` with ``num_valid`` and ``positions`` [rows]
    (the serving step), or None (whole sequences from zero state).
    ``head_at`` [rows, chunk], one-hot: the head runs on that one
    position of each row (logits [rows, 1, V]) and not on the rest."""
    std, dt, eps = cfg.initializer_range, cfg.param_dtype, cfg.rms_eps
    d, hd = cfg.hidden_size, cfg.head_dim
    qscale = (1.0 if cfg.attention_multiplier is None
              else cfg.attention_multiplier * math.sqrt(hd))
    state_out = {}

    def residual(h, y):
        return layers.elementwise_add(
            h, layers.scale(y, scale=cfg.residual_multiplier))

    def times(x, m):
        # a multiplier of 1 emits nothing: a config without it compiles
        # to the program it always did
        return x if m == 1.0 else layers.scale(x, scale=m)

    def mamba(i, pre, u):
        ssm = conv = None
        if state is not None:
            ssm = state[f"gen_state_ssm_{i}"]
            conv = state[f"gen_state_conv_{i}"]
        y, ssm, conv = layers.mamba2_mixer(
            u, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
            num_groups=cfg.mamba_groups, conv_width=cfg.mamba_conv,
            chunk_size=cfg.mamba_chunk, epsilon=eps,
            num_valid=num_valid, positions=positions, ssm_state=ssm,
            conv_state=conv, in_multiplier=cfg.ssm_in_multiplier,
            zxbcdt_multipliers=cfg.ssm_multipliers,
            param_attr=_attr(pre + "mamba_", std), dtype=dt)
        state_out[f"gen_state_ssm_{i}"] = ssm
        state_out[f"gen_state_conv_{i}"] = conv
        return times(y, cfg.ssm_out_multiplier)

    def self_attention(i, pre, u):
        a = times(u, cfg.attention_in_multiplier)
        q = layers.linear(a, cfg.num_heads * hd, dtype=dt,
                          param_attr=_attr(pre + "attn_q.w", std))
        k = layers.linear(a, cfg.num_kv_heads * hd, dtype=dt,
                          param_attr=_attr(pre + "attn_k.w", std))
        v = layers.linear(a, cfg.num_kv_heads * hd, dtype=dt,
                          param_attr=_attr(pre + "attn_v.w", std))
        k = times(k, cfg.key_multiplier)
        if cfg.rope_base is not None:
            q = layers.rotary_embedding(q, pos_ids, cfg.num_heads, hd,
                                        cfg.rope_base)
            k = layers.rotary_embedding(k, pos_ids, cfg.num_kv_heads, hd,
                                        cfg.rope_base)
        ctx = attention(i, layers.scale(q, scale=qscale), k, v)
        y = layers.linear(ctx, d, dtype=dt,
                          param_attr=_attr(pre + "attn_o.w", std))
        return times(y, cfg.attention_out_multiplier)

    emb = layers.embedding(tokens, size=[cfg.vocab_size, d], dtype=dt,
                           param_attr=_attr("hyb_tok_emb", std))
    h = layers.scale(layers.cast(emb, "float32"),
                     scale=cfg.embedding_multiplier)
    loads_out = []
    for i, (kind, ffn) in enumerate(zip(cfg.layer_types, cfg.ffn_kinds)):
        pre = f"hyb{i}_"
        u = layers.rms_norm(h, eps, param_attr=pre + "ln1.scale", dtype=dt)
        if kind == "mamba":
            y = mamba(i, pre, u)
        elif kind == "attention":
            y = self_attention(i, pre, u)
        else:           # parallel: both mixers on one input, summed
            y = layers.elementwise_add(mamba(i, pre, u),
                                       self_attention(i, pre, u))
        h = residual(h, y)
        u = layers.rms_norm(h, eps, param_attr=pre + "ln2.scale", dtype=dt)
        if ffn == "dense":
            h = residual(h, layers.gated_ffn(
                u, cfg.dense_size, dtype=dt, multipliers=cfg.mlp_multipliers,
                num_valid=num_valid, param_attr=_attr(pre + "ffn", std)))
            continue
        loads = None
        if state is not None:
            j = len(loads_out)
            loads = layers.reshape(
                layers.slice(state["gen_state_moe_loads"], axes=[0],
                             starts=[j], ends=[j + 1]), [cfg.held_experts])
        routed, loads = layers.topk_moe(
            u, cfg.moe_experts, cfg.moe_top_k, cfg.moe_expert_size,
            held_experts=cfg.held_experts, first_expert=cfg.moe_first,
            num_valid=num_valid, loads=loads,
            param_attr=_attr(pre, std), dtype=dt)
        loads_out.append(loads)
        shared = layers.gated_ffn(u, cfg.shared_size, dtype=dt,
                                  num_valid=num_valid,
                                  param_attr=_attr(pre + "shared", std))
        h = residual(h, layers.elementwise_add(routed, shared))
    if loads_out:
        state_out["gen_state_moe_loads"] = layers.stack(loads_out, axis=0)
    if head_at is not None:
        h = layers.reduce_sum(
            layers.elementwise_mul(h, layers.unsqueeze(head_at, [2])),
            dim=[1], keep_dim=True)                          # [rows, 1, d]
    u = layers.rms_norm(h, eps, param_attr="hyb_lnf.scale", dtype=dt)
    if cfg.tie_word_embeddings:
        logits = layers.linear(u, cfg.vocab_size, dtype=dt, transpose_w=True,
                               param_attr=_attr("hyb_tok_emb", std))
    else:
        logits = layers.linear(u, cfg.vocab_size, dtype=dt,
                               param_attr=_attr("hyb_head.w", std))
    return layers.scale(logits, scale=1.0 / cfg.logits_scaling), state_out


def _full_causal_attention(cfg: HybridConfig):
    rep, hd = cfg.num_heads // cfg.num_kv_heads, cfg.head_dim

    def spread(x):
        # KV head j serves query heads j*rep .. j*rep + rep - 1
        x = layers.reshape(x, [0, 0, cfg.num_kv_heads, 1, hd])
        x = layers.expand(x, [1, 1, 1, rep, 1])
        return layers.reshape(x, [0, 0, cfg.num_heads * hd])

    def attention(_i, q, k, v):
        return nets.scaled_dot_product_attention(
            q, spread(k), spread(v), num_heads=cfg.num_heads, causal=True)
    return attention


def build_hybrid_lm_program(cfg: HybridConfig, seq_len: int):
    """Loss-free causal LM: tokens [B, S] -> logits [B, S, V]; what
    ``save_inference_model`` exports and ``create_predictor`` loads."""
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("tokens", [seq_len], dtype="int64")
        pos_ids = None
        if cfg.rope_base is not None:
            pos_ids = layers.assign(np.arange(seq_len, dtype="int64")[None])
        logits, _state = hybrid_decoder(cfg, tokens,
                                        _full_causal_attention(cfg),
                                        pos_ids=pos_ids)
    return main, startup, {"tokens": tokens}, {"logits": logits}
