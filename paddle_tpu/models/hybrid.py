"""Hybrid decoder: a list of layers, each a Mamba-2 or a grouped-query
attention mixer followed by dropless top-k experts plus a shared
expert; RMS norms, no bias, no position embedding, tied head, and the
four multipliers of the `granitemoehybrid` family (embedding, residual,
attention, logits). One chip may hold a share of it: the experts
``moe_first .. moe_first + moe_held`` of the ``moe_experts`` the router
ranks, and the first ``vocab_size`` rows of the embedding.

    h = E[token] * embedding_multiplier
    h = h + residual_multiplier * Mixer_i(rms_norm(h))
    h = h + residual_multiplier * (MoE_i(u) + Shared_i(u)),  u = rms_norm(h)
    logits = rms_norm(h) E^T / logits_scaling

``hybrid_decoder`` writes the stack once; ``build_hybrid_lm_program`` is
the exportable full causal forward (Mamba from zero state over the whole
sequence), ``generation.model.build_hybrid_step_program`` the serving
step (pages for the attention layers, a per-lane recurrent state for the
Mamba layers). Parameter names: ``hyb_tok_emb``, ``hyb{i}_ln1.scale``,
``hyb{i}_mamba_*`` or ``hyb{i}_attn_{q,k,v,o}.w``, ``hyb{i}_ln2.scale``,
``hyb{i}_router.w``, ``hyb{i}_experts_{in,out}.w``,
``hyb{i}_shared_{in,out}.w``, ``hyb_lnf.scale``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

from .. import layers, nets
from ..core.framework import Program, program_guard, unique_name
from .gpt import _attr

__all__ = ["HybridConfig", "hybrid_decoder", "build_hybrid_lm_program"]


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]        # "mamba" | "attention", a layer each
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

    def __post_init__(self):
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"layer_types: unknown kinds {sorted(bad)}")
        if self.num_heads % self.num_kv_heads or \
                self.mamba_heads % self.mamba_groups:
            raise ValueError("query heads must be a multiple of KV heads, "
                             "Mamba heads of Mamba groups")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "attention")

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "mamba")

    @property
    def held_experts(self) -> int:
        return self.moe_experts if self.moe_held is None else self.moe_held

    def state_shapes(self, rows: int):
        """The recurrent state a step over ``rows`` lanes carries, in
        feed order: name -> (shape, dtype). Per Mamba layer the SSM state
        and the conv's last inputs; last, the experts' load counts."""
        ch = (self.mamba_heads * self.mamba_head_dim
              + 2 * self.mamba_groups * self.mamba_state)
        out = {}
        for i in self.mamba_layers:
            out[f"gen_state_ssm_{i}"] = (
                (rows, self.mamba_heads, self.mamba_head_dim,
                 self.mamba_state), self.state_dtype)
            out[f"gen_state_conv_{i}"] = (
                (rows, self.mamba_conv - 1, ch), self.state_dtype)
        out["gen_state_moe_loads"] = (
            (self.num_layers, self.held_experts), "int32")
        return out


def hybrid_decoder(cfg: HybridConfig, tokens, attention: Callable,
                   num_valid=None, positions=None, state=None,
                   head_at=None):
    """The stack on ``tokens`` [rows, chunk] -> (logits [rows, chunk, V],
    state_out). ``attention(i, q, k, v)`` -> ctx is the caller's (full
    causal, or pages); q arrives scaled so that a 1/sqrt(head_dim)
    kernel applies ``attention_multiplier``. ``state``: {name: Variable}
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

    emb = layers.embedding(tokens, size=[cfg.vocab_size, d], dtype=dt,
                           param_attr=_attr("hyb_tok_emb", std))
    h = layers.scale(layers.cast(emb, "float32"),
                     scale=cfg.embedding_multiplier)
    loads_out = []
    for i, kind in enumerate(cfg.layer_types):
        pre = f"hyb{i}_"
        u = layers.rms_norm(h, eps, param_attr=pre + "ln1.scale", dtype=dt)
        if kind == "mamba":
            ssm = conv = None
            if state is not None:
                ssm = state[f"gen_state_ssm_{i}"]
                conv = state[f"gen_state_conv_{i}"]
            y, ssm, conv = layers.mamba2_mixer(
                u, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
                num_groups=cfg.mamba_groups, conv_width=cfg.mamba_conv,
                chunk_size=cfg.mamba_chunk, epsilon=eps,
                num_valid=num_valid, positions=positions, ssm_state=ssm,
                conv_state=conv, param_attr=_attr(pre + "mamba_", std),
                dtype=dt)
            state_out[f"gen_state_ssm_{i}"] = ssm
            state_out[f"gen_state_conv_{i}"] = conv
        else:
            q = layers.linear(u, cfg.num_heads * hd, dtype=dt,
                              param_attr=_attr(pre + "attn_q.w", std))
            k = layers.linear(u, cfg.num_kv_heads * hd, dtype=dt,
                              param_attr=_attr(pre + "attn_k.w", std))
            v = layers.linear(u, cfg.num_kv_heads * hd, dtype=dt,
                              param_attr=_attr(pre + "attn_v.w", std))
            ctx = attention(i, layers.scale(q, scale=qscale), k, v)
            y = layers.linear(ctx, d, dtype=dt,
                              param_attr=_attr(pre + "attn_o.w", std))
        h = residual(h, y)
        u = layers.rms_norm(h, eps, param_attr=pre + "ln2.scale", dtype=dt)
        loads = None
        if state is not None:
            loads = layers.reshape(
                layers.slice(state["gen_state_moe_loads"], axes=[0],
                             starts=[i], ends=[i + 1]), [cfg.held_experts])
        routed, loads = layers.topk_moe(
            u, cfg.moe_experts, cfg.moe_top_k, cfg.moe_expert_size,
            held_experts=cfg.held_experts, first_expert=cfg.moe_first,
            num_valid=num_valid, loads=loads,
            param_attr=_attr(pre, std), dtype=dt)
        loads_out.append(loads)
        shared = layers.gated_ffn(u, cfg.shared_size, dtype=dt,
                                  param_attr=_attr(pre + "shared", std))
        h = residual(h, layers.elementwise_add(routed, shared))
    state_out["gen_state_moe_loads"] = layers.stack(loads_out, axis=0)
    if head_at is not None:
        h = layers.reduce_sum(
            layers.elementwise_mul(h, layers.unsqueeze(head_at, [2])),
            dim=[1], keep_dim=True)                          # [rows, 1, d]
    u = layers.rms_norm(h, eps, param_attr="hyb_lnf.scale", dtype=dt)
    logits = layers.linear(u, cfg.vocab_size, dtype=dt, transpose_w=True,
                           param_attr=_attr("hyb_tok_emb", std))
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
        logits, _state = hybrid_decoder(cfg, tokens,
                                        _full_causal_attention(cfg))
    return main, startup, {"tokens": tokens}, {"logits": logits}
