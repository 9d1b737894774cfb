"""MiMo-V2 decoder (`mimo_v2`): layers that differ in attention kind
and in feed-forward kind.

* attention ``full``: grouped queries over every earlier key;
  ``window``: over the last ``window`` keys (the token's own included),
  its own count of KV heads, its own rotary base, and a learned sink
  logit a head in the softmax's denominator. Keys are wider than values
  (192 / 128); the first ``rotary_dim`` values of a head are rotated at
  the token's position, the rest pass; values are scaled by
  ``value_scale``.
* feed-forward ``dense`` (gated SiLU) or ``experts``: sigmoid router
  scores, a selection bias that ranks and does not weigh, the top-k's
  scores renormalised; one chip may hold a share (``moe_first ..
  moe_first + moe_held`` of the ``moe_experts`` the router ranks, and
  the first ``vocab_size`` rows of the embedding and the head).

    h = E[token]
    h = h + Attention_i(rms_norm(h)) W_o
    h = h + FFN_i(rms_norm(h))
    logits = rms_norm(h) W_head                 (untied, no multipliers)

``mimo_decoder`` writes the stack once; ``build_mimo_lm_program`` is the
exportable full causal forward, ``generation.model.
build_mimo_step_program`` the serving step (pages by attention kind).
Parameter names: ``mimo_tok_emb``, ``mimo{i}_ln1.scale``,
``mimo{i}_attn_{q,k,v,o}.w``, ``mimo{i}_attn_sink`` (window layers),
``mimo{i}_ln2.scale``, ``mimo{i}_ffn_{in,out}.w`` or
``mimo{i}_router.{w,bias}`` and ``mimo{i}_experts_{in,out}.w``,
``mimo_lnf.scale``, ``mimo_head.w``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

from .. import layers
from ..core.framework import Program, program_guard, unique_name
from ..initializer import NormalInitializer
from .gpt import _attr

__all__ = ["MiMoConfig", "mimo_decoder", "build_mimo_lm_program"]


@dataclasses.dataclass(frozen=True)
class MiMoConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    attention_kinds: Tuple[str, ...]    # "full" | "window", a layer each
    ffn_kinds: Tuple[str, ...]          # "dense" | "experts", a layer each
    num_kv_heads: int                   # of a full layer
    window_kv_heads: int                # of a window layer
    k_dim: int
    v_dim: int
    rotary_dim: int
    window: int
    dense_size: int
    moe_experts: int                    # what the router ranks
    moe_top_k: int
    moe_expert_size: int
    max_position: int
    rope_base: float = 1e7
    window_rope_base: float = 1e4
    sink: bool = True                   # window layers carry a sink logit
    value_scale: float = 1.0
    moe_held: Optional[int] = None      # experts held here (None: all)
    moe_first: int = 0
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    param_dtype: str = "float32"

    def __post_init__(self):
        if len(self.attention_kinds) != len(self.ffn_kinds):
            raise ValueError("attention_kinds and ffn_kinds: a layer each")
        bad = (set(self.attention_kinds) - {"full", "window"}
               | set(self.ffn_kinds) - {"dense", "experts"})
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.num_heads % self.num_kv_heads or \
                self.num_heads % self.window_kv_heads:
            raise ValueError("query heads must be a multiple of KV heads")

    @property
    def num_layers(self) -> int:
        return len(self.attention_kinds)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The layers whose attention is ``kind``, in order."""
        return tuple(i for i, k in enumerate(self.attention_kinds)
                     if k == kind)

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.ffn_kinds)
                     if k == "experts")

    def kv_heads_of(self, kind: str) -> int:
        return self.window_kv_heads if kind == "window" else self.num_kv_heads

    @property
    def held_experts(self) -> int:
        return self.moe_experts if self.moe_held is None else self.moe_held

    def state_shapes(self, rows: int):
        """What a step carries beside the pages: the experts' load
        counts, an expert layer a row."""
        del rows
        return {"gen_state_moe_loads": (
            (len(self.expert_layers), self.held_experts), "int32")}


def mimo_decoder(cfg: MiMoConfig, tokens, pos_ids, attention: Callable,
                 num_valid=None, state=None, head_at=None):
    """The stack on ``tokens`` [rows, chunk] at ``pos_ids`` [rows, chunk]
    -> (logits [rows, chunk, V], state_out). ``attention(i, kind, q, k,
    v, sink)`` -> ctx [rows, chunk, H * v_dim] is the caller's (full
    causal, or pages): q and k arrive rotated, v scaled. ``state`` /
    ``num_valid`` / ``head_at`` as ``hybrid_decoder`` takes them."""
    std, dt, eps = cfg.initializer_range, cfg.param_dtype, cfg.rms_eps
    d = cfg.hidden_size
    state_out, loads_out = {}, []
    emb = layers.embedding(tokens, size=[cfg.vocab_size, d], dtype=dt,
                           param_attr=_attr("mimo_tok_emb", std))
    h = layers.cast(emb, "float32")
    for i, (kind, ffn) in enumerate(zip(cfg.attention_kinds, cfg.ffn_kinds)):
        pre = f"mimo{i}_"
        kvh = cfg.kv_heads_of(kind)
        base = cfg.window_rope_base if kind == "window" else cfg.rope_base
        u = layers.rms_norm(h, eps, param_attr=pre + "ln1.scale", dtype=dt)
        q = layers.linear(u, cfg.num_heads * cfg.k_dim, dtype=dt,
                          param_attr=_attr(pre + "attn_q.w", std))
        k = layers.linear(u, kvh * cfg.k_dim, dtype=dt,
                          param_attr=_attr(pre + "attn_k.w", std))
        v = layers.linear(u, kvh * cfg.v_dim, dtype=dt,
                          param_attr=_attr(pre + "attn_v.w", std))
        q = layers.rotary_embedding(q, pos_ids, cfg.num_heads,
                                    cfg.rotary_dim, base)
        k = layers.rotary_embedding(k, pos_ids, kvh, cfg.rotary_dim, base)
        sink = None
        if kind == "window" and cfg.sink:
            sink = layers.create_parameter(
                [cfg.num_heads], dt, name=pre + "attn_sink",
                default_initializer=NormalInitializer(0.0, std))
        ctx = attention(i, kind, q, k,
                        layers.scale(v, scale=cfg.value_scale), sink)
        h = layers.elementwise_add(h, layers.linear(
            ctx, d, dtype=dt, param_attr=_attr(pre + "attn_o.w", std)))
        u = layers.rms_norm(h, eps, param_attr=pre + "ln2.scale", dtype=dt)
        if ffn == "dense":
            y = layers.gated_ffn(u, cfg.dense_size, dtype=dt,
                                 param_attr=_attr(pre + "ffn", std))
        else:
            j = len(loads_out)
            loads = None
            if state is not None:
                loads = layers.reshape(
                    layers.slice(state["gen_state_moe_loads"], axes=[0],
                                 starts=[j], ends=[j + 1]),
                    [cfg.held_experts])
            y, loads = layers.topk_moe(
                u, cfg.moe_experts, cfg.moe_top_k, cfg.moe_expert_size,
                held_experts=cfg.held_experts, first_expert=cfg.moe_first,
                num_valid=num_valid, loads=loads, score_func="sigmoid",
                select_bias=True, param_attr=_attr(pre, std), dtype=dt)
            loads_out.append(loads)
        h = layers.elementwise_add(h, y)
    if loads_out:
        state_out["gen_state_moe_loads"] = layers.stack(loads_out, axis=0)
    if head_at is not None:
        h = layers.reduce_sum(
            layers.elementwise_mul(h, layers.unsqueeze(head_at, [2])),
            dim=[1], keep_dim=True)                          # [rows, 1, d]
    u = layers.rms_norm(h, eps, param_attr="mimo_lnf.scale", dtype=dt)
    logits = layers.linear(u, cfg.vocab_size, dtype=dt,
                           param_attr=_attr("mimo_head.w", std))
    return logits, state_out


def build_mimo_lm_program(cfg: MiMoConfig, seq_len: int):
    """Loss-free causal LM: tokens [B, S] -> logits [B, S, V]; what
    ``save_inference_model`` exports and ``create_predictor`` loads."""
    import numpy as np

    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("tokens", [seq_len], dtype="int64")
        pos_ids = layers.assign(np.arange(seq_len, dtype="int64")[None, :])

        def attention(_i, kind, q, k, v, sink):
            return layers.causal_attention(
                q, k, v, cfg.num_heads, cfg.kv_heads_of(kind),
                window=cfg.window if kind == "window" else None, sink=sink)

        logits, _state = mimo_decoder(cfg, tokens, pos_ids, attention)
    return main, startup, {"tokens": tokens}, {"logits": logits}
