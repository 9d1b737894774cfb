"""MiMo-V2 decoder (`mimo_v2`): layers that differ in attention kind
and in feed-forward kind.

* attention ``full``: grouped queries over every earlier key;
  ``window``: over the last ``window`` keys (the token's own included),
  its own count of KV heads, its own rotary base, and a learned sink
  logit a head in the softmax's denominator. Keys are wider than values
  (192 / 128); the first ``rotary_dim`` values of a head are rotated at
  the token's position, the rest pass; values are scaled by
  ``value_scale``.
  ``latent`` (MLA, pangu_ultra_moe / DeepSeek-V3): every head's query
  through a low rank, ``c_q = rms_norm(u W_q_a)`` (``q_rank``), ``q =
  c_q W_q_b`` (a head ``k_dim`` = nope + rope wide, its last
  ``rotary_dim`` values rotated); one cache entry a token for all heads,
  ``[c_kv | k_pe] = u W_kv_a``, ``c_kv`` normed (``kv_rank``), ``k_pe``
  rotated (``rotary_dim``); keys and values a head from the latent,
  ``[k_nope | v]_h = c_kv W_kv_b,h``, scores (q_nope . k_nope + q_pe .
  k_pe) / sqrt(k_dim). Every layer is latent or none is.
* feed-forward ``dense`` (gated SiLU) or ``experts``: sigmoid router
  scores, a selection bias that ranks and does not weigh, the top-k's
  scores renormalised, times ``routed_scale``; a shared expert of
  ``shared_expert_size`` beside them if given; one chip may hold a
  share (``moe_first .. moe_first + moe_held`` of the ``moe_experts``
  the router ranks, and the first ``vocab_size`` rows of the embedding
  and the head).

    h = E[token]
    h = h + post_1(Attention_i(rms_norm(h)) W_o)
    h = h + post_2(FFN_i(rms_norm(h)))
    logits = rms_norm(h) W_head                 (untied, no multipliers)

``post_*`` is an RMS norm with ``sandwich_norm`` (a norm after each
sublayer as well as before) and the identity otherwise.

``mimo_decoder`` writes the stack once; ``build_mimo_lm_program`` is the
exportable full causal forward, ``generation.model.
build_mimo_step_program`` the serving step (pages by attention kind).
Parameter names: ``mimo_tok_emb``, ``mimo{i}_ln1.scale``,
``mimo{i}_attn_{q,k,v,o}.w``, ``mimo{i}_attn_sink`` (window layers) or
``mimo{i}_attn_{q_a,q_b,kv_a,kv_b,o}.w`` and
``mimo{i}_attn_{q_a,kv_a}_norm.scale`` (latent layers),
``mimo{i}_ln1_post.scale``, ``mimo{i}_ln2.scale``,
``mimo{i}_ffn_{in,out}.w`` or ``mimo{i}_router.{w,bias}``,
``mimo{i}_experts_{in,out}.w`` and ``mimo{i}_shared_{in,out}.w``,
``mimo{i}_ln2_post.scale``, ``mimo_lnf.scale``, ``mimo_head.w``.
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
    attention_kinds: Tuple[str, ...]    # "full" | "window" | "latent"
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
    q_rank: int = 0                     # latent layers: the query's rank
    kv_rank: int = 0                    # latent layers: the cached latent
    sandwich_norm: bool = False         # a norm after each sublayer too
    shared_expert_size: int = 0         # width of the shared expert, if any
    routed_scale: float = 1.0           # multiplies the routed experts' sum
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    param_dtype: str = "float32"

    def __post_init__(self):
        if len(self.attention_kinds) != len(self.ffn_kinds):
            raise ValueError("attention_kinds and ffn_kinds: a layer each")
        bad = (set(self.attention_kinds) - {"full", "window", "latent"}
               | set(self.ffn_kinds) - {"dense", "experts"})
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.latent and (set(self.attention_kinds) != {"latent"}
                            or not self.q_rank or not self.kv_rank):
            raise ValueError("latent layers: every layer latent, with "
                             "q_rank and kv_rank")
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
    def latent(self) -> bool:
        return "latent" in self.attention_kinds

    def pool_geometry(self, kind: str):
        """(KV heads, value width, key width) of a page of a layer of
        ``kind``: a latent layer's one entry a token is a key
        ``kv_rank + rotary_dim`` wide whose first ``kv_rank`` values
        are its value."""
        if kind == "latent":
            return 1, self.kv_rank, self.kv_rank + self.rotary_dim
        return self.kv_heads_of(kind), self.v_dim, self.k_dim

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


def _latent_qkv(cfg: MiMoConfig, pre: str, u, pos_ids):
    """A latent layer's attention inputs: q [rows, chunk, H * k_dim]
    (rope part rotated), the cache entry [rows, chunk, kv_rank +
    rotary_dim] (c_kv normed, k_pe rotated) and the up-projection W_kv_b
    [kv_rank, H * (k_dim - rotary_dim + v_dim)]."""
    std, dt, eps = cfg.initializer_range, cfg.param_dtype, cfg.rms_eps
    rot, rank = cfg.rotary_dim, cfg.kv_rank
    c_q = layers.rms_norm(
        layers.linear(u, cfg.q_rank, dtype=dt,
                      param_attr=_attr(pre + "attn_q_a.w", std)),
        eps, param_attr=pre + "attn_q_a_norm.scale", dtype=dt)
    q = layers.rotary_embedding(
        layers.linear(c_q, cfg.num_heads * cfg.k_dim, dtype=dt,
                      param_attr=_attr(pre + "attn_q_b.w", std)),
        pos_ids, cfg.num_heads, rot, cfg.rope_base, offset=cfg.k_dim - rot)
    kv = layers.linear(u, rank + rot, dtype=dt,
                       param_attr=_attr(pre + "attn_kv_a.w", std))
    c_kv = layers.rms_norm(
        layers.slice(kv, axes=[2], starts=[0], ends=[rank]), eps,
        param_attr=pre + "attn_kv_a_norm.scale", dtype=dt)
    k_pe = layers.rotary_embedding(
        layers.slice(kv, axes=[2], starts=[rank], ends=[rank + rot]),
        pos_ids, 1, rot, cfg.rope_base)
    kv_b = layers.create_parameter(
        [rank, cfg.num_heads * (cfg.k_dim - rot + cfg.v_dim)], dt,
        name=pre + "attn_kv_b.w",
        default_initializer=NormalInitializer(0.0, std))
    return q, layers.concat([c_kv, k_pe], axis=2), kv_b


def mimo_decoder(cfg: MiMoConfig, tokens, pos_ids, attention: Callable,
                 num_valid=None, state=None, head_at=None):
    """The stack on ``tokens`` [rows, chunk] at ``pos_ids`` [rows, chunk]
    -> (logits [rows, chunk, V], state_out). ``attention(i, kind, q, k,
    v, sink)`` -> ctx [rows, chunk, H * v_dim] is the caller's (full
    causal, or pages): q and k arrive rotated, v scaled; of a latent
    layer k is the cache entry and v the up-projection W_kv_b
    (``_latent_qkv``). ``state`` / ``num_valid`` / ``head_at`` as
    ``hybrid_decoder`` takes them."""
    std, dt, eps = cfg.initializer_range, cfg.param_dtype, cfg.rms_eps
    d = cfg.hidden_size
    state_out, loads_out = {}, []
    emb = layers.embedding(tokens, size=[cfg.vocab_size, d], dtype=dt,
                           param_attr=_attr("mimo_tok_emb", std))
    h = layers.cast(emb, "float32")

    def post(y, name):
        if not cfg.sandwich_norm:
            return y
        return layers.rms_norm(y, eps, param_attr=name, dtype=dt)

    for i, (kind, ffn) in enumerate(zip(cfg.attention_kinds, cfg.ffn_kinds)):
        pre = f"mimo{i}_"
        kvh = cfg.kv_heads_of(kind)
        base = cfg.window_rope_base if kind == "window" else cfg.rope_base
        u = layers.rms_norm(h, eps, param_attr=pre + "ln1.scale", dtype=dt)
        if kind == "latent":
            ctx = attention(i, kind, *_latent_qkv(cfg, pre, u, pos_ids),
                            None)
        else:
            q = layers.linear(u, cfg.num_heads * cfg.k_dim, dtype=dt,
                              param_attr=_attr(pre + "attn_q.w", std))
            k = layers.linear(u, kvh * cfg.k_dim, dtype=dt,
                              param_attr=_attr(pre + "attn_k.w", std))
            v = layers.linear(u, kvh * cfg.v_dim, dtype=dt,
                              param_attr=_attr(pre + "attn_v.w", std))
            q = layers.rotary_embedding(q, pos_ids, cfg.num_heads,
                                        cfg.rotary_dim, base)
            k = layers.rotary_embedding(k, pos_ids, kvh, cfg.rotary_dim,
                                        base)
            sink = None
            if kind == "window" and cfg.sink:
                sink = layers.create_parameter(
                    [cfg.num_heads], dt, name=pre + "attn_sink",
                    default_initializer=NormalInitializer(0.0, std))
            ctx = attention(i, kind, q, k,
                            layers.scale(v, scale=cfg.value_scale), sink)
        h = layers.elementwise_add(h, post(layers.linear(
            ctx, d, dtype=dt, param_attr=_attr(pre + "attn_o.w", std)),
            pre + "ln1_post.scale"))
        u = layers.rms_norm(h, eps, param_attr=pre + "ln2.scale", dtype=dt)
        if ffn == "dense":
            y = layers.gated_ffn(u, cfg.dense_size, dtype=dt,
                                 num_valid=num_valid,
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
                select_bias=True, param_attr=_attr(pre, std), dtype=dt,
                routed_scale=cfg.routed_scale)
            loads_out.append(loads)
            if cfg.shared_expert_size:
                y = layers.elementwise_add(y, layers.gated_ffn(
                    u, cfg.shared_expert_size, dtype=dt,
                    num_valid=num_valid,
                    param_attr=_attr(pre + "shared", std)))
        h = layers.elementwise_add(h, post(y, pre + "ln2_post.scale"))
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
            if kind == "latent":
                from ..kernels import latent_attention_layer

                return latent_attention_layer(q, k, v, cfg.num_heads,
                                              cfg.v_dim)
            return layers.causal_attention(
                q, k, v, cfg.num_heads, cfg.kv_heads_of(kind),
                window=cfg.window if kind == "window" else None, sink=sink)

        logits, _state = mimo_decoder(cfg, tokens, pos_ids, attention)
    return main, startup, {"tokens": tokens}, {"logits": logits}
