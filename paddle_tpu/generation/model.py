"""Program builders for the generation subsystem.

The GenerationEngine drives TWO Program-IR executables against the
predictor's scope (same parameter names as models/gpt.py, so the
weights a saved LM was trained/exported with serve both lanes):

* ``build_prefill_program(cfg, seq_len, geom)`` — full causal forward
  over a [B, S] prompt window (flash attention when the config asks
  for it), PLUS per-layer ``kv_cache_write`` of the prompt's K/V into
  the page pool, PLUS in-graph last-token selection and greedy argmax.
  One executable per (batch-bucket, seq-bucket) pair.
* ``build_decode_program(cfg, geom)`` — ONE token per sequence: embed,
  per layer (ln -> fused qkv -> kv_cache_write of the new row ->
  ``paged_attention`` over the updated pool -> proj/ffn), head matmul,
  in-graph argmax. The batch dim is the engine's fixed decode-lane
  count, so the whole continuous-batching life of the engine is ONE
  compiled executable driven through the PR-2 BoundStep cache.

``build_lm_program(cfg, seq_len)`` is the loss-free LM used to export
an inference model for the Predictor (build_gpt_lm always wires a CE
loss, which would drag a labels feed into serving).

``build_ragged_step_program(cfg, geom, chunk, kv_dtype)`` is the
tentpole successor to the pair above: ONE [lanes, chunk] executable
whose rows are whatever each sequence needs this step — a prefill
chunk, a decode token, a decode token + speculative drafts, or an
idle lane — through ``kernels/ragged_paged_attention``. The engine's
"ragged" mode (the default) runs its whole life through it; the
prefill/decode pair remains for mode="two_lane" (the identity
oracle).

The page pools are STATE, not feeds: every builder declares them as
persistable variables (``_page_pools``) that the layer's cache-write op
reads and rewrites under one name, the ``ParamOut = Param`` convention
of the optimizer ops. ``analyze_block_state`` therefore lists them as
rewritten state, the executor donates them to the step, XLA writes the
new rows into the same buffers, and ``BoundStep`` stores the arrays the
step returns back into the scope the engine bound it with (the
``PagedKVCache``'s). A program that fed them would make XLA copy every
pool every step: a fed array is never donated.

Feed-name contract (the engine assembles these every step):
  gen_tokens       [B, S] / [B, 1] / [B, chunk] int64
  gen_pos_ids      [B, chunk] int64  ragged only: absolute position
                               ids of each chunk token (row start + j)
  gen_positions    [B] int64   absolute position of each new row
                               (prefill: 0; decode: current length;
                               ragged: the row's chunk start)
  gen_num_valid    [B] int32   real rows in this window (prefill: the
                               true prompt length; decode: 1 active /
                               0 idle lane; ragged: chunk tokens)
  gen_attend_lens  [B] int32   decode only: tokens to attend over
                               (= position + 1)
  gen_last_index   [B] int64   prefill only: index of the true last
                               prompt token (length - 1)
  gen_block_tables [B, max_pages_per_seq] int32
State (scope variables, ``kvcache.pool_names``):
  gen_k_pages_{l} / gen_v_pages_{l}   the per-layer page pools
  gen_k_scales_{l} / gen_v_scales_{l} int8 pools only: fp32 scale
                               planes [kv_heads, pages, page_size]
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .. import layers, nets
from ..core.framework import Program, program_guard, unique_name
from ..models.gpt import GPTConfig, _attr
from ..models.hybrid import HybridConfig, hybrid_decoder
from ..models.mimo import MiMoConfig, mimo_decoder
from ..param_attr import ParamAttr
from .kvcache import key_page_shape, pool_names

__all__ = ["CacheGeometry", "build_lm_program", "build_prefill_program",
           "build_decode_program", "build_ragged_step_program",
           "build_hybrid_step_program", "build_mimo_step_program",
           "GPTConfig", "HybridConfig", "MiMoConfig"]


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """The page-pool shape both programs compile against."""
    num_pages: int
    page_size: int
    max_pages_per_seq: int
    # window layers (a model with two kinds of attention layer): their
    # pools' page count and the width of their ring tables
    window_num_pages: int = 0
    window_pages_per_seq: int = 0

    @property
    def max_tokens_per_seq(self) -> int:
        return self.max_pages_per_seq * self.page_size


def _page_pools(main: Program, num_layers: int, num_kv_heads: int,
                head_dim: int, geom: CacheGeometry, dtype: str = "float32",
                k_dim=None, window: bool = False):
    """Declare the page pools of ``num_layers`` attention layers as
    persistable variables of ``main``; returns ``(k, v, k_scales,
    v_scales)`` lists by layer, the scale lists ``[None] * num_layers``
    unless ``dtype`` is int8. ``k_dim``: keys wider than the values'
    ``head_dim`` (``kvcache.key_page_shape``); ``window``: the window
    layers' pools, with their own names and page count. No startup op:
    the ``PagedKVCache`` makes the arrays."""
    block = main.global_block()
    pages = geom.window_num_pages if window else geom.num_pages
    shape = [num_kv_heads, pages, geom.page_size, head_dim]
    k_shape = shape[:2] + list(key_page_shape(
        geom.page_size, k_dim or head_dim, head_dim))
    names = pool_names(num_layers, dtype == "int8", "w" if window else "")
    pools = [[block.create_var(name=n, shape=shp, dtype=dt, persistable=True,
                               stop_gradient=True) for n in kind]
             for kind, shp, dt in zip(
                 names, (k_shape, shape, shape[:3], shape[:3]),
                 (dtype, dtype, "float32", "float32"))]
    return tuple(p or [None] * num_layers for p in pools)


def _ln(x, name):
    return layers.layer_norm(
        x, begin_norm_axis=2,
        param_attr=ParamAttr(name=f"{name}.scale"),
        bias_attr=ParamAttr(name=f"{name}.bias"))


def _qkv_split(x, cfg: GPTConfig, pre: str):
    qkv = layers.fc(
        x, 3 * cfg.hidden_size, num_flatten_dims=2,
        param_attr=_attr(f"{pre}_qkv.w", cfg.initializer_range),
        bias_attr=ParamAttr(name=f"{pre}_qkv.b"))
    return layers.split(qkv, 3, dim=2)


def _proj_ffn(x, ctx, cfg: GPTConfig, pre: str):
    """Post-attention half of the decoder layer (shared verbatim by
    both lanes so prefill and decode numerics can only diverge in the
    attention read itself)."""
    h, std = cfg.hidden_size, cfg.initializer_range
    proj = layers.fc(
        ctx, h, num_flatten_dims=2,
        param_attr=_attr(f"{pre}_proj.w", std),
        bias_attr=ParamAttr(name=f"{pre}_proj.b"))
    x = layers.elementwise_add(x, proj)
    ln2 = _ln(x, f"{pre}_ln2")
    ffn1 = layers.fc(
        ln2, cfg.ffn_size, num_flatten_dims=2, act="gelu",
        param_attr=_attr(f"{pre}_ffn1.w", std),
        bias_attr=ParamAttr(name=f"{pre}_ffn1.b"))
    ffn2 = layers.fc(
        ffn1, h, num_flatten_dims=2,
        param_attr=_attr(f"{pre}_ffn2.w", std),
        bias_attr=ParamAttr(name=f"{pre}_ffn2.b"))
    return layers.elementwise_add(x, ffn2)


def _head(x, cfg: GPTConfig):
    x = _ln(x, "gpt_lnf")
    return layers.fc(
        x, cfg.vocab_size, num_flatten_dims=2,
        param_attr=_attr("gpt_head.w", cfg.initializer_range),
        bias_attr=ParamAttr(name="gpt_head.b"))


def _embed(tokens, cfg: GPTConfig):
    return layers.embedding(
        tokens, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=_attr("gpt_tok_emb", cfg.initializer_range))


def _pos_embed(ids, cfg: GPTConfig):
    return layers.embedding(
        ids, size=[cfg.max_position, cfg.hidden_size],
        param_attr=_attr("gpt_pos_emb", cfg.initializer_range))


def build_lm_program(cfg: GPTConfig, seq_len: int):
    """Loss-free causal LM: tokens [B, S] -> logits [B, S, V]. The
    exportable inference twin of models/gpt.build_gpt_lm (which always
    appends a CE loss and therefore a labels feed)."""
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("tokens", [seq_len], dtype="int64")
        x = layers.elementwise_add(
            _embed(tokens, cfg),
            _pos_embed(layers.assign(
                np.arange(seq_len, dtype="int64")[None, :]), cfg))
        for i in range(cfg.num_layers):
            pre = f"dec{i}"
            ln1 = _ln(x, f"{pre}_ln1")
            q, k, v = _qkv_split(ln1, cfg, pre)
            if cfg.use_flash_attention:
                from ..kernels import flash_attention_layer

                ctx = flash_attention_layer(q, k, v, cfg.num_heads,
                                            causal=True)
            else:
                ctx = nets.scaled_dot_product_attention(
                    q, k, v, num_heads=cfg.num_heads, causal=True)
            x = _proj_ffn(x, ctx, cfg, pre)
        logits = _head(x, cfg)
    return main, startup, {"tokens": tokens}, {"logits": logits}


def build_prefill_program(cfg: GPTConfig, seq_len: int, geom: CacheGeometry):
    """Prefill lane: forward the prompt window, write its K/V into the
    page pool, emit the first greedy token per row — all one
    executable. Returns (program, [next_token])."""
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("gen_tokens", [seq_len], dtype="int64")
        positions = layers.data("gen_positions", [], dtype="int64")
        num_valid = layers.data("gen_num_valid", [], dtype="int32")
        last_index = layers.data("gen_last_index", [], dtype="int64")
        tables = layers.data("gen_block_tables", [geom.max_pages_per_seq],
                             dtype="int32")
        kps, vps, _, _ = _page_pools(
            main, cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, geom)
        from ..kernels import kv_cache_write_layer

        x = layers.elementwise_add(
            _embed(tokens, cfg),
            _pos_embed(layers.assign(
                np.arange(seq_len, dtype="int64")[None, :]), cfg))
        for i in range(cfg.num_layers):
            pre = f"dec{i}"
            ln1 = _ln(x, f"{pre}_ln1")
            q, k, v = _qkv_split(ln1, cfg, pre)
            kv_cache_write_layer(
                kps[i], vps[i], k, v, tables, positions, num_valid,
                cfg.num_heads)
            if cfg.use_flash_attention:
                from ..kernels import flash_attention_layer

                ctx = flash_attention_layer(q, k, v, cfg.num_heads,
                                            causal=True)
            else:
                ctx = nets.scaled_dot_product_attention(
                    q, k, v, num_heads=cfg.num_heads, causal=True)
            x = _proj_ffn(x, ctx, cfg, pre)
        logits = _head(x, cfg)                      # [B, S, V]
        # in-graph last-token selection: one_hot(last_index) row-dots
        # the logits so the [B, S, V] tensor never leaves the device
        sel = layers.one_hot(layers.unsqueeze(last_index, [1]), seq_len)
        last_logits = layers.reduce_sum(
            layers.elementwise_mul(logits, layers.unsqueeze(sel, [2])),
            dim=[1])                                # [B, V]
        next_tok = layers.argmax(last_logits, axis=-1)   # [B]
    return main, [next_tok]


def build_ragged_step_program(cfg: GPTConfig, geom: CacheGeometry,
                              chunk: int, kv_dtype: str = "float32"):
    """THE ragged executable: one [lanes, chunk] mixed batch serves
    prefill chunks, decode rows and speculative-verify rows side by
    side — the whole GenerationEngine life is this ONE program bound
    to ONE BoundStep.

    Per row r the engine feeds up to ``chunk`` NEW tokens starting at
    absolute position gen_positions[r] (gen_num_valid[r] of them are
    real; 0 = idle lane). Each layer scatters the chunk's K/V into the
    page pool (int8-quantized when ``kv_dtype == "int8"``), then
    ragged_paged_attention attends every chunk token over its
    sequence's full prefix through the block tables. The head runs
    over ALL chunk positions and argmax is fetched for every position
    — the engine reads the last valid column for plain rows and every
    column for speculative verification (greedy target tokens at each
    draft offset).

    Returns (program, [next_tokens(R*C)]).
    """
    quantized = kv_dtype == "int8"
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("gen_tokens", [chunk], dtype="int64")
        pos_ids = layers.data("gen_pos_ids", [chunk], dtype="int64")
        positions = layers.data("gen_positions", [], dtype="int64")
        num_valid = layers.data("gen_num_valid", [], dtype="int32")
        tables = layers.data("gen_block_tables", [geom.max_pages_per_seq],
                             dtype="int32")
        kps, vps, kss, vss = _page_pools(
            main, cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, geom,
            "int8" if quantized else "float32")
        from ..kernels import (kv_cache_write_layer,
                               quantized_kv_cache_write_layer,
                               ragged_paged_attention_layer)

        x = layers.elementwise_add(_embed(tokens, cfg),
                                   _pos_embed(pos_ids, cfg))   # [R, C, H]
        for i in range(cfg.num_layers):
            pre = f"dec{i}"
            ln1 = _ln(x, f"{pre}_ln1")
            q, k, v = _qkv_split(ln1, cfg, pre)
            if quantized:
                quantized_kv_cache_write_layer(
                    kps[i], vps[i], kss[i], vss[i], k, v, tables,
                    positions, num_valid, cfg.num_heads)
            else:
                kv_cache_write_layer(
                    kps[i], vps[i], k, v, tables, positions, num_valid,
                    cfg.num_heads)
            ctx = ragged_paged_attention_layer(
                q, kps[i], vps[i], tables, positions, num_valid,
                cfg.num_heads, k_scales_var=kss[i], v_scales_var=vss[i])
            x = _proj_ffn(x, ctx, cfg, pre)
        logits = _head(x, cfg)                      # [R, C, V]
        next_tok = layers.argmax(
            layers.reshape(logits, [-1, cfg.vocab_size]), axis=-1)  # [R*C]
    return main, [next_tok]


def build_hybrid_step_program(cfg: HybridConfig, geom: CacheGeometry,
                              chunk: int, kv_dtype: str = "float32"):
    """The ragged executable of a hybrid decoder (models/hybrid.py): the
    same [lanes, chunk] window and feed contract as
    ``build_ragged_step_program`` (``gen_pos_ids`` is fed and unused:
    there is no position embedding), with

    * page pools ``gen_k_pages_{j}`` / ``gen_v_pages_{j}`` for the j-th
      ATTENTION layer only, ``[num_kv_heads, pages, page_size,
      head_dim]``, rewritten in place as state like the dense step's:
      grouped queries, the kernel's own 1/sqrt(head_dim) with the
      attention multiplier folded into q;
    * the per-lane recurrent state of ``cfg.state_shapes(lanes)`` fed as
      ``gen_state_*`` and fetched back, rewritten whole: a row advances
      its Mamba layers by its valid tokens, and a row at position 0
      starts from zero state inside the graph;
    * padding rows taking no expert, and the head on each row's last
      valid position only (``next_tokens`` repeats that token over the
      row's columns).

    Returns (program, fetches) with fetch order
    [next_tokens(R*C), state.. (feed order)].
    """
    if kv_dtype == "int8":
        raise ValueError("a hybrid step keeps float pages: int8 scale "
                         "planes are not wired through it")
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("gen_tokens", [chunk], dtype="int64")
        layers.data("gen_pos_ids", [chunk], dtype="int64")
        positions = layers.data("gen_positions", [], dtype="int64")
        num_valid = layers.data("gen_num_valid", [], dtype="int32")
        tables = layers.data("gen_block_tables", [geom.max_pages_per_seq],
                             dtype="int32")
        kps, vps, _, _ = _page_pools(
            main, len(cfg.attention_layers), cfg.num_kv_heads, cfg.head_dim,
            geom, kv_dtype)
        pools = dict(zip(cfg.attention_layers, zip(kps, vps)))
        state = {name: layers.data(name, list(shp), dtype=dt,
                                   append_batch_size=False)
                 for name, (shp, dt) in cfg.state_shapes(-1).items()}
        from ..kernels import (kv_cache_write_layer,
                               ragged_paged_attention_layer)

        def attention(i, q, k, v):
            kp, vp = pools[i]
            kv_cache_write_layer(kp, vp, k, v, tables, positions,
                                 num_valid, cfg.num_kv_heads)
            return ragged_paged_attention_layer(
                q, kp, vp, tables, positions, num_valid, cfg.num_heads)

        # no speculative rows here, so the engine reads one token a row,
        # the one after its last valid position: the head runs on that
        # position alone (a sixteenth of the window's rows) and its
        # token fills the row. An idle row selects nothing and reads 0.
        last = layers.slice(
            layers.one_hot(layers.unsqueeze(num_valid, [1]), chunk + 1),
            axes=[1], starts=[1], ends=[chunk + 1])     # hot at nv - 1
        logits, state_out = hybrid_decoder(cfg, tokens, attention,
                                           num_valid, positions, state,
                                           head_at=last)
        next_tok = layers.reshape(layers.expand(
            layers.argmax(logits, axis=-1), [1, chunk]), [-1])      # [R*C]
    return main, [next_tok] + [state_out[name] for name in state]


def build_mimo_step_program(cfg: MiMoConfig, geom: CacheGeometry,
                            chunk: int, kv_dtype: str = "float32"):
    """The ragged executable of a MiMo-V2 decoder (models/mimo.py): the
    [lanes, chunk] window and feed contract of
    ``build_hybrid_step_program`` (``gen_pos_ids`` feeds the rotary
    embedding), with page pools by KIND of attention layer:

    * ``gen_k_pages_{j}`` / ``gen_v_pages_{j}`` for the j-th FULL layer,
      ``cfg.num_kv_heads`` heads, read through ``gen_block_tables``;
    * ``gen_wk_pages_{j}`` / ``gen_wv_pages_{j}`` for the j-th WINDOW
      layer, ``cfg.window_kv_heads`` heads and ``geom.window_num_pages``
      pages, read through the ring tables ``gen_block_tables_window``
      [lanes, geom.window_pages_per_seq]: the kernel walks only the
      pages that overlap a lane's window, under a name of its own
      (``ragged_paged_attention_window``), with the layer's sink;
    * keys ``cfg.k_dim`` wide in the split page layout, values
      ``cfg.v_dim``; float pages: the products round to bfloat16 unless
      the pages are float32; a decode lane multiplies its one token's
      rows only (``lean_decode``);
    * the experts' load counts ``gen_state_moe_loads`` fed and fetched.

    Returns (program, fetches) with fetch order [next_tokens(R*C),
    gen_state_moe_loads].
    """
    if kv_dtype == "int8":
        raise ValueError("a MiMo step keeps float pages: int8 scale "
                         "planes know no window and no split keys")
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("gen_tokens", [chunk], dtype="int64")
        pos_ids = layers.data("gen_pos_ids", [chunk], dtype="int64")
        positions = layers.data("gen_positions", [], dtype="int64")
        num_valid = layers.data("gen_num_valid", [], dtype="int32")
        tables = {"full": layers.data(
            "gen_block_tables", [geom.max_pages_per_seq], dtype="int32")}
        pools = {}
        for kind in ("full", "window"):
            held = cfg.layers_of(kind)
            if kind == "window" and held:
                tables[kind] = layers.data(
                    "gen_block_tables_window", [geom.window_pages_per_seq],
                    dtype="int32")
            kps, vps, _, _ = _page_pools(
                main, len(held), cfg.kv_heads_of(kind), cfg.v_dim, geom,
                kv_dtype, k_dim=cfg.k_dim, window=kind == "window")
            pools.update(zip(held, zip(kps, vps)))
        state = {name: layers.data(name, list(shp), dtype=dt,
                                   append_batch_size=False)
                 for name, (shp, dt) in cfg.state_shapes(-1).items()}
        from ..kernels import (ragged_paged_attention_layer,
                               split_kv_cache_write_layer)

        def attention(i, kind, q, k, v, sink):
            kp, vp = pools[i]
            windowed = kind == "window"
            split_kv_cache_write_layer(
                kp, vp, k, v, tables[kind], positions, num_valid,
                cfg.kv_heads_of(kind), ring=windowed)
            return ragged_paged_attention_layer(
                q, kp, vp, tables[kind], positions, num_valid,
                cfg.num_heads, window=cfg.window if windowed else None,
                sink_var=sink, stored_products=kv_dtype != "float32",
                kernel_name=("ragged_paged_attention_window" if windowed
                             else None), lean_decode=True)

        # the head on each row's last valid position alone, as the
        # hybrid step's
        last = layers.slice(
            layers.one_hot(layers.unsqueeze(num_valid, [1]), chunk + 1),
            axes=[1], starts=[1], ends=[chunk + 1])     # hot at nv - 1
        logits, state_out = mimo_decoder(cfg, tokens, pos_ids, attention,
                                         num_valid, state, head_at=last)
        next_tok = layers.reshape(layers.expand(
            layers.argmax(logits, axis=-1), [1, chunk]), [-1])      # [R*C]
    return main, [next_tok] + [state_out[name] for name in state]


def build_decode_program(cfg: GPTConfig, geom: CacheGeometry):
    """Decode lane: one new token per sequence through the paged
    cache. Returns (program, [next_token]) as prefill does."""
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = layers.data("gen_tokens", [1], dtype="int64")
        positions = layers.data("gen_positions", [], dtype="int64")
        num_valid = layers.data("gen_num_valid", [], dtype="int32")
        attend = layers.data("gen_attend_lens", [], dtype="int32")
        tables = layers.data("gen_block_tables", [geom.max_pages_per_seq],
                             dtype="int32")
        kps, vps, _, _ = _page_pools(
            main, cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, geom)
        from ..kernels import kv_cache_write_layer, paged_attention_layer

        x = layers.elementwise_add(
            layers.unsqueeze(_embed(tokens, cfg), [1]),
            layers.unsqueeze(_pos_embed(positions, cfg), [1]))  # [B, 1, H]
        for i in range(cfg.num_layers):
            pre = f"dec{i}"
            ln1 = _ln(x, f"{pre}_ln1")
            q, k, v = _qkv_split(ln1, cfg, pre)
            kv_cache_write_layer(
                kps[i], vps[i], k, v, tables, positions, num_valid,
                cfg.num_heads)
            ctx = paged_attention_layer(q, kps[i], vps[i], tables, attend,
                                        cfg.num_heads)
            x = _proj_ffn(x, ctx, cfg, pre)
        logits = _head(x, cfg)                      # [B, 1, V]
        next_tok = layers.argmax(
            layers.reshape(logits, [-1, cfg.vocab_size]), axis=-1)  # [B]
    return main, [next_tok]
