"""Layers of bias-free decoders whose weights stay in their storage
type (ops/decoder.py), of dropless top-k experts (ops/moe.py
``topk_moe``) and of Mamba-2 mixers (ops/ssm.py). Each takes ``dtype``:
the type its parameters are created and kept in (default: the
input's)."""

from __future__ import annotations

from ..initializer import ConstantInitializer, XavierInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .nn import _out

__all__ = ["rms_norm", "linear", "gated_ffn", "topk_moe", "mamba2_mixer",
           "rotary_embedding", "causal_attention"]


def _slot(attr, suffix, initializer=None):
    """A per-parameter copy of a layer's one ParamAttr (a layer that
    owns several parameters), its name extended by ``suffix``; with
    ``initializer`` the parameter is structural (a bias of zeros, a
    scale of ones) and the attr's own initializer is not for it."""
    a = ParamAttr._to_attr(attr)
    a = ParamAttr(**a.__dict__.copy())
    if a.name is not None:
        a.name = f"{a.name}{suffix}"
    if initializer is not None:
        a.initializer = initializer
    return a


def rms_norm(input, epsilon=1e-5, param_attr=None, dtype=None, name=None):
    """``x * rsqrt(mean(x^2) + epsilon) * scale`` over the last axis."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        helper.param_attr, [int(input.shape[-1])], dtype or input.dtype,
        default_initializer=ConstantInitializer(1.0))
    out = _out(helper, input)
    helper.append_op(type="rms_norm", inputs={"X": [input], "Scale": [scale]},
                     outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def linear(input, size=None, param_attr=None, dtype=None, transpose_w=False,
           name=None):
    """A projection without bias, ``x W`` with W ``[in, size]`` or, with
    ``transpose_w`` (a tied output head over an embedding table ``[size,
    in]``), ``x W^T``. The operand is rounded to W's type, the result is
    float32."""
    helper = LayerHelper("linear", param_attr=param_attr, name=name)
    d = int(input.shape[-1])
    w = helper.create_parameter(
        helper.param_attr, [size, d] if transpose_w else [d, size],
        dtype or input.dtype, default_initializer=XavierInitializer())
    out = _out(helper, input, shape=tuple(input.shape[:-1]) + (size,),
               dtype="float32")
    helper.append_op(type="linear_stored", inputs={"X": [input], "W": [w]},
                     outputs={"Out": [out]},
                     attrs={"transpose_w": bool(transpose_w)})
    return out


def gated_ffn(input, size, param_attr=None, dtype=None, name=None):
    """Gated-SiLU feed-forward of width ``size``: ``(silu(a1) * a2)
    W_out`` with ``(a1, a2) = split(x W_in)``; parameters ``<name>_in.w``
    [d, 2 size] and ``<name>_out.w`` [size, d]."""
    helper = LayerHelper("gated_ffn", param_attr=param_attr, name=name)
    d, dt = int(input.shape[-1]), dtype or input.dtype
    w_in = helper.create_parameter(
        _slot(helper.param_attr, "_in.w"), [d, 2 * size], dt,
        default_initializer=XavierInitializer())
    w_out = helper.create_parameter(
        _slot(helper.param_attr, "_out.w"), [size, d], dt,
        default_initializer=XavierInitializer())
    out = _out(helper, input, dtype="float32")
    helper.append_op(type="gated_silu_ffn",
                     inputs={"X": [input], "WIn": [w_in], "WOut": [w_out]},
                     outputs={"Out": [out]})
    return out


def rotary_embedding(input, positions, num_heads, rotary_dim, base=10000.0):
    """Rotary position embedding over the first ``rotary_dim`` values of
    each of ``num_heads`` heads of ``input`` [rows, chunk, heads * D], at
    ``positions`` [rows, chunk]; the rest of a head passes. float32."""
    helper = LayerHelper("rotary_embedding")
    if rotary_dim % 2 or rotary_dim > int(input.shape[-1]) // num_heads:
        raise ValueError(f"rotary_dim {rotary_dim}: even, at most the head")
    out = _out(helper, input, dtype="float32")
    helper.append_op(
        type="rotary_embedding",
        inputs={"X": [input], "Positions": [positions]},
        outputs={"Out": [out]},
        attrs={"num_heads": int(num_heads), "rotary_dim": int(rotary_dim),
               "base": float(base)})
    return out


def causal_attention(q, k, v, num_heads, num_kv_heads, window=None,
                     sink=None):
    """Causal attention of whole sequences, q [B, T, H * D] on k [B, T,
    KVH * D] and v [B, T, KVH * Dv] -> [B, T, H * Dv] (ops/decoder.py):
    grouped queries, values that may be narrower than keys, ``window``
    (keys i - window < j <= i) and ``sink`` [H] (a logit a head in the
    denominator). What a full forward pass uses where the serving step
    uses ragged paged attention."""
    helper = LayerHelper("causal_attention")
    dv = int(v.shape[-1]) // num_kv_heads
    out = _out(helper, q, shape=tuple(q.shape[:-1]) + (num_heads * dv,),
               dtype="float32")
    inputs = {"Q": [q], "K": [k], "V": [v]}
    attrs = {"num_heads": int(num_heads), "num_kv_heads": int(num_kv_heads)}
    if sink is not None:
        inputs["Sink"] = [sink]
    if window is not None:
        attrs["window"] = int(window)
    helper.append_op(type="causal_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def topk_moe(input, num_experts, top_k, expert_size, held_experts=None,
             first_expert=0, num_valid=None, loads=None,
             param_attr=None, dtype=None, name=None, score_func="softmax",
             select_bias=False):
    """Dropless top-k mixture of gated-SiLU experts over ``[rows, chunk,
    d]`` (ops/moe.py ``topk_moe``): the router is ``num_experts`` wide,
    this program holds experts ``first_expert .. first_expert +
    held_experts`` (default: all) and computes their part of the sum.
    ``num_valid`` [rows]: tokens of each row that are real; ``loads``
    [held] int32: running per-expert assignment counts. ``score_func``
    "softmax" (gates: the softmax over the chosen logits) or "sigmoid"
    (gates: the chosen experts' sigmoid scores over their sum);
    ``select_bias``: a per-expert bias ``<name>router.bias`` that ranks
    the experts and weighs nothing. Returns (out, loads_out). Parameters
    ``<name>router.w``, ``<name>experts_in.w`` [held, d, 2 expert_size],
    ``<name>experts_out.w``."""
    helper = LayerHelper("topk_moe", param_attr=param_attr, name=name)
    d, dt = int(input.shape[-1]), dtype or input.dtype
    held = int(held_experts if held_experts is not None else num_experts)
    router = helper.create_parameter(
        _slot(helper.param_attr, "router.w"), [d, num_experts], dt,
        default_initializer=XavierInitializer())
    w_in = helper.create_parameter(
        _slot(helper.param_attr, "experts_in.w"), [held, d, 2 * expert_size],
        dt, default_initializer=XavierInitializer())
    w_out = helper.create_parameter(
        _slot(helper.param_attr, "experts_out.w"), [held, expert_size, d],
        dt, default_initializer=XavierInitializer())
    out = _out(helper, input, dtype="float32")
    loads_out = _out(helper, input, shape=(held,), dtype="int32",
                     stop_gradient=True)
    inputs = {"X": [input], "RouterW": [router], "ExpertWIn": [w_in],
              "ExpertWOut": [w_out]}
    if num_valid is not None:
        inputs["NumValid"] = [num_valid]
    if loads is not None:
        inputs["Loads"] = [loads]
    attrs = {"top_k": int(top_k), "num_experts": int(num_experts),
             "first_expert": int(first_expert)}
    if select_bias:
        inputs["SelectBias"] = [helper.create_parameter(
            _slot(helper.param_attr, "router.bias"), [num_experts], dt,
            default_initializer=ConstantInitializer(0.0))]
    if score_func != "softmax":
        attrs["score_func"] = str(score_func)
    helper.append_op(
        type="topk_moe", inputs=inputs,
        outputs={"Out": [out], "LoadsOut": [loads_out]}, attrs=attrs)
    return out, loads_out


def mamba2_mixer(input, num_heads, head_dim, state_size, num_groups=1,
                 conv_width=4, chunk_size=256, epsilon=1e-5, num_valid=None,
                 positions=None, ssm_state=None, conv_state=None,
                 param_attr=None, dtype=None, name=None):
    """Mamba-2 mixer over ``[rows, chunk, d]`` (ops/ssm.py). Without
    state every row is a whole sequence from zero state; with
    ``ssm_state`` [rows, H, P, N], ``conv_state`` [rows, K-1, HP + 2GN],
    ``num_valid`` and ``positions`` [rows] it advances each row by its
    valid tokens. Returns (out, ssm_state_out, conv_state_out)."""
    helper = LayerHelper("mamba2_mixer", param_attr=param_attr, name=name)
    d, dt = int(input.shape[-1]), dtype or input.dtype
    d_in = num_heads * head_dim
    ch = d_in + 2 * num_groups * state_size

    def param(suffix, shape, structural=None):
        return helper.create_parameter(
            _slot(helper.param_attr, suffix, structural), shape, dt,
            default_initializer=XavierInitializer())

    zeros, ones = ConstantInitializer(0.0), ConstantInitializer(1.0)
    inputs = {
        "X": [input],
        "WIn": [param("in.w", [d, d_in + ch + num_heads])],
        "ConvW": [param("conv.w", [ch, conv_width])],
        "ConvB": [param("conv.b", [ch], zeros)],
        "DtBias": [param("dt_bias", [num_heads], zeros)],
        "ALog": [param("a_log", [num_heads], zeros)],
        "D": [param("d", [num_heads], ones)],
        "NormW": [param("norm.scale", [d_in], ones)],
        "WOut": [param("out.w", [d_in, d])],
    }
    rows = input.shape[0]
    sdt = ssm_state.dtype if ssm_state is not None else "float32"
    out = _out(helper, input, dtype="float32")
    ssm_out = _out(helper, input, dtype=sdt, stop_gradient=True,
                   shape=(rows, num_heads, head_dim, state_size))
    conv_out = _out(helper, input, dtype=sdt, stop_gradient=True,
                    shape=(rows, conv_width - 1, ch))
    for slot, var in (("NumValid", num_valid), ("Positions", positions),
                      ("SsmState", ssm_state), ("ConvState", conv_state)):
        if var is not None:
            inputs[slot] = [var]
    helper.append_op(
        type="mamba2_mixer", inputs=inputs,
        outputs={"Out": [out], "SsmStateOut": [ssm_out],
                 "ConvStateOut": [conv_out]},
        attrs={"num_heads": int(num_heads), "head_dim": int(head_dim),
               "num_groups": int(num_groups), "state_size": int(state_size),
               "chunk_size": int(chunk_size), "epsilon": float(epsilon)})
    return out, ssm_out, conv_out
