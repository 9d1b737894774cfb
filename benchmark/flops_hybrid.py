"""Operations and bytes a hybrid decoder's serving step is due, from the
configuration and the window's counts. Matrix products only (2 FLOPs a
multiply-add), and the recurrence's own multiply-adds; norms, softmax,
SiLU, the conv's taps and the embedding's gather are left out, as is
anything computed on padding.

Names: d hidden_size; per Mamba layer d_in = H * P, G groups, N state;
per attention layer nh query heads on kvh KV heads of hd; E the router's
width, f an expert's width, fs the shared expert's."""

import math

import numpy as np


def _dims(cfg):
    d = cfg["hidden_size"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return dict(d=d, H=H, P=P, G=G, N=N, d_in=H * P, ch=H * P + 2 * G * N,
                K=cfg["mamba_d_conv"], nh=nh, kvh=kvh, hd=d // nh,
                f=cfg["intermediate_size"],
                fs=cfg["shared_intermediate_size"],
                E=cfg["deployment"]["router_experts"],
                held=cfg["num_local_experts"], v=cfg["vocab_size"],
                n_mamba=kinds.count("mamba"),
                n_attn=kinds.count("attention"), layers=len(kinds))


def token_flops(cfg):
    """FLOPs of one token through every layer but the routed experts,
    the head and attention over the cache: the mixers' projections, the
    recurrence (update and read-out of S [H, P, N]: 2 multiply-adds an
    element), the router and the shared expert."""
    m = _dims(cfg)
    mamba = (2 * m["d"] * (m["d_in"] + m["ch"] + m["H"])
             + 2 * m["d_in"] * m["d"] + 4 * m["H"] * m["P"] * m["N"])
    attn = (2 * m["d"] * (m["nh"] + 2 * m["kvh"]) * m["hd"]
            + 2 * m["nh"] * m["hd"] * m["d"])
    ffn = 2 * m["d"] * m["E"] + 6 * m["d"] * m["fs"]
    return m["n_mamba"] * mamba + m["n_attn"] * attn + m["layers"] * ffn


def assignment_flops(cfg):
    """One (token, held expert) pair: d x 2f in, f x d out."""
    return 6 * cfg["hidden_size"] * cfg["intermediate_size"]


def hybrid_forward_flops(cfg, tokens_processed, tokens_emitted, context_sum,
                         held_assignments):
    """What the window's work is due: every token processed through the
    layers, every pair that landed on a held expert through that expert,
    every token emitted through the head over the vocabulary held, and a
    token at cached length c pays 4 * nh * hd * c in each attention
    layer (`context_sum` is the sum of c over the tokens processed)."""
    m = _dims(cfg)
    return (token_flops(cfg) * tokens_processed
            + assignment_flops(cfg) * held_assignments
            + 2 * m["d"] * m["v"] * tokens_emitted
            + 4 * m["n_attn"] * m["nh"] * m["hd"] * context_sum)


def _itemsize(dtype):
    return 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize


def weight_bytes(cfg, spec):
    """Stated bytes of the weights: every one is read once a step (a
    step of 32 lanes routes some token to every held expert)."""
    return _itemsize(cfg["storage_dtype"]) * sum(
        math.prod(shape) for _n, shape, _i in spec)


def step_bytes(cfg, spec, state_bytes, context_per_step):
    """Bytes one step must move: the weights read, the recurrent state
    read and written, and the K and V of the cached tokens its rows
    attend over, in each attention layer."""
    m = _dims(cfg)
    live_kv = (m["n_attn"] * 2 * m["kvh"] * m["hd"]
               * _itemsize(cfg["storage_dtype"]) * context_per_step)
    return weight_bytes(cfg, spec) + 2 * state_bytes + live_kv


def state_step_call(cfg):
    """(FLOPs, bytes) of one call of the `mamba2_state_step` kernel: one
    Mamba layer, every lane, a chunk of `chunk_tokens`. It reads the
    state [lanes, H, P, N] once and writes it once (float32), reads the
    weighted inputs and writes the read-out (both [lanes, T, H*P]) and
    reads B, C [lanes, G, T, N] and the decays; two products of 2*T
    multiply-adds an element of the state."""
    m, eng = _dims(cfg), cfg["engine"]
    lanes, t = eng["lanes"], eng["chunk_tokens"]
    item = _itemsize(cfg["state_dtype"])
    state = lanes * m["H"] * m["P"] * m["N"]
    rows = lanes * t * m["H"] * m["P"]
    small = 2 * lanes * m["G"] * t * m["N"] + lanes * m["H"]
    return 4 * t * state, item * 2 * state + 4 * (2 * rows + small)


def roofline_seconds(flops, bytes_moved, peaks):
    """The least time the chip could take for that work."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])
