"""Mixture-of-Experts layers. Two, for two jobs:

``switch_moe`` (training): Switch-Transformer routing, top-1 with a
capacity and dropped tokens, the load-balancing auxiliary loss, expert
parallelism over an ``ep`` mesh axis. The rest of this docstring and
the first half of the file.

``topk_moe`` (serving): top-k routing over ALL experts with no capacity
and no dropped token, computing only the experts this chip holds
(``first_expert .. first_expert + held``: a chip's share of an
expert-parallel deployment) for the tokens routed to them, and taking no
padding row of a ragged window. The last part of the file.

switch_moe. Beyond the reference (SURVEY §2f last row names EP as a
north-star axis; the reference snapshot has no MoE). Design follows the
Switch Transformer recipe: top-1 routing, capacity-bounded dispatch, and
the load-balancing auxiliary loss aux = E * sum_e(frac_e * mean_prob_e).

Two lowerings behind ONE op type, selected by the compile mesh (the
same routing contract as the fused attention op's `sp` axis):
  - dense: every expert computed on-device; einsum over the expert dim
    (XLA batches the [E, C, D] x [E, D, F] as one MXU-friendly matmul).
  - expert-parallel (`ep` mesh axis, CompiledProgram.
    with_expert_parallel): shard_map shards the expert WEIGHTS and the
    expert compute over `ep`; each device routes its (optionally
    dp-sharded) tokens, computes only its local experts, and a psum
    over `ep` combines contributions. Router stats psum over `dp` so
    the aux loss matches the unsharded value exactly.

Tokens over capacity C = ceil(T/E * capacity_factor) are dropped
(pass through with zero expert output), the Switch convention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..kernels import moe_ffn


def _moe_math(x2, wg, w1, b1, w2, b2, cap, act, e_first, e_local,
              dp_axis=None, ep_axis=None):
    """Core switch-MoE on [T, D] tokens against experts
    [e_first : e_first + e_local) of the global E.

    Returns (out [T, D] — LOCAL experts' contribution only, aux []).
    """
    T, D = x2.shape
    E = wg.shape[1]
    logits = x2 @ wg                               # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)            # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, E, dtype=x2.dtype)   # [T, E]
    # rank of each token within its expert's queue (0-based)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)
    pos = jnp.sum(pos * onehot, axis=1).astype(jnp.int32)  # [T]
    keep = pos < cap

    eloc = expert.astype(jnp.int32) - e_first
    mine = keep & (eloc >= 0) & (eloc < e_local)
    ec = jnp.clip(eloc, 0, e_local - 1)
    pc = jnp.clip(pos, 0, cap - 1)
    disp = jnp.zeros((e_local, cap, D), x2.dtype)
    disp = disp.at[ec, pc].add(x2 * mine[:, None].astype(x2.dtype))
    h = jnp.einsum("ecd,edf->ecf", disp, w1) + b1[:, None, :]
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    y = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]
    out = y[ec, pc] * (gate * mine.astype(gate.dtype))[:, None]

    # load-balance stats — global over the dp token shards
    count_e = jnp.sum(onehot, axis=0)              # [E]
    prob_e = jnp.sum(probs, axis=0)                # [E]
    t_total = jnp.asarray(T, x2.dtype)
    if dp_axis is not None:
        count_e = jax.lax.psum(count_e, dp_axis)
        prob_e = jax.lax.psum(prob_e, dp_axis)
        t_total = jax.lax.psum(t_total, dp_axis)
    aux = E * jnp.sum((count_e / t_total) * (prob_e / t_total))
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    return out, aux


def _moe_math_a2a(x2, wg, w1l, b1l, w2l, b2l, cap, act, ep, e_local,
                  token_axes):
    """All-to-all dispatch (the DeepSpeed/GShard EP form): tokens are
    sharded over `ep` too; each rank routes its T_local tokens into
    per-destination buffers [ep, E_local, cap, D], ONE all_to_all
    delivers every rank exactly the tokens its local experts own, and a
    second all_to_all returns the outputs — comm volume is the routed
    tokens (2x), not the full activation psum.

    Capacity is per (source rank, expert): cap = ceil(T_local/E * f).
    """
    T, D = x2.shape
    E = wg.shape[1]
    logits = x2 @ wg
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, E, dtype=x2.dtype)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)
    pos = jnp.sum(pos * onehot, axis=1).astype(jnp.int32)
    keep = pos < cap

    dest = (expert.astype(jnp.int32) // e_local)
    eloc = (expert.astype(jnp.int32) % e_local)
    dc = jnp.clip(dest, 0, ep - 1)
    ec = jnp.clip(eloc, 0, e_local - 1)
    pc = jnp.clip(pos, 0, cap - 1)
    disp = jnp.zeros((ep, e_local, cap, D), x2.dtype)
    disp = disp.at[dc, ec, pc].add(x2 * keep[:, None].astype(x2.dtype))
    # send slice [d] to rank d; receive [s] = slice from source s
    recv = jax.lax.all_to_all(disp, "ep", split_axis=0, concat_axis=0,
                              tiled=True)
    h = jnp.einsum("secd,edf->secf", recv, w1l) + b1l[None, :, None, :]
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    y = jnp.einsum("secf,efd->secd", h, w2l) + b2l[None, :, None, :]
    back = jax.lax.all_to_all(y, "ep", split_axis=0, concat_axis=0,
                              tiled=True)
    # back[d, e, c] = output rank d computed for MY slot (d, e, c)
    out = back[dc, ec, pc] * (gate * keep.astype(gate.dtype))[:, None]

    count_e, prob_e, t_total = jax.lax.psum(
        (jnp.sum(onehot, axis=0), jnp.sum(probs, axis=0),
         jnp.asarray(T, x2.dtype)),
        tuple(token_axes))
    aux = E * jnp.sum((count_e / t_total) * (prob_e / t_total))
    return out, aux


def _ep_mesh(ctx):
    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        return None
    try:
        if dict(mesh.shape).get("ep", 1) > 1:
            return mesh
    except (TypeError, AttributeError):
        return None
    return None


@register_op(
    "switch_moe",
    inputs=("X", "GateW", "ExpertW1", "ExpertB1", "ExpertW2", "ExpertB2"),
    outputs=("Out", "AuxLoss"),
)
def _switch_moe(ctx, op, ins):
    x = ins["X"][0]
    wg = ins["GateW"][0]
    w1, b1 = ins["ExpertW1"][0], ins["ExpertB1"][0]
    w2, b2 = ins["ExpertW2"][0], ins["ExpertB2"][0]
    cap_factor = float(op.attrs.get("capacity_factor", 1.25))
    act = op.attrs.get("act", "gelu")
    E = int(w1.shape[0])
    D = x.shape[-1]

    mesh = _ep_mesh(ctx)
    if mesh is None:
        x2 = x.reshape(-1, D)
        T = x2.shape[0]
        cap = max(int(-(-T * cap_factor // E)), 1)
        out, aux = _moe_math(x2, wg, w1, b1, w2, b2, cap, act, 0, E)
        return {"Out": [out.reshape(x.shape)], "AuxLoss": [aux.reshape(1)]}

    from jax.sharding import PartitionSpec as P

    axes = dict(mesh.shape)
    ep = axes["ep"]
    dp = axes.get("dp", 1)
    if E % ep:
        raise ValueError(f"switch_moe: the ep mesh axis ({ep}) must "
                         f"divide num_experts ({E})")
    e_local = E // ep
    dispatch = (ctx.axis_env or {}).get("ep_dispatch", "psum")
    espec = P("ep", None, None)
    bspec = P("ep", None)

    if dispatch == "alltoall":
        # tokens sharded over ep (and dp): batch dim splits over both
        n_shards = dp * ep
        if int(x.shape[0]) % n_shards:
            raise ValueError(
                f"switch_moe alltoall dispatch: batch size {x.shape[0]} "
                f"must be divisible by dp*ep = {n_shards} (tokens shard "
                "over both axes); use dispatch='psum' otherwise")
        tok_axes = ("dp", "ep") if dp > 1 else ("ep",)
        xspec = P(*((tok_axes,) + (None,) * (len(x.shape) - 1)))

        def local_fn(xl, wgl, w1l, b1l, w2l, b2l):
            x2 = xl.reshape(-1, D)
            cap = max(int(-(-x2.shape[0] * cap_factor // E)), 1)
            out, aux = _moe_math_a2a(x2, wgl, w1l, b1l, w2l, b2l, cap,
                                     act, ep, e_local, tok_axes)
            return out.reshape(xl.shape), aux.reshape(1)
    else:
        # tokens replicated over ep; each rank computes its local
        # experts for ALL tokens and a psum combines contributions
        dp_axis = "dp" if dp > 1 else None
        xspec = P(*((("dp",) if dp > 1 else (None,))
                    + (None,) * (len(x.shape) - 1)))

        def local_fn(xl, wgl, w1l, b1l, w2l, b2l):
            x2 = xl.reshape(-1, D)
            cap = max(int(-(-x2.shape[0] * cap_factor // E)), 1)
            e_first = jax.lax.axis_index("ep") * e_local
            out, aux = _moe_math(x2, wgl, w1l, b1l, w2l, b2l, cap, act,
                                 e_first, e_local, dp_axis=dp_axis,
                                 ep_axis="ep")
            return out.reshape(xl.shape), aux.reshape(1)

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(None, None), espec, bspec, espec, bspec),
        out_specs=(xspec, P()),
    )(x, wg, w1, b1, w2, b2)
    return {"Out": [out], "AuxLoss": [aux]}


# -- topk_moe: dropless top-k over a chip's share of the experts ------------

_HI = jax.lax.Precision.HIGHEST


def topk_moe(x, valid, router_w, w_in, w_out, loads=None, *, top_k: int,
             num_experts: int, first_expert: int, block_rows: int = 128,
             score_func: str = "softmax", select_bias=None):
    """x [T, d]; valid [T] bool (padding rows of a ragged window take no
    expert); router_w [d, num_experts]; w_in [held, d, 2f] and w_out
    [held, f, d]: experts first_expert .. first_expert + held, each a
    gated-SiLU feed-forward. Returns (out [T, d] float32: the held
    experts' part of ``sum_e gate_e o_e``; loads [held] int32: the
    assignments each held expert took, added to ``loads`` if given).

    Every token routes over all ``num_experts`` (the router runs in
    float32 at HIGHEST: a rounded logit would flip the tenth expert);
    gates are the softmax over its top_k logits, or with ``score_func``
    "sigmoid" the chosen experts' sigmoid scores over their sum.
    ``select_bias`` [num_experts] is added to the scores for the ranking
    alone: it chooses and does not weigh. The (token, expert)
    pairs that landed on held experts are sorted by expert and go
    through the grouped gated feed-forward: ``kernels/moe_ffn.py`` where
    it runs (``moe_ffn.fits``: a TPU or the interpreter, shapes its tiles
    take), one call over the step's live pairs that reads each visited
    expert's weights once; otherwise two ``jax.lax.ragged_dot`` products
    in blocks of ``block_rows`` sorted rows, as many blocks as there are
    live pairs, rows picked and results put back by one-hot products
    (exact: one term each).
    """
    return _topk_moe(
        x, valid, router_w, w_in, w_out, loads, select_bias, top_k=top_k,
        num_experts=num_experts, first_expert=first_expert,
        block_rows=block_rows, score_func=score_func,
        kernel=moe_ffn.fits(x.shape[0], x.shape[1], w_out.shape[1],
                            w_in.dtype))


# jitted so that a program's expert layers share one trace and lowering;
# ``kernel`` (the routing, decided by the caller from the environment and
# the shapes) is part of the key
@functools.partial(jax.jit, static_argnames=(
    "top_k", "num_experts", "first_expert", "block_rows", "score_func",
    "kernel"))
def _topk_moe(x, valid, router_w, w_in, w_out, loads, select_bias, *, top_k,
              num_experts, first_expert, block_rows, score_func, kernel):
    T, d = x.shape
    held = w_in.shape[0]
    if router_w.shape[1] != num_experts or first_expert + held > num_experts:
        raise ValueError(
            f"topk_moe: router {router_w.shape}, experts {first_expert}.."
            f"{first_expert + held} of {num_experts}")
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=_HI)
    if score_func not in ("softmax", "sigmoid"):
        raise ValueError(f"topk_moe: score_func {score_func!r}")
    scores = jax.nn.sigmoid(logits) if score_func == "sigmoid" else logits
    if select_bias is None:
        vals, idx = jax.lax.top_k(scores, top_k)            # [T, k]
    else:
        _, idx = jax.lax.top_k(
            scores + select_bias.astype(jnp.float32), top_k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    gates = (vals / jnp.sum(vals, axis=-1, keepdims=True)
             if score_func == "sigmoid" else jax.nn.softmax(vals, axis=-1))
    local = idx.astype(jnp.int32) - first_expert
    mine = valid[:, None] & (local >= 0) & (local < held)
    key = jnp.where(mine, local, held).reshape(-1)          # dead pairs last
    pairs = T * top_k
    order = jnp.argsort(key, stable=True)
    tok_sorted = (order // top_k).astype(jnp.int32)
    gate_sorted = jnp.where(mine, gates, 0.0).reshape(-1)[order]
    counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)               # [held]
    loads = counts if loads is None else loads + counts
    if kernel:
        return moe_ffn.grouped_ffn(x, w_in, w_out, tok_sorted, gate_sorted,
                                   counts), loads
    pad = (0, -pairs % block_rows)
    e_sorted = jnp.pad(key[order], pad, constant_values=held)
    tok_sorted = jnp.pad(tok_sorted, pad)
    gate_sorted = jnp.pad(gate_sorted, pad)
    live = jnp.sum(counts)
    xs = x.astype(w_in.dtype)
    tokens = jnp.arange(T, dtype=jnp.int32)
    experts = jnp.arange(held, dtype=jnp.int32)

    def block(i, out):
        at = i * block_rows
        e = jax.lax.dynamic_slice(e_sorted, (at,), (block_rows,))
        tok = jax.lax.dynamic_slice(tok_sorted, (at,), (block_rows,))
        g = jax.lax.dynamic_slice(gate_sorted, (at,), (block_rows,))
        sizes = jnp.sum(e[:, None] == experts, axis=0, dtype=jnp.int32)
        pick = tok[:, None] == tokens[None, :]              # [rows, T]
        xb = jnp.dot(pick.astype(xs.dtype), xs,
                     preferred_element_type=jnp.float32).astype(xs.dtype)
        a = jax.lax.ragged_dot(xb, w_in, sizes,
                               preferred_element_type=jnp.float32)
        a1, a2 = jnp.split(a, 2, axis=-1)
        h = (jax.nn.silu(a1) * a2).astype(w_out.dtype)
        o = jax.lax.ragged_dot(h, w_out, sizes,
                               preferred_element_type=jnp.float32)
        # rows past the block's pairs belong to no group: ragged_dot
        # leaves them undefined
        o = jnp.where((e < held)[:, None], o, 0.0)
        put = jnp.where(pick, g[:, None], 0.0)              # [rows, T]
        return out + jnp.dot(put.T, o, precision=_HI)

    out = jax.lax.fori_loop(0, -(-live // block_rows), block,
                            jnp.zeros((T, d), jnp.float32))
    return out, loads


@register_op("topk_moe",
             inputs=("X", "NumValid", "RouterW", "ExpertWIn", "ExpertWOut",
                     "Loads", "SelectBias"),
             outputs=("Out", "LoadsOut"),
             no_grad=("NumValid", "Loads", "SelectBias"), stop_gradient=True)
def _topk_moe_op(ctx, op, ins):
    x = ins["X"][0]                                         # [R, C, d]
    R, C, d = x.shape
    nv = ins.get("NumValid")
    valid = (jnp.ones((R, C), bool) if not nv else
             jnp.arange(C, dtype=jnp.int32)[None, :]
             < nv[0].astype(jnp.int32)[:, None])
    loads = ins.get("Loads")
    bias = ins.get("SelectBias")
    out, loads = topk_moe(
        x.reshape(R * C, d), valid.reshape(-1), ins["RouterW"][0],
        ins["ExpertWIn"][0], ins["ExpertWOut"][0],
        loads[0] if loads else None, top_k=int(op.attrs["top_k"]),
        num_experts=int(op.attrs["num_experts"]),
        first_expert=int(op.attrs["first_expert"]),
        score_func=str(op.attrs.get("score_func", "softmax")),
        select_bias=bias[0] if bias else None)
    return {"Out": [out.reshape(R, C, d)], "LoadsOut": [loads]}
