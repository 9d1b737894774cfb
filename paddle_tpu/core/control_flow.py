"""Structured-control-flow lowering: while / conditional_block.

Reference: operators/controlflow/while_op.cc and
conditional_block_op.cc run their sub-blocks with a nested Executor on
fresh scopes. XLA requires functional control flow, so the lowering
computes the *carry set* (vars that exist before the op and are written
inside the sub-block) and compiles the sub-block body as a
lax.while_loop / lax.cond over that carry; block-local temporaries stay
internal SSA values.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from .executor import _lower_block, register_control_flow


def _written_names(sub_block, env) -> List[str]:
    seen = []
    for op in sub_block.ops:
        for names in op.outputs.values():
            for n in names:
                if n in env and n not in seen:
                    seen.append(n)
        for v in op.attrs.values():
            if hasattr(v, "ops") and hasattr(v, "vars"):  # nested Block
                for n in _written_names(v, env):
                    if n not in seen:
                        seen.append(n)
    return seen


@register_control_flow("while")
def _lower_while(block, op, env, ctx):
    sub = op.attrs["sub_block"]
    cond_name = op.inputs["Condition"][0]
    carry_names = _written_names(sub, env)
    if cond_name not in carry_names:
        carry_names = [cond_name] + carry_names
    cond_idx = carry_names.index(cond_name)

    def cond_fn(carry):
        c = carry[cond_idx]
        return jnp.reshape(c, ()).astype(bool)

    def body_fn(carry):
        local = dict(env)
        local.update(zip(carry_names, carry))
        _lower_block(sub, local, ctx)
        return tuple(local[n] for n in carry_names)

    init = tuple(env[n] for n in carry_names)
    out = jax.lax.while_loop(cond_fn, body_fn, init)
    env.update(zip(carry_names, out))


@register_control_flow("conditional_block")
def _lower_conditional_block(block, op, env, ctx):
    sub = op.attrs["sub_block"]
    cond_name = op.inputs.get("Cond", op.inputs.get("Input"))[0]
    carry_names = _written_names(sub, env)
    if not carry_names:
        return
    pred = jnp.reshape(env[cond_name], ()).astype(bool)

    def true_fn(carry):
        local = dict(env)
        local.update(zip(carry_names, carry))
        _lower_block(sub, local, ctx)
        return tuple(local[n] for n in carry_names)

    def false_fn(carry):
        return carry

    init = tuple(env[n] for n in carry_names)
    out = jax.lax.cond(pred, true_fn, false_fn, init)
    env.update(zip(carry_names, out))


@register_control_flow("recompute_segment_grad")
def _lower_recompute_segment_grad(block, op, env, ctx):
    """Segment-level gradient with rematerialization.

    Emitted by backward.append_backward_with_recompute (reference
    backward.py:618 checkpoint-aware backward). Re-runs the segment's
    forward lowering under jax.checkpoint and applies the incoming
    cotangents with jax.vjp. jax.checkpoint's optimization barriers
    stop XLA from CSE-ing the recompute with the original forward, so
    the segment's internal activations are actually freed after the
    forward pass and recomputed here.
    """
    sub = op.attrs["sub_block"]
    in_names = op.inputs["Inputs"]
    out_names = op.attrs["seg_outputs"]
    wanted = op.attrs["wanted"]
    out_grad_names = op.inputs["OutGrads"]

    diff = {n: env[n] for n in wanted}
    aux = {n: env[n] for n in in_names if n not in set(wanted)}

    def seg_fn(diff_vals):
        local = dict(aux)
        local.update(diff_vals)
        # as in registry._make_auto_grad: the scope takes the
        # transform's wrapper, kernel names inside stay whole
        with jax.named_scope("recompute_segment"):
            _lower_block(sub, local, ctx)
        return tuple(local[n] for n in out_names)

    primals, vjp_fn = jax.vjp(jax.checkpoint(seg_fn), diff)
    cots = tuple(
        jnp.asarray(env[g], dtype=p.dtype)
        for g, p in zip(out_grad_names, primals)
    )
    (grads,) = vjp_fn(cots)
    for n, gname in zip(wanted, op.outputs["InGrads"]):
        env[gname] = grads[n]
