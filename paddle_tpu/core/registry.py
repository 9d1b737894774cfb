"""Op registry: op type -> JAX lowering (+ slot metadata + grad policy).

Reference equivalents: framework/op_registry.h:68,223 (static kernel
registrars), framework/grad_op_desc_maker.h (per-op grad-op makers),
framework/operator.cc:1041 (kernel choice by place/dtype/layout).

TPU-native redesign: an op is one Python lowering function emitting jax
ops.  There is no kernel selection — XLA compiles for whatever backend
the executor targets.  Gradients come in two flavors:

  * explicit: a registered ``<type>_grad`` lowering (used where the
    reference semantics diverge from plain vjp, e.g. ops with auxiliary
    outputs);
  * automatic: the default — the grad op re-traces the forward lowering
    under ``jax.vjp`` and applies the incoming cotangents.  Because the
    whole block is compiled as one XLA program, the re-trace costs
    nothing at runtime (XLA CSEs the shared forward subgraph).

RNG-consuming ops (dropout, uniform_random, ...) draw keys from the
LoweringContext by folding the op's stable identity into the step key,
so an auto-vjp grad op reproduces the same randomness as its forward op
(reference instead materializes a Mask output: dropout_op.cc).
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

# stable per-op identity counter (used for RNG key folding)
_op_ident_counter = itertools.count(1)


def next_op_ident() -> int:
    return next(_op_ident_counter)


class LoweringContext:
    """Carried through a block lowering.

    step_key: jax PRNG key for this executor run (traced value).
    mesh/axis info is attached by the distributed executor for
    collective ops (reference ring_id -> mesh axis name).
    """

    def __init__(self, step_key=None, mesh=None, axis_env=None, scope=None,
                 manual_axes=()):
        self.step_key = step_key
        self.mesh = mesh
        self.axis_env = axis_env or {}
        self.scope = scope
        # mesh axes already inside a manual shard_map region (the
        # pipeline schedule sets ("pp",)) — kernels/mesh_wrap.py uses
        # this to decide whether a Pallas call may wrap itself in a
        # shard_map (real TPU: Mosaic cannot be GSPMD-auto-partitioned)
        self.manual_axes = tuple(manual_axes or ())

    def op_key(self, op) -> jax.Array:
        """Deterministic per-op PRNG key: fold the op's stable ident into
        the step key. Grad ops copy the forward op's ident so they see
        identical randomness."""
        ident = int(op.attrs.get("op_ident", 0)) or 0
        if self.step_key is None:
            # eager/startup path: derive from the op's seed attr
            seed = int(op.attrs.get("seed", 0) or 0)
            return jax.random.PRNGKey(seed ^ (ident * 2654435761 % (2**31)))
        return jax.random.fold_in(self.step_key, ident)


class OpDef:
    """Metadata + lowering for one op type.

    input_slots/output_slots: ordered slot names; needed by
    append_backward to build grad ops and by auto-vjp to split a grad
    op's inputs into forward-inputs vs output-grads.
    no_grad_slots: input slots that never receive gradients (integer
    labels, shapes, ...), mirroring reference no_need_buffer/stop-grad
    declarations.
    """

    def __init__(
        self,
        type: str,
        lower: Callable,
        input_slots: Sequence[str] = ("X",),
        output_slots: Sequence[str] = ("Out",),
        no_grad_slots: Sequence[str] = (),
        stop_gradient: bool = False,
    ):
        self.type = type
        self.lower = lower
        self.input_slots = tuple(input_slots)
        self.output_slots = tuple(output_slots)
        self.no_grad_slots = tuple(no_grad_slots)
        self.stop_gradient = stop_gradient


_OP_REGISTRY: Dict[str, OpDef] = {}


def register_op(
    type: str,
    inputs: Sequence[str] = ("X",),
    outputs: Sequence[str] = ("Out",),
    no_grad: Sequence[str] = (),
    stop_gradient: bool = False,
):
    """Decorator. The lowering signature is ``fn(ctx, op, ins)`` where
    ``ins`` maps slot -> list of jax values (parallel to op.inputs), and
    returns slot -> list of jax values for op.outputs."""

    def deco(fn):
        _OP_REGISTRY[type] = OpDef(
            type,
            fn,
            input_slots=inputs,
            output_slots=outputs,
            no_grad_slots=no_grad,
            stop_gradient=stop_gradient,
        )
        return fn

    return deco


def get_op_def(type: str) -> OpDef:
    if type in _OP_REGISTRY:
        return _OP_REGISTRY[type]
    if type.endswith("_grad"):
        fwd = _OP_REGISTRY.get(type[: -len("_grad")])
        if fwd is not None:
            gd = _make_auto_grad(fwd)
            _OP_REGISTRY[type] = gd
            return gd
    near = suggest_ops(type)
    hint = f" (did you mean {' / '.join(repr(n) for n in near)}?)" if near else ""
    raise NotImplementedError(
        f"op type {type!r} has no registered lowering{hint}")


def has_op(type: str) -> bool:
    if type in _OP_REGISTRY:
        return True
    return type.endswith("_grad") and type[: -len("_grad")] in _OP_REGISTRY


def registered_ops() -> List[str]:
    return sorted(_OP_REGISTRY)


def abstract_arg_specs(vars_by_slot) -> Optional[Dict[str, List[Any]]]:
    """{slot: [Variable]} -> {slot: [jax.ShapeDtypeStruct]} for
    abstract (eval_shape) re-inference of an op's lowering, with
    -1/None dims mapped to 1. Returns None when any input is missing a
    Variable, a shape, or a resolvable dtype — nothing to infer
    against. Shared by the eager layer path
    (layer_helper.infer_op_shapes) and the static shape-dtype analysis
    pass (analysis/passes.py)."""
    specs: Dict[str, List[Any]] = {}
    for slot, vs in vars_by_slot.items():
        lst = []
        for v in vs:
            if v is None or getattr(v, "shape", None) is None:
                return None
            try:
                dt = jnp.dtype(str(v.dtype or "float32"))
            except TypeError:
                return None
            shape = tuple(1 if (d is None or int(d) < 0) else int(d)
                          for d in v.shape)
            lst.append(jax.ShapeDtypeStruct(shape, dt))
        specs[slot] = lst
    return specs


def suggest_ops(name: str, n: int = 3) -> List[str]:
    """Nearest registered op types for an unknown `name` (typo help in
    NotImplementedError messages and the PTL030 lint diagnostic)."""
    import difflib

    base = name[: -len("_grad")] if name.endswith("_grad") else name
    hits = difflib.get_close_matches(base, registered_ops(), n=n, cutoff=0.6)
    if base is not name:
        hits = [h + "_grad" for h in hits]
    return hits


# --------------------------------------------------------------------------
# automatic gradient lowering via jax.vjp
# --------------------------------------------------------------------------


class _PseudoOp:
    """Stand-in forward op handed to the forward lowering during vjp
    re-trace: carries the grad op's (copied) attrs."""

    __slots__ = ("type", "attrs", "inputs", "outputs")

    def __init__(self, type, attrs, inputs, outputs):
        self.type = type
        self.attrs = attrs
        self.inputs = inputs
        self.outputs = outputs


def _make_auto_grad(fwd: OpDef) -> OpDef:
    grad_type = fwd.type + "_grad"

    def lower(ctx: LoweringContext, op, ins: Dict[str, List[Any]]):
        # Which input slots need grads = grad op's declared outputs.
        want = [
            s[: -len("@GRAD")]
            for s in op.outputs
            if s.endswith("@GRAD") and op.outputs[s]
        ]
        diff_ins = {}
        aux_ins = {}
        for slot in fwd.input_slots:
            vals = ins.get(slot, [])
            if slot in want and slot not in fwd.no_grad_slots:
                diff_ins[slot] = vals
            else:
                aux_ins[slot] = vals
        fwd_attrs = {k: v for k, v in op.attrs.items() if k not in ("fwd_type",)}
        pseudo = _PseudoOp(
            fwd.type,
            fwd_attrs,
            {s: op.inputs.get(s, []) for s in fwd.input_slots},
            {s: op.inputs.get(s, []) for s in fwd.output_slots},
        )

        def fwd_fn(d_ins):
            all_ins = {**aux_ins, **d_ins}
            # the scope takes the jvp(...) / transpose(jvp(...)) wrapper
            # that jax puts around the innermost scope under a transform,
            # so a Pallas kernel's own name (pallas_call(name=...), one
            # scope further in) reaches the device trace unwrapped
            with jax.named_scope(fwd.type):
                outs = fwd.lower(ctx, pseudo, all_ins)
            # keep only real (listed) outputs, as a dict of lists
            return {s: list(outs.get(s, [])) for s in fwd.output_slots}

        primals, vjp_fn = jax.vjp(fwd_fn, diff_ins)

        cotangents = {}
        for s in fwd.output_slots:
            prim_list = primals.get(s, [])
            gs = ins.get(s + "@GRAD", [])
            cots = []
            for i, p in enumerate(prim_list):
                if i < len(gs) and gs[i] is not None:
                    cots.append(jnp.asarray(gs[i], dtype=p.dtype) if hasattr(p, "dtype") else gs[i])
                else:
                    cots.append(jnp.zeros_like(p))
            cotangents[s] = cots
        (grads,) = vjp_fn(cotangents)

        out = {}
        for slot in want:
            if slot in grads:
                out[slot + "@GRAD"] = list(grads[slot])
            else:
                # non-differentiable input (e.g. int labels): zeros
                out[slot + "@GRAD"] = [jnp.zeros_like(v) for v in ins.get(slot, [])]
        return out

    return OpDef(
        grad_type,
        lower,
        input_slots=tuple(fwd.input_slots)
        + tuple(s + "@GRAD" for s in fwd.output_slots),
        output_slots=tuple(s + "@GRAD" for s in fwd.input_slots),
    )


# every op type the executor has actually lowered in this process —
# the mechanical backing for the "no lowering ships unexercised" test
# sweep (tests/test_op_sweep.py; reference op_test.py discipline)
_EXERCISED: set = set()


def exercised_ops():
    return sorted(_EXERCISED)
