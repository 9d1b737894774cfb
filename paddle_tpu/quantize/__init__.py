"""paddle_tpu.quantize — post-training weight quantization for the
inference path, end to end: checkpoint load -> one-shot program
rewrite -> quantized serving.

``rewrite_for_inference(program, scope, wdtype=...)`` walks a LOADED
inference Program once and, for every eligible weight (a 2-D
persistable consumed only as the right-hand operand of ``mul`` /
``matmul`` / ``matmul_v2``):

  * quantizes the Scope value ONCE into a device-resident int8/fp8
    buffer plus an fp32 scale plane (``kernels/quant_matmul
    .quantize_weight``) and DROPS the fp32 original from the Scope —
    the HBM win is real, not a shadow copy (tests/test_quantize.py
    holds the originals gone from Scope and Program);
  * repoints every consumer op onto the registered quantized ops
    (``quantized_fc`` / ``quantized_matmul``), which carry the scale
    tracking through the matmul (dequantize-in-registers on TPU, a
    pure-JAX reference on CPU CI);
  * stamps the quantized weight + scale variables with the original's
    ``logical_axes``/``sharding`` tags, so TP partitioning
    (paddle_tpu.partition) resolves them exactly like the fp32 weights
    they replace;
  * records a per-var skip reason for everything it left alone
    (embedding tables, transposed operands, non-2D weights ...) — the
    PR-8 report style: "why is my weight still fp32" is one lookup.

The rewritten program passes strict proglint (the quantized ops are
registered, shape-inference first-class).

Opt-in is the ``quantize_weights`` flag ("off" | "int8" | "int8_block"
| "fp8"), consumed at Predictor construction
(``Config.enable_weight_quantization`` overrides per instance) and by
GenerationEngine (both modes) — quantized weights compose with
``kv_dtype="int8"`` pages for a fully-quantized ragged decode. Every
program sharing one Scope must be rewritten together (the fp32
buffers are gone); the Predictor/engine seams handle that ordering.

``calibrate(program, feeds)`` is the optional ACTIVATION-scale path:
it wires the existing fake-quantize scale observers (ops/quant.py
``moving_average_abs_max_scale``) onto every eligible matmul input,
runs a few calibration batches, and returns the running abs-max scale
per activation — the ingredient an activation-quantized (w8a8) op
variant would consume. Weight-only quantization needs none of it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..kernels.quant_matmul import (DEFAULT_BLOCK, QUANT_MODES,
                                    quantize_weight, quantized_weight_bytes,
                                    scale_shape)

__all__ = ["rewrite_for_inference", "calibrate", "QuantizeReport",
           "QUANT_MODES", "DEFAULT_BLOCK"]

# op types whose right-hand ("Y") operand is a weight the rewrite can
# quantize, with the attr that would make it ineligible
_MATMUL_OPS = {
    "mul": None,
    "matmul": "transpose_Y",
    "matmul_v2": "trans_y",
}
_QUANTIZED_OPS = {"quantized_fc", "quantized_matmul"}


class QuantizeReport:
    """What the rewrite did, per variable: quantized (with the byte
    accounting) or skipped (with the reason). ``summary()`` gives the
    headline: weight bytes before/after and their ratio
    (tests/test_quantize.py holds it under 0.5)."""

    def __init__(self, mode: str, block: int):
        self.mode = mode
        self.block = block
        self.rows: List[Dict[str, Any]] = []
        # machine-readable partition-tag accounting, one row per
        # quantized var that carried tags: what the original declared,
        # what the rewrite put on the .q/.qscale vars, and why anything
        # was dropped. The same rows are stamped onto the program as
        # ``_quant_tag_record`` so the partition-consistency analysis
        # pass (PTL060/PTL064) can check the inheritance invariant on
        # the rewritten program alone.
        self.tag_rows: List[Dict[str, Any]] = []

    def quantized(self, name, shape, dtype, q_bytes):
        self.rows.append({
            "name": name, "action": "quantized", "shape": list(shape),
            "dtype": dtype, "bytes_before": _nbytes(shape, dtype),
            "bytes_after": int(q_bytes), "reason": None,
        })

    def skipped(self, name, shape, dtype, reason):
        self.rows.append({
            "name": name, "action": "skipped",
            "shape": list(shape) if shape else None, "dtype": dtype,
            "bytes_before": _nbytes(shape, dtype) if shape else 0,
            "bytes_after": _nbytes(shape, dtype) if shape else 0,
            "reason": reason,
        })

    @property
    def n_quantized(self) -> int:
        return sum(1 for r in self.rows if r["action"] == "quantized")

    def skip_reasons(self) -> Dict[str, str]:
        return {r["name"]: r["reason"] for r in self.rows
                if r["action"] == "skipped"}

    def summary(self) -> Dict[str, Any]:
        before = sum(r["bytes_before"] for r in self.rows)
        after = sum(r["bytes_after"] for r in self.rows)
        return {
            "mode": self.mode, "block": self.block,
            "vars_quantized": self.n_quantized,
            "vars_skipped": len(self.rows) - self.n_quantized,
            "weight_bytes_before": before,
            "weight_bytes_after": after,
            "weight_bytes_ratio": round(after / before, 4) if before else 1.0,
        }

    def tag_record(self, name, qname, sname, kind, original, inherited,
                   dropped_reason=None):
        row = {
            "name": name, "qname": qname, "sname": sname, "kind": kind,
            "original": list(original),
            "inherited": list(inherited) if inherited is not None else None,
            "dropped_reason": dropped_reason,
        }
        self.tag_rows.append(row)
        return row

    def to_dict(self) -> Dict[str, Any]:
        return {"summary": self.summary(), "vars": list(self.rows),
                "partition_tags": list(self.tag_rows)}


def _nbytes(shape, dtype) -> int:
    n = 1
    for d in shape or ():
        n *= max(int(d), 1)
    try:
        return n * np.dtype(str(dtype)).itemsize
    except TypeError:
        return n


def _weight_uses(program):
    """name -> list of (op, role) across every block, where role is
    "weight" (eligible right-hand matmul operand), "transposed"
    (right-hand operand under a Y-transpose), or the op type for any
    other consumption."""
    uses: Dict[str, List] = {}
    for blk in program.blocks:
        for op in blk.ops:
            if op.type in ("feed", "fetch"):
                continue
            tattr = _MATMUL_OPS.get(op.type, "__not_a_matmul__")
            y = op.inputs.get("Y", []) if tattr != "__not_a_matmul__" else []
            for slot, names in op.inputs.items():
                for n in names:
                    if (tattr != "__not_a_matmul__" and slot == "Y"
                            and len(y) == 1):
                        role = ("transposed"
                                if tattr and op.attrs.get(tattr, False)
                                else "weight")
                    else:
                        role = f"{op.type}:{slot}"
                    uses.setdefault(n, []).append((op, role))
    return uses


def rewrite_for_inference(program, scope, wdtype: str = "int8",
                          block: int = DEFAULT_BLOCK,
                          min_elements: int = 0) -> QuantizeReport:
    """Quantize every eligible matmul/fc weight of ``program`` in place
    (see module docstring). Idempotent: a second call finds no
    remaining eligible consumers and changes nothing. Returns the
    ``QuantizeReport``.

    Scope conversion is shared: the first program rewritten against a
    Scope converts the buffers (and drops the fp32 originals); later
    programs over the same Scope just repoint their ops onto the
    already-quantized vars — which is exactly how the Predictor's
    program and the GenerationEngine's ragged program share one set of
    quantized weights."""
    if wdtype not in QUANT_MODES:
        raise ValueError(
            f"rewrite_for_inference: wdtype must be one of {QUANT_MODES} "
            f"(or gate on the 'off' flag value before calling), "
            f"got {wdtype!r}")
    block = int(block)
    if wdtype == "int8_block" and block % 128:
        import logging

        # the Pallas kernel's contraction tile is the block: a
        # non-128-multiple falls back to the reference dequantize path
        # on TPU for every weight with K > block (numerics identical,
        # the HBM-streaming win lost there). Say so ONCE at rewrite
        # time instead of per-matmul at bind time.
        logging.getLogger("paddle_tpu.quantize").warning(
            "quantize_block=%d is not a multiple of 128: weights whose "
            "contraction dim exceeds it will run the reference "
            "dequantize path on TPU (Mosaic lane constraint) — use a "
            "128-multiple block for the in-register kernel", block)
    report = QuantizeReport(wdtype, block)
    uses = _weight_uses(program)
    gb = program.global_block()
    rewrote = False

    for name, consumers in uses.items():
        var = gb._find_var_recursive(name)
        if var is None or not getattr(var, "persistable", False):
            continue
        shape, dtype = var.shape, var.dtype
        if not any(role == "weight" for _op, role in consumers):
            # not a matmul weight anywhere — but a big 2-D float
            # persistable (an embedding table) is exactly what someone
            # reading the report wants accounted for, so say why it
            # stays fp32. Operands of the ALREADY-quantized ops (a 2-D
            # .qscale plane on a re-rewrite) are this pass's own
            # output, not un-quantized weights — never report those
            if (var.ndim == 2 and dtype in ("float32", "bfloat16")
                    and not all(role.split(":")[0] in _QUANTIZED_OPS
                                for _op, role in consumers)):
                kinds = sorted({role for _op, role in consumers})
                report.skipped(
                    name, shape, dtype,
                    "never consumed as a matmul right-hand operand "
                    f"(ops: {', '.join(kinds)})")
            continue
        bad = [(op, role) for op, role in consumers if role != "weight"]
        if var.ndim != 2:
            report.skipped(name, shape, dtype, f"not 2-D (shape {shape})")
            continue
        if dtype not in ("float32", "bfloat16"):
            report.skipped(name, shape, dtype,
                           f"dtype {dtype} is not a float weight")
            continue
        if bad:
            kinds = sorted({role for _op, role in bad})
            report.skipped(
                name, shape, dtype,
                "also consumed outside an eligible matmul right-hand "
                f"operand: {', '.join(kinds)}")
            continue
        n_el = int(shape[0]) * int(shape[1])
        if n_el < min_elements:
            report.skipped(name, shape, dtype,
                           f"{n_el} elements < min_elements "
                           f"{min_elements}")
            continue
        qname, sname = name + ".q", name + ".qscale"
        val = scope.find_var(name)
        meta = getattr(scope, "_quantize_meta", None)
        if meta is None:
            meta = scope._quantize_meta = {}
        if scope.find_var(qname) is None:
            if val is None:
                report.skipped(name, shape, dtype,
                               "weight missing from scope (run the "
                               "startup program / load the checkpoint "
                               "before rewriting)")
                continue
            q, s = quantize_weight(np.asarray(val), wdtype, block)
            scope.set_var(qname, q)
            scope.set_var(sname, s)
            meta[name] = (wdtype, block)
        else:
            # reuse path: the buffer in the scope must have been
            # produced with THIS mode/block — decoding one format's
            # bytes as another would be silent garbage, not an error
            have = meta.get(name)
            if have is None:
                # scope converted by an older caller: fall back to a
                # structural check (dtype catches int8-vs-fp8, scale
                # shape catches per-channel-vs-blockwise)
                want_dt = "float8_e4m3fn" if wdtype == "fp8" else "int8"
                sval = scope.find_var(sname)
                ok = (str(np.asarray(scope.find_var(qname)).dtype)
                      == want_dt
                      and sval is not None
                      and tuple(np.shape(sval))
                      == scale_shape(shape, wdtype, block))
            else:
                ok = have == (wdtype, block)
            if not ok:
                raise ValueError(
                    f"rewrite_for_inference: scope already holds "
                    f"{qname!r} quantized as "
                    f"{have or 'an incompatible format'}, but "
                    f"wdtype={wdtype!r} block={block} was requested — "
                    "every program sharing one scope must quantize "
                    "with the same mode and block")
        # the HBM win must be real: the fp32 original leaves the scope
        if scope.find_var(name) is not None:
            scope.erase(name)

        qdtype = "float8_e4m3fn" if wdtype == "fp8" else "int8"
        if not gb.has_var(qname):
            qv = gb.create_parameter(qname, list(shape), qdtype,
                                     trainable=False, stop_gradient=True)
            sv = gb.create_parameter(sname,
                                     list(scale_shape(shape, wdtype, block)),
                                     "float32", trainable=False,
                                     stop_gradient=True)
            # TP composes: the quantized weight means the same thing
            # the fp32 one did, so it inherits the partition tags; the
            # scale plane shards with the OUTPUT-channel axis (its
            # last dim tracks N). Every inheritance (and every drop)
            # is recorded machine-readably — PTL060/PTL064 check these
            # records instead of re-guessing what the rewrite meant.
            tag_rec = getattr(program, "_quant_tag_record", None)
            if tag_rec is None:
                tag_rec = program._quant_tag_record = []
            for kind, tags in (("logical_axes",
                                getattr(var, "logical_axes", None)),
                               ("sharding", getattr(var, "sharding", None))):
                if tags is None:
                    continue
                if len(tags) == 2:
                    setattr(qv, kind, tuple(tags))
                    setattr(sv, kind,
                            ((None, tags[1]) if wdtype == "int8_block"
                             else (tags[1],)))
                    tag_rec.append(report.tag_record(
                        name, qname, sname, kind, tags, tuple(tags)))
                else:
                    tag_rec.append(report.tag_record(
                        name, qname, sname, kind, tags, None,
                        dropped_reason=(
                            f"{kind} arity {len(tags)} does not match the "
                            "2-D weight — tags dropped by the quantize "
                            "rewrite")))

        for op, _role in consumers:
            if op.type == "mul":
                op.type = "quantized_fc"
                op.attrs.pop("y_num_col_dims", None)
            else:
                op.type = "quantized_matmul"
                op.attrs.pop("transpose_Y", None)
                op.attrs.pop("trans_y", None)
            op.inputs = {"X": list(op.inputs["X"]),
                         "QWeight": [qname], "Scale": [sname]}
            op.attrs["quant_mode"] = wdtype
            op.attrs["quant_block"] = block
        for blk in program.blocks:
            blk.vars.pop(name, None)
        report.quantized(name, shape, dtype,
                         quantized_weight_bytes(shape, wdtype, block))
        rewrote = True

    if rewrote:
        program._bump()
    return report


def calibrate(program, feeds, scope=None, executor=None,
              moving_rate: float = 0.9,
              max_batches: int = 8) -> Dict[str, float]:
    """Observe activation scales for the (optional) w8a8 path: insert
    one ``moving_average_abs_max_scale`` observer (ops/quant.py — the
    reference fake-quantize family's scale observer, running-mean
    abs-max) per distinct matmul input, drive ``max_batches`` feeds
    from ``feeds`` through an instrumented CLONE of ``program``, and
    return {activation var name: calibrated scale}.

    Works on fp32 AND already-rewritten (quantized-weight) programs —
    the observers attach to the X operand of ``mul``/``matmul``/
    ``matmul_v2``/``quantized_fc``/``quantized_matmul`` alike. The
    observer state rides persistable vars, so the accumulation uses
    the exact functional semantics the QAT ops define; nothing about
    the observed program's own numerics changes (the observer's Out
    passes X through and is never consumed)."""
    import paddle_tpu as fluid

    scope = scope if scope is not None else fluid.global_scope()
    inst = program.clone(for_test=True)
    blk = inst.global_block()
    targets = []
    seen = set()
    for op in blk.ops:
        if op.type not in set(_MATMUL_OPS) | _QUANTIZED_OPS:
            continue
        xs = op.inputs.get("X", [])
        if len(xs) != 1 or xs[0] in seen:
            continue
        seen.add(xs[0])
        targets.append(xs[0])
    if not targets:
        return {}
    state = {}
    for x in targets:
        accum, st = f"{x}.act_accum", f"{x}.act_state"
        out, osc = f"{x}.act_obs_out", f"{x}.act_scale"
        for n in (accum, st):
            blk.create_var(n, shape=[1], dtype="float32", persistable=True)
            scope.set_var(n, np.zeros(1, np.float32))
        blk.create_var(out, shape=None, dtype="float32")
        blk.create_var(osc, shape=[1], dtype="float32")
        blk.append_op(
            type="moving_average_abs_max_scale",
            inputs={"X": [x], "InAccum": [accum], "InState": [st]},
            outputs={"Out": [out], "OutScale": [osc],
                     "OutAccum": [accum], "OutState": [st]},
            attrs={"moving_rate": float(moving_rate)})
        state[x] = (accum, st)
    inst._bump()
    exe = executor or fluid.Executor(fluid.TPUPlace())
    n = 0
    with fluid.scope_guard(scope):
        for feed in feeds:
            if n >= max_batches:
                break
            exe.run(inst, feed=dict(feed),
                    fetch_list=[f"{targets[0]}.act_scale"], scope=scope)
            n += 1
    if n == 0:
        raise ValueError("calibrate: the feeds iterable yielded no batches")
    scales = {}
    for x, (accum, st) in state.items():
        a = float(np.asarray(scope.find_var(accum)).reshape(()))
        s = float(np.asarray(scope.find_var(st)).reshape(()))
        scales[x] = a / s if s else 0.0
        # calibration state is scratch, not model state
        scope.erase(accum)
        scope.erase(st)
    return scales
