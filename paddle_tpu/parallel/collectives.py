"""Gradient-collective planner: bucketed, backward-overlapped,
optionally int8-quantized data-parallel gradient all-reduce.

PR 8's partitioner made DP training real, but its gradient reduction
is whatever GSPMD infers: one logical all-reduce per gradient,
materialized where the (end-of-step) optimizer consumes it — the
classic comm-bound cliff where every byte of gradient serializes after
the last backward op. The reference framework's answer was a
fused-all-reduce graph pass + NCCL streams
(fuse_all_reduce_op_pass.cc); the TPU-native answer here is a PROGRAM
rewrite feeding one shard_map:

  1. ``ensure_planned`` partitions the param gradients into size-capped
     buckets in backward-production order (the reverse of parameter
     order — deepest layer's grads complete first) and inserts one
     ``collective_bucket_reduce`` op right after each bucket's last
     producer, rewriting every downstream consumer (clip,
     regularization, optimizer) onto the reduced values;
  2. at compile time ``build_collective_fn`` splits the step at the
     last bucket op: everything up to it — forward, backward, the
     bucket reduces — lowers INSIDE a shard_map whose manual axis is
     the mesh's ``dp`` axis (other axes stay GSPMD-auto), so each
     bucket's all-reduce is an EXPLICIT collective that becomes
     data-ready mid-backward and can overlap the remaining backward
     compute under XLA's latency-hiding scheduler; the optimizer tail
     runs after the shard_map at the GSPMD level, so ZeRO-sharded
     state composes unchanged.

Semantics contract (the classic DP/allreduce contract, i.e. the
reference GradAllReduce + 1/nranks): the loss is a batch MEAN, each
shard computes grads of its local-batch mean, and the bucket reduce
averages them. For power-of-two batch/mesh sizes this is bit-identical
to the monolithic GSPMD path (scaling by powers of two is exact);
scalar float fetches produced inside the sharded segment are returned
as the cross-replica mean (== the global batch mean for equal shards).

``collective_quantization="int8"`` swaps each bucket's psum for the
EQuARX-style two-shot blockwise exchange (kernels/quant.py): ~3.9x
fewer wire bytes at block 256, one quantization step of error per
phase (tests/test_collectives.py::test_int8_zero1_trains_close_to_fp32
holds the loss trajectory).
"""

from __future__ import annotations

import contextlib
import logging
import weakref
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

_log = logging.getLogger("paddle_tpu.collectives")

OP_TYPE = "collective_bucket_reduce"
REDUCED_SUFFIX = "@BUCKETREDUCED"

__all__ = ["CollectivePlan", "ensure_planned", "build_collective_fn",
           "OP_TYPE", "parse_bucket_mb", "effective_bucket_mb"]


def parse_bucket_mb(spec):
    """``collective_bucket_mb`` in either form: a single size
    (number / numeric string — today's behavior, applied to every
    axis) or per-mesh-axis ``"dp=32,dcn=8"`` (sizes in MB), so a
    reduce crossing DCN can amortize its far-higher per-collective
    latency with bigger buckets than an ICI-local one. Returns a float
    or an {axis: mb} dict; malformed entries are named by position
    (the PR-9 diagnostic style)."""
    if spec is None:
        return 0.0
    if isinstance(spec, (int, float)):
        return float(spec)
    if isinstance(spec, dict):
        return {str(k): float(v) for k, v in spec.items()}
    s = str(spec).strip()
    if not s:
        return 0.0
    if "=" not in s:
        try:
            return float(s)
        except ValueError:
            raise ValueError(
                f"collective_bucket_mb: {s!r} is neither a bucket size "
                "in MB nor the per-axis form axis=mb[,axis=mb...] "
                "(e.g. '32' or 'dp=32,dcn=8')") from None
    out: Dict[str, float] = {}
    for pos, part in enumerate(s.replace(";", ",").split(","), 1):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"collective_bucket_mb: entry {pos} ({part!r}) of "
                f"{spec!r}: expected axis=mb (e.g. 'dp=32,dcn=8')")
        k, v = part.split("=", 1)
        if not k.strip():
            raise ValueError(
                f"collective_bucket_mb: entry {pos} ({part!r}) of "
                f"{spec!r}: the axis name is empty — expected axis=mb "
                "(e.g. 'dp=32,dcn=8')")
        try:
            out[k.strip()] = float(v)
        except ValueError:
            raise ValueError(
                f"collective_bucket_mb: entry {pos} ({part!r}) of "
                f"{spec!r}: size {v.strip()!r} is not a number (MB) — "
                "expected axis=mb (e.g. 'dp=32,dcn=8')") from None
    return out


def effective_bucket_mb(spec, mesh=None, crosses_hosts=None) -> float:
    """The bucket cap the planner should use for the DP gradient
    reduce under ``spec``. Scalar form: applies everywhere. Per-axis
    form: a reduce that crosses hosts (the mesh places devices from
    more than one process, or — with no mesh to inspect — the world
    has more than one process) picks the ``dcn`` entry first, an
    ICI-local one picks ``dp`` first; either falls back to the other,
    and no matching entry means 0 (planner off)."""
    parsed = parse_bucket_mb(spec)
    if not isinstance(parsed, dict):
        return parsed
    if crosses_hosts is None:
        if mesh is not None:
            from ..distributed.coordinator import spans_processes

            crosses_hosts = spans_processes(mesh)
        else:
            try:
                import jax

                crosses_hosts = jax.process_count() > 1
            except Exception:  # noqa: BLE001 — jax not initialized
                crosses_hosts = False
    for axis in (("dcn", "dp") if crosses_hosts else ("dp", "dcn")):
        if axis in parsed:
            return float(parsed[axis])
    return 0.0


def _numel(shape) -> int:
    n = 1
    for d in shape or ():
        if d is None or d < 0:
            return 0
        n *= int(d)
    return n


def _itemsize(dtype) -> int:
    try:
        return np.dtype(str(dtype)).itemsize
    except TypeError:
        return 4


class CollectivePlan:
    """The planner's output, stamped on the Program as
    ``_collective_plan``: the bucket assignment plus the quantization
    config, with the wire-byte model and measured overlap/accuracy
    numbers exported as ``paddle_collective_*{plan=}`` gauges."""

    def __init__(self, program, buckets: List[Dict[str, Any]],
                 quantization: str, quant_block: int, bucket_mb: float,
                 axis: str = "dp"):
        self._program = weakref.ref(program)
        self.buckets = buckets
        self.quantization = quantization
        self.quant_block = int(quant_block)
        self.bucket_mb = float(bucket_mb)
        self.axis = axis
        # timing-only debug mode: lower the bucket ops as identity so a
        # compute-only baseline step can be measured; toggling re-keys
        # the executable (fingerprint+bump). Nothing but its test
        # sets it (ROADMAP queue 3, orphan knobs)
        self.skip_reduce = False
        self._dp: Optional[int] = None
        self._exchange = False  # set by attach(): real int8 exchange?
        self._measured: Dict[str, float] = {}
        from ..observability import watch_collectives

        watch_collectives(self)

    # -- identity -----------------------------------------------------------
    def reduced_names(self) -> List[str]:
        return [n for b in self.buckets for n in b["reduced"]]

    def fingerprint(self) -> Tuple:
        """Compile-identity fragment for runtime.dispatch
        program_fingerprint: two content-identical programs whose plans
        differ (quant mode, skip_reduce) must not share executables."""
        return (
            tuple(tuple(b["grads"]) for b in self.buckets),
            self.quantization, self.quant_block, self.skip_reduce,
        )

    def set_skip_reduce(self, flag: bool) -> None:
        if bool(flag) == self.skip_reduce:
            return
        self.skip_reduce = bool(flag)
        prog = self._program()
        if prog is not None:
            prog._bump()

    # -- wire model ---------------------------------------------------------
    def attach(self, mesh) -> None:
        """Called by build_collective_fn when the plan first compiles
        over a concrete mesh: records the dp degree — and whether the
        real int8 exchange lowers there (dp-only mesh) or the
        psum-form fallback moves fp32 bytes — so the wire-byte gauges
        become concrete AND honest."""
        self._dp = int(dict(mesh.shape).get(self.axis, 1))
        # mirrors build_collective_fn's collective_exchange_ok: any
        # other mesh axis (even size 1) makes the region partial-manual,
        # where only psum lowers
        self._exchange = not any(a != self.axis for a in mesh.axis_names)

    def wire_stats(self) -> Dict[str, float]:
        """Per-device per-step wire bytes under the standard ring
        model: fp32 all-reduce moves 2*(n-1)/n * payload; the quantized
        two-shot exchange moves 2*(n-1)/n * (int8 payload + fp32
        scales). On a partial-manual mesh the int8 mode's psum-form
        fallback transports the dequantized fp32 payload, so no wire
        saving is claimed there. Zeros until the plan has compiled over
        a mesh."""
        dp = self._dp
        if not dp or dp <= 1:
            return {"wire_bytes_per_step": 0.0,
                    "wire_bytes_fp32_per_step": 0.0,
                    "wire_bytes_saved_per_step": 0.0,
                    "wire_bytes_saved_ratio": 1.0}
        ring = 2.0 * (dp - 1) / dp
        fp32 = q = 0.0
        for b in self.buckets:
            # the op reduces each bucket as one flat payload (per
            # dtype; model with the dominant 4-byte case), so block +
            # chunk padding amortize over the whole bucket
            numel = sum(b["numels"])
            fp32 += ring * sum(
                ne * it for ne, it in zip(b["numels"], b["itemsizes"]))
            if self.quantization == "int8":
                nb = -(-numel // self.quant_block)
                nb = -(-nb // dp) * dp  # chunk padding to dp
                if self._exchange:
                    q += ring * (nb * self.quant_block + 4 * nb)
                else:
                    # psum fallback: fp32 body of the padded blocks
                    q += ring * nb * self.quant_block * 4
            else:
                q += ring * sum(
                    ne * it for ne, it in zip(b["numels"], b["itemsizes"]))
        return {
            "wire_bytes_per_step": q,
            "wire_bytes_fp32_per_step": fp32,
            "wire_bytes_saved_per_step": fp32 - q,
            "wire_bytes_saved_ratio": (fp32 / q) if q else 1.0,
        }

    # -- observability ------------------------------------------------------
    def set_measured(self, **metrics: float) -> None:
        """Bench-measured gauges (overlap_hidden_fraction,
        max_quant_error, ...): merged into the scrape."""
        for k, v in metrics.items():
            if v is not None:
                self._measured[k] = float(v)

    def snapshot(self) -> Dict[str, Any]:
        out = {
            "buckets": len(self.buckets),
            "grads_total": sum(len(b["grads"]) for b in self.buckets),
            "bucket_bytes_max": max(
                (b["bytes"] for b in self.buckets), default=0),
            "quant_block": self.quant_block if self.quantization != "none"
            else 0,
            "quantized": self.quantization == "int8",
            "quantized_exchange": (self.quantization == "int8"
                                   and self._exchange),
            "dp": self._dp or 0,
        }
        out.update(self.wire_stats())
        out.update(self._measured)
        return out


# -- the planner rewrite ------------------------------------------------------


def _grad_pairs_from_block(block):
    """Reconstruct (param, grad var) by the append_backward naming
    convention, for callers (with_partitioning) that plan after
    minimize without holding params_grads."""
    pairs = []
    for p in block.all_parameters():
        if not getattr(p, "trainable", True):
            continue
        g = block.vars.get(p.name + "@GRAD")
        if g is not None:
            pairs.append((p, g))
    return pairs


_SUPPRESSED = 0


@contextlib.contextmanager
def suppress_planning():
    """Context manager: make ``ensure_planned`` a no-op inside the
    ``with`` body. Used by builders whose gradient flow the planner
    must not touch — PipelineOptimizer stamps its cuts only AFTER the
    inner optimizer's minimize, so the flag seam would otherwise
    rewrite a program that is about to become pipelined (a bucket op
    spanning stages breaks the schedule's stage partitioner)."""
    global _SUPPRESSED
    _SUPPRESSED += 1
    try:
        yield
    finally:
        _SUPPRESSED -= 1


def ensure_planned(program=None, params_grads=None, bucket_mb=None,
                   quantization=None, quant_block=None) -> Optional[CollectivePlan]:
    """Plan gradient collectives for ``program`` if the flags (or the
    explicit arguments) ask for them and the program has parameter
    gradients. Idempotent: a program is planned at most once (the plan
    is stamped as ``program._collective_plan``). Returns the plan, or
    None when planning is off / inapplicable.

    The rewrite: for each size-capped bucket of param grads (grouped in
    the order backward produces them), insert one
    ``collective_bucket_reduce`` op immediately after the bucket's last
    producer and repoint every later consumer (gradient clip,
    regularization, the optimizer ops) at the reduced outputs.
    """
    from ..core.framework import OpRole, default_main_program
    from ..flags import flag

    program = program if program is not None else default_main_program()

    # bucket_mb accepts the per-axis form too ("dp=32,dcn=8"); at this
    # seam the reduce axis is dp, crossing hosts exactly when the world
    # does (a multi-process dp reduce IS a DCN reduce)
    mb = effective_bucket_mb(
        flag("collective_bucket_mb") if bucket_mb is None else bucket_mb)
    quant = str(flag("collective_quantization") if quantization is None
                else quantization) or "none"
    qblock = int(flag("collective_quant_block") if quant_block is None
                 else quant_block)
    if quant not in ("none", "int8"):
        raise ValueError(
            f"collective_quantization={quant!r}: supported modes are "
            "'none' (fp32 psum) and 'int8' (blockwise-quantized)")
    if qblock <= 0:
        raise ValueError(
            f"collective_quant_block={qblock}: block must be positive")
    off = mb <= 0 and quant == "none"
    if mb <= 0 and not off:
        mb = 25.0  # quantization requested: a sane default bucket cap

    existing = getattr(program, "_collective_plan", None)
    if existing is not None:
        # the rewrite is one-shot: the block already consumes the
        # reduced twins, so a later request with different settings
        # cannot be honored — say so instead of silently ignoring it
        if (off or quant != existing.quantization
                or (quant == "int8" and qblock != existing.quant_block)
                or mb != existing.bucket_mb):
            _log.warning(
                "collectives: program already planned with bucket_mb=%s "
                "quantization=%r quant_block=%s; ignoring conflicting "
                "request bucket_mb=%s quantization=%r quant_block=%s — "
                "set the collective_* flags / PartitionConfig fields "
                "before the first minimize/compile of this program",
                existing.bucket_mb, existing.quantization,
                existing.quant_block,
                "off" if off else mb, quant, qblock)
        else:
            # same settings, but the one-shot rewrite cannot cover
            # gradients a LATER minimize added (multi-optimizer
            # programs): those reduce via the GSPMD export fallback —
            # correct, but un-bucketed and un-quantized, and absent
            # from the wire-byte gauges. Say so instead of silently
            # over-claiming coverage.
            pairs = (params_grads if params_grads is not None
                     else _grad_pairs_from_block(program.global_block()))
            planned = {n for b in existing.buckets for n in b["grads"]}
            uncovered = sorted({g.name for _, g in pairs
                                if g is not None
                                and g.name not in planned})
            if uncovered:
                _log.warning(
                    "collectives: program already planned; %d "
                    "gradient(s) added after the plan (%s%s) stay "
                    "un-bucketed/un-quantized (monolithic GSPMD "
                    "reduce). Plan once, after the last minimize.",
                    len(uncovered), ", ".join(uncovered[:3]),
                    ", ..." if len(uncovered) > 3 else "")
        return existing
    if _SUPPRESSED:
        return None
    if off:
        return None  # planner off

    if getattr(program, "_pipeline_cuts", None):
        _log.info("collectives: program has pipeline cuts — the "
                  "pipeline schedule owns its gradient flow; not planned")
        return None
    if int(getattr(program, "_gradient_merge_k", 0) or 0) > 1:
        # the scan-based merge path (executor _build_gradient_merge_fn)
        # wins the build_block_fn routing: bucket ops would lower as
        # identity while the gauges claim savings that never happen
        _log.info("collectives: program uses gradient merge — the scan "
                  "accumulator owns its gradient flow; not planned")
        return None

    block = program.global_block()
    if params_grads is None:
        pairs = _grad_pairs_from_block(block)
    else:
        pairs = [(p, g) for p, g in params_grads if g is not None]
    if not pairs:
        return None

    # last producer index per grad var (sum/rename aggregation means
    # the LAST write is the value the optimizer consumes)
    producer: Dict[str, int] = {}
    for i, op in enumerate(block.ops):
        for ns in op.outputs.values():
            for n in ns:
                producer[n] = i
    entries = []
    for p, g in pairs:
        idx = producer.get(g.name)
        if idx is None:
            continue  # grad declared but never produced (frozen param)
        shape = g.shape if g.shape else p.shape
        nbytes = _numel(shape) * _itemsize(g.dtype)
        entries.append((idx, g.name, shape, g.dtype, nbytes))
    if not entries:
        return None
    entries.sort(key=lambda e: e[0])  # backward-production order

    cap = mb * (1 << 20)
    buckets: List[Dict[str, Any]] = []
    cur: Optional[Dict[str, Any]] = None
    for idx, gname, shape, dtype, nbytes in entries:
        if cur is None or (cur["bytes"] and cur["bytes"] + nbytes > cap):
            cur = {"grads": [], "reduced": [], "numels": [],
                   "itemsizes": [], "bytes": 0, "insert_after": -1}
            buckets.append(cur)
        cur["grads"].append(gname)
        cur["numels"].append(_numel(shape))
        cur["itemsizes"].append(_itemsize(dtype))
        cur["bytes"] += nbytes
        cur["insert_after"] = max(cur["insert_after"], idx)
        # the reduced twin the downstream consumers switch to
        rname = gname + REDUCED_SUFFIX
        gv = block.var(gname)
        block.create_var(name=rname, shape=gv.shape, dtype=gv.dtype,
                         stop_gradient=True)
        cur["reduced"].append(rname)

    # insert the bucket ops (descending position keeps indices valid)
    for b in sorted(buckets, key=lambda b: -b["insert_after"]):
        op = block.append_op(
            type=OP_TYPE,
            inputs={"X": list(b["grads"])},
            outputs={"Out": list(b["reduced"])},
            attrs={"op_role": OpRole.Backward,
                   "quantization": quant, "quant_block": qblock},
        )
        block.ops.insert(b["insert_after"] + 1, block.ops.pop())

    # repoint consumers AFTER each grad's bucket op at the reduced var
    reduce_idx: Dict[str, int] = {}
    mapping: Dict[str, str] = {}
    for i, op in enumerate(block.ops):
        if op.type == OP_TYPE:
            for raw, red in zip(op.inputs["X"], op.outputs["Out"]):
                reduce_idx[raw] = i
                mapping[raw] = red
    for i, op in enumerate(block.ops):
        if op.type == OP_TYPE:
            continue
        for slot, names in op.inputs.items():
            if any(n in mapping and i > reduce_idx[n] for n in names):
                op.inputs[slot] = [
                    mapping[n] if (n in mapping and i > reduce_idx[n])
                    else n for n in names]

    plan = CollectivePlan(program, buckets, quant, qblock, mb)
    program._collective_plan = plan
    program._bump()
    _log.info(
        "collectives: planned %d bucket(s) over %d gradient(s) "
        "(cap %.1f MB, quantization=%s block=%d)",
        len(buckets), len(entries), mb, quant, qblock)
    return plan


# -- compile-time: the split + shard_map step builder -------------------------


def _reads_of(ops) -> set:
    from ..core.framework import Block

    names = set()

    def visit(opl):
        for op in opl:
            for ns in op.inputs.values():
                names.update(ns)
            for v in op.attrs.values():
                if isinstance(v, Block):
                    visit(v.ops)

    visit(ops)
    return names


_RNG_OPS: Optional[set] = None
_RNG_OPS_COUNT = -1  # registry size the cache was computed at


def _rng_op_types() -> set:
    """Op types whose lowering draws from the per-step PRNG key. Inside
    the collective segment the key is folded with the dp rank (dropout
    must decorrelate across shards), so these ops' outputs are
    shard-divergent even when every input is replicated — they seed the
    taint analysis alongside the dp-split inputs. Detected by
    inspecting each lowering for ``op_key`` use, so newly registered
    stochastic ops are picked up mechanically (ops only ever register,
    so the registry size dates the cache)."""
    global _RNG_OPS, _RNG_OPS_COUNT
    import inspect

    from ..core.registry import get_op_def, registered_ops

    types = registered_ops()
    if _RNG_OPS is None or _RNG_OPS_COUNT != len(types):
        found = set()
        for t in types:
            try:
                if "op_key" in inspect.getsource(get_op_def(t).lower):
                    found.add(t)
            except (OSError, TypeError):  # uninspectable: assume stochastic
                found.add(t)
        _RNG_OPS = found
        _RNG_OPS_COUNT = len(types)
    return _RNG_OPS


def _outs_of(ops) -> set:
    # recurse into nested-Block attrs like _reads_of: the control-flow
    # lowerings (core/control_flow.py) publish sub-block writes of
    # outer vars back into the outer env, so a while/cond body is a
    # real producer for the export and taint analyses
    from ..core.framework import Block

    names = set()

    def visit(opl):
        for op in opl:
            for ns in op.outputs.values():
                names.update(ns)
            for v in op.attrs.values():
                if isinstance(v, Block):
                    visit(v.ops)

    visit(ops)
    return names


def _strip_axis(spec, axis: str):
    """Remove ``axis`` from a PartitionSpec-like entry list, keeping
    other placements: (('dp','tp'), None) -> ('tp', None); ('dp', None)
    -> (None, None)."""
    out = []
    for e in tuple(spec):
        if e is None:
            out.append(None)
            continue
        axes = tuple(a for a in ((e,) if isinstance(e, str) else tuple(e))
                     if a != axis)
        out.append(None if not axes else
                   (axes[0] if len(axes) == 1 else axes))
    return tuple(out)


def _dp_component(spec, axis: str):
    """Keep only the manual axis of a PartitionSpec-like entry list:
    ('dp', None) -> ('dp', None); (('dp','tp'), None) -> ('dp', None);
    ('tp', None) -> (None, None)."""
    out = []
    for e in tuple(spec):
        if e is None:
            out.append(None)
        else:
            axes = (e,) if isinstance(e, str) else tuple(e)
            out.append(axis if axis in axes else None)
    return tuple(out)


def build_collective_fn(block, feed_names, state_names, fetch_names,
                        written_names, mesh, axis_env, plan,
                        in_shardings=None, state_shardings=None):
    """Build the step function for a collective-planned program over a
    mesh whose ``plan.axis`` ("dp") degree is > 1. Called from
    ``core.executor.build_block_fn``; same signature contract:
    f(step_key, *feeds, *state) -> (*fetches, *new_state).

    The block splits at the LAST bucket-reduce op: segment 1 (forward +
    backward + bucket reduces) lowers inside a shard_map manual over
    the dp axis (other mesh axes stay GSPMD-auto), segment 2 (clip /
    regularization / optimizer) lowers after it at the GSPMD level on
    the reduced, replicated gradients — so ZeRO state shardings keep
    working untouched.

    Per-shard semantics: feeds whose sharding places dp on a dim enter
    split on that dim (others replicated — each shard then computes the
    identical value and the mean-reduce is exact); state enters
    replicated w.r.t. dp; the step PRNG key folds in the dp rank so
    dropout decorrelates across shards. Exports from segment 1 are
    reassembled by shape: dims shrunk by exactly dp come back
    concatenated over dp, shape-identical float values come back as the
    cross-replica mean (the global batch-mean for mean-reduced losses),
    and the bucket outputs are already replicated by their psum.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..core.executor import _lower_block
    from ..core.registry import LoweringContext

    axis = plan.axis
    sizes = dict(mesh.shape)
    dp = int(sizes.get(axis, 1))
    plan.attach(mesh)
    auto = frozenset(a for a in mesh.axis_names if a != axis)

    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    reduce_positions = [i for i, op in enumerate(ops) if op.type == OP_TYPE]
    last = max(reduce_positions)
    seg1, seg2 = ops[:last + 1], ops[last + 1:]

    seg1_out = _outs_of(seg1)
    exports = sorted(
        (seg1_out & _reads_of(seg2))
        | (seg1_out & set(fetch_names))
        | (seg1_out & set(written_names)))
    reduced = set(plan.reduced_names())
    env_names = set(feed_names) | set(state_names)
    seg1_in = sorted(_reads_of(seg1) & env_names)
    in_shardings = in_shardings or {}
    state_shardings = state_shardings or {}

    def _state_spec(n):
        # the executor's state-sharding resolution (_state_sharding):
        # per-compile specs first, then the var's own annotation
        if n in state_shardings:
            return tuple(state_shardings[n])
        if block.has_var(n):
            spec = getattr(block.var(n), "sharding", None)
            if spec is not None:
                return tuple(spec)
        return None

    inner_env = dict(axis_env or {})
    inner_env["collective_axis"] = axis
    inner_env["collective_axis_size"] = dp
    # all_to_all/all_gather only lower inside FULLY-manual regions on
    # this XLA; a mixed mesh keeps the int8 numerics via the psum form
    inner_env["collective_exchange_ok"] = not auto
    if plan.skip_reduce:
        inner_env["collective_skip_reduce"] = True

    from ..flags import flag

    check = flag("check_nan_inf")

    def seg1_run(key, vals, collective: bool):
        env = dict(zip(seg1_in, vals))
        if collective:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
            ctx = LoweringContext(step_key=key, mesh=mesh,
                                  axis_env=inner_env, manual_axes=(axis,))
        else:
            # abstract shape probes run OUTSIDE the shard_map: identity
            # reduces, no axis to fold
            ctx = LoweringContext(step_key=key, mesh=None,
                                  axis_env=axis_env)
        ctx.check_nan_inf = check
        _lower_block(block, env, ctx, ops=seg1)
        return tuple(env[n] for n in exports)

    def fn(step_key, *args):
        env: Dict[str, Any] = {}
        for i, n in enumerate(feed_names):
            env[n] = args[i]
        for i, n in enumerate(state_names):
            env[n] = args[len(feed_names) + i]

        # manual-axis input specs: feeds split where their sharding
        # placed dp; state enters replicated w.r.t. dp. State whose
        # jit-level sharding itself places dp (ZeRO-3 params, joint
        # ("dp","tp") megatron specs) is re-sharded dp-free by a GSPMD
        # constraint BEFORE the manual region — the same all-gather
        # ZeRO had GSPMD insert at the point of use; XLA's
        # partial-manual resharder cannot synthesize it across the
        # manual boundary itself (observed hard abort)
        from jax.sharding import NamedSharding

        in_specs = []
        local_sds = []
        for n in seg1_in:
            v = env[n]
            nd = np.ndim(v)
            spec = (None,) * nd
            if n in in_shardings:
                spec = _dp_component(tuple(in_shardings[n]), axis)
                spec = spec + (None,) * (nd - len(spec))
                lshape = tuple(
                    d // dp if spec[j] == axis else d
                    for j, d in enumerate(np.shape(v)))
            else:
                sspec = _state_spec(n)
                if sspec is not None and any(
                        axis in ((e,) if isinstance(e, str) else tuple(e))
                        for e in sspec if e is not None):
                    env[n] = jax.lax.with_sharding_constraint(
                        v, NamedSharding(
                            mesh, P(*_strip_axis(sspec, axis))))
                lshape = np.shape(v)
            in_specs.append(P(*spec))
            local_sds.append(jax.ShapeDtypeStruct(lshape, v.dtype))
        key_sds = jax.ShapeDtypeStruct(np.shape(step_key), step_key.dtype)

        # dp-taint: anything transitively computed from a dp-SPLIT input
        # — or drawn from the rank-folded PRNG — differs per shard.
        # Shape-identical float exports come back as the cross-replica
        # mean (below; for RNG-derived floats that is the documented
        # decorrelated-dropout contract); integers have no sound generic
        # correction, so a tainted integer export must be refused rather
        # than silently returning one shard's local value.
        rng_ops = _rng_op_types()
        tainted = {n for n, s in zip(seg1_in, in_specs)
                   if axis in tuple(s)}
        for op in seg1:
            if op.type in rng_ops or _reads_of([op]) & tainted:
                tainted |= _outs_of([op])

        glob = jax.eval_shape(
            lambda k, vs: seg1_run(k, vs, False), key_sds,
            [jax.ShapeDtypeStruct(np.shape(env[n]), env[n].dtype)
             for n in seg1_in])
        loc = jax.eval_shape(
            lambda k, vs: seg1_run(k, vs, False), key_sds, local_sds)

        out_specs = []
        corrections = []  # index -> "mean" | None
        for i, n in enumerate(exports):
            g, l = glob[i], loc[i]
            if n in reduced or tuple(g.shape) == tuple(l.shape):
                is_float = jnp.issubdtype(g.dtype, jnp.floating)
                if n not in reduced and not is_float and n in tainted:
                    raise NotImplementedError(
                        f"collectives: integer var {n!r} exported from "
                        "the sharded segment depends on dp-split inputs "
                        "or per-shard randomness, so its value differs "
                        "per shard and has no cross-replica correction "
                        "(floats return the pmean); fetch it from "
                        "outside the backward segment or disable "
                        "collective_bucket_mb for this program")
                out_specs.append(P())
                corrections.append(
                    None if (n in reduced or not is_float) else "mean")
                continue
            spec = []
            for gd, ld in zip(g.shape, l.shape):
                if gd == ld:
                    spec.append(None)
                elif ld * dp == gd:
                    spec.append(axis)
                else:
                    raise NotImplementedError(
                        f"collectives: var {n!r} exported from the "
                        f"sharded segment has local shape {l.shape} vs "
                        f"global {g.shape} — neither replicated nor "
                        f"split by {axis}={dp}; fetch it from outside "
                        "the backward segment or disable "
                        "collective_bucket_mb for this program")
            out_specs.append(P(*spec))
            corrections.append(None)

        def body(key, *vals):
            outs = list(seg1_run(key, vals, True))
            for i, how in enumerate(corrections):
                if how == "mean":
                    outs[i] = jax.lax.pmean(outs[i], axis)
            return tuple(outs)

        kwargs = dict(mesh=mesh, in_specs=(P(),) + tuple(in_specs),
                      out_specs=tuple(out_specs), check_vma=False)
        if auto:
            # partial-manual: only the reduce axis is manual, the rest
            # of the mesh stays GSPMD-auto inside the region
            kwargs["axis_names"] = {axis}
        sharded = jax.shard_map(body, **kwargs)
        outs = sharded(step_key, *(env[n] for n in seg1_in))
        env.update(zip(exports, outs))

        ctx2 = LoweringContext(step_key=step_key, mesh=mesh,
                               axis_env=axis_env)
        ctx2.check_nan_inf = check
        # the optimizer tail runs at GSPMD level: fused_optim lowerings
        # need the ZeRO state specs to wrap their Pallas pass correctly
        ctx2.state_shardings = state_shardings
        _lower_block(block, env, ctx2, ops=seg2)

        fetched = []
        for n in fetch_names:
            if n not in env:
                raise KeyError(f"fetch var {n!r} was never produced")
            fetched.append(env[n])
        new_state = [env[n] for n in written_names]
        return tuple(fetched) + tuple(new_state)

    return fn
