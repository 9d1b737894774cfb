"""Executor: compiles whole Program blocks to single XLA executables.

Reference: framework/executor.cc:195 (Executor::Run) interprets a
ProgramDesc one op at a time, choosing a kernel per op and launching it
(operator.cc:918-1027 RunImpl), with scope-based GC of dead tensors.

TPU-native redesign: `Executor.run(program, feed, fetch_list)` lowers
the *entire block* through the op registry into one JAX function

    f(step_key, *feed_values, *state_values) -> (*fetch_values, *new_state)

jit-compiles it (cached on (program, version, feed shapes)), and runs
it. Consequences, all deliberate:
  * no per-op dispatch: XLA fuses the whole step (forward, backward,
    optimizer) into one executable — the interpreter hot loop (CS1 in
    SURVEY.md) disappears;
  * no garbage collector: SSA values die by liveness inside XLA;
  * no data-layout transfer machinery: XLA assigns layouts;
  * persistable variables (parameters, optimizer state) live in a Scope
    as device arrays and are donated back to the executable each step
    (buffer aliasing ≈ the reference's in-place param update).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import threading
import time as _time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import framework
from .framework import Program, Block, Variable
from .registry import LoweringContext, get_op_def
from .places import Place, TPUPlace


class Scope:
    """name -> device array store for persistable variables.

    Reference framework/scope.h:46 is a hierarchical name->Variable map;
    executor-managed temporaries don't exist here (they are SSA values
    inside the compiled function), so a flat dict with a parent link
    suffices.
    """

    _uid_counter = itertools.count(1)
    # shared by all scopes: generation bumps must not lose increments
    # under concurrent mutation (python's `+= 1` is a non-atomic
    # read/add/store) — a lost bump would let a BoundStep keep stale
    # state refs past the documented one-step staleness window
    _gen_lock = threading.Lock()

    def __init__(self, parent: Optional["Scope"] = None):
        self.vars: Dict[str, Any] = {}
        self.parent = parent
        self.uid = next(Scope._uid_counter)
        # bumped on every mutation: the dispatch fast path
        # (runtime/dispatch.BoundStep) caches state-var refs and
        # re-resolves only when this counter moves, instead of walking
        # the scope every step
        self.generation = 0

    def _bump_generation(self):
        with Scope._gen_lock:
            self.generation += 1

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        return self.find_var(name) is not None

    def set_var(self, name: str, value):
        self.vars[name] = value
        self._bump_generation()

    def erase(self, name: str):
        self.vars.pop(name, None)
        self._bump_generation()

    def new_scope(self) -> "Scope":
        return Scope(parent=self)

    def local_var_names(self) -> List[str]:
        return list(self.vars)

    # numpy convenience for tests / io
    def get_numpy(self, name: str):
        v = self.find_var(name)
        return None if v is None else np.asarray(v)


_global_scope = Scope()
_scope_stack: List[Scope] = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope: Scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


# --------------------------------------------------------------------------


class _CompiledBlock:
    """One jitted executable for (program version, feed signature).

    ``fn`` has signature ``(base_key, step_index, *feeds, *state)`` —
    the per-step PRNG fold runs INSIDE the executable so the hot path
    pays exactly one dispatch per step (pre-dispatch-cache it was two:
    a jitted fold_in, then the step)."""

    def __init__(self, fn, feed_names, state_names, fetch_names, written_names, donate):
        self.fn = fn
        self.feed_names = feed_names
        self.state_names = state_names
        self.fetch_names = fetch_names
        self.written_names = written_names
        self.donate = donate
        # set on first invocation (trace + XLA compile happen there);
        # None marks "not yet compiled" for the stats instrumentation
        self.compile_time: Optional[float] = None
        self.tag = ""
        # donation-audit metadata (tools/donation_audit.py): which
        # rewritten-state args COULD alias their input buffer, which
        # actually do, and why the gap is deliberate when it is
        # ("cpu" skip / disable_donation); mesh marks executables whose
        # arg placement is owned by GSPMD (the async feed stage must
        # not device_put those onto the default device)
        self.donatable_names: List[str] = []
        self.donated_names: List[str] = []
        self.donation_skip_reason: Optional[str] = None
        self.mesh = None
        # multi-host (mesh spanning processes): the per-arg shardings
        # the dispatch layer needs to assemble GLOBAL jax.Arrays from
        # each process's LOCAL feed batch / host-value state
        # (jax.make_array_from_process_local_data) — host numpy cannot
        # be passed straight into a jit whose in_shardings are
        # non-addressable
        self.feed_shardings: Optional[Dict[str, Any]] = None
        self.state_sharding_by_name: Optional[Dict[str, Any]] = None


def _lower_block(
    block: Block,
    env: Dict[str, Any],
    ctx: LoweringContext,
    ops=None,
):
    """Interpret ops of a block symbolically, updating env in place."""
    from .registry import _EXERCISED

    for op in (block.ops if ops is None else ops):
        if op.type in ("feed", "fetch"):
            continue
        _EXERCISED.add(op.type)
        lower_control = _CONTROL_FLOW.get(op.type)
        if lower_control is not None:
            lower_control(block, op, env, ctx)
            continue
        opdef = get_op_def(op.type)
        ins: Dict[str, List[Any]] = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                if n not in env:
                    raise KeyError(
                        f"op {op.type!r} input {slot}={n!r} is not defined; "
                        "did you run the startup program / feed this var?"
                    )
                vals.append(env[n])
            ins[slot] = vals
        scope_name = op.attrs.get("name_scope")
        if scope_name:
            with jax.named_scope(scope_name):
                outs = opdef.lower(ctx, op, ins)
        else:
            outs = opdef.lower(ctx, op, ins)
        for slot, names in op.outputs.items():
            vals = outs.get(slot, [])
            for i, n in enumerate(names):
                if i < len(vals):
                    env[n] = vals[i]
                    if getattr(ctx, "check_nan_inf", False):
                        _emit_nan_check(op.type, n, vals[i])


def _emit_nan_check(op_type: str, var_name: str, value):
    """Per-op output nan/inf scan, FLAGS_check_nan_inf (reference
    details/nan_inf_utils.h:28 scans op outputs after each kernel)."""
    import jax.numpy as jnp

    if not hasattr(value, "dtype") or not jnp.issubdtype(value.dtype, jnp.floating):
        return
    bad = jnp.any(~jnp.isfinite(value))
    jax.lax.cond(
        bad,
        lambda: jax.debug.print(
            "[check_nan_inf] op {op} output {var}: non-finite values detected",
            op=op_type, var=var_name,
        ),
        lambda: None,
    )


def build_block_fn(
    block: Block,
    feed_names: Sequence[str],
    state_names: Sequence[str],
    fetch_names: Sequence[str],
    written_names: Sequence[str],
    mesh=None,
    axis_env=None,
    in_shardings=None,
    state_shardings=None,
):
    """Build the pure function f(step_key, *feeds, *state) ->
    (*fetches, *new_state) for a block. This is the object XLA
    compiles; also used directly by __graft_entry__ and the bench."""

    cuts = getattr(block.program, "_pipeline_cuts", None)
    if cuts and mesh is not None and "pp" in getattr(mesh, "shape", {}):
        if int(getattr(block.program, "_gradient_merge_k", 0) or 0) > 1:
            raise NotImplementedError(
                "PipelineOptimizer + GradientMergeOptimizer cannot be "
                "composed yet — raise num_microbatches instead (the "
                "pipeline already accumulates over microbatches)"
            )
        from .pipeline_program import build_pipeline_fn

        return build_pipeline_fn(
            block, feed_names, state_names, fetch_names, written_names, mesh
        )

    k = int(getattr(block.program, "_gradient_merge_k", 0) or 0)
    if k > 1:
        return _build_gradient_merge_fn(
            block, feed_names, state_names, fetch_names, written_names, mesh, k,
            bool(getattr(block.program, "_gradient_merge_avg", True)),
            axis_env=axis_env,
        )

    # collective-planned programs (parallel/collectives.py) over a mesh
    # with a real dp axis: forward+backward+bucket-reduces run inside a
    # shard_map manual over dp, so each gradient bucket's all-reduce is
    # an explicit, overlappable collective instead of one GSPMD blob
    # after the whole backward. Without a dp>1 mesh (or under the
    # pipeline/gradient-merge paths above) the bucket ops lower as
    # identity and the program behaves exactly monolithic.
    plan = getattr(block.program, "_collective_plan", None)
    if (plan is not None and mesh is not None
            and int(dict(mesh.shape).get(plan.axis, 0)) > 1):
        from ..parallel.collectives import build_collective_fn

        return build_collective_fn(
            block, feed_names, state_names, fetch_names, written_names,
            mesh, axis_env, plan, in_shardings, state_shardings,
        )

    def fn(step_key, *args):
        from ..flags import flag

        env: Dict[str, Any] = {}
        for i, n in enumerate(feed_names):
            env[n] = args[i]
        for i, n in enumerate(state_names):
            env[n] = args[len(feed_names) + i]
        ctx = LoweringContext(step_key=step_key, mesh=mesh, axis_env=axis_env)
        ctx.check_nan_inf = flag("check_nan_inf")
        # state-var partition specs, for lowerings that must wrap a
        # Pallas kernel in a shard_map over the mesh (fused_optim:
        # Mosaic cannot be GSPMD-auto-partitioned, and the wrap wants
        # the ZeRO moment specs so the local update stays local)
        ctx.state_shardings = state_shardings or {}
        _lower_block(block, env, ctx)
        fetched = []
        for n in fetch_names:
            if n not in env:
                raise KeyError(f"fetch var {n!r} was never produced")
            fetched.append(env[n])
        new_state = [env[n] for n in written_names]
        return tuple(fetched) + tuple(new_state)

    return fn


def _build_gradient_merge_fn(
    block, feed_names, state_names, fetch_names, written_names, mesh, k, avg,
    axis_env=None,
):
    """Gradient accumulation (reference ir/multi_batch_merge_pass.cc:
    repeat fwd/bwd k times, apply the optimizer once).

    TPU-native: the batch is split into k microbatches; a lax.scan runs
    forward+backward per microbatch, accumulating the values the
    optimizer ops consume (running mean — no [k, ...] stacking, so
    accumulator memory is one extra grad set); the optimizer ops then
    run once on the merged grads. Persistable vars written in the
    forward (e.g. batch-norm stats) thread through the scan carry
    sequentially.
    """
    from ..core.framework import OpRole

    def is_opt(op):
        role = int(op.attrs.get("op_role", 0))
        return bool(role & (OpRole.Optimize | OpRole.LRSched))

    body_ops = [op for op in block.ops
                if op.type not in ("feed", "fetch") and not is_opt(op)]
    opt_ops = [op for op in block.ops
               if op.type not in ("feed", "fetch") and is_opt(op)]

    produced = {n for op in body_ops for names in op.outputs.values() for n in names}
    opt_needed = sorted({
        n for op in opt_ops for names in op.inputs.values() for n in names
        if n in produced
    })
    acc_names = sorted(set(opt_needed) | (set(fetch_names) & produced))
    body_written = [n for n in written_names
                    if n in produced]  # persistable writes in fwd/bwd

    def fn(step_key, *args):
        from ..flags import flag

        base_env: Dict[str, Any] = {}
        feeds = {}
        for i, n in enumerate(feed_names):
            v = args[i]
            if v.shape[0] % k:
                raise ValueError(
                    f"gradient merge k={k} does not divide batch {v.shape[0]} "
                    f"of feed {n!r}"
                )
            feeds[n] = v.reshape((k, v.shape[0] // k) + v.shape[1:])
        for i, n in enumerate(state_names):
            base_env[n] = args[len(feed_names) + i]

        check = flag("check_nan_inf")

        def one_mb(state_env, i):
            env = dict(base_env)
            env.update(state_env)
            for n in feed_names:
                env[n] = feeds[n][i]
            ctx = LoweringContext(
                step_key=jax.random.fold_in(step_key, i), mesh=mesh,
                axis_env=axis_env,
            )
            ctx.check_nan_inf = check
            _lower_block(block, env, ctx, ops=body_ops)
            return (
                {n: env[n] for n in body_written},
                {n: env[n] for n in acc_names},
            )

        w0, a0 = one_mb({}, 0)

        def scan_body(carry, i):
            st, acc = carry
            w, a = one_mb(st, i)
            return (w, {n: acc[n] + a[n] for n in acc}), None

        (wk, acc), _ = jax.lax.scan(scan_body, (w0, a0), jnp.arange(1, k))
        if avg:
            acc = {n: v / k for n, v in acc.items()}

        env = dict(base_env)
        env.update(wk)
        env.update(acc)
        ctx = LoweringContext(step_key=jax.random.fold_in(step_key, k),
                              mesh=mesh, axis_env=axis_env)
        ctx.check_nan_inf = check
        _lower_block(block, env, ctx, ops=opt_ops)

        fetched = []
        for n in fetch_names:
            if n not in env:
                raise KeyError(f"fetch var {n!r} was never produced")
            fetched.append(env[n])
        new_state = [env[n] for n in written_names]
        return tuple(fetched) + tuple(new_state)

    return fn


def analyze_block_state(block: "Block", feed_names):
    """Classify a block's vars for the donation contract: returns
    (state_needed, written) — persistable/scope inputs the executable
    must be handed, and persistable outputs it rewrites. The donation
    plan is exactly ``[n for n in state_needed if n in written]``.

    Module-level single source of truth: ``Executor._compile`` derives
    the runtime donate_argnums from this, and the static
    ``donation-safety`` analysis pass (analysis/dist_passes.py, PTL08x)
    plus ``tools/donation_audit.py --check-static`` call the SAME
    function — the offline plan and the runtime plan cannot drift."""
    produced = set(feed_names)
    state_needed: List[str] = []
    written: List[str] = []
    seen_state = set()
    seen_written = set()

    def is_persistable(name: str) -> bool:
        if block.has_var(name):
            return block.var(name).persistable
        return False

    def visit_block(blk: Block, local_names=frozenset()):
        # local_names: vars created IN a nested block (recurrent
        # step inputs / pre-memories) — bound by the structured
        # op's lowering, never scope state
        for op in blk.ops:
            if op.type in ("feed", "fetch"):
                continue
            for names in op.inputs.values():
                for n in names:
                    if n in local_names:
                        continue
                    if n not in produced and n not in seen_state:
                        # must come from scope
                        seen_state.add(n)
                        state_needed.append(n)
            for names in op.outputs.values():
                for n in names:
                    produced.add(n)
                    if is_persistable(n) and n not in seen_written:
                        seen_written.add(n)
                        written.append(n)
            for v in op.attrs.values():
                if isinstance(v, Block):
                    visit_block(v, local_names | set(v.vars))

    visit_block(block)
    return state_needed, written


def _cpu_only_target(mesh) -> bool:
    """True when the step will run exclusively on CPU devices (donation
    is pure overhead there)."""
    if mesh is not None:
        return all(d.platform == "cpu" for d in mesh.devices.flat)
    return jax.default_backend() == "cpu"


def _fetch_to_host(v):
    """numpy-ify a fetched value; SelectedRows fetches (sparse grads,
    e.g. the PS trainer fetching embedding grads) come back as a host
    SelectedRows instead of being densified."""
    from .selected_rows import SelectedRows

    if isinstance(v, SelectedRows):
        return SelectedRows(np.asarray(v.rows), np.asarray(v.values), v.height)
    return np.asarray(v)


# control-flow ops that need sub-block lowering (registered by
# core/control_flow.py to avoid a circular import)
_FOLD_JIT = None  # module-level: one compiled fold_in for all Executors

_COMPILED_PROGRAM_CLS = None


def _compiled_program_cls():
    """CompiledProgram, imported once (core.compiler imports this
    module's siblings — a top-level import would be circular; a
    function-local import costs a sys.modules lookup on the hot path)."""
    global _COMPILED_PROGRAM_CLS
    if _COMPILED_PROGRAM_CLS is None:
        from .compiler import CompiledProgram

        _COMPILED_PROGRAM_CLS = CompiledProgram
    return _COMPILED_PROGRAM_CLS


_CONTROL_FLOW: Dict[str, Any] = {}


def register_control_flow(op_type: str):
    def deco(fn):
        _CONTROL_FLOW[op_type] = fn
        return fn

    return deco


class Executor:
    """Reference API: python/paddle/fluid/executor.py:432."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place or TPUPlace()
        if self.place.device_id != 0:
            # state and step live on the process's default device; a
            # place that names another one must not be ignored
            raise NotImplementedError(
                f"Executor({self.place!r}): a single-device Executor "
                "runs on device 0 of its backend. Span devices with "
                "CompiledProgram(...).with_data_parallel/"
                "with_partitioning(places=[...]) instead.")
        self._cache: Dict[Tuple, _CompiledBlock] = {}
        self._run_counter = 0
        self._base_keys: Dict[int, Any] = {}
        # hogwild path: concurrent steps over a shared scope must not
        # alias-donate the same param buffers
        self.disable_donation = False
        # donate even on the CPU, where the executor otherwise donates
        # nothing (tools/donation_audit.py, tests/test_generation_donation.py)
        self._force_donation = False
        # hot-path dispatch (runtime/dispatch): fully-resolved BoundSteps
        # keyed on the cheap raw signature; fast_dispatch=False forces
        # the slow path every call. No caller sets it (ROADMAP queue 3).
        # LRU-capped: each entry pins a scope's state arrays via its
        # cached refs, and dead scopes / superseded flag generations
        # mint new keys without retiring old ones
        import collections

        self._bound: "collections.OrderedDict[Tuple, Any]" = (
            collections.OrderedDict())
        self._bound_cap = 256
        self.fast_dispatch = True
        # serializes bind/resolve (NOT the per-step fast path): serving
        # workers and predictor clones share one Executor, and two
        # threads resolving the same signature concurrently would race
        # the bound cache and duplicate the jit compile
        self._dispatch_lock = threading.Lock()
        self._stats: Dict[str, Any] = {
            "bound_hits": 0, "bound_misses": 0, "jit_compiles": 0,
            "shared_cache_hits": 0, "build_time_s": 0.0,
            "compile_time_s": 0.0,
        }
        # unified telemetry: live executors aggregate into the
        # paddle_executor_* families of observability's one registry
        from ..observability import watch_executor

        watch_executor(self)

    def cache_stats(self) -> Dict[str, Any]:
        """Dispatch/compilation cache counters for THIS executor, plus
        the process-wide view (shared compiled-block cache, persistent
        on-disk cache). ``jit_compiles`` counts executables this
        executor actually built — a second Executor running an
        already-compiled program reports 0 here and positive
        ``shared_cache_hits`` instead. ``compile_time_s`` is first-call
        time (jax trace + XLA compile + one step); ``build_time_s`` is
        the python-side program analysis + function construction."""
        from ..runtime import dispatch as _dispatch

        out = dict(self._stats)
        out["bound_steps"] = len(self._bound)
        out["compiled_blocks"] = len(self._cache)
        out["process"] = _dispatch.cache_stats()
        return out

    # -- public API -----------------------------------------------------------
    def aot_compile(self, program, feed, fetch_list, scope=None,
                    devices=None):
        """Compile the train/eval step WITHOUT executing it — for an
        arbitrary device set, e.g. a jax.experimental.topologies AOT
        topology of real TPU devices (round-5: libtpu compiles for
        v5e/v5p locally with no chip attached). Accepts a Program or a
        CompiledProgram (whose mesh, if any, is re-laid over `devices`
        with the same axis names/shape). Returns the jax compiled
        object — .memory_analysis() / .as_text() give the target's own
        HBM accounting and SPMD HLO.

        The scope must hold initialized persistables (run the startup
        program first); `feed` supplies example arrays or
        ShapeDtypeStructs. Compilation caching is NOT used: an AOT
        target must never collide with the live-device cache."""
        from jax.sharding import Mesh

        from .compiler import CompiledProgram

        mesh = in_shardings = state_shardings = axis_env = None
        if isinstance(program, CompiledProgram):
            mesh = program._mesh
            in_shardings = program._in_shardings
            state_shardings = getattr(program, "_state_shardings", None)
            axis_env = getattr(program, "_axis_env", None)
            program = program._program
        if mesh is not None and devices is not None:
            need = mesh.devices.size
            if len(devices) < need:
                raise ValueError(
                    f"aot_compile: mesh needs {need} devices, "
                    f"got {len(devices)}")
            mesh = Mesh(
                np.array(devices[:need]).reshape(mesh.devices.shape),
                mesh.axis_names)
        elif mesh is None and devices is not None:
            # plain Program on an AOT target: a 1-device mesh pins the
            # compile to that device kind (vars carrying multi-axis
            # sharding annotations need the CompiledProgram form)
            mesh = Mesh(np.array(devices[:1]), ("aot",))
        scope = scope or global_scope()
        block = program.global_block()
        # the docstring promises ShapeDtypeStruct feeds; _prepare_feed
        # np.asarray()s its values, so materialize structs as zeros
        feed = {
            n: (np.zeros(v.shape, v.dtype)
                if isinstance(v, jax.ShapeDtypeStruct) else v)
            for n, v in dict(feed).items()
        }
        feed_vals, _ = self._prepare_feed(block, feed)
        feed_names = sorted(feed_vals)
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v)
            for v in fetch_list
        ]
        compiled_blk = self._compile(
            program, block, feed_names, fetch_names, scope, mesh,
            in_shardings, state_shardings, axis_env)
        abstract = [jax.ShapeDtypeStruct((2,), jnp.uint32),
                    jax.ShapeDtypeStruct((), jnp.int32)]
        for n in compiled_blk.feed_names:
            a = np.asarray(feed_vals[n])
            abstract.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
        for n in compiled_blk.state_names:
            v = scope.find_var(n)
            a = np.asarray(v)
            abstract.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
        return compiled_blk.fn.lower(*abstract).compile()

    def run(
        self,
        program=None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        if program is None:
            program = framework.default_main_program()
        scope = scope or global_scope()
        feed = feed if feed is not None else {}
        fetch_list = fetch_list if fetch_list is not None else []

        # -- hot path: one dict hit resolves the whole dispatch --------
        bkey = bound = None
        if use_program_cache and self.fast_dispatch:
            from ..observability import tracing

            with tracing.annotation("executor/bind"):
                bkey = self._bound_key(program, feed, fetch_list, scope)
                if bkey is not None:
                    bound = self._bound.get(bkey)
                    if bound is not None:
                        self._stats["bound_hits"] += 1
                        self._bound.move_to_end(bkey)
            if bound is not None:
                return bound.run(feed, return_numpy)
        self._stats["bound_misses"] += 1
        return self._run_slow(
            program, dict(feed), list(fetch_list), scope, return_numpy,
            use_program_cache, bkey,
        )

    def run_pipelined(
        self,
        program=None,
        feeds: Optional[Any] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        depth: Optional[int] = None,
    ):
        """Overlapped step driver: a generator yielding ``run``'s
        fetches for every feed dict in ``feeds`` (any iterable —
        a list, a generator, a ``GeneratorLoader``), bit-identical to
        calling ``run`` per feed but with the host side of step N+1
        (feed normalization, padding casts, the H2D ``device_put``)
        running on a feeder thread while step N executes on device
        (``runtime.dispatch.BoundStep.run_pipelined``).

        Feeds whose signature (shapes/dtypes) changes mid-stream are
        handled by draining the pipeline and re-binding — churny-shape
        streams stay correct, they just pay a bubble at each boundary.
        ``depth`` defaults to the ``dispatch_pipeline_depth`` flag
        (2 = classic double buffering)."""
        from ..flags import flag
        from ..runtime.dispatch import feed_signature

        if program is None:
            program = framework.default_main_program()
        scope = scope or global_scope()
        fetch_list = list(fetch_list) if fetch_list is not None else []
        it = iter(feeds if feeds is not None else ())
        _END = object()
        pending = next(it, _END)
        while pending is not _END:
            bound = self.bind(program, pending, fetch_list, scope=scope)
            # depth resolves AFTER the bind: the first bind may apply
            # an autotune profile that tunes dispatch_pipeline_depth —
            # reading the flag up front would run the whole stream at
            # the default (an explicit depth= argument still wins)
            seg_depth = (depth if depth is not None
                         else int(flag("dispatch_pipeline_depth")))
            sig = feed_signature(pending)

            def _segment():
                # consumed on the FEEDER thread; `pending` is read back
                # on the caller thread only after the pipeline's end
                # sentinel, which the queue orders after this write
                nonlocal pending
                while pending is not _END and feed_signature(pending) == sig:
                    f = pending
                    try:
                        pending = next(it, _END)
                    except BaseException:
                        # the lookahead pull for the NEXT feed failed:
                        # the current good feed must still reach the
                        # device before the error surfaces, or an input
                        # error at feed K would cost step K-1 too
                        pending = _END
                        yield f
                        raise
                    yield f

            for outs in bound.run_pipelined(
                    _segment(), return_numpy=return_numpy,
                    depth=seg_depth):
                yield outs

    def _bound_key(self, program, feed, fetch_list, scope):
        """Cheap raw-signature key for the BoundStep cache; None when
        the feed holds non-array values (those take the slow path,
        which normalizes them first)."""
        frag = None
        if isinstance(program, _compiled_program_cls()):
            frag = program._dispatch_fragment()
            program = program._program
        try:
            fsig = tuple((n, v.shape, v.dtype) for n, v in feed.items())
        except AttributeError:
            return None
        from .. import flags as _flags

        return (
            program.uid,
            program.version,
            # random_seed is a plain attr (no version bump) read at
            # BoundStep bind; changing it must re-bind
            program.random_seed,
            scope.uid,
            fsig,
            tuple(v.name if isinstance(v, Variable) else str(v)
                  for v in fetch_list),
            frag,
            _flags._generation,
            self.disable_donation,
            self._force_donation,
        )

    def bind(self, program, feed, fetch_list, scope=None, tag=None):
        """Resolve (compiling if needed, running nothing) the
        ``runtime.dispatch.BoundStep`` for this exact (program, feed
        signature, fetch list, scope) and return it. A caller looping
        a fixed-shape step — the generation engine's per-token decode
        — holds the bound step directly and pays neither the bound-key
        assembly nor the dict probe ``Executor.run`` does per call.

        ``feed`` supplies example arrays (shapes/dtypes are what bind;
        values are never executed here). ``tag`` labels the compiled
        block for trace spans / compile events — only meaningful for
        programs not shared with other call sites, since the compiled
        block (and its tag) is shared by content fingerprint."""
        scope = scope or global_scope()
        feed = dict(feed)
        fetch_list = list(fetch_list)
        bkey = self._bound_key(program, feed, fetch_list, scope)
        # double-checked: a cache hit must not serialize behind a
        # concurrent _resolve_bound (tens of ms of lowering under the
        # lock) — the generation prefill path binds per batch and a
        # hit stalling on another thread's compile would spike TTFT
        bound = self._bound.get(bkey) if bkey is not None else None
        if bound is not None:
            self._stats["bound_hits"] += 1
            self._bound.move_to_end(bkey)
        else:
            with self._dispatch_lock:
                bound = self._bound.get(bkey) if bkey is not None else None
                if bound is None:
                    self._stats["bound_misses"] += 1
                    bound = self._resolve_bound(
                        program, feed, fetch_list, scope, True, bkey)
                else:
                    self._stats["bound_hits"] += 1
                    self._bound.move_to_end(bkey)
        if tag is not None:
            bound.compiled.tag = tag
        return bound

    def _run_slow(
        self, program, feed, fetch_list, scope, return_numpy,
        use_program_cache, bkey,
    ):
        with self._dispatch_lock:
            bound = self._resolve_bound(
                program, feed, fetch_list, scope, use_program_cache, bkey)
        return bound.run(feed, return_numpy)

    def _resolve_bound(
        self, program, feed, fetch_list, scope, use_program_cache, bkey,
    ):
        from ..runtime import dispatch as _dispatch

        # level-2 on disk: route XLA through the persistent compilation
        # cache before anything might compile (bind time, not per step —
        # an in-memory cache hit can still be a fresh jit in a process
        # whose flag changed)
        _dispatch.ensure_persistent_cache()

        # autotune seam (runtime.dispatch.autotune_for_program): a
        # profile recorded for this program's fingerprint pre-tunes the
        # runtime knobs (pipeline depth, prefetch, serving buckets...)
        # before the step binds — once per fingerprint, explicit
        # user-set flags always win, absence is free (one set probe).
        # A non-empty apply bumped the flags generation AFTER the
        # caller computed bkey: recompute it, or this bind would be
        # cached under a dead key and the next run would re-lower and
        # re-compile the whole program
        if _dispatch.autotune_for_program(program) and bkey is not None:
            bkey = self._bound_key(program, feed, fetch_list, scope)

        mesh = None
        in_shardings = None
        state_shardings = None
        axis_env = None
        strategy = None
        if isinstance(program, _compiled_program_cls()):
            mesh = program._mesh
            in_shardings = program._in_shardings
            state_shardings = getattr(program, "_state_shardings", None)
            axis_env = getattr(program, "_axis_env", None)
            strategy = getattr(program, "_strategy", None)
            program = program._program
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in fetch_list
        ]

        block = program.global_block()
        feed_vals, feed_sig = self._prepare_feed(block, feed)
        # the CALLER's dtypes, pre-normalization: the BoundStep's
        # normalization plan must be derived from what arrives each
        # step (e.g. an undeclared float64 feed), not from the
        # already-normalized signature
        raw_dtypes = {
            n: (v.dtype if hasattr(v, "dtype") else np.asarray(v).dtype)
            for n, v in feed.items()
        }
        from ..flags import flag

        # NOTE: no scope identity in the compiled-block key — state
        # analysis depends only on the program, and jax.jit already
        # retraces when a different scope supplies different
        # shapes/dtypes. Keying on scope.uid forced a recompile per
        # Scope, which made the predictor's clone-per-thread pattern
        # recompile per clone. (The BoundStep key DOES carry scope.uid
        # — bound steps cache scope-resolved state refs — but bound
        # steps for two scopes share one compiled block.)
        inshard_key = (
            tuple(sorted((k, tuple(v)) for k, v in in_shardings.items()))
            if in_shardings else None)
        common = (
            feed_sig,
            tuple(fetch_names),
            # feed shardings are part of the executable's identity: two
            # CompiledPrograms on one mesh with different input specs
            # must not share an executable
            inshard_key,
            # the mesh SHAPE, DEVICE SET and sharding choices, not just
            # presence: the same program compiled dp-then-sp (or with
            # different expert placements) must not hit the stale
            # executable, and two same-shape meshes over different
            # devices (e.g. [0,1] vs [2,3]) compile to different
            # device assignments
            (tuple(sorted(dict(mesh.shape).items())),
             tuple(d.id for d in mesh.devices.flat))
            if mesh is not None else None,
            tuple(sorted((k, tuple(v)) for k, v in state_shardings.items()))
            if state_shardings else None,
            tuple(sorted(axis_env.items())) if axis_env else None,
            flag("check_nan_inf"),
            self.disable_donation,
            self._force_donation,
        )
        key = (program.uid, program.version) + common
        compiled = self._cache.get(key) if use_program_cache else None
        if compiled is None:
            shared_key = None
            if use_program_cache:
                # level-2 in-memory: compiled blocks shared across ALL
                # Executor instances, keyed on program CONTENT — the
                # PS/hogwild/predictor clone-per-thread patterns stop
                # re-jitting the same program per instance
                shared_key = (
                    _dispatch.program_fingerprint(program),
                ) + common
                compiled = _dispatch.shared_cache_get(shared_key)
                if compiled is not None:
                    self._stats["shared_cache_hits"] += 1
            if compiled is None:
                t0 = _time.perf_counter()
                compiled = self._compile(
                    program, block, sorted(feed), fetch_names, scope, mesh,
                    in_shardings, state_shardings, axis_env
                )
                dt = _time.perf_counter() - t0
                compiled.tag = f"uid={program.uid} v={program.version}"
                self._stats["jit_compiles"] += 1
                self._stats["build_time_s"] += dt
                _dispatch._GLOBAL_STATS["jit_compiles"] += 1
                _dispatch._GLOBAL_STATS["build_time_s"] += dt
                if shared_key is not None:
                    _dispatch.shared_cache_put(shared_key, compiled)
            if use_program_cache:
                self._cache[key] = compiled

        # the collective plan's wire-byte gauges need the mesh degree
        # even when the executable came out of a (shared) cache and
        # build_collective_fn never ran for this instance
        plan = getattr(program, "_collective_plan", None)
        if plan is not None and mesh is not None:
            plan.attach(mesh)

        # pre-flight: sharded feeds must divide over their mesh axes —
        # fail HERE with the strategy named, not inside GSPMD
        if mesh is not None and in_shardings:
            _dispatch.validate_feed_shardings(
                compiled.feed_names,
                [np.shape(feed_vals[n]) for n in compiled.feed_names],
                in_shardings, mesh, strategy,
            )

        bound = _dispatch.BoundStep(
            self, compiled, scope, block, raw_dtypes,
            feed_avals=[
                jax.ShapeDtypeStruct(np.shape(feed_vals[n]),
                                     feed_vals[n].dtype)
                for n in compiled.feed_names])
        if bkey is not None:
            self._bound[bkey] = bound
            while len(self._bound) > self._bound_cap:
                self._bound.popitem(last=False)
        return bound

    # -- internals ------------------------------------------------------------
    def _base_key(self, seed: int):
        """Cached per-seed base PRNG key. The per-step fold_in runs
        INSIDE the compiled step function (one dispatch per step); only
        the base key is materialized host-side."""
        base = self._base_keys.get(seed)
        if base is None:
            base = jax.random.PRNGKey(seed)
            self._base_keys[seed] = base
        return base

    def _prepare_feed(self, block: Block, feed: Dict[str, Any]):
        from ..runtime.dispatch import _want_dtype

        vals = {}
        sig = []
        for name in sorted(feed):
            v = feed[name]
            if isinstance(v, jax.Array):
                # DataLoader prefetch already device_put the batch —
                # a numpy round-trip here would undo the async H2D
                vals[name] = v
                sig.append((name, tuple(v.shape), str(v.dtype)))
                continue
            arr = np.asarray(v)
            # honor declared var dtype (and keep everything x64-free) —
            # ONE policy, shared with the BoundStep feed normalizers
            want = _want_dtype(block, name, arr.dtype)
            if want is not None:
                arr = arr.astype(want, copy=False)
            vals[name] = arr
            sig.append((name, arr.shape, str(arr.dtype)))
        return vals, tuple(sig)

    def _analyze_block(self, program: Program, block: Block, feed_names):
        """Classify vars: produced (by ops), state (persistable inputs),
        written state (persistable outputs)."""
        return analyze_block_state(block, feed_names)

    def _compile(
        self,
        program: Program,
        block: Block,
        feed_names: List[str],
        fetch_names: List[str],
        scope: Scope,
        mesh=None,
        in_shardings=None,
        state_shardings=None,
        axis_env=None,
    ) -> _CompiledBlock:
        from ..flags import flag
        from ..runtime import dispatch as _dispatch

        # level-2 on disk: EVERY compile path routes XLA through the
        # persistent compilation cache, including aot_compile — the
        # shape-bucketing warmup compiles its buckets through there
        # before any bind ever runs, and those executables were
        # silently skipping the cache (a bucketed serving worker
        # re-compiled from scratch on every rolling restart)
        _dispatch.ensure_persistent_cache()

        # static Program-IR verification (analysis/) BEFORE any lowering:
        # "warn" runs the structural passes and logs findings; "strict"
        # runs everything (incl. abstract shape re-inference) and raises
        # ProgramVerificationError so no JAX tracing ever starts on a
        # malformed program. Runs on compile-cache misses only.
        mode = flag("validate_program")
        if mode and mode != "off":
            from ..analysis import validate_for_run

            validate_for_run(
                program, fetch_names=fetch_names, feed_names=feed_names,
                mode=mode, label=f"program uid={program.uid}",
                mesh_axes=dict(mesh.shape) if mesh is not None else None)

        state_names, written_names = self._analyze_block(program, block, feed_names)

        # multi-PROCESS collective mode (reference: NCCL2 transpile +
        # dist trainers): the GradAllReduce transpiler inserted
        # c_allreduce ops and stamped _dist_plan; lower them onto a pmap
        # axis spanning every process (jax.distributed world) so grad
        # averaging crosses process boundaries, the TestDistBase setup.
        plan = getattr(program, "_dist_plan", None)
        if (
            plan is not None
            and plan.get("mode") == "collective"
            and int(plan.get("trainers", 1) or 1) > 1
        ):
            if jax.process_count() > 1:
                return self._compile_multiprocess(
                    block, feed_names, fetch_names, state_names, written_names
                )
            if mesh is None:
                # falling through would make c_allreduce identity while
                # the transpiler's 1/nranks scale still runs — every
                # grad silently shrunk
                raise RuntimeError(
                    f"program was transpiled for {plan.get('trainers')} "
                    "collective trainers but this run has one process and "
                    "no device mesh — launch via paddle_tpu.distributed."
                    "launch (jax.distributed) or compile with "
                    "with_data_parallel()"
                )
        raw_fn = build_block_fn(block, feed_names, state_names, fetch_names,
                                written_names, mesh, axis_env=axis_env,
                                in_shardings=in_shardings,
                                state_shardings=state_shardings)

        # fold the per-step PRNG key INSIDE the executable: the hot
        # path passes (base_key, step_index) and pays ONE dispatch per
        # step instead of a separate jitted fold_in + the step
        def step_fn(base_key, step_index, *args):
            return raw_fn(jax.random.fold_in(base_key, step_index), *args)

        # donate the state args that are rewritten (buffer aliasing for
        # in-place param update, reference ParamOut=Param convention).
        # Skipped on CPU-only targets: there is no HBM to save there,
        # and jax's per-call donated-buffer bookkeeping costs ~35us PER
        # DONATED ARG on the host — measured 294us vs 90us per step for
        # a 6-param MLP — which would dominate small-model dispatch.
        written_set = set(written_names)
        donatable = [n for n in state_names if n in written_set]
        donate = tuple(
            2 + len(feed_names) + i
            for i, n in enumerate(state_names)
            if n in written_set
        )
        skip_reason = None
        if self.disable_donation:
            donate = ()
            skip_reason = "disable_donation"
        elif _cpu_only_target(mesh) and not self._force_donation:
            donate = ()
            skip_reason = "cpu"
        jit_kwargs: Dict[str, Any] = {"donate_argnums": donate}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            in_shardings = in_shardings or {}

            def _state_sharding(n):
                # Per-compile specs (CompiledProgram._state_shardings,
                # e.g. with_expert_parallel) take precedence; Variables
                # may also carry a PartitionSpec-like annotation (tuple
                # of axis-name-or-None per dim) — the GSPMD equivalent
                # of the reference's per-device param placement
                # (multi_devices_graph_pass var scattering).
                if state_shardings and n in state_shardings:
                    return NamedSharding(mesh, P(*state_shardings[n]))
                if block.has_var(n):
                    spec = block.var(n).sharding
                    if spec is not None:
                        return NamedSharding(mesh, P(*spec))
                return NamedSharding(mesh, P())

            # base_key + step_index replicated
            shardings = [NamedSharding(mesh, P()), NamedSharding(mesh, P())]
            for n in feed_names:
                spec = in_shardings.get(n, P())
                shardings.append(NamedSharding(mesh, spec))
            for n in state_names:
                shardings.append(_state_sharding(n))
            jit_kwargs["in_shardings"] = tuple(shardings)
            # pin outputs too: without this GSPMD may hand back written
            # state (e.g. params updated from ZeRO-sharded moments)
            # dp-sharded, and the NEXT call's in_shardings reject the
            # committed arrays
            jit_kwargs["out_shardings"] = tuple(
                [NamedSharding(mesh, P())] * len(fetch_names)
                + [_state_sharding(n) for n in written_names]
            )
        jitted = jax.jit(step_fn, **jit_kwargs)
        blk = _CompiledBlock(
            jitted, list(feed_names), state_names, fetch_names, written_names, donate
        )
        blk.donatable_names = donatable
        blk.donated_names = donatable if donate else []
        blk.donation_skip_reason = skip_reason
        blk.mesh = mesh
        if mesh is not None:
            # dispatch needs the per-arg shardings when this mesh spans
            # processes: host feeds/state must be assembled into global
            # jax.Arrays (BoundStep._globalize) before the jit call
            shardings = jit_kwargs["in_shardings"]
            blk.feed_shardings = {
                n: shardings[2 + i] for i, n in enumerate(feed_names)}
            blk.state_sharding_by_name = {
                n: shardings[2 + len(feed_names) + i]
                for i, n in enumerate(state_names)}
        return blk

    def _compile_multiprocess(
        self, block, feed_names, fetch_names, state_names, written_names
    ) -> _CompiledBlock:
        """One pmap axis ("dp", all rings) over every device in the
        jax.distributed world; each process feeds its local batch and
        c_allreduce_sum lowers to a cross-process psum."""
        if jax.local_device_count() != 1:
            raise NotImplementedError(
                "multi-process collective mode drives one device per "
                f"process; this process sees {jax.local_device_count()} "
                "(use per-process data parallelism OR a mesh, not both)"
            )
        # every ring id appearing in the program rides the one axis
        ring_ids = {0}
        for op in block.ops:
            if "ring_id" in op.attrs:
                ring_ids.add(int(op.attrs["ring_id"]))
        axis_env = {i: "dp" for i in ring_ids}
        fn = build_block_fn(
            block, feed_names, state_names, fetch_names, written_names,
            mesh=None, axis_env=axis_env,
        )
        donate = tuple(
            1 + len(feed_names) + i
            for i, n in enumerate(state_names)
            if n in set(written_names)
        )
        pfn = jax.pmap(fn, axis_name="dp", donate_argnums=donate)

        def wrapped(base_key, step_index, *args):
            global _FOLD_JIT
            if _FOLD_JIT is None:
                _FOLD_JIT = jax.jit(jax.random.fold_in)
            step_key = _FOLD_JIT(base_key, step_index)
            expand = lambda a: jnp.asarray(a)[None]
            outs = pfn(expand(step_key), *map(expand, args))
            return tuple(o[0] for o in outs)

        blk = _CompiledBlock(
            wrapped, list(feed_names), state_names, fetch_names, written_names, donate
        )
        written_set = set(written_names)
        blk.donatable_names = [n for n in state_names if n in written_set]
        blk.donated_names = list(blk.donatable_names) if donate else []
        blk.mesh = "pmap"  # placement owned by pmap, not the feeder
        return blk

    def export_fn(self, program, feed, fetch_list, scope=None, mesh=None):
        """Return (raw_fn, example_args) for a program — the un-jitted
        pure step function plus concrete arguments. Used by
        __graft_entry__."""
        scope = scope or global_scope()
        block = program.global_block()
        feed_vals, _ = self._prepare_feed(block, dict(feed))
        feed_names = sorted(feed_vals)
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in fetch_list
        ]
        state_names, written = self._analyze_block(program, block, feed_names)
        fn = build_block_fn(block, feed_names, state_names, fetch_names, written, mesh)
        state_vals = []
        for n in state_names:
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(f"state var {n!r} missing; run startup first")
            state_vals.append(v)
        key = jax.random.PRNGKey(0)
        args = (key, *(feed_vals[n] for n in feed_names), *state_vals)
        meta = {
            "feed_names": feed_names,
            "state_names": state_names,
            "written_names": written,
            "fetch_names": fetch_names,
        }
        return fn, args, meta

    # -- dataset path (reference executor.py:1191 train_from_dataset) ---------
    def train_from_dataset(
        self, program=None, dataset=None, scope=None, thread=0, debug=False,
        fetch_list=None, fetch_info=None, print_period=100,
    ):
        from ..dataset_runner import run_from_dataset

        return run_from_dataset(
            self, program, dataset, scope, fetch_list, fetch_info,
            print_period, train=True, thread=thread,
        )

    def infer_from_dataset(self, program=None, dataset=None, scope=None, **kw):
        from ..dataset_runner import run_from_dataset

        return run_from_dataset(
            self, program, dataset, scope, kw.get("fetch_list"), kw.get("fetch_info"),
            kw.get("print_period", 100), train=False,
        )

    def close(self):
        self._cache.clear()
        self._bound.clear()
