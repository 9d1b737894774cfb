"""Executor hot-path dispatch + compilation caching (two levels).

The whole point of the TPU-native redesign is that the reference's
per-op interpreter loop disappears into ONE XLA executable per program
— but that only pays off if the per-step python control path stays out
of the way of the fused kernels, and if compile cost is amortized
across processes.

Level 1 — hot-path dispatch (`BoundStep`): everything `Executor.run`
used to redo every step — cache-key assembly, `sorted(feed)`, feed
dtype normalization decisions, the scope walk for state vars, flag
reads, the separate jitted PRNG fold dispatch — is resolved ONCE per
(program uid, version, feed signature, fetch names, mesh fingerprint,
scope, flags generation) and reused. Per step the bound path does: one
dict lookup, one list comprehension over precomputed normalizers, one
jitted call (the RNG fold runs INSIDE the executable — no second
dispatch), and an in-place state write-back. State refs are
re-resolved only when the scope's generation counter bumps (any
external `Scope.set_var`/`erase`), so `scope.set_var` invalidation
stays exact without a per-step scope walk.

Level 2 — compilation caching:
  * a MODULE-LEVEL shared compiled-block cache keyed on a canonical
    program fingerprint (content hash, not object identity), so
    multiple `Executor` instances — the PS/hogwild/predictor
    clone-per-thread patterns — stop re-jitting the same program;
  * jax's persistent on-disk compilation cache, kept where
    `JAX_COMPILATION_CACHE_DIR` says or else at one fixed path inside
    the checkout (`ensure_persistent_cache`), so a NEW PROCESS
    re-running an already-seen program deserializes the executable
    instead of re-compiling.

Counters for all of it are surfaced via `Executor.cache_stats()` and
the profiler host-event log (compiles show up as named ranges in
`tools/timeline.py` traces).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import queue as _queue_mod
import re
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..observability import tracing

_log = logging.getLogger("paddle_tpu.dispatch")

# -- global (process-wide) state -------------------------------------------

# canonical-fingerprint-keyed compiled blocks, shared by every Executor.
# LRU-bounded: every Program mutation mints a new fingerprint, and
# nothing else ever evicts the stranded executables of old versions in
# a long-lived process
_SHARED_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()
_SHARED_CACHE_CAP = 512

# process-wide counters; per-Executor counters live on the Executor
_GLOBAL_STATS: Dict[str, Any] = {
    "jit_compiles": 0,          # compiled blocks built in this process
    "shared_cache_hits": 0,     # per-executor miss served by shared cache
    "build_time_s": 0.0,        # python-side analysis + fn construction
    "compile_time_s": 0.0,      # first-call time: trace + XLA compile (+1 step)
}

# the persistent cache's directory when JAX_COMPILATION_CACHE_DIR is not
# set: resolved from the package's location, because the path is part
# of the cache key — a home directory, a temporary name or a pid would
# never hit again on the next machine
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_PERSISTENT_DIR: Optional[str] = None

# every live BoundStep in the process — the donation/host-sync audit
# (tools/donation_audit.py) walks this to prove each subsystem's
# executables donate their rewritten state and to attribute host-sync
# points per call site. Weak: a retired bound step drops out on GC.
_LIVE_BOUND: "weakref.WeakSet" = weakref.WeakSet()


def live_bound_steps() -> List["BoundStep"]:
    """Snapshot of every live BoundStep (any executor, any subsystem).
    Order is unspecified; callers needing stable reports should sort on
    ``audit_info()['tag']``."""
    return list(_LIVE_BOUND)


def ensure_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache (idempotent) and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    the directory — jax reads it at import and nothing here touches
    it. Otherwise the cache is ``DEFAULT_CACHE_DIR``, one fixed path
    inside the checkout. An unusable directory raises."""
    global _PERSISTENT_DIR
    if _PERSISTENT_DIR is not None:
        return _PERSISTENT_DIR
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    # the default thresholds skip small/fast compiles — a framework
    # whose unit of compilation is the WHOLE train step wants every
    # executable persisted, including the tiny eval/infer programs
    # that dominate cold-start counts
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _PERSISTENT_DIR = path
    return path


def persistent_cache_dir() -> Optional[str]:
    return _PERSISTENT_DIR


def program_fingerprint(program) -> str:
    """Canonical content hash of a Program: two Programs with identical
    IR (e.g. `clone()`s, or the same model re-built in two processes)
    fingerprint equal, so they share compiled blocks. Cached per
    (uid, version); volatile identity fields are excluded."""
    cached = getattr(program, "_fp_cache", None)
    if cached is not None and cached[0] == program.version:
        return cached[1]
    try:
        d = program.to_dict()
        d.pop("version", None)
        d.pop("random_seed", None)  # consumed at step-key time, not compile
        # compile-affecting Program attrs that to_dict() does not
        # serialize — two content-identical programs differing in any
        # of these must NOT share an executable (e.g. gpipe vs 1f1b
        # schedules lower to different step functions)
        extra = {
            "pipeline_cuts": getattr(program, "_pipeline_cuts", None),
            "pipeline_mb": getattr(program, "_pipeline_microbatches", None),
            "pipeline_sched": getattr(program, "_pipeline_schedule", None),
            "gm_k": getattr(program, "_gradient_merge_k", None),
            "gm_avg": getattr(program, "_gradient_merge_avg", None),
            "dist_plan": getattr(program, "_dist_plan", None),
            # bucketed/quantized collectives (parallel/collectives.py):
            # two content-identical programs whose plans differ (quant
            # mode, skip_reduce timing variant) lower differently
            "collective": (
                program._collective_plan.fingerprint()
                if getattr(program, "_collective_plan", None) is not None
                else None),
        }
        digest = hashlib.sha256(
            json.dumps([d, extra], sort_keys=True, default=str).encode()
        ).hexdigest()
    except Exception:  # noqa: BLE001 — unserializable attr: identity fallback
        digest = f"uid:{program.uid}"
    program._fp_cache = (program.version, digest)
    return digest


def autotune_for_program(program) -> Dict[str, Any]:
    """THE autotune-profile construction seam (Executor bind, the
    serving/generation engine constructors): unwrap a CompiledProgram,
    fingerprint, and best-effort apply a matching tuned-flags profile
    (flags.autotune_apply_for — once per fingerprint per process,
    explicit user flags always win, absence costs one set probe).
    Returns the flags actually applied so callers can react to a
    flags-generation bump (e.g. recompute a bound key)."""
    if program is None:
        return {}
    from .. import flags as _flags

    prog = getattr(program, "_program", None) or program
    try:
        return _flags.autotune_apply_for(program_fingerprint(prog))
    except Exception:  # noqa: BLE001 — construction must survive
        return {}


def shared_cache_get(key):
    hit = _SHARED_CACHE.get(key)
    if hit is not None:
        _SHARED_CACHE.move_to_end(key)
    return hit


def shared_cache_put(key, compiled) -> None:
    _SHARED_CACHE[key] = compiled
    while len(_SHARED_CACHE) > _SHARED_CACHE_CAP:
        _SHARED_CACHE.popitem(last=False)


def shared_cache_size() -> int:
    return len(_SHARED_CACHE)


def cache_stats() -> Dict[str, Any]:
    """Process-wide dispatch/compile counters (Executor.cache_stats()
    merges these under the "process" key)."""
    out = dict(_GLOBAL_STATS)
    out["shared_compiled_blocks"] = len(_SHARED_CACHE)
    out["persistent_cache_dir"] = _PERSISTENT_DIR
    return out


def reset_cache_stats() -> None:
    for k in _GLOBAL_STATS:
        _GLOBAL_STATS[k] = 0.0 if isinstance(_GLOBAL_STATS[k], float) else 0


def scope_chain_generation(scope) -> int:
    """Sum of generation counters along the parent chain: bumps when
    any scope a lookup could resolve through is mutated. Chains are
    1-2 deep in practice, so this is a handful of attribute reads."""
    g = scope.generation
    s = scope.parent
    while s is not None:
        g += s.generation
        s = s.parent
    return g


def validate_feed_shardings(feed_names, feed_shapes, in_shardings, mesh,
                            strategy: Optional[str]) -> None:
    """Pre-flight divisibility check for sharded feeds: a batch that
    does not divide over the mesh axis surfaces here as a clear
    message naming the strategy, not as an opaque GSPMD/shard_map
    failure three layers down."""
    if mesh is None or not in_shardings:
        return
    axis_size = dict(mesh.shape)
    label = strategy or "the compiled mesh"
    for name, shape in zip(feed_names, feed_shapes):
        spec = in_shardings.get(name)
        if spec is None:
            continue
        for dim, axes in enumerate(tuple(spec)):
            if axes is None or dim >= len(shape):
                continue
            axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
            k = 1
            for a in axes_t:
                k *= int(axis_size.get(a, 1))
            if k > 1 and shape[dim] % k:
                raise ValueError(
                    f"{label}: feed {name!r} dim {dim} has size "
                    f"{shape[dim]}, not divisible by mesh axis"
                    f"{'es' if len(axes_t) > 1 else ''} "
                    f"{'x'.join(axes_t)} (size {k}) — pad the "
                    f"{'batch' if dim == 0 else 'dimension'} or change "
                    "the parallel degree")


# -- feed normalization plans ----------------------------------------------


def _feed_normalizer(want: Optional[str]) -> Callable[[Any], Any]:
    """One per feed name. jax.Arrays pass through zero-copy (DataLoader
    prefetch already device_put the batch — a numpy round-trip would
    undo the async H2D); everything else is np.asarray'd and cast to
    the precomputed target dtype."""
    import jax

    if want is None:
        def norm(v):
            if isinstance(v, jax.Array):
                return v
            return np.asarray(v)
    else:
        want_np = np.dtype(want)

        def norm(v):
            if isinstance(v, jax.Array):
                return v
            arr = np.asarray(v)
            if arr.dtype != want_np:
                arr = arr.astype(want_np, copy=False)
            return arr
    return norm


def _want_dtype(block, name: str, raw_dtype) -> Optional[str]:
    """The same dtype policy as Executor._prepare_feed, decided once at
    bind time instead of per step."""
    import jax

    from ..core.framework import convert_dtype

    if block.has_var(name):
        want = convert_dtype(block.var(name).dtype)
        if want == "int64" and not jax.config.jax_enable_x64:
            want = "int32"
        return want
    raw = np.dtype(raw_dtype) if raw_dtype is not None else None
    if raw == np.float64:
        return "float32"
    if raw == np.int64 and not jax.config.jax_enable_x64:
        return "int32"
    return None


def feed_signature(feed: Dict[str, Any]) -> Tuple:
    """(name, shape, dtype) per feed, sorted by name — WITHOUT
    materializing anything: jax.Arrays and numpy arrays answer from
    their metadata; only values with neither attribute (lists,
    scalars) pay one np.asarray. This is the signature both the
    Predictor's bucket cache and the pipelined driver key on, so it
    must cost attribute reads, not copies."""
    sig = []
    for n in sorted(feed):
        v = feed[n]
        shp = getattr(v, "shape", None)
        dt = getattr(v, "dtype", None)
        if shp is None or dt is None:
            v = np.asarray(v)
            shp, dt = v.shape, v.dtype
        sig.append((n, tuple(shp), str(dt)))
    return tuple(sig)


def pad_to(value, pads) -> Any:
    """Zero-pad one feed value, honoring the BoundStep feed-normalizer
    policy: a device-resident jax.Array is padded ON DEVICE (jnp.pad —
    an np.pad here would round-trip the batch through host memory and
    undo the loader's async H2D); anything else pads as numpy. No-op
    (and no copy) when no padding is needed."""
    if not any(p != (0, 0) for p in pads):
        return value
    import jax

    if isinstance(value, jax.Array):
        import jax.numpy as jnp

        return jnp.pad(value, pads)
    return np.pad(np.asarray(value), pads)


def _globalizing_normalizer(norm, sharding):
    """Compose a feed normalizer with local->global assembly for a
    mesh spanning processes: every process passes its LOCAL rows and
    ``jax.make_array_from_process_local_data`` lines them up into one
    global array per the feed's sharding. Values that are already
    jax.Arrays (a coordinator-aware loader built them globally) pass
    through untouched."""
    import jax

    def globalize(v):
        v = norm(v)
        if isinstance(v, jax.Array) or sharding is None:
            return v
        arr = np.asarray(v)
        if getattr(sharding, "is_fully_addressable", True):
            return jax.device_put(arr, sharding)
        return jax.make_array_from_process_local_data(sharding, arr)

    return globalize


def hlo_donation_aliases(compiled, hlo_text: str):
    """What XLA made of a compiled block's donation, read from its
    optimized HLO (``BoundStep.aot_compiled().as_text()``, or an
    ``Executor.aot_compile`` for a described chip): ``{donated state
    name: written name whose output its buffer is aliased onto, or
    None}``. Donation only offers a buffer; jax pairs offered inputs
    with outputs of one shape and type in order, so a name aliased onto
    ANOTHER name's output (or onto none) is a buffer XLA has to copy
    into. In-place state reads ``name -> name`` for every name."""
    # jit drops unused arguments, so a parameter's number is not its
    # position: its op_name says which of step_fn's *args it is
    arg_of_param = {
        int(m.group(1)): int(m.group(2)) for m in re.finditer(
            r'parameter\((\d+)\)[^\n]*?op_name="args\[(\d+)\]"', hlo_text)}
    header = re.search(r"input_output_alias=\{(.*?)\}, \w+=", hlo_text)
    out_of_arg = {}
    for m in re.finditer(r"\{(\d*)\}: \((\d+), \{\}",
                         header.group(1) if header else ""):
        arg = arg_of_param.get(int(m.group(2)))
        if arg is not None:
            out_of_arg[arg] = int(m.group(1) or 0)
    n_feed, n_fetch = len(compiled.feed_names), len(compiled.fetch_names)
    state_pos = {n: i for i, n in enumerate(compiled.state_names)}
    written = compiled.written_names
    report = {}
    for n in getattr(compiled, "donated_names", ()) or ():
        out = out_of_arg.get(n_feed + state_pos[n])
        j = None if out is None else out - n_fetch
        report[n] = (written[j] if j is not None and 0 <= j < len(written)
                     else None)
    return report


# -- the bound step ---------------------------------------------------------


class BoundStep:
    """One fully-resolved dispatch path: (program, feed signature,
    fetch list, mesh, scope, flags snapshot) -> compiled executable +
    precomputed arg assembly. `Executor.run` resolves this once and
    thereafter the per-step work is a dict hit + one jitted call."""

    __slots__ = (
        "executor", "compiled", "scope", "block", "base_key",
        "feed_plan", "state_vals", "written_into_state", "scope_gen",
        "n_fetch", "benchmark", "obs_tel", "rows_hint",
        "host_sync_calls", "feed_avals", "feed_shardings", "__weakref__",
    )

    def __init__(self, executor, compiled, scope, block, raw_dtypes,
                 feed_avals=None):
        from ..flags import flag

        self.executor = executor
        self.compiled = compiled
        # the normalized feed signature this step was bound for, in
        # compiled.feed_names order (aot_compiled lowers against it)
        self.feed_avals = feed_avals
        # the shardings of the committed jax.Arrays among the feeds of
        # the last run (None for every other feed): jit specialises on
        # them, so aot_compiled lowers against them
        self.feed_shardings: Optional[List[Any]] = None
        self.scope = scope
        self.block = block
        self.benchmark = bool(flag("benchmark"))
        # observability, resolved ONCE at bind time (the bound key
        # carries the flags generation, so a flag flip re-binds):
        # obs_tel holds pre-resolved registry instruments — per step
        # the cost is one perf_counter pair + a few locked adds
        self.obs_tel = None
        if flag("observability_metrics"):
            from ..observability.registry import step_telemetry

            self.obs_tel = step_telemetry()
        # raw_dtypes: the CALLER's per-feed dtypes (pre-normalization)
        # — the plan must normalize what actually arrives each step
        raw_dtypes = raw_dtypes or {}
        self.feed_plan = [
            (n, _feed_normalizer(_want_dtype(block, n, raw_dtypes.get(n))))
            for n in compiled.feed_names
        ]
        # multi-host mesh (devices from >1 process): host feeds are
        # each process's LOCAL batch (a rank-sharded GeneratorLoader's
        # yield) and must be assembled into GLOBAL jax.Arrays before
        # the jit call — numpy cannot cross a non-addressable
        # in_sharding. Resolved once here; single-process meshes keep
        # the zero-overhead plan above.
        from ..distributed.coordinator import spans_processes

        if spans_processes(compiled.mesh) and compiled.feed_shardings:
            self.feed_plan = [
                (n, _globalizing_normalizer(
                    norm, compiled.feed_shardings.get(n)))
                for n, norm in self.feed_plan
            ]
        self.n_fetch = len(compiled.fetch_names)
        # positions of written state inside the state arg list (for the
        # in-place cached-ref update after each step); written names
        # that are not state inputs only go to the scope
        state_pos = {n: i for i, n in enumerate(compiled.state_names)}
        self.written_into_state = [
            (j, state_pos.get(n)) for j, n in enumerate(compiled.written_names)
        ]
        seed = 0
        prog = getattr(block, "program", None)
        if prog is not None:
            seed = prog.random_seed or 0
        self.base_key = executor._base_key(seed)
        self.state_vals: List[Any] = []
        self.scope_gen = -1  # force first resolve
        # callers whose first feed's dim 0 is NOT the example count
        # (generation's fixed decode-lane batch is mostly idle padding;
        # its first sorted feed is a page pool) set this per step so
        # the paddle_step_* examples/sec telemetry stays honest
        self.rows_hint: Optional[int] = None
        # host-sync accounting for the donation/host-sync audit: every
        # return_numpy fetch (and every FLAGS_benchmark forced sync) is
        # a point where the host blocks on the device
        self.host_sync_calls = 0
        _LIVE_BOUND.add(self)

    # -- state resolution ---------------------------------------------------
    def _resolve_state(self):
        scope, block = self.scope, self.block
        # snapshot BEFORE the walk: a concurrent set_var mid-walk must
        # leave the counters unequal so the next step re-resolves
        gen = scope_chain_generation(scope)
        # mesh-bound executable: state (startup init on the default
        # device, a restored checkpoint's host values) is placed per
        # each var's compiled sharding here, BEFORE the first call.
        # Left where startup put it, the first call would trace against
        # single-device types and the second — fed the first's
        # mesh-sharded outputs — would trace and compile the whole
        # step again (jax types carry the mesh).
        shardings = self.compiled.state_sharding_by_name
        vals = []
        for n in self.compiled.state_names:
            v = scope.find_var(n)
            if v is None:
                if block.has_var(n) and block.var(n).is_data:
                    raise RuntimeError(
                        f"data var {n!r} was not fed — add it to the feed dict"
                    )
                raise RuntimeError(
                    f"persistable var {n!r} not found in scope — run the "
                    "startup program first"
                )
            if shardings:
                v = self._place_state(shardings.get(n), v)
            vals.append(v)
        self.state_vals = vals
        self.scope_gen = gen

    @staticmethod
    def _place_state(sharding, v):
        """One state var as a global jax.Array laid out per its
        compiled sharding. Arrays already laid out that way (the
        previous step's outputs) pass through; on a mesh spanning
        processes a host value (identical on every process by the
        deterministic-replay contract) is assembled from each
        process's copy."""
        import jax

        if sharding is None:
            return v
        if isinstance(v, jax.Array):
            if v.sharding == sharding or not v.is_fully_addressable:
                return v
            return jax.device_put(v, sharding)
        arr = np.asarray(v)
        if getattr(sharding, "is_fully_addressable", True):
            return jax.device_put(arr, sharding)
        # global_shape == local shape selects full-value semantics:
        # every process holds the whole array (identical by the
        # deterministic-replay contract) and each device takes its
        # slice of it — the host-restore case, vs. the per-process
        # LOCAL-batch semantics feeds use
        return jax.make_array_from_process_local_data(
            sharding, arr, global_shape=arr.shape)

    # -- the hot path -------------------------------------------------------
    def run(self, feed: Dict[str, Any], return_numpy: bool):
        with tracing.annotation("executor/feed"):
            ordered = [norm(feed[n]) for n, norm in self.feed_plan]
        return self._run_ordered(ordered, return_numpy)

    def _run_ordered(self, ordered: List[Any], return_numpy: bool):
        """Dispatch one already-normalized arg list. This is THE single
        execution path: ``run`` (sync callers), ``run_pipelined`` (the
        async feed stage) and every subsystem above them funnel here, so
        per-step accounting and every future optimization land in
        exactly one place."""
        scope = self.scope
        entry_gen = scope_chain_generation(scope)
        if entry_gen != self.scope_gen:
            self._resolve_state()
            entry_gen = self.scope_gen
        self.feed_shardings = [
            v.sharding if getattr(v, "committed", False) else None
            for v in ordered]
        ex = self.executor
        ex._run_counter += 1
        compiled = self.compiled
        fn = compiled.fn
        counter = np.int32(ex._run_counter)
        t0 = time.perf_counter() if self.benchmark else 0.0
        tel = self.obs_tel
        if compiled.compile_time is None:
            # compile path: counted as a compile event, NOT a step
            # sample — seconds of XLA compile in the step histogram
            # would bury the real quantiles
            tel = None
        t_obs = time.perf_counter() if tel is not None else 0.0
        if compiled.compile_time is None:
            outs = self._first_call(fn, counter, ordered)
        else:
            with tracing.span("executor/step",
                              lambda: {"step": int(counter),
                                       "tag": compiled.tag or "program"}):
                outs = fn(self.base_key, counter, *ordered, *self.state_vals)
        n_fetch = self.n_fetch
        new_state = outs[n_fetch:]
        if new_state:
            state_vals = self.state_vals
            sv = scope.vars
            with tracing.annotation("executor/writeback"):
                for j, pos in self.written_into_state:
                    v = new_state[j]
                    sv[compiled.written_names[j]] = v
                    if pos is not None:
                        state_vals[pos] = v
            # the write-back stored directly (no per-name set_var
            # bump): stamp the generation once so OTHER programs bound
            # to this scope re-resolve. Record entry_gen + 1 — OUR one
            # bump — not the live counter: a concurrent external
            # set_var during the jitted call (the PS communicator
            # pattern) must leave the counters unequal so the next
            # step re-resolves instead of absorbing the update
            scope._bump_generation()
            self.scope_gen = entry_gen + 1
        fetched = list(outs[:n_fetch])
        if tel is not None:
            # host-side step cadence (the device work is NOT forced
            # synchronous — steady-state examples/sec only needs the
            # dispatch-to-dispatch interval, and a sync here would
            # serialize the async pipeline the loader exists to fill)
            ms = (time.perf_counter() - t_obs) * 1e3
            rows = self.rows_hint
            if rows is None:
                rows = 0
                if ordered:
                    shp = getattr(ordered[0], "shape", None)
                    if shp:
                        rows = int(shp[0])
            tel.record(ms, rows, step=int(counter))
        if self.benchmark:
            # FLAGS_benchmark (reference operator.cc:1006 adds per-op
            # device syncs): force device sync + report wall time
            self.host_sync_calls += 1
            for v in fetched + list(new_state[:1]):
                np.asarray(v)
            _log.info("[benchmark] Executor.run: %.3f ms",
                      (time.perf_counter() - t0) * 1e3)
        if return_numpy:
            import jax

            from ..core.executor import _fetch_to_host

            if fetched:
                self.host_sync_calls += 1
            # where the host waits for the device: for the step to be
            # reported done, then for the copy. The copies are started
            # first, as np.asarray would start them, so that each runs
            # right behind the step and not after the host has heard
            # that the step finished (a round trip more: 0.1 ms a step
            # on a v5e)
            with tracing.annotation("executor/fetch"):
                with tracing.annotation("executor/fetch_ready"):
                    for v in fetched:
                        if isinstance(v, jax.Array):
                            v.copy_to_host_async()
                    jax.block_until_ready(fetched)
                fetched = [_fetch_to_host(v) for v in fetched]
        return fetched

    # -- async host/device pipeline -----------------------------------------
    def run_pipelined(self, feeds: Iterable[Dict[str, Any]],
                      return_numpy: bool = True, depth: int = 2):
        """Overlapped driver for a stream of same-signature feeds:
        yields each step's fetches in order, bit-identical to calling
        ``run`` per feed.

        A dedicated feeder thread runs the host side of step N+1 —
        feed normalization/padding/casting plus the ``jax.device_put``
        H2D start — while step N executes on device, through a bounded
        (``depth``, default 2 = double buffer) queue. The consumer
        (this generator, on the caller's thread) does only the
        dispatch + state write-back, so with a deep enough device
        queue the hot loop never blocks on host feed work. Values that
        are ALREADY jax.Arrays (the GeneratorLoader device buffer)
        pass through untouched — a device-resident batch is never
        re-materialized on host.

        Semantics:
          * ordering — results come back in feed order, always;
          * exceptions — an error raised by the feed iterable or the
            normalization of feed K surfaces here after step K-1's
            result, never silently; the feeder thread always exits;
          * shutdown — closing/abandoning the generator mid-stream
            stops and joins the feeder thread (no orphan thread, no
            pinned device batches);
          * state — scope state flows through the dispatch exactly as
            in ``run`` (the feeder touches feeds only, never state).

        Overlap efficiency is exported as ``paddle_step_overlap_*``:
        host feed time spent per step, how much of it the consumer
        actually waited for (NOT hidden), and the hidden fraction.
        """
        import jax

        depth = max(1, int(depth))
        q: "_queue_mod.Queue" = _queue_mod.Queue(maxsize=depth)
        stop = threading.Event()
        _END = object()
        overlap = None
        if self.obs_tel is not None:
            from ..observability.registry import overlap_telemetry

            overlap = overlap_telemetry()
        plan = self.feed_plan
        # only single-device targets device_put eagerly: for a mesh
        # executable the jit call owns placement/sharding, and a
        # default-device put here would force a resharding copy
        put_ok = getattr(self.compiled, "mesh", None) is None

        def feeder():
            err = None
            try:
                it = iter(feeds)
                while True:
                    if stop.is_set():
                        return
                    # the timed span starts BEFORE the next() pull: the
                    # iterable IS the input pipeline (reader/decode), and
                    # its production latency is exactly the host work the
                    # overlap hides — paddle_step_overlap_feed_ms must
                    # account for it or hidden-fraction under-reports
                    t0 = time.perf_counter()
                    try:
                        feed = next(it)
                    except StopIteration:
                        break
                    if stop.is_set():
                        # the consumer shut down while next() blocked:
                        # don't normalize/device_put one more batch
                        # (pinning device memory) on the way out
                        return
                    ordered = [norm(feed[n]) for n, norm in plan]
                    if put_ok:
                        ordered = [
                            v if isinstance(v, jax.Array)
                            else jax.device_put(v)
                            for v in ordered
                        ]
                    item = (ordered, (time.perf_counter() - t0) * 1e3)
                    while True:
                        if stop.is_set():
                            return
                        try:
                            q.put(item, timeout=0.05)
                            break
                        except _queue_mod.Full:
                            continue
            except BaseException as e:  # noqa: BLE001 — surfaced at the yield
                err = e
            while not stop.is_set():
                try:
                    q.put((_END, err), timeout=0.05)
                    return
                except _queue_mod.Full:
                    continue

        t = threading.Thread(target=feeder, name="pt-dispatch-feeder",
                             daemon=True)
        t.start()
        try:
            while True:
                try:
                    item = q.get_nowait()
                    waited_ms = 0.0
                except _queue_mod.Empty:
                    t0 = time.perf_counter()
                    item = q.get()
                    waited_ms = (time.perf_counter() - t0) * 1e3
                payload, extra = item
                if payload is _END:
                    if extra is not None:
                        raise extra
                    return
                fetched = self._run_ordered(payload, return_numpy)
                if overlap is not None:
                    overlap.record(extra, waited_ms)
                yield fetched
        finally:
            stop.set()
            # unblock a feeder parked in q.put, then reap it
            try:
                while True:
                    q.get_nowait()
            except _queue_mod.Empty:
                pass
            t.join(timeout=5.0)

    # -- audit ---------------------------------------------------------------
    def audit_info(self) -> Dict[str, Any]:
        """One report row for tools/donation_audit.py: which rewritten
        state buffers this executable donates (buffer aliasing) vs
        should donate, why donation was skipped if it was, how often
        callers forced a host sync on the fetch path, and the
        XLA memory/cost analysis captured at compile time (present
        when ``observability_xla_analysis`` was on)."""
        c = self.compiled
        donatable = list(getattr(c, "donatable_names", ()) or ())
        donated = list(getattr(c, "donated_names", ()) or ())
        skip = getattr(c, "donation_skip_reason", None)
        # mesh-bound executables are first-class audit subjects — a
        # sharded train state that stops being donated doubles the
        # per-device HBM exactly like a single-device one; the mesh
        # shape is reported so the allowlist diff can tell the sharded
        # and unsharded variants of one program apart
        mesh = getattr(c, "mesh", None)
        if mesh is not None and hasattr(mesh, "shape"):
            mesh = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        return {
            "tag": c.tag or "program",
            "mesh": mesh,
            "n_feeds": len(c.feed_names),
            "n_state": len(c.state_names),
            "n_written": len(c.written_names),
            "donatable": donatable,
            "donated": donated,
            "donation_missed": ([] if skip else
                                [n for n in donatable if n not in donated]),
            "donation_skip_reason": skip,
            # what the donated arrays hold, as the scope has them now
            "donated_bytes": sum(
                int(getattr(self.scope.find_var(n), "nbytes", 0))
                for n in donated),
            "host_sync_calls": self.host_sync_calls,
            "xla_analysis": dict(getattr(c, "analysis", None) or {}),
        }

    def aot_compiled(self):
        """The jax compiled object of this step for the feed signature
        it was bound with: ``.as_text()`` is the optimized HLO the
        device runs, ``.memory_analysis()``/``.cost_analysis()`` the
        target's own accounting. jax exposes these only on an
        AOT-compiled object, not on the jit path, so this costs one
        lower + compile — jax's own answer from memory when the step
        has run: a feed that last arrived as a committed jax.Array (the
        output of another computation) is lowered with its sharding,
        which is what jit specialised on."""
        import jax

        if self.scope_gen != scope_chain_generation(self.scope):
            self._resolve_state()
        avals = [a if s is None
                 else jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
                 for a, s in zip(self.feed_avals, self.feed_shardings
                                 or [None] * len(self.feed_avals))]
        return self.compiled.fn.lower(
            self.base_key, np.int32(0), *avals, *self.state_vals).compile()

    def donation_aliases(self):
        """``hlo_donation_aliases`` of this step's optimized HLO (one
        lower + compile, as ``aot_compiled``)."""
        return hlo_donation_aliases(self.compiled,
                                    self.aot_compiled().as_text())

    def _first_call(self, fn, counter, ordered):
        """First invocation of a fresh compiled block: this is where
        jax traces + XLA compiles. Timed, counted, and surfaced as the
        range ``executor/compile`` so compiles are visible in timelines."""
        from .. import profiler

        tag = self.compiled.tag or "program"
        t0 = time.perf_counter()
        with tracing.annotation("executor/compile", {"tag": tag}):
            outs = fn(self.base_key, counter, *ordered, *self.state_vals)
        dt = time.perf_counter() - t0
        profiler.record_compile(tag, dt)
        self.compiled.compile_time = dt
        _GLOBAL_STATS["compile_time_s"] += dt
        ex = self.executor
        ex._stats["compile_time_s"] = ex._stats.get("compile_time_s", 0.0) + dt
        self._xla_analysis()
        return outs

    def _xla_analysis(self):
        """Per-executable XLA ``memory_analysis()``/``cost_analysis()``
        surfaced as registry gauges (labeled by executable tag) and a
        flight-recorder entry. Behind ``observability_xla_analysis``:
        it costs one extra lower+compile per executable
        (``aot_compiled``). Every sub-step is best-effort: backends
        expose different analysis subsets."""
        from ..flags import flag

        if not flag("observability_xla_analysis"):
            return
        try:
            comp = self.aot_compiled()
        except Exception:  # noqa: BLE001 — analysis must never fail a step
            return
        vals: Dict[str, float] = {}
        try:
            mem = comp.memory_analysis()
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes",
                         "generated_code_size_in_bytes"):
                v = getattr(mem, attr, None)
                if isinstance(v, (int, float)):
                    vals["paddle_xla_"
                         + attr.replace("_size_in_bytes", "_bytes")] = v
        except Exception:  # noqa: BLE001
            pass
        try:
            cost = comp.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            for key, name in (("flops", "paddle_xla_flops"),
                              ("bytes accessed", "paddle_xla_bytes_accessed")):
                v = cost.get(key) if hasattr(cost, "get") else None
                if isinstance(v, (int, float)):
                    vals[name] = v
        except Exception:  # noqa: BLE001
            pass
        if not vals:
            return
        from ..observability import flight
        from ..observability.registry import registry

        tag = self.compiled.tag or "program"
        reg = registry()
        for name, v in vals.items():
            reg.gauge(name, "XLA compile-time analysis").labels(
                executable=tag).set(v)
        self.compiled.analysis = dict(vals)
        flight.note("xla_analysis", executable=tag, **vals)
