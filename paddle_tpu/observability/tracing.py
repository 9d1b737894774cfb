"""Trace spans: named host ranges on the profiler's clock, with
parentage when ``observability_tracing`` is on.

A ``jax.profiler.TraceAnnotation`` gives named host ranges; what it
cannot say is which serving request a micro-batch served, or which
supervisor step a rollback undid — ranges on different threads have no
shared identity. A span adds exactly that: a ``trace_id`` (one per root
request/step), a ``span_id``, and a ``parent_id``, carried in the
event's ``args`` so ``tools_timeline`` can draw Perfetto flow arrows
across threads (serving request -> admission queue -> micro-batch ->
worker -> dispatch -> jit step).

Propagation is ambient within a thread (a thread-local stack: nested
``span()`` calls parent automatically) and explicit across threads —
the submitting side stores ``ctx = span(...)``'s yielded context on
the work item, and the consuming thread opens its span with
``parent=ctx`` (or wraps its whole handling in ``attach(ctx)``).

Cost model. ALWAYS ON, whatever the flag: every ``span`` is a
``jax.profiler.TraceAnnotation`` — a named range in any profiler
session attached to the process (``jax.profiler.start_trace``, the
benchmark's ``--trace 1``), on the clock the device trace shares, and
a no-op in the runtime when no session records — plus, inside a
``paddle_tpu.profiler`` session (``profiler._recording``, one read of
a plain bool), one host-event append. That off path is a slotted
context manager of about a microsecond, so the step loops (``Executor``
/ ``BoundStep``: ``executor/bind|feed|step|fetch``; ``GenerationEngine``:
``generation/<phase>``) open their spans unconditionally, under
CONSTANT names: what varies per step goes into ``args``, which may be
a callable and is then built only when something will keep it. With
``observability_tracing`` ON a span additionally gets its identity:
two lock-free id draws, parentage from the thread-local stack,
``flow_from`` arrows, one flight-ring append (so a trace can be
assembled across threads and processes) — ``executor/step`` and
``generation/step`` do, as before; the other phases of the two loops are
``annotation``s, which stay on the off path, so the flag still costs one
ring entry a step.

The host's stalls have ranges of their own on the same off path:
``executor/fetch_ready`` and ``generation/fetch_ready`` (the wait for a
finished step inside ``executor/fetch`` / ``generation/fetch``; the
rest of the outer range is the copy to the host),
``executor/writeback`` (the written state stored into the scope) and
``executor/compile`` (a first call; the tag in ``args``). The cyclic
collector gets one ``gc.callbacks`` hook a process (``install_gc_hook``,
called by the package): a collection of generation 1 or 2 is the range
``executor/gc`` on the thread that ran it (the generation in ``args``),
and every collection, generation 0 too, adds to the registry's
``paddle_gc_collections_total{generation}`` and
``paddle_gc_pause_seconds_total{generation}``. The names carry the
runtime's ``executor/`` prefix because a profiler reader keeps host
ranges by prefix (``bench/``, ``executor/``, ``generation/``).
"""

from __future__ import annotations

import functools
import gc
import os
import threading
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Union

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .. import profiler
from ..flags import _flags  # hot path: direct flag-store reads
from . import flight, registry

__all__ = ["SpanContext", "span", "annotation", "traced", "attach", "current",
           "enabled", "install_gc_hook"]


class SpanContext(NamedTuple):
    trace_id: str
    span_id: str


_tls = threading.local()

# process-unique ids without locks or syscalls: a per-process random
# prefix + a per-thread random prefix + a per-thread counter. Spans
# from two processes (or a reused OS thread ident) stay distinct.
_proc_prefix = os.urandom(4).hex()


def _new_id() -> str:
    n = getattr(_tls, "id_n", None)
    if n is None:
        _tls.id_prefix = f"{_proc_prefix}{os.urandom(3).hex()}"
        n = 0
    _tls.id_n = n + 1
    return f"{_tls.id_prefix}{n:08x}"


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def enabled() -> bool:
    return bool(_flags["observability_tracing"])


def current() -> Optional[SpanContext]:
    """The innermost active span on THIS thread (the ambient parent),
    or None outside any span."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


class _AmbientType:
    """Sentinel for "parent from the thread-local stack". Stable repr:
    the api-spec ratchet records default values, and a bare object()'s
    repr embeds a memory address."""

    def __repr__(self):
        return "<ambient parent>"


_AMBIENT = _AmbientType()


class _Span:
    """One traced range. Slotted class CM instead of a
    @contextmanager generator: the per-step/per-request path cannot
    afford two generator frames per span."""

    __slots__ = ("name", "meta", "ctx", "t0", "_ta", "_stack")

    def __init__(self, name: str, args: Optional[Dict[str, Any]], parent):
        st = _stack()
        par = (st[-1] if st else None) if parent is _AMBIENT else parent
        ctx = SpanContext(par.trace_id if par is not None else _new_id(),
                          _new_id())
        meta = dict(args) if args else {}
        meta["trace_id"] = ctx.trace_id
        meta["span_id"] = ctx.span_id
        if par is not None:
            meta["parent_id"] = par.span_id
        self.name = name
        self.meta = meta
        self.ctx = ctx
        self._stack = st

    def __enter__(self) -> SpanContext:
        self._stack.append(self.ctx)
        self._ta = _TraceAnnotation(self.name)
        self._ta.__enter__()
        self.t0 = time.time()
        return self.ctx

    # entry keys the recorder owns: user span args must not be able to
    # collide with them (a span("x", {"name": ...}) would otherwise
    # TypeError at exit)
    _RESERVED = frozenset(("kind", "t", "name", "ts", "dur", "tid"))

    def __exit__(self, *exc):
        dur = time.time() - self.t0
        self._ta.__exit__(*exc)
        self._stack.pop()
        profiler.emit_event(self.name, self.t0, dur, self.meta)
        entry = {"kind": "span", "t": self.t0, "name": self.name,
                 "ts": self.t0, "dur": dur, "tid": profiler.thread_tid()}
        for k, v in self.meta.items():
            if k not in self._RESERVED:
                entry[k] = v
        flight.append_entry(entry)
        return False


class _Annotation:
    """The off path of ``span``: the range on the profiler's clock and,
    inside a ``paddle_tpu.profiler`` session, the host-event log entry.
    No ids, no parentage, no flight ring."""

    __slots__ = ("name", "args", "t0", "_ta")

    def __init__(self, name: str, args):
        self.name = name
        self.args = args

    def __enter__(self) -> None:
        self._ta = _TraceAnnotation(self.name)
        self._ta.__enter__()
        self.t0 = time.time() if profiler._recording else 0.0
        return None

    def __exit__(self, *exc):
        self._ta.__exit__(*exc)
        if self.t0:
            args = self.args
            profiler.emit_event(self.name, self.t0, time.time() - self.t0,
                                args() if callable(args) else args)
        return False


def span(name: str,
         args: Union[Dict[str, Any], Callable[[], Dict[str, Any]], None] = None,
         parent=_AMBIENT):
    """Context manager for one traced range, under a CONSTANT ``name``.
    Yields the SpanContext (or None when ``observability_tracing`` is
    off: the range is then a profiler annotation and nothing more).

    ``args``: a dict of what varies (step number, row count,
    ``flow_from`` span ids), or a callable returning one — called only
    when the flag is on or a ``paddle_tpu.profiler`` session records,
    so a hot loop pays nothing to describe a span nobody keeps.

    ``parent``: default is the ambient thread-local span; pass an
    explicit SpanContext to stitch across threads, or None to force a
    new root trace."""
    if not _flags["observability_tracing"]:
        return _Annotation(name, args)
    return _Span(name, args() if callable(args) else args, parent)


def annotation(name: str, args=None) -> _Annotation:
    """The off path of ``span`` whatever the flag says: for the phases
    of a hot loop, whose place is the timeline beside the device trace,
    not a request's trace. Were they full spans, ``observability_tracing``
    would put a dozen flight-ring entries a step where one was, and a
    request's submit span would leave the ring within seconds. ``args``
    as for ``span``: kept only inside a ``paddle_tpu.profiler`` session."""
    return _Annotation(name, args)


class _Attach:
    __slots__ = ("ctx", "_st")

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        self._st = _stack() if self.ctx is not None else None
        if self._st is not None:
            self._st.append(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        if self._st is not None:
            self._st.pop()
        return False


def attach(ctx: Optional[SpanContext]) -> _Attach:
    """Adopt ``ctx`` as this thread's ambient parent for the duration
    — the cross-thread handoff primitive (a worker wraps its handling
    in ``attach(req.ctx)`` and every span inside parents correctly)."""
    return _Attach(ctx)


def traced(name: Optional[str] = None, args: Optional[Dict[str, Any]] = None):
    """Decorator form: ``@traced("serving/rebatch")``."""

    def deco(fn):
        span_name = name or f"{fn.__module__}.{fn.__qualname__}"

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with span(span_name, args):
                return fn(*a, **kw)

        return wrapper

    return deco


# -- the cyclic collector ----------------------------------------------------

# the hook's state in preallocated slots: a generation-0 callback runs
# every ~700 allocations, so it reads the clock, adds to two lists and
# allocates no container (a collection is never nested, and start and
# stop come on the thread that collects)
_gc_t0 = [0.0]
_gc_open = [None]
_gc_collections = [0, 0, 0]
_gc_pause_s = [0.0, 0.0, 0.0]
_perf_counter = time.perf_counter


def _on_gc(phase: str, info) -> None:
    gen = info["generation"]
    if phase == "start":
        if gen:
            ann = annotation("executor/gc", {"generation": gen})
            ann.__enter__()
            _gc_open[0] = ann
        _gc_t0[0] = _perf_counter()
        return
    t0 = _gc_t0[0]
    if t0:
        _gc_pause_s[gen] += _perf_counter() - t0
        _gc_collections[gen] += 1
        _gc_t0[0] = 0.0
    ann = _gc_open[0]
    if ann is not None:
        _gc_open[0] = None
        ann.__exit__(None, None, None)


def _collect_gc():
    return {
        "paddle_gc_collections_total": [
            ({"generation": str(g)}, n) for g, n in enumerate(_gc_collections)],
        "paddle_gc_pause_seconds_total": [
            ({"generation": str(g)}, s) for g, s in enumerate(_gc_pause_s)],
    }


def install_gc_hook() -> None:
    """Put the cyclic collector on the profiler's clock and in the
    registry; idempotent (one callback a process however often it is
    called). Changes no threshold and freezes nothing."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    registry.registry().register_collector("gc", _collect_gc)
