"""Profiler: wraps jax.profiler with the reference's context-manager
API and chrome-trace output.

Reference: python/paddle/fluid/profiler.py (profiler context manager),
platform/profiler.h RecordEvent, tools/timeline.py (chrome trace).
jax.profiler natively emits xplane/perfetto traces viewable in
chrome://tracing or TensorBoard — same workflow.

Status lines go through the ``paddle_tpu.profiler`` logging logger,
never stdout — the serving HTTP server and pipe-reading tools share
this process's stdout and a stray print corrupts their streams.
"""

from __future__ import annotations

import contextlib
import logging
import os
import tempfile
import threading
import time

_log = logging.getLogger("paddle_tpu.profiler")


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    import jax

    global _recording
    logdir = profile_path if os.path.isdir(profile_path) else tempfile.mkdtemp(prefix="pt_prof_")
    jax.profiler.start_trace(logdir)
    with _events_lock:
        _host_events.clear()  # fresh session: no stale events in the trace
    _recording = True
    t0 = time.time()
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        _recording = False
        dt = time.time() - t0
        if profile_path and not os.path.isdir(profile_path):
            from .tools_timeline import save_chrome_trace

            save_chrome_trace(profile_path, host_events())
        _log.info("traced %.3fs -> %s (open with tensorboard --logdir "
                  "or perfetto)", dt, logdir)


# host-side event log (reference platform/profiler.cc's Event vector):
# populated by record_event while profiling is on; rendered to a
# chrome trace by tools/timeline.py.
#
# Appends arrive from ARBITRARY threads — serving workers, DataLoader
# prefetch, dispatch first-call compiles — and the ring-trim below
# deletes a slice. Unsynchronized list mutation + `del` can drop or
# duplicate events (and a reader can see a half-trimmed list), so every
# mutation and snapshot goes through one module lock. The lock guards
# the LISTS only; `_recording` stays a plain bool (a racy read at worst
# drops the first/last event of a session, never corrupts state).
# Re-entrant: the cyclic collector's hook (observability.tracing) logs
# its range from inside a collection, which an allocation made under
# this lock can start on the same thread.
_events_lock = threading.RLock()
_host_events: list = []
_recording = False
# a session left recording for hours must stay constant-memory (the
# flight-recorder contract extends here): trim half past the cap
_HOST_EVENTS_CAP = 200_000

# stable per-thread trace ids: chrome/perfetto group events by tid, so
# the id must be (a) small, (b) stable for a thread's lifetime, and
# (c) carry the thread NAME so timelines read "pt-serving-worker-1",
# not "tid 7". threading.get_ident() % 10_000 (the old scheme) could
# collide and renumbered on every interpreter run.
_thread_tids: dict = {}


def thread_tid() -> int:
    """Small stable tid for the calling thread (registers its name on
    first use; tools_timeline emits the name as trace metadata). The
    name is refreshed when it no longer matches — the OS reuses thread
    idents after a thread dies, and the reused ident must not carry a
    dead thread's label into the trace."""
    ident = threading.get_ident()
    name = threading.current_thread().name
    tid = _thread_tids.get(ident)
    if tid is None:
        with _events_lock:
            tid = _thread_tids.get(ident)
            if tid is None:
                tid = len(_thread_tids)
                _thread_tids[ident] = tid
            _thread_names[tid] = name
    elif _thread_names.get(tid) != name:
        with _events_lock:
            _thread_names[tid] = name
    return tid


_thread_names: dict = {}


def thread_names() -> dict:
    """tid -> thread name for every thread that ever emitted an event."""
    with _events_lock:
        return dict(_thread_names)


def _append_host_event(ev: dict) -> None:
    # caller holds _events_lock
    _host_events.append(ev)
    if len(_host_events) > _HOST_EVENTS_CAP:
        del _host_events[:_HOST_EVENTS_CAP // 2]


@contextlib.contextmanager
def record_event(name: str, args=None):
    """RAII event annotation (reference platform/profiler.h:124
    RecordEvent). Shows up as a named range in the XLA trace AND in the
    host event log consumed by tools/timeline.py. ``args`` attaches
    structured metadata (step number, checkpoint path, retry count,
    trace/span ids from observability.tracing) that tools/timeline.py
    renders as the chrome-trace event's args panel."""
    import jax

    t0 = time.time()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            if _recording:
                ev = {
                    "name": name,
                    "ts": t0,
                    "dur": time.time() - t0,
                    "tid": thread_tid(),
                }
                if args:
                    ev["args"] = dict(args)
                with _events_lock:
                    _append_host_event(ev)


def emit_event(name: str, ts: float, dur: float, args=None) -> None:
    """Append one pre-timed host event (no-op outside a recording
    session). The fast path for observability.tracing spans — they
    already own the timing and the TraceAnnotation, so routing them
    through the record_event context manager would just add a second
    generator frame per span."""
    if not _recording:
        return
    ev = {"name": name, "ts": ts, "dur": dur, "tid": thread_tid()}
    if args:
        ev["args"] = dict(args)
    with _events_lock:
        _append_host_event(ev)


@contextlib.contextmanager
def host_trace(clear: bool = True):
    """Capture host events (record_event / tracing spans) WITHOUT
    starting a jax device trace — the cheap host-only session that
    tests and benchmarks use to observe spans deterministically."""
    global _recording
    if clear:
        with _events_lock:
            _host_events.clear()
    prev = _recording
    _recording = True
    try:
        yield
    finally:
        _recording = prev


def host_events():
    with _events_lock:
        return list(_host_events)


# compile-event history (runtime/dispatch._first_call): kept
# unconditionally — knowing WHEN each executable was built matters for
# post-hoc TPU-window accounting. The compile's range in a profiling
# session is the caller's span (``executor/compile``), not an entry
# here. Ring-capped: use_program_cache=False loops compile every step,
# which must not grow memory forever.
_compile_events: list = []
_COMPILE_EVENTS_CAP = 1000


def record_compile(name: str, dur: float):
    ev = {
        "name": name,
        "ts": time.time() - dur,
        "dur": dur,
        "tid": thread_tid(),
    }
    with _events_lock:
        _compile_events.append(ev)
        if len(_compile_events) > _COMPILE_EVENTS_CAP:
            del _compile_events[:_COMPILE_EVENTS_CAP // 2]
    # observability: compiles count in the unified registry and land in
    # the crash-time flight ring (lazy import: observability imports us)
    from .observability import flight, registry

    registry.registry().counter(
        "paddle_compile_total", "XLA executables built").inc()
    registry.registry().gauge(
        "paddle_compile_last_s", "duration of the last compile").set(dur)
    flight.note("compile", name=name, dur=dur)


def compile_events():
    with _events_lock:
        return list(_compile_events)


def start_profiler(state="All"):
    import jax

    global _trace_dir, _recording
    _trace_dir = tempfile.mkdtemp(prefix="pt_prof_")
    with _events_lock:
        _host_events.clear()  # fresh session
    _recording = True
    jax.profiler.start_trace(_trace_dir)


def stop_profiler(sorted_key=None, profile_path=None):
    import jax

    global _recording
    jax.profiler.stop_trace()
    _recording = False
    if profile_path:
        from .tools_timeline import save_chrome_trace

        save_chrome_trace(profile_path, host_events())
    _log.info("trace in %s", _trace_dir)


def reset_profiler():
    with _events_lock:
        _host_events.clear()


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Reference profiler.py cuda_profiler (CUPTI). No CUDA exists on
    this stack; kept as a working context manager that records a jax
    trace instead so legacy call sites still profile something real."""
    with profiler(profile_path=output_file or "/tmp/profile"):
        yield
