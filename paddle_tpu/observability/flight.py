"""Crash-time flight recorder: the last N telemetry events, always on,
constant memory, dumped as JSON the moment something goes wrong.

The postmortem problem with a training failure at step 40k is that the
evidence — which spans were in flight, what the step time was doing,
what compiled right before — is gone unless someone was already
profiling. An aircraft solves this with a flight recorder: a ring
buffer that is ALWAYS recording and costs the same whether the flight
is 2 minutes or 20 hours. Same here:

* ``note()`` appends one entry (span completions from ``tracing``,
  compile events from the dispatch cache, supervisor lifecycle events
  like retry/rollback/nan, step-metric samples) to a bounded deque —
  O(1), a few hundred ns, capacity ``observability_flight_capacity``.
* ``dump(reason)`` snapshots the ring plus the full metrics registry
  and the recent compile-event history into one JSON file. It is
  called from failure paths — the supervisor's NaN rollback, watchdog
  hang, uncaught loop exception and SIGTERM flush — and from SIGUSR2
  (``install_signal_handlers``), the live-debugging poke for a wedged
  process. A dump path must never make a crash worse: every failure
  inside ``dump`` is swallowed and reported as ``None``.

Deterministic coverage: ``resilience.faults`` (``nan@N``, ``hang@N``)
drives these triggers on demand — tests/test_observability.py asserts
a parseable dump containing the spans and metric samples leading up to
the injected fault.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import signal
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ["note", "entries", "clear", "dump", "last_dump_path",
           "install_signal_handlers"]

_log = logging.getLogger("paddle_tpu.observability")

from ..flags import _flags  # the live flag store: note() is hot-path

_lock = threading.Lock()
_ring: Optional[collections.deque] = None
_ring_flag_cap = None  # the RAW flag value the ring was last sized from
_dump_count = [0]
_last_dump: List[Optional[str]] = [None]


def _enabled() -> bool:
    return bool(_flags["observability_flight"])


def _get_ring() -> collections.deque:
    """The ring is sized from the flag at first use and re-sized when
    the flag changes (keeping the newest entries). The resize guard
    remembers the RAW flag value, not the clamped capacity — an
    out-of-range flag must not make every note() rebuild the ring."""
    global _ring, _ring_flag_cap
    raw = _flags["observability_flight_capacity"]
    if _ring is None or raw != _ring_flag_cap:
        cap = max(16, int(raw))
        old = list(_ring) if _ring is not None else []
        _ring = collections.deque(old[-cap:], maxlen=cap)
        _ring_flag_cap = raw
    return _ring


def note(kind: str, **fields) -> None:
    """Append one entry. Safe from any thread; silently a no-op when
    the recorder is disabled. This runs per STEP and per span — the
    direct flag-store read and the single uncontended lock keep it
    cheap."""
    if not _flags["observability_flight"]:
        return
    entry = {"kind": kind, "t": fields.pop("t", None) or time.time()}
    entry.update(fields)
    append_entry(entry)


def append_entry(entry: Dict[str, Any]) -> None:
    """Append a caller-built entry dict (the recorder takes ownership).
    The fast path for span exits, which already hold a dict and must
    not pay a kwargs re-splat; callers are responsible for the
    ``kind``/``t`` keys."""
    if not _flags["observability_flight"]:
        return
    with _lock:
        ring = _ring
        if ring is None or _ring_flag_cap != _flags["observability_flight_capacity"]:
            ring = _get_ring()
        ring.append(entry)


def entries() -> List[Dict[str, Any]]:
    """Consistent snapshot of the ring, oldest first."""
    with _lock:
        return list(_ring) if _ring is not None else []


def clear() -> None:
    with _lock:
        if _ring is not None:
            _ring.clear()


def last_dump_path() -> Optional[str]:
    return _last_dump[0]


def _json_default(o):
    try:
        import numpy as np

        if isinstance(o, np.integer):
            return int(o)
        if isinstance(o, np.floating):
            return float(o)
        if isinstance(o, np.ndarray):
            return o.tolist()
    except Exception:  # noqa: BLE001
        pass
    return str(o)


def dump(reason: str, extra: Optional[Dict[str, Any]] = None,
         path: Optional[str] = None) -> Optional[str]:
    """Write the flight snapshot; returns the file path or None (a
    crash path must never raise out of its own postmortem)."""
    try:
        from .. import profiler, version
        from .registry import registry

        payload = {
            "flight_recorder": 1,
            "reason": reason,
            "time": time.time(),
            "pid": os.getpid(),
            "version": version.full_version,
            "entries": entries(),
            "metrics": registry().snapshot(),
            "compile_events": profiler.compile_events()[-64:],
        }
        if extra:
            payload["extra"] = extra
        if path is None:
            from ..flags import flag

            d = os.path.expanduser(flag("observability_dump_dir") or "")
            if not d:
                d = tempfile.gettempdir()
            os.makedirs(d, exist_ok=True)
            safe = "".join(c if c.isalnum() or c in "-_" else "-"
                           for c in reason)[:48]
            _dump_count[0] += 1
            path = os.path.join(
                d, f"flight_{os.getpid()}_{_dump_count[0]:03d}_{safe}.json")
        with open(path, "w") as f:
            json.dump(payload, f, default=_json_default)
        _last_dump[0] = path
        _log.warning("flight recorder dumped (%s) -> %s", reason, path)
        return path
    except Exception as e:  # noqa: BLE001 — never worsen a crash
        try:
            _log.error("flight recorder dump failed: %r", e)
        except Exception:  # noqa: BLE001
            pass
        return None


def install_signal_handlers() -> bool:
    """SIGUSR2 -> dump (chains any existing handler). Main thread
    only — returns False (installed nothing) elsewhere, since signal
    handlers cannot be set from worker threads.

    The dump runs on a freshly-spawned thread, never in the handler
    itself: the handler executes on the main thread, which may be
    holding the flight/telemetry locks mid-append — dumping inline
    would self-deadlock on those non-reentrant locks. The side thread
    just waits its turn for them."""
    if threading.current_thread() is not threading.main_thread():
        return False
    prev = signal.getsignal(signal.SIGUSR2)

    def _handler(signum, frame):
        threading.Thread(target=dump, args=("sigusr2",),
                         name="pt-flight-dump", daemon=True).start()
        if callable(prev) and prev not in (signal.SIG_IGN, signal.SIG_DFL):
            prev(signum, frame)

    signal.signal(signal.SIGUSR2, _handler)
    return True


def install_excepthook() -> None:
    """Chain sys.excepthook so ANY uncaught exception in the process
    produces a flight dump before the traceback prints. Opt-in (the
    supervisor already dumps on its own failure paths)."""
    prev = sys.excepthook

    def _hook(exc_type, exc, tb):
        dump(f"uncaught:{exc_type.__name__}")
        prev(exc_type, exc, tb)

    sys.excepthook = _hook
