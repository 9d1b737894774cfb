"""GenerationEngine: continuous-batching autoregressive decode.

The serving stack (serving/engine.py) coalesces stateless predict
calls; what it cannot serve is the LLM workload — a request is not one
forward pass but a *sequence* of hundreds of dependent steps, each
producing one token. Batching those naively (gang-schedule N requests,
wait for the longest) wastes the accelerator on every finished-early
lane; re-running the growing prefix per token (the only thing a
stateless Predictor can do) wastes O(len) work per token. This engine
does what modern LLM serving does instead:

* **Paged KV cache** (kvcache.py): each sequence's K/V lives in
  fixed-size pages behind a block table; join/leave never copies or
  reallocates. ``kv_dtype="int8"`` stores pages blockwise-quantized
  (kernels/quant.py scales) for ~2x+ resident sequences per byte.
* **Radix prefix cache** (``prefix_cache=True`` /
  ``generation_prefix_cache``, ragged only): full pages publish into
  a refcounted prefix trie as they are produced; admission attaches a
  new prompt's matched prefix pages by reference and chunked prefill
  starts at the FORK POINT — a fully-warm prefix (shared system
  prompt, few-shot header, RAG boilerplate) collapses prefill to ~one
  step and its pages to one copy in HBM. Copy-on-write is structural
  (growth always pops fresh pages; full shared pages are never
  written), release is refcounted, and pool pressure reclaims
  trie-only leaves (LRU) before any live sequence is preempted.
* **ONE ragged executable** (mode="ragged", the default — Ragged
  Paged Attention, arXiv:2604.15464): every step runs a single
  [lanes, chunk] mixed batch where each row is whatever its sequence
  needs — a prefill chunk, one decode token, a decode token plus k
  speculative draft tokens, or nothing (idle lane). Prompts longer
  than ``chunk_tokens`` prefill in chunks ACROSS steps (chunked
  prefill), so a fat prompt arriving mid-traffic costs every running
  sequence a bounded slice per step instead of a whole-prompt stall
  (token identity: tests/test_ragged.py; the tails: PERF.md).
* **Speculative decoding** (``spec_tokens`` + a ``generation.draft``
  model): the draft proposes k tokens per sequence, the target
  verifies all of them in the SAME ragged call (its argmax at every
  chunk position IS the greedy continuation), and the accepted prefix
  + one correction token emit together — greedy-identical by
  construction, whatever the draft proposed.
* **mode="two_lane"**: the PR-6 engine — separate prefill-bucket and
  decode executables — retained as the token-identity oracle the
  ragged collapse is proven against (and for A/B perf archaeology).
* **One jitted call per step.** Either mode's program has fixed
  shapes, so the whole engine life is ONE executable (plus the
  prefill-bucket ladder in two_lane); the loop holds its
  ``runtime.dispatch.BoundStep`` (``Executor.bind``) directly — the
  per-step hot path is a feed-dict assembly and one jitted call,
  nothing else. The page pools are neither fed nor fetched: the step
  programs declare them as state they rewrite (generation/model.py),
  the engine binds every step against a scope of its own (a child of
  the predictor's: weights resolve through the parent, two engines
  over one predictor keep separate pools) in which the
  ``PagedKVCache`` keeps the arrays, and the executor donates them to
  the step, which writes the new K/V rows into the same buffers. A
  donated array is deleted at the dispatch, so the dispatch runs
  under ``cache.pools_locked()`` (the wait for the tokens does not),
  as every other reader or writer of the pools does: ``spill_run``
  from another thread reads the arrays of before a step or of after
  it. ``stats()["step_donated_bytes"]`` says that it engaged.
* **One step ahead** (ragged mode). The loop keeps one step in
  flight: an iteration is admit, grow, assemble, bind, dispatch of step
  N+1 WHILE STEP N RUNS, and only then the wait for step N's tokens
  and their emit. The jitted call's argument handling, the feeds'
  transfers and all of the loop's Python happen under step N's device
  time, and the device runs back to back. Nothing the host does
  between two steps needs the value of the token just sampled except
  to feed it back, so it stays on the device: a decode row's input is
  merged in from the previous step's output (``_carry_tokens``, one
  small jitted call ahead of the step; the step's own executable and
  arguments are what they were). Known a step early: lengths,
  positions, page growth (each row in flight counts:
  ``_GenRequest.ahead``) and the finish by ``max_new`` or the position
  window (the lane takes no row after the one that samples its last
  token). Learned a step late: EOS, cancel, deadline; the row already
  computed for such a sequence is dropped at its emit, never streamed,
  and its K/V landed in pages the sequence still owned when the write
  was enqueued (the device runs what was enqueued in order, so a page
  freed at emit(N) and reused by step N+2 is written after). Whatever
  needs every token on the host or no step running reads the step in
  flight out first (``_drain_inflight``): speculative drafting (an
  engine with a draft never runs ahead), a pool-dry eviction, a base
  swap, the close, a step that raised. Never more than one step is
  ahead. With a lane free, nothing queued and a step in flight the
  loop waits for ``submit`` before it admits (``_hold_for_submit``:
  admission at the last moment, while the device is busy anyway), so a
  finished request's successor joins the next step and not the one
  after by a race. Counters, beside the ``loop_*`` ones:
  ``steps_dispatched_ahead_total``, ``device_carried_tokens_total``,
  ``discarded_rows_total``, ``inflight_drains_<reason>_total``,
  ``admit_holds_total`` / ``admit_hold_us_total``. ``two_lane`` runs
  its serial loop as before.
* **Loop phases** (ragged mode). One iteration of the step loop is
  partitioned, with nothing left between them, into ``wait`` (starved:
  no queue, no live lane), ``admit`` (the hold for a submitter,
  page-store consult, prefix lookup, lane + page reservation), ``grow``
  (retire dead rows, page growth / eviction, the speculative budget,
  the adapter check), ``draft`` (only with speculative rows),
  ``assemble`` (the numpy batch, block tables, the feed dict: tokens,
  positions, tables, and a hybrid model's recurrent state), ``bind``,
  ``step`` (the token merge and the dispatch of the new step with the
  pools donated, under the cache's pool lock, then the wait for the
  tokens of the step before it — ``generation/fetch``, which holds
  ``generation/fetch_ready``, the wait for that step to be reported
  done, before the copy) and ``emit``
  (of that earlier step: advance, stop conditions, the clients'
  ``on_token`` callbacks, trie publish). What a step was fed is
  released at its dispatch. The last step of a burst is read by an
  iteration that dispatches nothing (grow, step, emit).
  Each is the range ``generation/<phase>`` on the
  profiler's clock — always on, so any attached profiler session reads
  the device's idle gaps in these terms — and, at the same boundary,
  the exact counter ``loop_<phase>_us_total`` (``stats()``,
  ``paddle_generation_loop_*`` on ``/metrics``).
* **Streaming.** ``submit()`` returns a ``GenerationStream`` —
  iterate it for tokens as they are sampled (time-to-first-token is a
  prefill, not a whole generation), or ``result()`` for the full list.
  Stop conditions: max_new_tokens, EOS, deadline, cancel, drain.
* **Backpressure + eviction.** A full admission queue (or a prompt
  that could never fit the pool) raises ``serving.Overloaded`` at
  submit — BEFORE any prefill work. A pool that runs dry mid-decode
  evicts the youngest sequence (pages freed, request re-queued for
  re-prefill of prompt+generated — greedy decode makes the resumed
  continuation identical), so the oldest work always completes.

* **Recurrent layers beside the pages** (a ``models.hybrid.
  HybridConfig``: Mamba-2 and attention layers mixed, or both mixers in
  one ``parallel`` layer; dropless top-k experts or a dense feed-forward).
  The page pools cover the layers with attention only; what the
  recurrent layers carry is a per-lane state ``[lanes, ...]`` that the
  cache manager owns beside the pools and that is fed to the step and
  fetched back, rewritten whole (the pools are donated state; the
  recurrent state is not yet: PERF.md, open questions). A lane that takes a new sequence is zeroed
  inside the step (its first row is at position 0), so neither
  admission nor release touches it, and pool-dry eviction keeps its
  rule: the re-prefill from prompt + generated restarts the state from
  zero. What such a sequence cannot have yet is refused at
  construction, by what is missing: a prefix cache (state at the fork
  point), speculative rows (state rollback), a page store (state on
  the wire), the two_lane programs. The builder and the extra arrays
  are chosen once, in ``__init__``; the dense decoder's step is what
  it was.

The engine runs *over a cloned Predictor*: the clone shares the loaded
weights (scope) and executor, so generation and plain ``/v1/predict``
serving coexist on one model instance, and the caller's predictor
lock is never held by the step loop.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..observability import tracing
from ..serving.engine import (DeadlineExceeded, EngineClosed, Overloaded,
                              RequestCancelled, ServingError)
from ..serving.metrics import StreamingHistogram
from .kvcache import (PagedKVCache, PagePoolExhausted, WindowKind,
                      window_ring_pages)
from .model import (CacheGeometry, HybridConfig, MiMoConfig,
                    build_decode_program, build_hybrid_step_program,
                    build_mimo_step_program, build_prefill_program,
                    build_ragged_step_program)

__all__ = ["GenerationEngine", "GenerationStream", "GenerationMetrics"]

_DONE = object()  # stream sentinel

# one iteration of the ragged step loop, in order, nothing between
# them (module docstring, "Loop phases")
LOOP_PHASES = ("wait", "admit", "grow", "draft", "assemble", "bind",
               "step", "emit")
# why a step in flight was read and emitted before the loop went on
# (module docstring, "One step ahead")
DRAIN_REASONS = ("draft", "evict", "swap", "close", "error")


class GenerationStream:
    """Per-request handle: an iterator over tokens as they are
    sampled, plus future-style ``result()``/``cancel()``. One of
    ``finish_reason`` in {"eos", "length", "deadline", "cancelled",
    "closed", "capacity", "error"} is set by the time iteration
    ends."""

    def __init__(self, engine: "GenerationEngine", on_token=None):
        self._engine = engine
        self._q: "collections.deque" = collections.deque()
        self._cond = threading.Condition()
        self._done = threading.Event()
        self._on_token = on_token
        self._tokens: List[int] = []
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self._cancelled = False
        self.first_token_at: Optional[float] = None
        self._callbacks: List = []
        # per-request speculative-decoding accounting (the /v1/generate
        # usage fragment): every emitted token is target-VERIFIED;
        # accepted_draft_tokens counts how many of them the draft
        # proposed (0 with speculation off)
        self.verified_tokens = 0
        self.accepted_draft_tokens = 0

    def usage(self) -> Dict[str, int]:
        """The response ``usage`` fragment: spec-decode behavior is
        visible per request, not just in fleet-wide gauges."""
        return {"completion_tokens": len(self._tokens),
                "verified_tokens": int(self.verified_tokens),
                "accepted_draft_tokens": int(self.accepted_draft_tokens)}

    # -- engine side ---------------------------------------------------------
    def _push(self, token: int) -> None:
        if self.first_token_at is None:
            self.first_token_at = time.monotonic()
        self._tokens.append(int(token))
        with self._cond:
            self._q.append(int(token))
            self._cond.notify_all()
        if self._on_token is not None:
            try:
                self._on_token(int(token))
            except Exception:  # noqa: BLE001 — a bad callback is the caller's bug
                pass

    def _finish(self, reason: str, error: Optional[BaseException] = None):
        if self._done.is_set():
            return
        self.finish_reason = reason
        self.error = error
        self._done.set()
        with self._cond:
            self._q.append(_DONE)
            self._cond.notify_all()
            callbacks, self._callbacks = self._callbacks, []
        for cb in callbacks:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — a bad callback is the caller's bug
                pass

    def add_done_callback(self, fn) -> None:
        """``fn(self)`` once the stream reaches a terminal state
        (immediately if it already has) — the traffic layer's
        completion accounting, no waiter thread per request."""
        with self._cond:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        try:
            fn(self)
        except Exception:  # noqa: BLE001
            pass

    # -- caller side ---------------------------------------------------------
    def __iter__(self):
        while True:
            with self._cond:
                while not self._q:
                    self._cond.wait(0.1)
                item = self._q.popleft()
            if item is _DONE:
                if self.error is not None:
                    raise self.error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the request finishes; the full generated token
        list (raises the terminal error for rejected/failed
        requests)."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"generation not finished within {timeout}s")
        if self.error is not None:
            raise self.error
        return list(self._tokens)

    @property
    def tokens(self) -> List[int]:
        """Tokens sampled so far (grows while streaming)."""
        return list(self._tokens)

    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self) -> bool:
        """Request cancellation; the step loop retires the sequence at
        the next step boundary. False if already finished."""
        if self._done.is_set():
            return False
        self._cancelled = True
        self._engine._kick()
        return True


class _GenRequest:
    __slots__ = ("prompt", "orig_prompt", "max_new", "eos_id", "deadline",
                 "stream", "enqueue_t", "slot", "pending", "n_generated",
                 "ctx", "admit_seq", "last_tok_t", "prefill_off", "drafts",
                 "tenant", "store_checked", "adapter", "ahead")

    def __init__(self, prompt, max_new, eos_id, deadline, stream, ctx,
                 tenant=None, adapter=None):
        self.prompt = prompt            # context to prefill (grows on resume)
        self.orig_prompt = prompt       # the caller's prompt, immutable
        self.max_new = max_new
        self.eos_id = eos_id
        self.deadline = deadline        # absolute monotonic or None
        self.stream = stream
        self.enqueue_t = time.monotonic()
        self.slot: Optional[int] = None
        self.pending: Optional[int] = None   # sampled, K/V not yet cached
        self.n_generated = 0                 # across evict/resume cycles
        self.ctx = ctx                       # tracing ctx of the submit span
        self.admit_seq = 0                   # admission order (evict victim)
        self.last_tok_t: Optional[float] = None
        self.prefill_off = 0            # prompt tokens already written
        self.drafts = None              # this step's speculative proposals
        self.tenant = tenant            # traffic identity (trie quotas)
        self.store_checked = False      # page-store consult done once
        self.adapter = adapter          # resident LoRA adapter id (or None)
        # positions the request's row in the step in flight writes:
        # dispatched, not yet emitted (0: no such row)
        self.ahead = 0

    def token_in_flight(self) -> bool:
        """The request's row in the step in flight samples a token of
        its answer: a decode row, or its prompt's final chunk."""
        return (self.ahead > 0 and
                self.prefill_off + self.ahead >= int(self.prompt.size))


class _Phase:
    """One loop phase: the range ``generation/<phase>`` on the profiler's
    clock and, at the same boundary, its wall time into
    ``loop_<phase>_us_total``. With ``args`` (the step phase) it is a
    span, which ``observability_tracing`` gives ids and ``flow_from``."""

    __slots__ = ("metrics", "counter", "span", "t0")

    def __init__(self, metrics, phase: str, args=None):
        self.metrics = metrics
        self.counter = "loop_" + phase + "_us_total"
        name = "generation/" + phase
        self.span = (tracing.annotation(name) if args is None
                     else tracing.span(name, args))

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self.span.__enter__()

    def __exit__(self, *exc):
        self.span.__exit__(*exc)
        self.metrics.inc(self.counter,
                         (time.perf_counter_ns() - self.t0) // 1000)
        return False


class _StepInFlight:
    """A ragged step that was dispatched and whose tokens the host has
    not read: ``rows`` are ``(slot, request, num_valid)`` as they were
    assembled, ``tokens`` the device array ``[lanes * chunk]`` whose
    copy to the host started at the dispatch, ``t0`` when that was."""

    __slots__ = ("rows", "tokens", "t0")

    def __init__(self, rows, tokens, t0):
        self.rows, self.tokens, self.t0 = rows, tokens, t0


def _carry_tokens(tokens, prev, col):
    """``gen_tokens`` of a step: the host's ``[lanes, chunk]`` rows with
    column 0 of every lane whose ``col`` is not -1 taken from the
    previous step's output ``prev[lane, col]``: a decode row's input is
    the token its sequence sampled a step before, which the host may not
    have read yet."""
    import jax.numpy as jnp

    prev = prev.reshape(tokens.shape).astype(tokens.dtype)
    carried = jnp.take_along_axis(prev, jnp.maximum(col, 0)[:, None], axis=1)
    return tokens.at[:, :1].set(
        jnp.where(col[:, None] >= 0, carried, tokens[:, :1]))


@functools.lru_cache(maxsize=None)
def _carry_fn():
    import jax

    return jax.jit(_carry_tokens)


class GenerationMetrics:
    """Lock-protected counters + streaming histograms for the engine.
    The ENGINE (which also owns the page-pool stats) self-registers
    into the PR-5 unified registry via observability.watch_generation,
    exporting everything here as ``paddle_generation_*{engine=}``
    series."""

    _COUNTERS = ("requests_total", "responses_total", "rejected_total",
                 "expired_total", "cancelled_total", "evicted_total",
                 "prefill_batches_total", "decode_steps_total",
                 "prefill_tokens_total", "decode_tokens_total",
                 "prefill_rows_total", "prefill_capacity_rows_total",
                 "decode_active_lane_steps_total",
                 "decode_capacity_lane_steps_total",
                 # ragged mode: every step is one mixed executable run
                 "ragged_steps_total", "prefill_chunks_total",
                 # speculative decoding (exported as the
                 # paddle_generation_spec_* gauge family)
                 "spec_rounds_total", "spec_proposed_total",
                 "spec_accepted_total",
                 # ragged mode: pages the step's lanes hold (what the
                 # attention kernel walks) against the width of their
                 # block tables (lanes x max_pages_per_seq)
                 "attn_live_pages_total", "attn_table_pages_total",
                 # the same walk by kind of attention layer (a model
                 # with window layers: the pages that overlap the window)
                 "attn_live_pages_full_total",
                 "attn_live_pages_window_total",
                 # of attn_live_pages_total, the pages of lanes that take
                 # the kernel's lean decode branch (their rows alone)
                 "attn_lean_pages_total",
                 # recurrent layers and experts in the step (a hybrid
                 # config): valid tokens x expert layers, and lanes that
                 # took a new sequence (its state restarts from zero in
                 # the graph). The pairs that landed on held experts are
                 # counted on the device (stats(): moe_held_assignments_
                 # total, moe_expert_load_max / _mean)
                 "moe_tokens_routed_total", "state_lane_resets_total",
                 # ragged mode, one step ahead: steps dispatched before
                 # their predecessor's tokens were read (over
                 # ragged_steps_total: the share the overlap engaged
                 # on), decode rows whose input token never left the
                 # device, rows computed for a sequence that had ended
                 # (EOS, cancel, deadline: never emitted), and the waits
                 # for a submitter before an admission with a free lane
                 "steps_dispatched_ahead_total",
                 "device_carried_tokens_total", "discarded_rows_total",
                 "admit_holds_total", "admit_hold_us_total"
                 ) + tuple(f"inflight_drains_{r}_total"
                           for r in DRAIN_REASONS
                 # ragged mode: wall microseconds of the loop thread by
                 # phase, counted where the generation/<phase> span
                 # closes (GenerationEngine._phase)
                 ) + tuple(f"loop_{p}_us_total" for p in LOOP_PHASES)

    def __init__(self):
        self._lock = threading.Lock()
        self._c: Dict[str, int] = {k: 0 for k in self._COUNTERS}
        self.ttft_ms = StreamingHistogram()
        self.itl_ms = StreamingHistogram()
        self.decode_step_ms = StreamingHistogram()
        self.prefill_ms = StreamingHistogram()
        self.queue_wait_ms = StreamingHistogram()
        self._queue_depth = 0
        self._active = 0
        self._decode_wall_s = 0.0

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._c[name] += n

    def observe(self, hist: str, v: float) -> None:
        with self._lock:
            getattr(self, hist).record(v)

    def observe_decode_step(self, ms: float, active: int, lanes: int,
                            tokens: Optional[int] = None) -> None:
        """One decode/ragged step: ``active`` lanes did real work out
        of ``lanes``; ``tokens`` overrides the emitted-token count
        (speculative steps emit more than one per lane)."""
        with self._lock:
            self.decode_step_ms.record(ms)
            self._decode_wall_s += ms / 1e3
            self._c["decode_steps_total"] += 1
            self._c["decode_tokens_total"] += (
                active if tokens is None else tokens)
            self._c["decode_active_lane_steps_total"] += active
            self._c["decode_capacity_lane_steps_total"] += lanes

    def set_gauges(self, queue_depth: int, active: int) -> None:
        with self._lock:
            self._queue_depth = queue_depth
            self._active = active

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self._c)
            out["queue_depth"] = self._queue_depth
            out["active_seqs"] = self._active
            out["ttft_ms"] = self.ttft_ms.snapshot()
            out["itl_ms"] = self.itl_ms.snapshot()
            out["decode_step_ms"] = self.decode_step_ms.snapshot()
            out["prefill_ms"] = self.prefill_ms.snapshot()
            out["queue_wait_ms"] = self.queue_wait_ms.snapshot()
            cap = self._c["decode_capacity_lane_steps_total"]
            out["decode_occupancy"] = (
                round(self._c["decode_active_lane_steps_total"] / cap, 4)
                if cap else 0.0)
            pcap = self._c["prefill_capacity_rows_total"]
            out["prefill_occupancy"] = (
                round(self._c["prefill_rows_total"] / pcap, 4)
                if pcap else 0.0)
            out["decode_tokens_per_s"] = (
                round(self._c["decode_tokens_total"] / self._decode_wall_s, 2)
                if self._decode_wall_s > 0 else 0.0)
            # spec-decode health as ratios (the satellite gauges:
            # draft acceptance rate + accepted tokens per step) —
            # flattened by the registry into paddle_generation_spec_*
            prop = self._c["spec_proposed_total"]
            out["spec_acceptance_rate"] = (
                round(self._c["spec_accepted_total"] / prop, 4)
                if prop else 0.0)
            rounds = self._c["spec_rounds_total"]
            out["spec_accepted_tokens_per_step"] = (
                round(self._c["spec_accepted_total"] / rounds, 4)
                if rounds else 0.0)
            return out


class GenerationEngine:
    """Continuous-batching autoregressive decode over a cloned
    Predictor's weights.

        pred = create_predictor(Config(lm_model_dir))
        eng = generation.GenerationEngine(pred, cfg)   # cfg: GPTConfig
        stream = eng.submit([1, 5, 9], max_new_tokens=32, eos_id=2)
        for tok in stream: ...                         # tokens as sampled
        eng.generate([1, 5, 9])                        # sync helper
        eng.close(drain=True)

    ``serving.ServingServer(engine, generation_engine=eng)`` adds the
    streamed ``POST /v1/generate`` HTTP endpoint on top.
    """

    def __init__(self, predictor, config, *,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_decode_batch: Optional[int] = None,
                 queue_capacity: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None,
                 dtype: str = "float32",
                 mode: Optional[str] = None,
                 chunk_tokens: Optional[int] = None,
                 spec_tokens: Optional[int] = None,
                 draft=None,
                 kv_dtype: Optional[str] = None,
                 quantize_weights: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 page_store=None, phase: Optional[str] = None,
                 adapter_store=None, model_version: Optional[str] = None,
                 warmup: bool = False, start: bool = True):
        from ..flags import flag

        # autotune seam: a profile recorded for this model pre-tunes
        # the generation_* knobs (chunk tokens, lane count, pages)
        # BEFORE they are read below (explicit flags/ctor args win)
        from ..runtime.dispatch import autotune_for_program

        autotune_for_program(getattr(predictor, "_program", None))

        self.config = config
        # the clone shares scope + executor + compiled executables with
        # the caller's predictor but owns its own lock/IO handles — the
        # step loop never contends with concurrent predictor.run users
        self._pred = predictor.clone()
        self._exe = self._pred._exe
        self._scope = self._pred._scope
        # what the steps are bound against: this engine's own state (the
        # page pools, kept there by the cache) over the shared weights
        self._step_scope = self._scope.new_scope()
        self.page_size = int(page_size or flag("generation_page_size"))
        self.num_pages = int(num_pages or flag("generation_num_pages"))
        self.lanes = int(max_decode_batch
                         or flag("generation_max_decode_batch"))
        self.queue_capacity = int(queue_capacity
                                  or flag("generation_queue_capacity"))
        self.default_max_new = int(flag("generation_max_new_tokens"))
        self.default_eos = eos_id
        self.mode = str(mode or flag("generation_engine_mode"))
        if self.mode not in ("ragged", "two_lane"):
            raise ValueError(
                f"generation_engine_mode must be 'ragged' or 'two_lane', "
                f"got {self.mode!r}")
        self.spec_tokens = int(spec_tokens if spec_tokens is not None
                               else flag("generation_spec_tokens"))
        self._draft = draft
        if self._draft is None:
            self.spec_tokens = 0
        elif hasattr(self._draft, "min_rows"):
            # pin the draft's row bucket to the lane count: one draft
            # executable per length bucket for the engine's whole life
            self._draft.min_rows = max(int(self._draft.min_rows or 1),
                                       self.lanes)
        self.chunk_tokens = int(chunk_tokens
                                or flag("generation_chunk_tokens"))
        # a speculative row is [pending + k drafts] wide; the chunk
        # must hold it
        self.chunk_tokens = max(2, self.chunk_tokens, self.spec_tokens + 1)
        # precedence: kv_dtype param > legacy dtype param > flag
        if kv_dtype is None:
            kv_dtype = (dtype if dtype != "float32"
                        else flag("generation_kv_dtype"))
        self.kv_dtype = str(kv_dtype)
        # weight quantization (paddle_tpu.quantize): param > flag. The
        # engine's programs rewrite onto the scope's quantized buffers
        # below, AFTER they are built — composing with int8 KV pages
        # for the fully-quantized ragged decode
        self.quantize_weights = str(
            quantize_weights if quantize_weights is not None
            else flag("quantize_weights")) or "off"
        self.quantize_report = None
        self._quant_block = int(flag("quantize_block"))
        if self.kv_dtype == "int8" and self.mode != "ragged":
            raise ValueError("int8 KV pages require the ragged engine "
                             "(generation_engine_mode='ragged')")
        if self.mode != "ragged" and self.spec_tokens:
            raise ValueError("speculative decoding requires the ragged "
                             "engine (generation_engine_mode='ragged')")
        # radix prefix cache: param > flag. Ragged-only — the two_lane
        # prefill executable writes the whole window from position 0,
        # so it cannot start at a fork point (and is kept pristine as
        # the cold token-identity oracle the radix tests prove
        # against).
        self.prefix_cache = bool(
            prefix_cache if prefix_cache is not None
            else flag("generation_prefix_cache"))
        if self.prefix_cache and self.mode != "ragged":
            raise ValueError("prefix caching requires the ragged engine "
                             "(generation_engine_mode='ragged')")
        if prefill_buckets is None:
            prefill_buckets = tuple(
                int(x) for x in
                str(flag("generation_prefill_buckets")).split(",") if x)
        max_seq = int(config.max_position)
        self._seq_buckets = tuple(sorted(
            {min(b, max_seq) for b in prefill_buckets} | {max_seq}))
        maxp = -(-max_seq // self.page_size)
        # what the model is, decided here once: a hybrid config (a list
        # of attention and recurrent layers, models/hybrid.py) pages its
        # attention layers only and carries a per-lane recurrent state
        # beside the pools; a MiMo config (models/mimo.py) pages two
        # kinds of attention layer, full and window, in pools of their
        # own; the step loop only ever sees how many pooled layers there
        # are, which extra arrays ride the step, and whether the cache
        # has window pages to turn over
        hybrid = isinstance(config, HybridConfig)
        mimo = isinstance(config, MiMoConfig)
        self._kv_layers = (len(config.attention_layers) if hybrid
                           else len(config.layers_of("full")) if mimo
                           else config.num_layers)
        self._expert_layers = (len(config.expert_layers) if hybrid or mimo
                               else 0)
        self._state_names: tuple = ()
        state = window = None
        if hybrid or mimo:
            refuse = (self._refuse_for_recurrent_state if hybrid
                      else self._refuse_for_window_layers)
            refuse(page_store)
            state = config.state_shapes(self.lanes)
            self._state_names = tuple(state)
        if mimo and config.layers_of("window"):
            window = WindowKind(
                len(config.layers_of("window")), config.window_kv_heads,
                config.window, window_ring_pages(
                    config.window, self.chunk_tokens, self.page_size))
        self.geom = CacheGeometry(
            num_pages=self.num_pages, page_size=self.page_size,
            max_pages_per_seq=maxp,
            window_num_pages=(self.lanes * window.pages_per_seq + 1
                              if window else 0),
            window_pages_per_seq=window.pages_per_seq if window else 0)
        self.cache = PagedKVCache(
            self._kv_layers,
            config.num_heads if not (hybrid or mimo) else config.num_kv_heads,
            config.v_dim if mimo else config.head_dim,
            k_dim=config.k_dim if mimo else None, window=window,
            num_pages=self.num_pages, page_size=self.page_size,
            max_seqs=self.lanes, max_pages_per_seq=maxp,
            dtype=self.kv_dtype, state=state, scope=self._step_scope,
            prefix_cache=self.prefix_cache,
            prefix_min_pages=int(flag("generation_prefix_min_pages")),
            trie_max_pages=int(flag("generation_trie_max_pages")),
            tenant_quota_pages=int(flag("generation_trie_tenant_quota")))
        self.cache.reset_buffers()      # the steps find their state
        # disagg seam: a page store (HostPageStore / PageStoreClient
        # duck) makes this engine a split-topology citizen — admission
        # consults it for queued prompts before cold prefill
        # (_consult_store), spill_run/spill_trie export finished pages
        # back, and close(drain=True) spills the whole trie so rolling
        # restarts resume warm. ``phase`` is the routing label the
        # traffic tier and /healthz report ("prefill"/"decode"/"both").
        self._page_store = page_store
        self.phase = str(phase) if phase else "both"
        self._wire_encoding = str(flag("disagg_wire_encoding"))
        self.store_lookups_total = 0
        self.store_hits_total = 0
        self.store_pages_pulled_total = 0
        self.store_pages_spilled_total = 0
        self.store_errors_total = 0
        self.metrics = GenerationMetrics()
        # unified telemetry: this engine's counters + page-pool stats
        # join the scrape as paddle_generation_*{engine=} series
        from ..observability import watch_generation

        watch_generation(self)

        self._ragged_bound = None       # resolved on the first step
        self._moe_kernel_layers = 0     # counted when it is
        self._decode_bound = None       # two_lane: first decode step
        self._prefill_progs: Dict[int, Any] = {}    # seq bucket -> (prog, fetches)
        if self.mode == "ragged":
            # THE executable: one mixed prefill+decode program for the
            # engine's whole life, one BoundStep per step
            build = (build_hybrid_step_program if hybrid
                     else build_mimo_step_program if mimo
                     else build_ragged_step_program)
            self._ragged_prog, self._ragged_fetches = build(
                config, self.geom, self.chunk_tokens, self.kv_dtype)
            self._lean_tokens = self._lean_decode_tokens()
        else:
            self._decode_prog, self._decode_fetches = build_decode_program(
                config, self.geom)
        if self.quantize_weights != "off":
            from .. import quantize as _quantize

            # the caller's predictor shares this scope — dropping the
            # fp32 buffers under a program still pointing at them
            # would brick predictor.run, so the predictor's program is
            # rewritten FIRST (a no-op when Predictor construction
            # already consumed the flag: the scope conversion is
            # shared and idempotent)
            if getattr(self._pred, "quantize_report", None) is None:
                if getattr(self._pred, "partition", None) is not None:
                    # with_partitioning resolved its shardings from
                    # the fp32 var names at Predictor construction —
                    # rewriting underneath it would bind the .q/
                    # .qscale vars REPLICATED (no resolve entry, no
                    # tag fallback), silently defeating the TP layout.
                    # The ordered path exists: quantize at Predictor
                    # construction, where the rewrite runs BEFORE the
                    # partition resolve.
                    raise ValueError(
                        "quantize_weights on a partitioned predictor "
                        "must be enabled at Predictor construction "
                        "(Config.enable_weight_quantization or the "
                        "quantize_weights flag), so the partition "
                        "resolve sees the quantized vars")
                rep = _quantize.rewrite_for_inference(
                    self._pred._program, self._scope,
                    wdtype=self.quantize_weights, block=self._quant_block)
                # stamp the CALLER's predictor too — the clone copied
                # the attribute by value, and the caller is the object
                # later code inspects (and the one a second engine's
                # already-rewritten check must see)
                self._pred.quantize_report = rep
                predictor.quantize_report = rep
            prog = (self._ragged_prog if self.mode == "ragged"
                    else self._decode_prog)
            self.quantize_report = _quantize.rewrite_for_inference(
                prog, self._scope, wdtype=self.quantize_weights,
                block=self._quant_block)

        # batched LoRA multiplexing (paddle_tpu.adapters): pools built
        # and the RAGGED program repointed AFTER the quantize seam, so
        # the lora rewrite sees the quantized ops and composes (the
        # adapter delta applies to the dequantized product). Nothing
        # is erased: the predictor's program keeps serving the same
        # scope untouched. Per-row slots ride the gen_adapter_slots
        # feed; the pools are scope-resident state, so upload/evict
        # (and the base swap below) are scope.set_var — the live
        # BoundStep re-resolves, zero recompiles.
        self.adapter_store = adapter_store
        self.lora_report = None
        if self.adapter_store is None and self.mode == "ragged" \
                and int(flag("adapter_pool_max_bytes")) > 0:
            from ..adapters import AdapterStore

            buckets = tuple(
                int(x) for x in
                str(flag("adapter_rank_buckets")).split(",") if x)
            self.adapter_store = AdapterStore.for_program(
                self._ragged_prog,
                rank_buckets=buckets or (8, 16),
                max_bytes=int(flag("adapter_pool_max_bytes")),
                slots_per_bucket=(
                    int(flag("adapter_slots_per_bucket")) or None),
                tenant_quota=int(flag("adapter_tenant_quota")))
        if self.adapter_store is not None:
            if self.mode != "ragged":
                raise ValueError(
                    "adapter multiplexing requires the ragged engine "
                    "(generation_engine_mode='ragged')")
            from ..adapters import rewrite_for_lora

            self.adapter_store.attach(self._scope)
            self.lora_report = rewrite_for_lora(self._ragged_prog,
                                                self.adapter_store)
        # hot base-model swap: a staged signature-identical checkpoint
        # is applied by the LOOP thread between steps (_pending_swap),
        # so no in-flight batch ever sees half-old half-new weights
        self.model_version = str(model_version or "base")
        self.model_swaps = 0
        self._pending_swap = None
        # one step ahead (module docstring): the step whose tokens are
        # not read yet; the newest step's token array, from which the
        # next step's decode rows take their input on the device; and
        # what bounds the hold before an admission: when the last
        # tokens arrived, the device's last step and the loop's own
        # time from admit to dispatch
        self._inflight: Optional[_StepInFlight] = None
        self._run_ahead = True          # False: the serial order (tests)
        self._prev_tokens = None
        self._last_fetch_t = 0.0
        self._step_s = 0.0
        self._last_took = 0.0
        self._host_s = 0.0
        self._iter_t0 = 0.0

        self._cond = threading.Condition()
        self._queue: "collections.deque[_GenRequest]" = collections.deque()
        self._by_slot: Dict[int, _GenRequest] = {}
        self._admit_counter = 0
        self._closed = False
        self._stop = False
        self._loop_thread: Optional[threading.Thread] = None
        self._started = False
        if warmup:
            self._warmup()
        if start:
            self.start()

    def _refuse_for_recurrent_state(self, page_store):
        """A sequence with recurrent layers keeps part of its past in a
        per-lane state that is in no page. What would have to exist
        before each of these could serve it is named; until then
        construction fails rather than serving wrong tokens."""
        if self.mode != "ragged":
            raise ValueError(
                "a config with recurrent layers needs the ragged engine: "
                "the two_lane prefill and decode programs carry no state")
        if self.prefix_cache:
            raise ValueError(
                "prefix_cache with recurrent layers: a shared prefix's "
                "pages hold its K/V but not the recurrent state at the "
                "fork point; it needs state snapshots at page boundaries")
        if self._draft is not None or self.spec_tokens > 0:
            raise ValueError(
                "speculative decoding with recurrent layers: a rejected "
                "draft has already advanced the state; it needs a state "
                "rollback to the last accepted token")
        if page_store is not None:
            raise ValueError(
                "page_store with recurrent layers: exported pages carry "
                "K/V only; it needs the recurrent state on the wire")
        if self.quantize_weights != "off":
            raise ValueError(
                "quantize_weights with a hybrid config: the rewrite knows "
                "mul ops only, not the stored-type products of its layers")

    def _refuse_for_window_layers(self, page_store):
        """A model with window layers keeps, of those layers, only the
        pages a query can still reach: what counts on every page of a
        sequence being there is refused at construction, by what it
        would need."""
        if self.mode != "ragged":
            raise ValueError(
                "a config with window layers needs the ragged engine: the "
                "two_lane programs know one kind of page pool")
        if self.prefix_cache:
            raise ValueError(
                "prefix_cache with window layers: a shared prefix's full "
                "pages are in the trie, its window pages were recycled; it "
                "needs the window's last pages kept at page boundaries")
        if self._draft is not None or self.spec_tokens > 0:
            raise ValueError(
                "speculative decoding with window layers: a rejected "
                "draft's rows have recycled pages the accepted prefix "
                "still needs; it needs recycling held back by the draft")
        if page_store is not None:
            raise ValueError(
                "page_store with window layers: exported runs carry the "
                "full layers' pages only; it needs the window pages on "
                "the wire")
        if self.quantize_weights != "off" or self.kv_dtype == "int8":
            raise ValueError(
                "quantize_weights or int8 pages with a MiMo config: the "
                "rewrite knows mul ops only, and int8 scale planes know "
                "no window and no split keys")

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "GenerationEngine":
        with self._cond:
            if self._started:
                return self
            if self._closed:
                raise EngineClosed("generation engine already closed")
            self._started = True
        self._loop_thread = threading.Thread(
            target=self._loop, name="pt-generation-loop", daemon=True)
        self._loop_thread.start()
        return self

    def close(self, drain: bool = True, timeout: Optional[float] = 60.0):
        """Stop admission. ``drain=True`` (the PR-3 serving contract)
        serves everything already submitted — running sequences AND
        queued requests — to their stop conditions, then exits;
        ``drain=False`` retires everything immediately."""
        with self._cond:
            already = self._closed and self._stop
            self._closed = True
            if not drain:
                self._stop = True
            self._cond.notify_all()
        if already:
            return
        if self._started:
            self._loop_thread.join(timeout)
        else:
            self._fail_queued(EngineClosed("engine closed before start()"))
        if drain and self._page_store is not None and self.prefix_cache:
            # drain-spill: trie-only pages outlive this engine in the
            # page store, so the rolling-restart replacement (or any
            # decode worker on this store) resumes warm instead of
            # re-prefilling the fleet's shared prefixes from scratch
            self.spill_trie()
            self.cache.drop_trie()

    def __enter__(self) -> "GenerationEngine":
        return self

    def __exit__(self, *exc):
        self.close(drain=exc[0] is None)

    @property
    def closed(self) -> bool:
        return self._closed

    def _kick(self):
        with self._cond:
            self._cond.notify_all()

    # -- submission ----------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = "default",  # type: ignore[assignment]
               deadline_ms: Optional[float] = None,
               on_token=None, tenant: Optional[str] = None,
               adapter: Optional[str] = None) -> GenerationStream:
        """Admit one prompt (1-D int sequence). Raises ``Overloaded``
        when the admission queue is full OR when the prompt + budget
        could never fit the page pool — both BEFORE any prefill
        work; raises ``EngineClosed`` after close(). ``tenant`` is the
        traffic-tier identity trie publishes are attributed to (the
        per-tenant quota unit). ``adapter`` names a RESIDENT LoRA
        adapter every row of this request decodes through (raises
        ``AdapterMissing`` before any queueing when it is not); the
        adapter is refcount-pinned until the request's terminal state,
        so evict cannot pull the factors out from under it."""
        prompt = np.asarray(prompt, dtype=np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.default_max_new)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eos = self.default_eos if eos_id == "default" else eos_id
        total = int(prompt.size) + max_new
        if total > self.config.max_position:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds max_position {self.config.max_position}")
        if not self.cache.can_fit_ever(total):
            # exhaustion surfaces at ADMISSION, not three layers into a
            # prefill: this request can never be served by this pool
            self.metrics.inc("rejected_total")
            raise Overloaded(
                f"request needs {self.cache.pages_needed(total)} pages; "
                f"pool holds {self.cache.usable_pages} "
                f"(generation_num_pages x generation_page_size)")
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        if adapter is not None:
            if self.adapter_store is None:
                raise ValueError(
                    f"request names adapter {adapter!r} but this engine "
                    "has no adapter store (set adapter_pool_max_bytes "
                    "or pass adapter_store=)")
            # pin BEFORE queueing (raises AdapterMissing when not
            # resident); released exactly once at the stream's terminal
            # state — every retirement path funnels through _finish
            self.adapter_store.acquire(adapter)
        stream = GenerationStream(self, on_token=on_token)
        if adapter is not None:
            stream.add_done_callback(
                lambda _s, _a=adapter: self.adapter_store.release(_a))
        # request tracing, on a client thread: behind the flag, unlike
        # the loop's phases (a short span here would claim device gaps
        # that belong to the loop thread's phase)
        with (tracing.span("generation/submit", {"prompt": int(prompt.size),
                                                 "max_new": max_new})
              if tracing.enabled() else contextlib.nullcontext()) as ctx:
            req = _GenRequest(prompt, max_new, eos, deadline, stream, ctx,
                              tenant=tenant, adapter=adapter)
            try:
                with self._cond:
                    if self._closed:
                        raise EngineClosed("GenerationEngine is closed")
                    if len(self._queue) >= self.queue_capacity:
                        self.metrics.inc("rejected_total")
                        raise Overloaded(
                            f"generation queue full ({self.queue_capacity} "
                            "pending); retry with backoff or raise "
                            "generation_queue_capacity")
                    self._queue.append(req)
                    self.metrics.inc("requests_total")
                    self._cond.notify_all()
            except BaseException:
                # rejected before the queue owned it: unpin here (the
                # stream never reaches a terminal state)
                if adapter is not None:
                    self.adapter_store.release(adapter)
                raise
        return stream

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 eos_id="default", deadline_ms: Optional[float] = None,
                 timeout: Optional[float] = None,
                 adapter: Optional[str] = None) -> List[int]:
        """Synchronous submit + result."""
        return self.submit(prompt, max_new_tokens, eos_id,
                           deadline_ms, adapter=adapter).result(timeout)

    # -- introspection -------------------------------------------------------
    def queue_depth(self) -> int:
        """Requests admitted but not yet prefilled (the traffic
        layer's backend-room check before dispatching a prompt).
        LOCKLESS on purpose: the traffic dispatcher calls this while
        holding its own condition variable, and this engine invokes
        stream done-callbacks (which re-enter the traffic layer) while
        holding ``self._cond`` — taking the engine lock here would be
        an ABBA deadlock. ``len`` of a deque is atomic under the GIL;
        an off-by-a-few readout only shifts one dispatch decision."""
        return len(self._queue)

    def prefix_probe(self, tokens) -> int:
        """Matched-prefix token count this prompt would get right now
        (a pure trie peek — no refcounts, no LRU touch). The traffic
        layer prices generate TTFT on the UNMATCHED suffix only; 0
        with the radix cache off."""
        if not self.prefix_cache:
            return 0
        return int(self.cache.match_len(
            np.asarray(tokens, dtype=np.int64).reshape(-1)))

    def stats(self) -> Dict[str, Any]:
        out = self.metrics.snapshot()
        out["cache"] = self.cache.stats()
        # flattened by the registry into paddle_generation_radix_*
        out["radix"] = self.cache.radix_stats()
        out["model_swaps"] = self.model_swaps
        # the bytes the bound step is donated and rewrites in place:
        # the page pools, or 0 beside the reason where the executor
        # donates nothing (a CPU)
        bound = self._ragged_bound or self._decode_bound
        if bound is not None:
            info = bound.audit_info()
            out["step_donated_bytes"] = info["donated_bytes"]
            if info["donation_skip_reason"]:
                out["donation_skip_reason"] = info["donation_skip_reason"]
        if self._state_names:
            out["recurrent_state_bytes"] = self.cache.state_bytes()
        if self._expert_layers and self._state_names:
            # the one read of the on-device load counts: never in a step.
            # With a step in flight it is that step's output, and the
            # read waits for it (any thread may: nothing is drained)
            loads = np.asarray(self.cache.state["gen_state_moe_loads"],
                               np.int64)
            out["moe_held_assignments_total"] = int(loads.sum())
            out["moe_expert_load_max"] = int(loads.max())
            out["moe_expert_load_mean"] = float(loads.mean())
            out["moe_kernel_layers"] = self._moe_kernel_layers
        # pages by kind of attention layer (window: 0 without any)
        out["kv_pages_resident_full"] = out["cache"]["pages_resident_full"]
        out["kv_pages_resident_window"] = \
            out["cache"]["pages_resident_window"]
        out["kv_window_pages_recycled_total"] = \
            out["cache"]["window_pages_recycled_total"]
        if self._page_store is not None:
            lk = self.store_lookups_total
            # flattened into paddle_generation_store_* — this WORKER's
            # page-store traffic (the store's own gauges are global)
            out["store"] = {
                "lookups_total": lk,
                "hits_total": self.store_hits_total,
                "hit_rate": (round(self.store_hits_total / lk, 4)
                             if lk else 0.0),
                "pages_pulled_total": self.store_pages_pulled_total,
                "pages_spilled_total": self.store_pages_spilled_total,
                "errors_total": self.store_errors_total,
            }
        return out

    def stats_numeric(self) -> Dict[str, Any]:
        """The registry collector's view (nested histograms flatten in
        the registry; this just merges cache stats in)."""
        return self.stats()

    def models_fragment(self) -> Dict[str, Any]:
        """The /healthz ``models`` fragment: base-model identity
        (program fingerprint + swap lineage) and the resident-adapter
        table — what a router needs to place by adapter residency
        instead of round-robin."""
        from ..runtime.dispatch import program_fingerprint

        prog = (self._ragged_prog if self.mode == "ragged"
                else self._decode_prog)
        return {
            "base": {
                "fingerprint": program_fingerprint(prog)[:12],
                "version": self.model_version,
                "swaps": int(self.model_swaps),
                "quantized": self.quantize_weights,
            },
            "phase": self.phase,
            "adapters": (self.adapter_store.resident()
                         if self.adapter_store is not None else []),
        }

    # -- hot base-model swap -------------------------------------------------
    def swap_base(self, weights: Dict[str, Any], *,
                  version: Optional[str] = None,
                  timeout: Optional[float] = 60.0) -> str:
        """Zero-downtime base-model swap: load a SIGNATURE-IDENTICAL
        checkpoint under live traffic. Heavy staging (array conversion
        and — when the base is quantized — re-quantization into the
        scope's exact mode/block) happens on THIS thread; the step
        loop applies the staged values between steps, so no in-flight
        batch ever mixes old and new weights and no request drops.

        Signature-identical means every name already lives in the
        scope with the same shape: the program, its fingerprint and
        the live BoundStep are untouched, so the swap costs ZERO new
        compile-cache entries (the rolling-restart warm-start proof,
        without the restart). Returns the new model version label."""
        meta = getattr(self._scope, "_quantize_meta", None) or {}
        staged = {}
        for name, val in weights.items():
            val = np.asarray(val)
            if name in meta:
                # quantized base: the serving buffers are {name}.q /
                # {name}.qscale — re-quantize into the scope's format
                from ..kernels.quant_matmul import quantize_weight

                wdtype, block = meta[name]
                q, s = quantize_weight(val, wdtype, block)
                staged[name + ".q"] = q
                staged[name + ".qscale"] = s
                continue
            cur = self._scope.find_var(name)
            if cur is None:
                raise ValueError(
                    f"swap_base: {name!r} is not a scope-resident "
                    "weight — a hot swap must be signature-identical "
                    "(same architecture, same var names)")
            if tuple(np.shape(cur)) != tuple(val.shape):
                raise ValueError(
                    f"swap_base: {name!r} shape {tuple(val.shape)} != "
                    f"serving shape {tuple(np.shape(cur))} — not "
                    "signature-identical; roll a new engine instead")
            staged[name] = val
        label = str(version) if version is not None \
            else f"swap-{self.model_swaps + 1}"
        done = threading.Event()
        with self._cond:
            if self._started and not self._closed:
                if self._pending_swap is not None:
                    raise RuntimeError(
                        "swap_base: another swap is already staged")
                self._pending_swap = (staged, label, done)
                self._cond.notify_all()
            else:
                # no loop running: apply inline (construction-time
                # load, or a drained engine)
                self._apply_swap(staged, label, done)
        if not done.wait(timeout if timeout is not None else 1e9):
            raise TimeoutError(
                f"swap_base: step loop did not apply the swap within "
                f"{timeout}s")
        return label

    def _apply_swap(self, staged: Dict[str, Any], label: str,
                    done: threading.Event) -> None:
        for name, val in staged.items():
            self._scope.set_var(name, val)
        self.model_swaps += 1
        self.model_version = label
        done.set()

    # -- the step loop -------------------------------------------------------
    def _phase(self, phase: str, args=None) -> _Phase:
        """``with self._phase("assemble"):`` — one of LOOP_PHASES, on the
        loop thread (ragged mode)."""
        return _Phase(self.metrics, phase, args)

    def _loop(self):
        try:
            while True:
                with self._cond:
                    if (not self._queue and not self._by_slot
                            and self._inflight is None):
                        # starved, not slow: nothing queued, no lane live
                        with self._phase("wait"):
                            while (not self._queue and not self._by_slot
                                   and not self._stop and not self._closed
                                   and self._pending_swap is None):
                                self._cond.wait(0.05)
                    if self._stop or (self._closed and not self._queue
                                      and not self._by_slot
                                      and self._inflight is None):
                        break
                    swap, self._pending_swap = self._pending_swap, None
                if swap is not None:
                    # the serving pointer flips BETWEEN steps, on the
                    # loop thread, with none in flight: no batch ever
                    # reads a half-swapped scope
                    with self._phase("emit"):
                        self._drain_inflight("swap")
                    self._apply_swap(*swap)
                if self.mode == "ragged":
                    with self._phase("admit"):
                        self._hold_for_submit()
                        self._iter_t0 = time.monotonic()
                        self._admit_ragged()
                    if self._by_slot or self._inflight is not None:
                        self._ragged_step()
                else:
                    self._admit_and_prefill()
                    if self._by_slot:
                        self._decode_step()
                self.metrics.set_gauges(len(self._queue), len(self._by_slot))
        finally:
            # loop exit — normal drain leaves nothing live; anything
            # still here (hard close, or the loop thread dying on an
            # unexpected exception) must fail loudly, and the engine
            # must reject future submits instead of queueing requests
            # nobody will ever serve
            with self._cond:
                self._closed = True
                swap, self._pending_swap = self._pending_swap, None
            try:
                # the tokens of a step in flight were computed: they go
                # out before their streams are closed
                self._drain_inflight("close")
            except Exception:  # noqa: BLE001 — the streams below must still end
                self._inflight = None
            if swap is not None:
                # a swap staged against a closing engine still lands
                # (scope outlives the loop) so its waiter never hangs
                self._apply_swap(*swap)
            self._fail_queued(EngineClosed(
                "engine closed before the request was served"))
            for slot, req in list(self._by_slot.items()):
                self.cache.release(slot)
                req.stream._finish("closed", EngineClosed(
                    "engine closed mid-generation"))
            self._by_slot.clear()
            self.metrics.set_gauges(0, 0)

    def _hold_for_submit(self) -> None:
        """Admission at the last moment. With a lane free, nothing queued
        and a step in flight, whether a finished request's successor
        joins the next step is a race between its client's thread and
        this loop. The device is busy with the step in flight, so the
        loop can afford to wait for ``submit`` (which notifies
        ``_cond``): until half of the device's last step has passed
        since the step in flight began, and no longer than leaves twice
        the loop's own time from admit to dispatch before it ends. With
        a queue waiting it never waits."""
        if self._inflight is None:
            return
        until = self._last_fetch_t + min(
            0.5 * self._step_s, self._step_s - 2.0 * self._host_s)

        def over():
            return (self._queue or self._stop or self._closed
                    or self._pending_swap is not None
                    or time.monotonic() >= until)

        with self._cond:
            if over() or self.cache.free_slots() <= 0:
                return
            t0 = time.perf_counter_ns()
            while not over():
                self._cond.wait(max(until - time.monotonic(), 0.0))
            self.metrics.inc("admit_holds_total")
            self.metrics.inc("admit_hold_us_total",
                             (time.perf_counter_ns() - t0) // 1000)

    def _fail_queued(self, err: BaseException):
        with self._cond:
            while self._queue:
                req = self._queue.popleft()
                req.stream._finish("closed", err)

    # -- admission + prefill lane -------------------------------------------
    def _seq_bucket(self, n: int) -> int:
        for b in self._seq_buckets:
            if n <= b:
                return b
        return self._seq_buckets[-1]

    def _pop_admissible(self) -> List[_GenRequest]:
        """FIFO admission: take queue-head requests while a slot AND
        pages for the whole prompt window are available (head-of-line
        blocking is deliberate — pool pressure must never starve the
        oldest request). Expired/cancelled requests drop here."""
        admitted: List[_GenRequest] = []
        now = time.monotonic()
        with self._cond:
            while self._queue:
                req = self._queue[0]
                if req.stream._cancelled:
                    self._queue.popleft()
                    self.metrics.inc("cancelled_total")
                    req.stream._finish("cancelled", RequestCancelled(
                        "cancelled while queued"))
                    continue
                if req.deadline is not None and now > req.deadline:
                    self._queue.popleft()
                    self.metrics.inc("expired_total")
                    req.stream._finish("deadline", DeadlineExceeded(
                        f"deadline passed after "
                        f"{(now - req.enqueue_t) * 1e3:.1f}ms in queue"))
                    continue
                # acquire marks slot + pages taken immediately, so
                # these checks already see earlier admissions. The
                # trie peek is race-free: only this loop thread
                # mutates the trie, so the acquire below matches at
                # least what match_len just saw. A matched prefix is
                # page-aligned, so suffix pages needed = total pages
                # - matched pages exactly.
                matched = (self.cache.match_len(req.prompt)
                           if self.prefix_cache else 0)
                if (self.cache.free_slots() <= 0
                        or not self.cache.can_acquire(
                            int(req.prompt.size) - matched,
                            prompt=req.prompt)):
                    break
                admitted.append(self._queue.popleft())
                req.slot, req.prefill_off = self.cache.acquire(req.prompt)
                if req.admit_seq == 0:
                    # first admission only: an evicted-and-resumed
                    # request keeps its original seniority, otherwise
                    # it would rank as the youngest and be the next
                    # eviction victim — thrashing the exact sequence
                    # the evict-youngest policy promises to finish
                    self._admit_counter += 1
                    req.admit_seq = self._admit_counter
                self.metrics.observe(
                    "queue_wait_ms", (now - req.enqueue_t) * 1e3)
        return admitted

    def _admit_and_prefill(self):
        admitted = self._pop_admissible()
        if not admitted:
            return
        # group by seq bucket; each group is one prefill executable run
        groups: Dict[int, List[_GenRequest]] = {}
        for req in admitted:
            groups.setdefault(self._seq_bucket(int(req.prompt.size)),
                              []).append(req)
        for bucket, reqs in sorted(groups.items()):
            self._prefill(bucket, reqs)

    def _prefill_prog(self, bucket: int):
        entry = self._prefill_progs.get(bucket)
        if entry is None:
            entry = build_prefill_program(self.config, bucket, self.geom)
            if self.quantize_weights != "off":
                # two_lane prefill executables build lazily per seq
                # bucket — each one repoints onto the scope's (already
                # converted) quantized buffers before first bind
                from .. import quantize as _quantize

                _quantize.rewrite_for_inference(
                    entry[0], self._scope, wdtype=self.quantize_weights,
                    block=self._quant_block)
            self._prefill_progs[bucket] = entry
        return entry

    def _prefill(self, bucket: int, reqs: List[_GenRequest]):
        t0 = time.monotonic()
        prog, fetches = self._prefill_prog(bucket)
        # FIXED prefill batch (the lane count): exactly ONE executable
        # per seq bucket for the engine's whole life — a variable batch
        # dim would mint an executable per (bucket, batch) pair and pay
        # XLA compiles mid-traffic (the padding rows are junk-routed
        # and nearly free; the compile stall is not)
        B = self.lanes
        tokens = np.zeros((B, bucket), np.int64)
        num_valid = np.zeros(B, np.int32)
        last_index = np.zeros(B, np.int64)
        tables = np.zeros((B, self.geom.max_pages_per_seq), np.int32)
        for i, req in enumerate(reqs):
            n = int(req.prompt.size)
            tokens[i, :n] = req.prompt
            num_valid[i] = n
            last_index[i] = n - 1
            tables[i] = self.cache.block_tables[req.slot]
        feed = {
            "gen_tokens": tokens,
            "gen_positions": np.zeros(B, np.int64),
            "gen_num_valid": num_valid,
            "gen_last_index": last_index,
            "gen_block_tables": tables,
        }

        def span_args():
            flow = [r.ctx.span_id for r in reqs[1:] if r.ctx is not None]
            return {"n": len(reqs), "bucket": bucket,
                    "rows": int(num_valid.sum()),
                    **({"flow_from": flow} if flow else {})}

        # the prefill lane drives the SAME resolved dispatch object as
        # every other subsystem (Executor.bind, one BoundStep per seq
        # bucket) — tagged for spans and the donation audit, with
        # rows_hint keeping examples/sec honest on the padded lanes
        bound = self._exe.bind(prog, feed, fetches, scope=self._step_scope,
                               tag=f"generation/prefill[{bucket}]")
        bound.rows_hint = len(reqs)
        try:
            with tracing.span("generation/prefill", span_args,
                              parent=reqs[0].ctx):
                outs = self._dispatch(bound, feed)
        except Exception as e:  # noqa: BLE001 — a bad prompt batch must not kill the loop
            for req in reqs:
                self.cache.release(req.slot)
                req.stream._finish("error", ServingError(
                    f"prefill execution failed: {e!r}"))
            self._recover_pools()
            return
        next_tok = np.asarray(outs[0]).reshape(-1)
        now = time.monotonic()
        self.metrics.inc("prefill_batches_total")
        self.metrics.inc("prefill_tokens_total", int(num_valid.sum()))
        self.metrics.inc("prefill_rows_total", len(reqs))
        self.metrics.inc("prefill_capacity_rows_total", B)
        self.metrics.observe("prefill_ms", (now - t0) * 1e3)
        for i, req in enumerate(reqs):
            self.cache.lengths[req.slot] = int(req.prompt.size)
            self._by_slot[req.slot] = req
            self._emit(req, int(next_tok[i]), now)

    # -- the ragged lane (mode="ragged") -------------------------------------
    def _admit_ragged(self):
        """Admission without a prefill executable: an admitted request
        takes a lane + pages for its whole prompt (the same FIFO
        head-of-line discipline as two_lane) and starts CHUNKED
        prefill on the next ragged step — at the trie fork point when
        the radix cache matched a prefix (acquire already set
        ``prefill_off`` / the cache length to the matched run, whose
        K/V is resident in the shared pages)."""
        self._consult_store()
        for req in self._pop_admissible():
            req.pending = None
            req.drafts = None
            self._by_slot[req.slot] = req
            if self._state_names:
                # the lane's first row is at position 0: the step zeroes
                # its recurrent state in the graph
                self.metrics.inc("state_lane_resets_total")

    # -- the page store seam (disagg) ----------------------------------------
    def _consult_store(self) -> None:
        """Before cold-prefilling queue-head prompts, ask the page
        store for their prefixes and splice any match into the local
        pool + trie — the decode-worker half of disaggregation and
        the warm-restart path. Runs on the LOOP THREAD only (the page
        bookkeeping of ``ingest_run`` is the loop's); the TCP fetch
        happens outside ``self._cond`` so submitters never block on
        the wire."""
        if self._page_store is None or not self.prefix_cache:
            return
        with self._cond:
            heads = [r for r in list(self._queue)[:self.lanes]
                     if not r.store_checked]
        for req in heads:
            req.store_checked = True
            try:
                self._pull_run(req.prompt, tenant=req.tenant)
            except Exception:  # noqa: BLE001 — a dead store degrades to cold prefill
                self.store_errors_total += 1

    def _pull_run(self, tokens, tenant=None) -> int:
        """Fetch + ingest the store's longest run for ``tokens``
        (capped like the trie match: at least one token is left to
        prefill). Returns pages ingested; 0 when the local trie
        already covers the store's match."""
        tokens = np.asarray(tokens, np.int64).reshape(-1)
        ps = self.page_size
        cap = (int(tokens.size) - 1) // ps
        local = self.cache.match_len(tokens) // ps
        if cap <= local:
            return 0
        self.store_lookups_total += 1
        blobs = self._page_store.match(tokens, max_pages=cap)
        if len(blobs) <= local:
            return 0
        from ..disagg.pagestore import run_for_pool

        n, k_run, v_run, ksc, vsc = run_for_pool(blobs, self.kv_dtype)
        if n <= local:
            return 0
        got = self.cache.ingest_run(tokens[:n * ps], k_run, v_run,
                                    ksc, vsc, tenant=tenant)
        if got:
            self.store_hits_total += 1
            self.store_pages_pulled_total += got
        return got

    def spill_run(self, tokens) -> int:
        """Export ``tokens``' trie-resident pages to the page store
        (the prefill-worker publish path). Safe from any thread —
        full trie pages are immutable, and ``export_run`` dispatches
        its reads under the cache's pool lock, which a step's dispatch
        holds while the pools it was donated are deleted and replaced:
        the reads see the arrays of before that step or of after it.
        No-op without a store."""
        if self._page_store is None or not self.prefix_cache:
            return 0
        n, k_run, v_run, ksc, vsc = self.cache.export_run(tokens)
        if not n:
            return 0
        from ..disagg.pagestore import encode_page

        blobs = [encode_page(k_run[i], v_run[i],
                             None if ksc is None else ksc[i],
                             None if vsc is None else vsc[i],
                             encoding=self._wire_encoding)
                 for i in range(n)]
        toks = np.asarray(tokens, np.int64).reshape(-1)[:n * self.page_size]
        self._page_store.put_run(toks, blobs)
        self.store_pages_spilled_total += n
        return n

    def spill_trie(self) -> int:
        """Spill EVERY trie-resident page run to the store — the
        drain hook: a rolling restart's replacement worker (or any
        fresh decode worker) then starts warm instead of cold."""
        if self._page_store is None or not self.prefix_cache:
            return 0
        total = 0
        for run in self.cache.trie_leaf_runs():
            try:
                total += self.spill_run(run)
            except Exception:  # noqa: BLE001 — spill is best-effort
                self.store_errors_total += 1
        return total

    def _bind_ragged(self, feed):
        if self._ragged_bound is None:
            self._ragged_bound = self._exe.bind(
                self._ragged_prog, feed, self._ragged_fetches,
                scope=self._step_scope, tag="generation/ragged_step")
            self._moe_kernel_layers = self._count_moe_kernel_layers()
        return self._ragged_bound

    def _lean_decode_tokens(self) -> int:
        """The most valid tokens of a lane that takes the attention
        kernel's lean branch in the step's full-attention layers (its
        rows, ``num_valid`` x the query group, fit ``lean_decode_rows``),
        or 0 where the step's kernel has no such branch."""
        from ..kernels.ragged_paged_attention import lean_decode_rows

        block = self._ragged_prog.global_block()
        for op in block.ops:
            if (op.type == "ragged_paged_attention"
                    and op.attr("lean_decode") and not op.attr("window")):
                kv_heads = block.var(op.input("KPages")[0]).shape[0]
                group = int(op.attr("num_heads")) // int(kv_heads)
                return lean_decode_rows(
                    group, self.chunk_tokens,
                    bool(op.attr("stored_products"))) // group
        return 0

    def _count_moe_kernel_layers(self) -> int:
        """The expert layers of the step that run kernels/moe_ffn.py and
        not the ``ragged_dot`` path: what ``topk_moe`` decides when the
        step is traced, asked of the same ``fits`` with the window's rows
        and the weights as the scope holds them."""
        from ..kernels import moe_ffn

        n = 0
        for op in self._ragged_prog.global_block().ops:
            if op.type == "topk_moe":
                w_out = self._step_scope.find_var(op.input("ExpertWOut")[0])
                _held, f, d = w_out.shape
                n += moe_ffn.fits(self.lanes * self.chunk_tokens, d, f,
                                  w_out.dtype)
        return n

    def _dispatch(self, bound, feed):
        """Dispatch one step that is donated the page pools: under the
        cache's pool lock from the moment the old arrays are deleted
        until the executor has stored the new ones (``BoundStep``
        writes them back before it returns), and no longer: the caller
        waits for the tokens outside it."""
        with self.cache.pools_locked():
            return bound.run(feed, False)

    def _recover_pools(self):
        """After a step that raised: one that failed on the device has
        consumed the pools it was donated. Serve on from empty pools
        (every sequence of the step has been failed already; the
        trie's pages are gone with the arrays)."""
        if self.cache.pools_alive():
            return
        for slot in list(self._by_slot):
            self._retire(slot, "error", ServingError(
                "the page pools were lost with a failed step"))
        self.cache.drop_trie()
        self.cache.reset_buffers()

    def _retire_dead_rows(self, now: float) -> None:
        """Retire cancelled/expired sequences before spending a step
        on them (shared by the ragged and two-lane step loops — the
        two engines must never diverge on retirement policy)."""
        for slot, req in list(self._by_slot.items()):
            if req.stream._cancelled:
                self._retire(slot, "cancelled")
                self.metrics.inc("cancelled_total")
            elif req.deadline is not None and now > req.deadline:
                self._retire(slot, "deadline")
                self.metrics.inc("expired_total")

    def _grow_or_evict(self, slot: int) -> bool:
        """Grow slot's page chain by one token past what is cached or in
        flight; a dry pool evicts (youngest first) and a truly stuck row
        finishes early ("capacity"). False when the slot was retired.
        Shared eviction policy for both engine modes. An eviction needs
        every token on the host (the victim resumes from prompt +
        emitted), so a step in flight is drained first, and what it
        retired may already be room enough."""
        while True:
            req = self._by_slot[slot]
            try:
                self.cache.ensure_capacity(
                    slot, int(self.cache.lengths[slot]) + req.ahead + 1)
                return True
            except PagePoolExhausted:
                if self._inflight is not None:
                    self._drain_inflight("evict")
                    if self._by_slot.get(slot) is not req:
                        return False
                elif not self._make_room(slot):
                    self._retire(slot, "capacity")
                    return False

    def _spec_budget(self, slot: int, req: _GenRequest) -> int:
        """Draft tokens this row could verify this step: bounded by
        the spec window, the chunk width, the request's remaining
        token budget and the position window."""
        if self._draft is None or self.spec_tokens <= 0:
            return 0
        L = int(self.cache.lengths[slot])
        return max(0, min(self.spec_tokens,
                          self.chunk_tokens - 1,
                          req.max_new - req.n_generated - 1,
                          self.config.max_position - L - 2))

    def _last_token_in_flight(self, req: _GenRequest) -> bool:
        """The finish by length is known before its token is: a row in
        flight samples this request's last token (``max_new``, or the
        position window), so the lane takes no further row and retires
        when that token is emitted. (EOS, cancel and deadline are
        learned at the emit: a row computed past them is discarded.)"""
        return req.token_in_flight() and (
            req.n_generated + 1 >= req.max_new
            or (int(self.cache.lengths[req.slot]) + req.ahead + 1
                >= self.config.max_position))

    def _ragged_step(self):
        """ONE mixed executable run: every active lane contributes
        whatever its sequence needs this step — a prefill chunk, a
        decode token, or a decode token plus speculative drafts — and
        the whole batch attends raggedly over the shared page pool.

        The step is dispatched while its predecessor still runs, and
        the predecessor's tokens are read and emitted after that
        (module docstring, "One step ahead")."""
        R, C = self.lanes, self.chunk_tokens
        with self._phase("grow"):
            self._retire_dead_rows(time.monotonic())
            # page growth for decode rows (+ the speculative window);
            # prefill rows were fully reserved at admission. A dry pool
            # first degrades speculation to plain decode, then evicts
            # (youngest first), then finishes the stuck row early.
            spec_rows: List = []
            for slot, req in list(self._by_slot.items()):
                if self._by_slot.get(slot) is not req:
                    continue
                if self._last_token_in_flight(req):
                    continue
                if req.prefill_off + req.ahead < int(req.prompt.size):
                    continue
                req.drafts = None
                k = self._spec_budget(slot, req)
                if k > 0:
                    try:
                        self.cache.ensure_capacity(
                            slot, int(self.cache.lengths[slot]) + 1 + k)
                        spec_rows.append((slot, req, k))
                        continue
                    except PagePoolExhausted:
                        pass
                self._grow_or_evict(slot)
            if self.adapter_store is not None:
                # a force-evicted adapter fails ITS rows here, before
                # they cost a step — never the whole batch
                from ..adapters import AdapterMissing

                for slot, req in list(self._by_slot.items()):
                    if req.adapter is None:
                        continue
                    try:
                        self.adapter_store.slots_row(req.adapter)
                    except AdapterMissing as e:
                        self._retire(slot, "error", ServingError(str(e)))
            spec_rows = [(s, r, k) for s, r, k in spec_rows
                         if s in self._by_slot]
            if self.cache.window is not None:
                # window layers: pages behind each row's window go back
                # to their free list, fresh ones cover the row's tokens
                for slot, req in self._by_slot.items():
                    if not self._last_token_in_flight(req):
                        self.cache.window_step(
                            slot, *self._row_extent(slot, req))
        if spec_rows:
            # batched drafting: ONE propose() call covers every
            # speculative row, so draft cost amortizes over the batch
            with self._phase("draft"):
                ctxs = [np.concatenate(
                    [r.orig_prompt, np.asarray(r.stream._tokens, np.int64)])
                    for _, r, _ in spec_rows]
                # always propose the FULL spec window and trim per row:
                # a shrinking k near a request's token budget would mint
                # a fresh draft executable per distinct k (warmup
                # compiled exactly the spec_tokens buckets)
                try:
                    props = self._draft.propose(ctxs, self.spec_tokens)
                except Exception:  # noqa: BLE001 — a broken draft must never kill decode
                    props = [np.zeros(0, np.int64)] * len(spec_rows)
                self.metrics.inc("spec_rounds_total")
                for (slot, req, k), dr in zip(spec_rows, props):
                    dr = np.asarray(dr, np.int64).reshape(-1)[:k]
                    req.drafts = dr
                    self.metrics.inc("spec_proposed_total", int(dr.size))
        rows = [(slot, req) for slot, req in self._by_slot.items()
                if not self._last_token_in_flight(req)]
        if not rows and self._inflight is None:
            return
        if rows:
            with self._phase("assemble"):
                feed, rows, carry = self._assemble(rows)
            with self._phase("bind"):
                bound = self._bind_ragged(feed)
                bound.rows_hint = len(rows)

        def step_args():
            flow = [r.ctx.span_id for _, r, _ in rows if r.ctx is not None]
            return {"n": len(rows), "lanes": R, "chunk": C,
                    "new_tokens": sum(nv for _, _, nv in rows),
                    **({"flow_from": flow} if flow else {})}

        with self._phase("step", step_args):
            due = []
            if rows:
                try:
                    step = self._dispatch_ahead(bound, feed, rows, carry)
                except Exception as e:  # noqa: BLE001 — a bad batch must not kill the loop
                    self._fail_step(rows, e)
                    return
                del feed
                if self._inflight is not None:
                    due.append(self._inflight)
                self._inflight = step
                if not self._run_ahead or self.spec_tokens > 0:
                    # a proposal needs the context's last token: an
                    # engine with a draft never runs ahead
                    if self.spec_tokens > 0:
                        self.metrics.inc("inflight_drains_draft_total")
                    due.append(step)
                    self._inflight = None
            else:
                due.append(self._inflight)
                self._inflight = None
            # where the loop waits for the device
            fetched = [(st, self._fetch(st)) for st in due]
        if fetched:
            with self._phase("emit"):
                for st, tokens in fetched:
                    self._emit_step(st, tokens)

    def _row_extent(self, slot: int, req: "_GenRequest"):
        """(first position, tokens) of the row the next step takes of a
        request without drafts: a prefill chunk, or one decode token."""
        off = req.prefill_off + req.ahead
        if off < int(req.prompt.size):
            return off, min(self.chunk_tokens, int(req.prompt.size) - off)
        return int(self.cache.lengths[slot]) + req.ahead, 1

    def _assemble(self, lanes):
        """The numpy batch of one step over ``lanes`` (slot, request):
        returns the feed dict, the rows as ``_StepInFlight`` keeps them
        and, per lane, the column of the step in flight whose token the
        row starts from (-1: the host's ``gen_tokens`` hold it)."""
        R, C = self.lanes, self.chunk_tokens
        tokens = np.zeros((R, C), np.int64)
        pos_ids = np.zeros((R, C), np.int64)
        positions = np.zeros(R, np.int64)
        num_valid = np.zeros(R, np.int32)
        carry = np.full(R, -1, np.int32)
        rows = []
        for slot, req in lanes:
            off = req.prefill_off + req.ahead
            if off < int(req.prompt.size):
                c = min(C, int(req.prompt.size) - off)
                tokens[slot, :c] = req.prompt[off:off + c]
                pos_ids[slot, :c] = np.arange(off, off + c)
                positions[slot] = off
                num_valid[slot] = c
                rows.append((slot, req, c))
                continue
            dr = (req.drafts if req.drafts is not None
                  else np.zeros(0, np.int64))
            L0 = int(self.cache.lengths[slot]) + req.ahead
            if req.token_in_flight():
                # the token this row starts from is the last valid
                # column of the request's row in the step in flight
                carry[slot] = req.ahead - 1
            else:
                tokens[slot, 0] = req.pending
            tokens[slot, 1:1 + dr.size] = dr
            pos_ids[slot, :1 + dr.size] = np.arange(L0, L0 + 1 + dr.size)
            positions[slot] = L0
            num_valid[slot] = 1 + dr.size
            rows.append((slot, req, 1 + int(dr.size)))
        live = positions + num_valid
        npages = -(-live // self.geom.page_size)
        walked = int(npages[num_valid > 0].sum())
        self.metrics.inc("attn_live_pages_total", walked)
        self.metrics.inc("attn_lean_pages_total", int(npages[
            (num_valid > 0) & (num_valid <= self._lean_tokens)].sum()))
        self.metrics.inc("attn_table_pages_total",
                         R * self.geom.max_pages_per_seq)
        feed = {
            "gen_tokens": tokens,
            "gen_pos_ids": pos_ids,
            "gen_positions": positions,
            "gen_num_valid": num_valid,
            "gen_block_tables": np.ascontiguousarray(
                self.cache.block_tables),
        }
        if self.adapter_store is not None:
            # per-row adapter slots, fed exactly like a block
            # table: zeros = the reserved zero adapter (base-only
            # rows / idle lanes), so the base path is identity by
            # construction
            aslots = np.zeros((R, self.adapter_store.n_buckets), np.int32)
            for slot, req in lanes:
                if req.adapter is not None:
                    aslots[slot] = self.adapter_store.slots_row(req.adapter)
            feed["gen_adapter_slots"] = aslots
        if self.cache.window is not None:
            ps, on = self.geom.page_size, num_valid > 0
            first = np.maximum(positions - self.cache.window.window + 1,
                               0) // ps
            self.metrics.inc("attn_live_pages_full_total", walked)
            self.metrics.inc("attn_live_pages_window_total", int(
                ((live - 1) // ps - first + 1)[on].sum()))
            # a copy: the ring's entries are rewritten for the next step
            # while this one may still read the array it was fed
            feed["gen_block_tables_window"] = self.cache.window_tables.copy()
        if self._state_names:
            # recurrent layers: the per-lane state is fed and fetched,
            # rewritten whole (the page pools are the step's state)
            feed.update(self.cache.state)
            self.metrics.inc("moe_tokens_routed_total",
                             int(num_valid.sum()) * self._expert_layers)
        return feed, rows, carry

    def _dispatch_ahead(self, bound, feed, rows, carry) -> _StepInFlight:
        """Enqueue one step behind whatever the device is running and
        return without waiting for it. The decode rows' input tokens
        are merged in on the device from the newest step's output (one
        small jitted call, so the step's own executable and arguments
        are what they were); the copy of the new tokens to the host
        starts here, and a hybrid model's new state replaces the old in
        the cache at once, so the host keeps no reference to what the
        step was fed."""
        t0 = time.monotonic()
        if self._prev_tokens is None:
            import jax.numpy as jnp

            # before the engine's first step: nothing to carry from
            self._prev_tokens = jnp.zeros(
                self.lanes * self.chunk_tokens, jnp.int32)
        feed["gen_tokens"] = _carry_fn()(
            feed["gen_tokens"], self._prev_tokens, carry)
        outs = self._dispatch(bound, feed)
        self._prev_tokens = outs[0]
        if hasattr(outs[0], "copy_to_host_async"):
            outs[0].copy_to_host_async()
        if self._state_names:
            self.cache.set_state(outs[1:])
        for _, req, nv in rows:
            req.ahead += nv
        if self._inflight is not None:
            self.metrics.inc("steps_dispatched_ahead_total")
        self.metrics.inc("device_carried_tokens_total",
                         int((carry >= 0).sum()))
        self._host_s = time.monotonic() - self._iter_t0
        return _StepInFlight(rows, outs[0], t0)

    def _fail_step(self, rows, e: BaseException) -> None:
        """A step raised, at its dispatch or when its tokens were read:
        its sequences fail; a step still in flight is read out (rows of
        the failed sequences are discarded there), and the pools, the
        carried tokens and a hybrid model's state start afresh if the
        device took them with it."""
        for slot, req, _ in rows:
            if self._by_slot.get(slot) is req:
                self._retire(slot, "error", ServingError(
                    f"ragged step execution failed: {e!r}"))
        self._drain_inflight("error")
        self._prev_tokens = None
        if self._state_names:
            self.cache.reset_state()
        self._recover_pools()

    def _fetch(self, step: _StepInFlight):
        """Wait for a dispatched step's tokens ``[lanes, chunk]``: for
        the step to be reported done (``generation/fetch_ready``), then
        for the copy; None when the step failed on the device (its
        sequences are failed here)."""
        import jax

        try:
            with tracing.annotation("generation/fetch"):
                with tracing.annotation("generation/fetch_ready"):
                    jax.block_until_ready(step.tokens)
                return np.asarray(step.tokens).reshape(
                    self.lanes, self.chunk_tokens)
        except Exception as e:  # noqa: BLE001 — a bad batch must not kill the loop
            self._fail_step(step.rows, e)
            return None

    def _drain_inflight(self, reason: str) -> None:
        """Read and emit the step in flight, if any, before something
        that needs every token on the host or no step running: an
        eviction, a base swap, the close, a failed step (and, each step,
        an engine with a draft). Runs inside the caller's phase."""
        step, self._inflight = self._inflight, None
        if step is None:
            return
        self.metrics.inc(f"inflight_drains_{reason}_total")
        self._emit_step(step, self._fetch(step))

    def _emit_step(self, step: _StepInFlight, next_all) -> None:
        """The host's half of a step whose tokens have arrived: advance,
        stop conditions, the clients' ``on_token`` callbacks, trie
        publish. A row whose sequence has ended since the dispatch (EOS
        a step earlier, cancel, deadline) is dropped, never emitted."""
        if next_all is None:
            return
        now = time.monotonic()
        self.metrics.inc("ragged_steps_total")
        emitted_total = n_active = 0
        for slot, req, nv in step.rows:
            n_active += 1
            if self._by_slot.get(slot) is not req:
                self.metrics.inc("discarded_rows_total")
                continue
            req.ahead -= nv
            if req.prefill_off < int(req.prompt.size):
                # a prefill chunk: its K/V is cached now; the FINAL
                # chunk additionally samples the first token
                # (TTFT). Publish BEFORE _emit: a request retiring
                # on its very first token must still leave its
                # prompt pages in the trie for the siblings behind
                # it.
                self.cache.advance(slot, nv)
                req.prefill_off += nv
                self.metrics.inc("prefill_chunks_total")
                self.metrics.inc("prefill_tokens_total", nv)
                if self.prefix_cache:
                    self.cache.publish(slot, req.prompt,
                                       tenant=req.tenant)
                if req.prefill_off >= int(req.prompt.size):
                    self.metrics.inc("prefill_batches_total")
                    self._emit(req, int(next_all[slot, nv - 1]), now)
                    emitted_total += 1
            else:
                # decode / speculative verify: next_all[slot, j] IS
                # the greedy token after position start+j, so draft
                # j is accepted iff it equals the target's token at
                # its own offset — the emitted stream is
                # greedy-identical by construction, whatever the
                # draft proposed
                dr = req.drafts if req.drafts is not None else ()
                for j in range(nv):
                    if j > 0:
                        if int(dr[j - 1]) != int(next_all[slot, j - 1]):
                            break       # rejected: the tail is dead
                        self.metrics.inc("spec_accepted_total")
                        req.stream.accepted_draft_tokens += 1
                    self.cache.advance(slot)
                    emitted_total += 1
                    self._emit(req, int(next_all[slot, j]), now)
                    if slot not in self._by_slot:
                        break       # retired (eos/length/deadline)
                if self.prefix_cache and self._by_slot.get(slot) is req:
                    # decode-produced full pages join the trie too:
                    # only positions < length publish, and rejected
                    # drafts live strictly at positions >= length
                    self.cache.publish(slot, np.concatenate(
                        [req.orig_prompt,
                         np.asarray(req.stream._tokens, np.int64)]),
                        tenant=req.tenant)
        # a step dispatched behind another took the device from when
        # the other's tokens arrived, not from its own dispatch
        began = max(step.t0, self._last_fetch_t)
        self.metrics.observe_decode_step(
            (now - began) * 1e3, n_active, self.lanes, tokens=emitted_total)
        if step.t0 < self._last_fetch_t:
            # the device's step, as the hold takes it: the shorter of the
            # last two, so that one slow step (a compile, a pause) bounds
            # no wait
            self._step_s = min(now - began, self._last_took)
            self._last_took = now - began
        self._last_fetch_t = now

    # -- decode lane ---------------------------------------------------------
    def _bind_decode(self, feed):
        if self._decode_bound is None:
            self._decode_bound = self._exe.bind(
                self._decode_prog, feed, self._decode_fetches,
                scope=self._step_scope, tag="generation/decode")
        return self._decode_bound

    def _make_room(self, slot: int) -> bool:
        """The pool is dry and `slot` needs one more page: evict the
        YOUNGEST other sequence that would actually RETURN pages (its
        request re-queues at the queue head; greedy decode resumes
        identically after re-prefill). Under the radix cache a
        sequence's pages may be shared with siblings or the trie —
        evicting a mostly-shared victim frees ~zero pages, so victims
        are filtered by ``reclaimable_pages`` first (without sharing
        every active sequence holds >= 1 private page, so this is
        exactly the old evict-youngest). Returns False when no
        eviction can free a page — the engine finishes `slot` early
        ("capacity") instead of deadlocking admission."""
        victims = sorted(
            (r for s, r in self._by_slot.items() if s != slot),
            key=lambda r: -r.admit_seq)
        victim = next((r for r in victims
                       if self.cache.reclaimable_pages(r.slot) > 0), None)
        if victim is None:
            return False
        vslot = victim.slot
        del self._by_slot[vslot]
        self.cache.evict(vslot)
        self.metrics.inc("evicted_total")
        # resume context = the caller's prompt + every token emitted so
        # far (the evicted cache held all but the pending one; the
        # re-prefill recomputes the lot and samples the NEXT token, so
        # nothing is re-emitted and nothing is skipped)
        victim.prompt = np.concatenate(
            [victim.orig_prompt,
             np.asarray(victim.stream._tokens, np.int64)])
        victim.slot = None
        victim.pending = None
        victim.prefill_off = 0
        victim.drafts = None
        with self._cond:
            self._queue.appendleft(victim)
            self._cond.notify_all()
        return True

    def _decode_step(self):
        Bd = self.lanes
        now = time.monotonic()
        self._retire_dead_rows(now)
        if not self._by_slot:
            return
        # grow page chains for the rows about to be written; evict on
        # exhaustion (youngest first), finish early when truly stuck
        for slot, req in list(self._by_slot.items()):
            if slot not in self._by_slot:   # evicted by an earlier row
                continue
            self._grow_or_evict(slot)
        if not self._by_slot:
            return
        tokens = np.zeros((Bd, 1), np.int64)
        positions = np.zeros(Bd, np.int64)
        num_valid = np.zeros(Bd, np.int32)
        attend = np.ones(Bd, np.int32)   # idle lanes read 1 junk slot
        for slot, req in self._by_slot.items():
            tokens[slot, 0] = req.pending
            positions[slot] = int(self.cache.lengths[slot])
            num_valid[slot] = 1
            attend[slot] = int(self.cache.lengths[slot]) + 1
        feed = {
            "gen_tokens": tokens,
            "gen_positions": positions,
            "gen_num_valid": num_valid,
            "gen_attend_lens": attend,
            "gen_block_tables": np.ascontiguousarray(
                self.cache.block_tables),
        }
        bound = self._bind_decode(feed)
        active = list(self._by_slot.items())
        bound.rows_hint = len(active)

        def span_args():
            flow = [r.ctx.span_id for _, r in active if r.ctx is not None]
            return {"n": len(active), "lanes": Bd,
                    **({"flow_from": flow} if flow else {})}

        t0 = time.monotonic()
        try:
            with tracing.span("generation/decode_step", span_args):
                outs = self._dispatch(bound, feed)
        except Exception as e:  # noqa: BLE001
            for slot, req in active:
                self._retire(slot, "error", ServingError(
                    f"decode execution failed: {e!r}"))
            self._recover_pools()
            return
        next_tok = np.asarray(outs[0]).reshape(-1)
        now = time.monotonic()
        self.metrics.observe_decode_step((now - t0) * 1e3, len(active), Bd)
        for slot, req in active:
            self.cache.advance(slot)    # pending's K/V is cached now
            self._emit(req, int(next_tok[slot]), now)

    # -- token emission + retirement ----------------------------------------
    def _emit(self, req: _GenRequest, token: int, now: float):
        """A token was just sampled for req: stream it, update timing
        metrics, apply stop conditions, otherwise leave it pending for
        the next decode step."""
        first = req.stream.first_token_at is None
        if req.last_tok_t is not None:
            self.metrics.observe("itl_ms", (now - req.last_tok_t) * 1e3)
        req.stream.verified_tokens += 1
        req.stream._push(token)
        req.last_tok_t = now
        if first:
            self.metrics.observe(
                "ttft_ms", (now - req.enqueue_t) * 1e3)
        req.pending = token
        req.n_generated += 1
        if req.eos_id is not None and token == req.eos_id:
            self._retire(req.slot, "eos")
        elif req.n_generated >= req.max_new:
            self._retire(req.slot, "length")
        elif (int(self.cache.lengths[req.slot]) + 1
                >= self.config.max_position):
            self._retire(req.slot, "length")
        elif req.deadline is not None and now > req.deadline:
            self._retire(req.slot, "deadline")
            self.metrics.inc("expired_total")

    def _retire(self, slot: int, reason: str,
                error: Optional[BaseException] = None):
        req = self._by_slot.pop(slot, None)
        if (self.prefix_cache and req is not None and error is None
                and self.cache.is_active(slot)):
            # last publish before the pages go back: every full page
            # below the length holds verified K/V whatever the finish
            # reason (cancel/deadline included — the release below is
            # refcounted, so trie-resident pages survive for siblings
            # while everything private frees)
            self.cache.publish(slot, np.concatenate(
                [req.orig_prompt,
                 np.asarray(req.stream._tokens, np.int64)]),
                tenant=req.tenant)
        self.cache.release(slot)
        if req is not None:
            if error is None and reason in ("eos", "length", "capacity"):
                self.metrics.inc("responses_total")
            req.slot = None
            req.stream._finish(reason, error)

    # -- warmup --------------------------------------------------------------
    def _warmup(self):
        """Compile every executable before serving traffic, so no
        request ever pays an XLA compile mid-generation. Ragged mode
        has exactly ONE executable to warm (a two-token prompt driven
        through prefill-chunk + decode phases of the same program, a
        step ahead as the loop runs it);
        two_lane warms the whole prefill-bucket ladder + decode."""
        if self.mode == "ragged":
            if self.spec_tokens > 0 and hasattr(self._draft, "warmup"):
                # the draft's jitted length-bucket ladder is part of
                # the no-compile-mid-generation contract too
                self._draft.warmup(self.spec_tokens)
            slot = self.cache.allocate_slot(2)
            # three tokens: the prompt's chunk, then decode rows whose
            # input is carried on the device from the step before, so
            # every shape the loop will dispatch has run once
            req = _GenRequest(np.asarray([0, 0], np.int64),
                              max(1, min(3, self.config.max_position - 2)),
                              None, None, GenerationStream(self), None)
            req.slot = slot
            self._by_slot[slot] = req
            try:
                for _ in range(8):
                    if slot not in self._by_slot:
                        break
                    self._ragged_step()
            finally:
                self._drain_inflight("close")
                if slot in self._by_slot:
                    self._retire(slot, "length")
                elif self.cache.is_active(slot):
                    self.cache.release(slot)
            if req.stream.error is not None:
                # the step loop turns a failed step into a per-request
                # error so one bad batch cannot stop serving; at warmup
                # there is nothing to keep serving — an executable that
                # does not compile or run must fail construction
                raise req.stream.error
            if self.prefix_cache:
                # warmup's dummy [0, 0] prompt must not seed the trie
                self.cache.drop_trie()
            if self._state_names:
                # nor count in the experts' loads
                self.cache.reset_state()
            self.metrics.__init__()
            # nor its compiling steps bound a hold
            self._step_s = self._last_took = 0.0
            return
        for bucket in self._seq_buckets:
            slot = self.cache.allocate_slot(2)
            try:
                req = _GenRequest(np.asarray([0, 0], np.int64), 2, None,
                                  None, GenerationStream(self), None)
                req.slot = slot
                self._prefill(bucket, [req])   # compiles this bucket
                if slot in self._by_slot:
                    self._decode_step()        # compiles + binds decode
            finally:
                if slot in self._by_slot:
                    self._retire(slot, "length")
                elif self.cache.is_active(slot):
                    self.cache.release(slot)
        # warmup traffic must not pollute the serving metrics
        self.metrics.__init__()
