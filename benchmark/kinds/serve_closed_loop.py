"""Traffic kind `serve_closed_loop`: a fixed number of clients, each
sending its next request through `GenerationEngine.submit` when its
last one completes, so every lane is busy for the whole window whatever
the engine's speed.

Traffic file keys: clients_per_lane, prompt_lens and answer_lens (request
k takes the k-th of each list, cycling: the seed draws token ids and
weights and never the lengths or their order, because a window cuts
the sequence of requests short and another order is other work),
drain ("cancel": at the close every request in
flight is cancelled; "first_token": first wait until every request
submitted in the window has its first token), check_requests (finished
requests the reference is run over, the longest among them),
check_pad_to (the reference's padded length), limits
{served_logit_gap, step_argument_bytes_gap}.
"""

import gc
import os
import queue
import threading

import numpy as np

import harness
import reduce

MODELS = os.path.join(harness.HERE, "models")


class Request:
    __slots__ = ("client", "prompt", "max_new", "submit_t", "times",
                 "tokens", "stream", "done_t")

    def __init__(self, client, prompt, max_new):
        self.client, self.prompt, self.max_new = client, prompt, max_new
        self.submit_t = self.done_t = None
        self.times, self.tokens, self.stream = [], [], None


def window_metrics(requests, t_start, t_end):
    """End-to-end numbers over all tokens and all requests of the
    window, from the benchmark's own timestamps."""
    tokens, gaps, ttft = 0, [], []
    for r in requests:
        for j, t in enumerate(r.times):
            if t_start < t <= t_end:
                tokens += 1
                if j > 0:
                    gaps.append((t - r.times[j - 1]) * 1e3)
        if r.submit_t is not None and t_start <= r.submit_t <= t_end and r.times:
            ttft.append((r.times[0] - r.submit_t) * 1e3)
    window = t_end - t_start
    return {"serve_out_tokens_per_s": tokens / window,
            "itl_p95_ms": reduce.quantile(gaps, 0.95),
            "ttft_p50_ms": reduce.quantile(ttft, 0.5),
            "tokens": tokens, "gaps": len(gaps), "ttft_samples": len(ttft)}


def work_counts(requests, t_start, t_end, prefill_tokens):
    """Tokens processed and their cached lengths, for serve_step.mfu:
    an output token j > 0 of a prompt of P was produced by one decode
    row at cached length P + j; prefill tokens come from the engine's
    counter, at half their prompt's length on average."""
    emitted = decode = context = 0
    prompts = []
    for r in requests:
        p = len(r.prompt)
        for j, t in enumerate(r.times):
            if t_start < t <= t_end:
                emitted += 1
                if j > 0:
                    decode += 1
                    context += p + j
                else:
                    prompts.append(p)
    mean_prompt = (sum(prompts) / len(prompts)) if prompts else 0.0
    return {"tokens_emitted": emitted,
            "tokens_processed": decode + prefill_tokens,
            "context_sum": context + prefill_tokens * mean_prompt / 2}


class Kind:
    def __init__(self, ctx):
        self.ctx = ctx
        self.model = harness.load_module(
            os.path.join(MODELS, ctx.config["model"] + "_program.py"))
        self.reference = harness.load_module(
            os.path.join(MODELS, ctx.config["model"] + "_reference.py"))
        self.rng = np.random.default_rng(ctx.seed)
        self.issued = 0
        self.requests = []
        self.done_q = queue.Queue()
        self.first_q = queue.Queue()
        self.sample = []

    # -- set-up ------------------------------------------------------------------
    def setup(self):
        from models import weights

        ctx, cfg, t = self.ctx, self.ctx.config, self.ctx.traffic
        self.spec = self.reference.spec(cfg)
        made = weights.make_weights(self.spec, cfg["initializer_range"],
                                    ctx.seed, ctx.devices[0])
        self.weight_arrays = list(made.values())
        self.eng, self.pred = self.model.build_engine(cfg, made)
        del made
        lanes = int(cfg["engine"]["lanes"])
        clients = int(t["clients_per_lane"]) * lanes
        # the first round's answers are staggered, so that the lanes do
        # not finish (and prefill) in step for the rest of the run
        for c in range(clients):
            self.submit(c, stagger=(c % lanes + 1) / lanes)
        # the clock starts once every lane is decoding
        for _ in range(lanes):
            self.first_q.get(timeout=600)
        self.first_q = None

    def next_request(self, client, stagger=1.0):
        k = self.issued
        self.issued += 1
        t = self.ctx.traffic
        p = int(t["prompt_lens"][k % len(t["prompt_lens"])])
        n = int(t["answer_lens"][k % len(t["answer_lens"])])
        n = max(8, int(n * stagger))
        prompt = self.rng.integers(1, self.ctx.config["vocab_size"], p,
                                   dtype=np.int64)
        return Request(client, prompt, n)

    def submit(self, client, stagger=1.0):
        r = self.next_request(client, stagger)
        clock = self.ctx.clock

        def on_token(tok, r=r):
            r.times.append(clock())
            r.tokens.append(tok)
            if len(r.tokens) == 1 and self.first_q is not None:
                self.first_q.put(r)

        def on_done(_stream, r=r):
            r.done_t = clock()
            self.done_q.put(r)

        self.requests.append(r)
        r.submit_t = clock()
        r.stream = self.eng.submit(r.prompt, max_new_tokens=r.max_new,
                                   eos_id=None, on_token=on_token)
        r.stream.add_done_callback(on_done)
        return r

    # -- the measured window -------------------------------------------------------
    def window(self):
        ctx, t = self.ctx, self.ctx.traffic
        clock = ctx.clock
        c0 = self.model.counters(self.eng)
        fill = []       # pages in use whenever a request completes
        with harness.span("bench/window"):
            t_start = clock()
            deadline = t_start + ctx.window_seconds
            while True:
                left = deadline - clock()
                if left <= 0:
                    break
                try:
                    r = self.done_q.get(timeout=left)
                except queue.Empty:
                    break
                fill.append(self.model.pool_fill(self.eng))
                self.submit(r.client)
            t_end = clock()
        fill.append(self.model.pool_fill(self.eng))
        c1 = self.model.counters(self.eng)
        program_peak = self.step_program_bytes()
        self.drain(t_start, t_end)

        m = window_metrics(self.requests, t_start, t_end)
        inside = [r for r in self.requests
                  if t_start <= r.submit_t <= t_end]
        failed = [r for r in inside if self.failed(r)]
        finished = [r for r in self.requests
                    if r.done_t is not None and t_start < r.done_t <= t_end
                    and r.stream.finish_reason == "length"
                    and len(r.tokens) == r.max_new]
        self.sample = self.draw_sample(finished)
        d = {k: c1[k] - c0[k] for k in c0}
        raw = {"end_to_end": {k: m[k] for k in ("serve_out_tokens_per_s",
                                                 "itl_p95_ms", "ttft_p50_ms")
                              if m[k] is not None},
               "attempted": len(inside), "failed": len(failed),
               "window_s": t_end - t_start,
               "ragged_steps": d["ragged_steps_total"],
               "active_lane_steps": d["decode_active_lane_steps_total"],
               "capacity_lane_steps": d["decode_capacity_lane_steps_total"],
               "prefill_tokens": d["prefill_tokens_total"],
               "memory_peak_bytes": program_peak}
        raw.update(work_counts(self.requests, t_start, t_end,
                               d["prefill_tokens_total"]))
        ctx.notes.update(finished_in_window=len(finished),
                         tokens_in_window=m["tokens"], itl_samples=m["gaps"],
                         ttft_samples=m["ttft_samples"],
                         requests_total=len(self.requests),
                         pool_fill_mean=sum(fill) / len(fill),
                         pool_fill_max=max(fill))
        return raw

    def step_program_bytes(self):
        """XLA's accounting of the ragged step the window drove: what
        it needs while it runs (weights and both copies of the page
        pools are its arguments and outputs; the allocator's counter
        stands where this cannot be read), and, kept for the check,
        the bytes of its arguments."""
        self.argument_bytes = None
        try:
            step = self.model.ragged_step()
            mem = step.aot_compiled().memory_analysis()
            self.argument_bytes = int(mem.argument_size_in_bytes)
            return harness.executable_bytes(step)
        except Exception as e:  # noqa: BLE001
            self.ctx.notes["step_program_bytes_error"] = repr(e)[:200]
            return 0

    def failed(self, r):
        """A request the harness itself cut off at the close is no
        failure, unless the drain waited for its first token and none
        came; any other must have ended by its length."""
        if r in self.cut_off:
            return self.ctx.traffic["drain"] == "first_token" and not r.times
        return r.stream.error is not None or r.stream.finish_reason != "length"

    def drain(self, t_start, t_end):
        """Late is late, not wrong: a request submitted in the window
        gets up to a minute past the close to give its first token."""
        if self.ctx.traffic["drain"] == "first_token":
            stop = self.ctx.clock() + 60.0
            waiting = [r for r in self.requests
                       if t_start <= r.submit_t <= t_end and not r.times]
            while waiting and self.ctx.clock() < stop:
                threading.Event().wait(0.01)
                waiting = [r for r in waiting
                           if not r.times and not r.stream.done()]
        self.cut_off = [r for r in self.requests if not r.stream.done()]
        for r in self.cut_off:
            r.stream.cancel()
        self.eng.close(drain=False)

    def draw_sample(self, finished):
        """The requests the reference is run over: the longest, and
        others drawn from the seed."""
        k = int(self.ctx.traffic["check_requests"])
        if not finished:
            return []
        order = sorted(finished, key=lambda r: -(len(r.prompt) + len(r.tokens)))
        rest = order[1:]
        rng = np.random.default_rng(self.ctx.seed + 1)
        picks = rng.permutation(len(rest))[:max(k - 1, 0)]
        return [order[0]] + [rest[i] for i in picks]

    def release(self):
        """Free the program's state for good: the weights it was given
        and its page pools."""
        cache = self.eng.cache
        for arr in (self.weight_arrays + list(cache.k_pages)
                    + list(cache.v_pages)):
            try:
                arr.delete()
            except Exception:  # noqa: BLE001 — already freed
                pass
        self.weight_arrays = self.eng = self.pred = None
        gc.collect()

    # -- the comparison that decides `correct` ---------------------------------------
    def check(self):
        from models import weights

        ctx, cfg, t = self.ctx, self.ctx.config, self.ctx.traffic
        limits = t["limits"]
        storage = self.storage_check(limits["step_argument_bytes_gap"])
        if not self.sample:
            return [("served_logit_gap", None, limits["served_logit_gap"]),
                    storage]
        params = weights.make_weights(self.spec, cfg["initializer_range"],
                                      ctx.seed, ctx.devices[0])
        pairs = [(r.prompt, np.asarray(r.tokens, np.int64))
                 for r in self.sample]
        gaps = self.reference.served_gaps(
            cfg, params, pairs, int(t["check_pad_to"]),
            max(t["answer_lens"]))
        ctx.notes.update(checked_requests=len(pairs), checked_tokens=len(gaps))
        self.params, self.pairs, self.gaps = params, pairs, gaps
        return [("served_logit_gap", max(gaps), limits["served_logit_gap"]),
                storage]

    def storage_check(self, limit):
        """Are the weights and the KV pages kept in the type the
        configuration states: the bytes the step program of the window
        takes as arguments against the stated bytes. The served
        tokens cannot tell (PERF.md): the products round their
        operands to bfloat16 already."""
        stated = self.reference.stated_storage_bytes(self.ctx.config)
        self.ctx.notes.update(step_argument_bytes=self.argument_bytes,
                              stated_storage_bytes=stated)
        gap = (None if self.argument_bytes is None
               else abs(self.argument_bytes - stated) / stated)
        return ("step_argument_bytes_gap", gap, limit)

    def control(self):
        """Readings for control.py: at each position of the same
        prompts and tokens, the gap of the token that a pass of the
        reference in a lower precision puts first. Neither is the
        cell's control (that is the program with its own bfloat16
        pages switched on: control.py --set engine.kv_dtype=bfloat16);
        `reference_in_bf16` is kept because it does not separate from
        the program, `reference_in_fp8` because the limit of
        served_logit_gap was set below it (PERF.md)."""
        t = self.ctx.traffic
        out = {}
        self.control_gaps = {}
        for precision in ("bf16", "fp8"):
            gaps = self.reference.served_gaps(
                self.ctx.config, self.params, self.pairs,
                int(t["check_pad_to"]), max(t["answer_lens"]),
                control=precision)
            self.control_gaps[precision] = gaps
            out["reference_in_" + precision] = [
                ("served_logit_gap", max(gaps),
                 t["limits"]["served_logit_gap"])]
        return out
