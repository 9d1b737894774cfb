"""Traffic kind `train_steps`: one `Executor.run` a step on the
configuration's pre-training program, the loss fetched to the host
each step, back to back for the length of the window.

Traffic file keys: seq_len, batch_per_chip, feeds (distinct host
batches reused in rotation), check_steps (steps the reference follows),
warm_steps (further steps before the window), block_rows (rows per
block of the reference), limits {loss_gap, grad_norm_gap,
delta_norm_gap}.
"""

import gc
import os
import statistics

import numpy as np

import harness

MODELS = os.path.join(harness.HERE, "models")


def run_window(step, clock, seconds):
    """Steps back to back from a step boundary until `seconds` have
    passed; the clock stops when the last step that was started has
    returned its fetch. Returns (steps, elapsed, end time of each
    step relative to the start)."""
    start = clock()
    ends = []
    while True:
        step()
        now = clock()
        ends.append(now - start)
        if now - start >= seconds:
            return len(ends), now - start, ends


def worst_leaf_gap(got, want, leaves=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf)."""
    leaves = list(leaves if leaves is not None else want)
    median = statistics.median(want[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], median, 1e-30)
        if not gap <= worst:        # a NaN is the worst there is
            worst, where = gap, k
    return worst, where


def moving_leaves(ref_grad_norm):
    """Leaves whose gradient is nought to rounding in the reference
    (a key's bias under softmax) move under Adam by round-off alone:
    under a thousandth of the median leaf's, they are left out of the
    comparison of the parameters' change."""
    median = statistics.median(ref_grad_norm.values())
    return [k for k, v in ref_grad_norm.items() if v >= 1e-3 * median]


def compare(got, want, limits):
    """[(name, value, limit)] for the harness to judge."""
    checks = []
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"]), start=1):
        checks.append((f"loss_gap_step{i}", abs(a - b) / abs(b),
                       limits["loss_gap"]))
    gap, leaf = worst_leaf_gap(got["grad_norm"], want["grad_norm"])
    checks.append(("grad_norm_gap", gap, limits["grad_norm_gap"]))
    gap2, leaf2 = worst_leaf_gap(got["delta_norm"], want["delta_norm"],
                                 moving_leaves(want["grad_norm"]))
    checks.append(("delta_norm_gap", gap2, limits["delta_norm_gap"]))
    return checks, {"grad_norm_gap_leaf": leaf, "delta_norm_gap_leaf": leaf2}


class Kind:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.seq = int(t["seq_len"])
        self.chips = len(ctx.devices)
        self.batch = int(t["batch_per_chip"]) * self.chips
        self.model = harness.load_module(
            os.path.join(MODELS, ctx.config["model"] + "_program.py"))
        self.reference = harness.load_module(
            os.path.join(MODELS, ctx.config["model"] + "_reference.py"))
        self.got = {"loss": []}
        self.losses = []

    # -- set-up: one object, driven from the seed through its first steps ----
    def setup(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu as fluid
        from models import weights

        ctx, t, cfg = self.ctx, self.ctx.traffic, self.ctx.config
        main, startup, loss = self.model.build(cfg, self.seq)
        self.loss = loss
        self.moment1 = self.model.moment1_names(main)
        self.scope = fluid.Scope()
        self.spec = self.reference.spec(cfg)
        names = [row[0] for row in self.spec]
        with fluid.scope_guard(self.scope):
            self.exe = fluid.Executor(fluid.TPUPlace())
            self.exe.run(startup)
        made = weights.make_weights(self.spec, cfg["initializer_range"],
                                    ctx.seed, ctx.devices[0])
        for name in names:
            have = self.scope.find_var(name)
            if have is None or tuple(have.shape) != tuple(made[name].shape):
                raise RuntimeError(f"the program has no parameter {name} of "
                                   f"shape {made[name].shape}")
            self.scope.set_var(name, made[name])
        del made
        self.program = main
        if len(ctx.devices) > 1:
            places = [fluid.TPUPlace(i) for i in range(len(ctx.devices))]
            self.program = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, places=places)
        rng = np.random.default_rng(ctx.seed)
        self.feeds = self.model.make_feeds(rng, int(t["feeds"]), self.batch,
                                           self.seq, cfg["vocab_size"])
        self.next_feed = 0

        norms = jax.jit(lambda tree: {
            k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()})
        beta1 = self.reference.ADAM["beta1"]
        check_steps = int(t["check_steps"])
        for i in range(1, check_steps + int(t["warm_steps"]) + 1):
            value = self.step()
            if i <= check_steps:
                self.got["loss"].append(value)
            if i == 1:
                # the first gradient as the optimizer got it: Adam's
                # first moment after one step is (1 - beta1) * g
                m1 = norms({n: self.scope.find_var(self.moment1[n])
                            for n in names})
                self.got["grad_norm"] = {k: float(v) / (1 - beta1)
                                         for k, v in m1.items()}
            if i == check_steps:
                start = weights.make_weights(
                    self.spec, cfg["initializer_range"], ctx.seed,
                    ctx.devices[0])
                now = {n: self.scope.find_var(n) for n in names}
                # on several chips the state is replicated over them
                start = {n: jax.device_put(v, now[n].sharding)
                         for n, v in start.items()}
                diff = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(
                    now, start)
                self.got["delta_norm"] = {k: float(v)
                                          for k, v in norms(diff).items()}
                del start, diff, now
        self.losses = []

    def step(self):
        """The window's own call and feed."""
        feed = self.feeds[self.next_feed % len(self.feeds)]
        self.next_feed += 1
        with harness.span("bench/exe.run"):
            (out,) = self.exe.run(self.program, feed=feed,
                                  fetch_list=[self.loss], scope=self.scope)
            value = float(np.asarray(out).reshape(-1)[0])
        self.losses.append(value)
        return value

    # -- the measured window ------------------------------------------------------
    def window(self):
        ctx = self.ctx
        with harness.span("bench/window"):
            steps, elapsed, ends = run_window(self.step, ctx.clock,
                                              ctx.window_seconds)
        tokens = steps * self.batch * self.seq
        bad = sum(1 for v in self.losses if not np.isfinite(v))
        # what tells a stalled step from a run that is slow throughout
        walls = sorted((b - a) * 1e3 for a, b in zip([0.0] + ends[:-1], ends))
        ctx.notes.update(step_ms_p50=statistics.median(walls),
                         step_ms_max=walls[-1],
                         steps_over_1p5_median=sum(
                             1 for w in walls
                             if w > 1.5 * statistics.median(walls)))
        return {"end_to_end": {"train_tokens_per_s": tokens / elapsed},
                "attempted": steps, "failed": bad, "steps": steps,
                "window_s": elapsed, "step_ends": ends, "tokens": tokens,
                "memory_peak_bytes": self.program_peak_bytes()}

    def program_peak_bytes(self):
        try:
            return harness.executable_bytes(self.exe.bind(
                self.program, self.feeds[0], [self.loss], scope=self.scope))
        except Exception as e:  # noqa: BLE001 — the allocator's counter stands
            self.ctx.notes["program_peak_bytes_error"] = repr(e)[:200]
            return 0

    def release(self):
        self.exe = self.program = self.scope = None
        gc.collect()

    # -- the comparison that decides `correct` ------------------------------------
    def check(self):
        from models import weights

        ctx, t, cfg = self.ctx, self.ctx.traffic, self.ctx.config
        params = weights.make_weights(self.spec, cfg["initializer_range"],
                                      ctx.seed, ctx.devices[0])
        want = self.reference.train_readings(
            cfg, params, self.feeds[:int(t["check_steps"])],
            block_rows=int(t["block_rows"]), devices=ctx.devices)
        checks, where = compare(self.got, want, t["limits"])
        ctx.notes.update(where)
        ctx.notes["loss_program"] = self.got["loss"]
        ctx.notes["loss_reference"] = want["loss"]
        self.want, self.params = want, params
        return checks

    def control(self):
        """Readings for control.py, the reference put in the program's
        place: computed in fp8 (the precision below the bfloat16 the
        configuration states), and with each fault a training cell can
        have planted in it. {name: [(check, value, limit)]}."""
        ctx, t, cfg = self.ctx, self.ctx.traffic, self.ctx.config
        feeds = self.feeds[:int(t["check_steps"])]
        plants = {"control_fp8": dict(precision="fp8"),
                  "fault_half_batch": dict(rows=slice(0, self.batch // 2))}
        out = {}
        for name, kw in plants.items():
            got = self.reference.train_readings(
                cfg, self.params, feeds, block_rows=int(t["block_rows"]),
                devices=None if "rows" in kw else ctx.devices, **kw)
            out[name] = compare(got, self.want, t["limits"])[0]
        # a step that returns its state unchanged: the parameters'
        # change is nought, which reads 1 by the measure; no run needed
        return out
