"""Traffic kind `serve_closed_loop_stated`: `serve_closed_loop` for a
configuration whose weights are kept in a stated type other than
float32 and whose model pair has more counters than the fixed set.

Traffic, window, metrics, drain and result line are inherited
unchanged. What differs:

* both copies of the weights (the program's, and the one the reference
  gets after the program's state is freed) are made in the
  configuration's `storage_dtype`: at 4.8e9 parameters a float32 copy
  would not fit beside the engine;
* every key of the model pair's `counters()` goes into
  `raw["counters"]` as the difference over the window (the two readings
  the window itself takes), and `gauges()`, if the pair has it, into
  `raw["gauges"]` as read at the close;
* the arrays of `state_arrays()` (a recurrent state beside the page
  pools) are freed with the pools.

Traffic file keys: as `serve_closed_loop`.
"""

import contextlib
import functools
import importlib.util
import os

import jax.numpy as jnp

import harness

_spec = importlib.util.spec_from_file_location(
    "bench_serve_closed_loop",
    os.path.join(harness.HERE, "kinds", "serve_closed_loop.py"))
serve_closed_loop = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve_closed_loop)


@contextlib.contextmanager
def weights_in(dtype):
    """models.weights.make_weights makes float32 unless told; the
    inherited set-up and check do not tell it."""
    from models import weights

    plain = weights.make_weights
    weights.make_weights = functools.partial(plain, dtype=jnp.dtype(dtype))
    try:
        yield
    finally:
        weights.make_weights = plain


class Kind(serve_closed_loop.Kind):
    def setup(self):
        with weights_in(self.ctx.config["storage_dtype"]):
            super().setup()

    def window(self):
        readings, read = [], self.model.counters

        def counters(eng):
            readings.append(read(eng))
            if hasattr(self.model, "gauges"):
                self.gauges = self.model.gauges(eng)
            return readings[-1]

        self.model.counters = counters
        try:
            raw = super().window()
        finally:
            self.model.counters = read
        first, last = readings
        raw["counters"] = {k: last[k] - first[k] for k in first}
        raw["gauges"] = getattr(self, "gauges", {})
        return raw

    def release(self):
        state = (self.model.state_arrays(self.eng)
                 if hasattr(self.model, "state_arrays") else [])
        super().release()
        for arr in state:
            try:
                arr.delete()
            except Exception:  # noqa: BLE001 — already freed
                pass

    def check(self):
        with weights_in(self.ctx.config["storage_dtype"]):
            return super().check()
