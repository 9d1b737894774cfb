"""Weights from the seed, made on the device in one jitted call.

The same function serves both sides of the comparison that decides
`correct`: the kind puts its output into the program's scope, and the
plain reference is handed a second, independently made copy after the
program's state is freed. Nothing here imports the program.

A *spec* is an ordered list of (name, shape, init) rows, init one of
"normal" (mean 0, the config's initializer_range), "ones", "zeros".
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed):
    """--seed may be a little over 2**31: carry it as two uint32 words,
    so one compiled program serves every seed."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _leaf_id(name):
    # a leaf's stream depends on its name only, so adding a leaf to a
    # spec moves no other leaf's weights
    return np.uint32(zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make_weights_fn(spec, std, dtype=jnp.float32):
    """Returns jitted f(seed_words) -> {name: array}."""
    rows = [(name, tuple(shape), init, _leaf_id(name))
            for name, shape, init in spec]

    def make(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        out = {}
        for name, shape, init, leaf in rows:
            if init == "normal":
                k = jax.random.fold_in(key, leaf)
                out[name] = (std * jax.random.normal(k, shape, jnp.float32)
                             ).astype(dtype)
            elif init == "ones":
                out[name] = jnp.ones(shape, dtype)
            elif init == "zeros":
                out[name] = jnp.zeros(shape, dtype)
            else:
                raise ValueError(f"unknown init {init!r} for {name}")
        return out

    return jax.jit(make)


def make_weights(spec, std, seed, device=None, dtype=jnp.float32):
    words = jnp.asarray(seed_words(seed))
    if device is not None:
        words = jax.device_put(words, device)
    return make_weights_fn(spec, std, dtype)(words)
