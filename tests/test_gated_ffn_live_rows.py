"""The dense gated feed-forward of a serving step on the window's live
rows only (ops/decoder.py ``gated_silu_ffn`` with ``num_valid``): a
window whose valid tokens number at most R gathers them into R rows,
multiplies those and puts them back; a fuller one multiplies every row.
Either way each valid row's result is the one the whole-window product
gives it. ``live_ffn_rows`` says where the path is taken at all: where
the weights outweigh the rows moved ten times over, which the published
widths of the cells' configurations decide.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.decoder import gated_silu_ffn, live_ffn_rows

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
TOL = 1e-6


def _case(rows=4, chunk=8, d=32, f=48, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(rows, chunk, d), jnp.float32)
    w_in = jnp.asarray(0.3 * rng.randn(d, 2 * f), jnp.float32)
    w_out = jnp.asarray(0.3 * rng.randn(f, d), jnp.float32)
    return x, w_in, w_out


def _live(num_valid, chunk):
    return np.arange(chunk)[None, :] < np.asarray(num_valid)[:, None]


@pytest.mark.parametrize("num_valid,narrow", [
    ([1, 8, 1, 3], True),       # 13 live of 32, R 16: gathered
    ([1, 1, 1, 1], True),       # four decode rows
    ([0, 8, 0, 8], True),       # exactly R: still gathered
    ([8, 8, 1, 0], False),      # 17 live: every row multiplied
    ([8, 8, 8, 8], False),
])
@pytest.mark.parametrize("multipliers", [(None, None), (0.7, 1.3)])
def test_live_rows_equal_the_whole_window_product(num_valid, narrow,
                                                   multipliers):
    """Both branches of the ``cond``: the valid rows equal today's op to
    1e-6 in float32; a gathered window's padding rows read 0, a full
    one's are today's."""
    x, w_in, w_out = _case()
    whole = np.asarray(gated_silu_ffn(x, w_in, w_out, *multipliers))
    got = np.asarray(jax.jit(
        lambda x, nv: gated_silu_ffn(x, w_in, w_out, *multipliers,
                                     num_valid=nv, live_rows=16))(
        x, jnp.asarray(num_valid, jnp.int32)))
    live = _live(num_valid, 8)
    assert int(live.sum() <= 16) == narrow
    np.testing.assert_allclose(got[live], whole[live], atol=TOL)
    if narrow:
        assert not got[~live].any()
    else:
        np.testing.assert_allclose(got, whole, atol=TOL)


def test_without_live_rows_the_op_is_todays():
    """No ``num_valid``, or a rule that says 0: the whole window, as
    before."""
    x, w_in, w_out = _case(seed=1)
    whole = np.asarray(gated_silu_ffn(x, w_in, w_out))
    nv = jnp.asarray([1, 1, 1, 1], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gated_silu_ffn(x, w_in, w_out, num_valid=nv,
                                  live_rows=0)), whole)


def test_bfloat16_weights_round_the_gathered_rows_as_the_window():
    """Stored weights in bfloat16: a gathered row's operand is rounded
    as the whole window's is, so its result is the same."""
    x, w_in, w_out = _case(seed=2)
    w_in, w_out = w_in.astype(jnp.bfloat16), w_out.astype(jnp.bfloat16)
    nv = [1, 2, 1, 1]
    whole = np.asarray(gated_silu_ffn(x, w_in, w_out))
    got = np.asarray(gated_silu_ffn(x, w_in, w_out,
                                    num_valid=jnp.asarray(nv, jnp.int32),
                                    live_rows=16))
    live = _live(nv, 8)
    np.testing.assert_allclose(got[live], whole[live], atol=1e-5)


def _widths(name, width_key):
    c = json.load(open(os.path.join(CONFIGS, name + ".json")))
    e = c["engine"]
    return (e["lanes"] * e["chunk_tokens"], c["hidden_size"], c[width_key],
            np.dtype(jnp.dtype(c["storage_dtype"])).itemsize)


@pytest.mark.parametrize("name,width_key,want", [
    ("falcon_h1_34b_serve", "intermediate_size", 256),
    ("mimo_v2_5_serve", "intermediate_size", 256),
    ("granite4_h_small_serve", "shared_intermediate_size", 0),
])
def test_the_rule_picks_the_published_widths(name, width_key, want):
    """Falcon-H1's SwiGLU (21504) and MiMo's dense layer (16384) on a
    window of 32 x 16 rows take the path at R = 256; granite's shared
    expert (1536) does not."""
    assert live_ffn_rows(*_widths(name, width_key)) == want


def test_the_rule_weighs_weights_against_rows():
    """f >= 20 x rows in bfloat16, 10 x rows in float32; the hidden
    width is on both sides."""
    assert live_ffn_rows(512, 5120, 10240, 2) == 256
    assert live_ffn_rows(512, 5120, 10239, 2) == 0
    assert live_ffn_rows(512, 64, 5120, 4) == 256
    assert live_ffn_rows(1024, 5120, 10240, 2) == 0
    assert live_ffn_rows(12, 48, 64, 4) == 0    # the tiny presets
