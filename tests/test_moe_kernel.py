"""kernels/moe_ffn.py under the Pallas interpreter against ``topk_moe``'s
``ragged_dot`` path: the same pairs, the same operands (rounded to the
weights' dtype), float32 sums in another order. With float32 weights the
two agree to float32 rounding; with bfloat16 weights a sum that lands on
the other side of a rounding boundary of ``h`` moves one term of the
down product by 2^-9 of itself.

The routing of each case is planted through the router's weights: every
token carries a constant 1 in feature 0, so row 0 of the router is a
per-expert bias that decides who is chosen."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe_ffn
from paddle_tpu.ops.moe import _topk_moe, topk_moe

E = 8           # experts the router ranks


def _case(T, d, f, held, dtype, seed, bias=None):
    rng = np.random.RandomState(seed)
    x = rng.randn(T, d).astype(np.float32)
    x[:, 0] = 1.0
    router = 0.1 * rng.randn(d, E).astype(np.float32)
    if bias is not None:
        router[0] = bias
    w_in = jnp.asarray(0.2 * rng.randn(held, d, 2 * f), dtype)
    w_out = jnp.asarray(0.2 * rng.randn(held, f, d), dtype)
    return jnp.asarray(x), jnp.asarray(router), w_in, w_out


def _only(*experts):
    """A router bias that sends every token to these experts first."""
    b = np.full(E, -50.0, np.float32)
    b[list(experts)] = 50.0
    return b


def _clear_traces():
    _topk_moe.clear_cache()
    moe_ffn._grouped_ffn_pallas.clear_cache()


# name: (T, d, f, held, first_expert, top_k, router bias, valid rows,
#        extra arguments of topk_moe, tile bytes)
CASES = {
    # granite-like: many narrow experts, softmax gates, top 3 of 8
    "hybrid_like": (16, 256, 128, 4, 0, 3, None, None, {}, None),
    # MiMo-like: wide experts, sigmoid scores, a selection bias
    "mimo_like": (16, 128, 256, 3, 0, 2, None, None,
                  {"score_func": "sigmoid", "select_bias": True}, None),
    # several weight tiles an array, an odd number of them for w_in
    "tiles_3_and_2": (16, 384, 256, 4, 0, 3, None, None, {}, 256 * 1024),
    # held expert 1 is never chosen: not visited, not read
    "an_expert_without_rows": (16, 128, 128, 4, 0, 2,
                               [0, -50, 0, 0, 0, 0, 0, 0], None, {}, None),
    # 48 rows on each of two experts: more than one sub-tile of rows
    "more_rows_than_a_row_tile": (48, 128, 128, 4, 0, 2, _only(1, 2), None,
                                  {}, None),
    # every pair on one expert, more than a visit takes: two visits
    "all_pairs_on_one_expert": (144, 128, 128, 4, 0, 1, _only(2), None, {},
                                128 * 1024),
    # no token is valid: no live pair, nothing fetched, zeros out
    "no_live_pair": (16, 128, 128, 4, 0, 2, None, [], {}, None),
    # nothing routed to this share: the same
    "no_pair_on_held_experts": (16, 128, 128, 2, 0, 2, _only(5, 6), None,
                                {}, None),
    # a share that starts at expert 3
    "first_expert_3": (16, 128, 128, 4, 3, 3, None, None, {}, None),
    # padding rows of a ragged window take no expert
    "padding_rows": (32, 128, 128, 8, 0, 3, None,
                     [0, 1, 2, 5, 8, 9, 13, 16, 21, 30], {}, None),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_equals_the_ragged_dot_path(name, dtype, monkeypatch):
    T, d, f, held, first, k, bias, rows, extra, tile = CASES[name]
    x, router, w_in, w_out = _case(T, d, f, held, jnp.dtype(dtype),
                                   seed=len(name), bias=bias)
    valid = np.ones(T, bool)
    if rows is not None:
        valid[:] = False
        valid[rows] = True
    kw = dict(top_k=k, num_experts=E, first_expert=first,
              score_func=extra.get("score_func", "softmax"))
    if extra.get("select_bias"):
        kw["select_bias"] = jnp.asarray(
            np.random.RandomState(3).randn(E), jnp.float32)
    before = jnp.arange(held, dtype=jnp.int32)
    assert not moe_ffn.fits(T, d, f, w_in.dtype)        # a CPU: ragged_dot
    want, want_loads = topk_moe(x, jnp.asarray(valid), router, w_in, w_out,
                                before, **kw)
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    if tile:        # read when the call is traced: no trace may be reused
        monkeypatch.setattr(moe_ffn, "_TILE_BYTES", tile)
        _clear_traces()
    assert moe_ffn.fits(T, d, f, w_in.dtype)
    got, got_loads = topk_moe(x, jnp.asarray(valid), router, w_in, w_out,
                              before, **kw)
    if tile:
        _clear_traces()
    np.testing.assert_array_equal(np.asarray(got_loads),
                                  np.asarray(want_loads))
    want, got = np.asarray(want), np.asarray(got)
    assert got.dtype == np.float32 and got.shape == (T, d)
    scale = max(float(np.abs(want).max()), 1.0)
    tol = 2e-6 if dtype == "float32" else 4e-3
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0)
    assert np.all(got[~valid] == 0.0)
    live = int(np.asarray(got_loads - before).sum())
    if name in ("no_live_pair", "no_pair_on_held_experts"):
        assert live == 0 and np.all(got == 0.0)
    else:
        assert live > 0 and np.abs(got).max() > 0


@pytest.mark.parametrize("counts,pairs", [
    ([3, 0, 130, 1], 256), ([0, 0, 0, 0], 16), ([0, 512, 0], 512),
    ([1] * 36, 5120)])
def test_visit_list_covers_every_live_row_once_and_no_empty_expert(counts,
                                                                   pairs):
    e, first, rows, live = map(np.asarray, moe_ffn.visits(
        jnp.asarray(counts, jnp.int32), pairs))
    n = int(live[0])
    assert len(e) == len(counts) + -(-pairs // moe_ffn.ROWS) >= n
    assert np.all(rows[n:] == 0) and np.all(rows[:n] > 0)
    assert np.all(rows <= moe_ffn.ROWS)
    start = np.cumsum(counts) - counts
    seen = np.zeros(sum(counts), int)
    for i in range(n):
        assert counts[e[i]] > 0
        assert start[e[i]] <= first[i]
        assert first[i] + rows[i] <= start[e[i]] + counts[e[i]]
        seen[first[i]:first[i] + rows[i]] += 1
    assert np.all(seen == 1)
    assert np.all(np.diff(e[:n]) >= 0)          # expert by expert


@pytest.mark.parametrize("T,d,f,dtype,ok", [
    (512, 4096, 768, "bfloat16", True),       # the hybrid cell
    (512, 4096, 2048, "bfloat16", True),      # the MiMo cell
    (16, 128, 128, "float32", True),
    (12, 32, 16, "float32", False),           # no whole lane tile
    (24, 128, 128, "float32", False),         # x not whole packed tiles
    (16, 128, 128, "float16", False),         # not an MXU operand here
    (16384, 4096, 768, "bfloat16", False),    # x and the output past VMEM
])
def test_shapes_the_tiles_take(T, d, f, dtype, ok, monkeypatch):
    assert (moe_ffn._plan(T, d, f, jnp.dtype(dtype)) is not None) == ok
    assert not moe_ffn.fits(T, d, f, jnp.dtype(dtype))      # a CPU
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    assert moe_ffn.fits(T, d, f, jnp.dtype(dtype)) == ok
    if ok and d == 4096:
        tk, tr, vmem = moe_ffn._plan(T, d, f, jnp.dtype(dtype))
        # whole rows, 2-4 MB a tile, inside a v5e's 128 MiB
        assert 2 * 2 ** 20 <= tk * 2 * f * 2 <= 4 * 2 ** 20
        assert 2 * 2 ** 20 <= tr * d * 2 <= 4 * 2 ** 20
        assert d % tk == 0 and f % tr == 0 and vmem < 100 * 2 ** 20
