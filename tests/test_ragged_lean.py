"""The ragged kernel's lean decode branch for any query group, and its
float32 products over bfloat16 pages.

A lane whose valid rows fit the kernel's lean rows multiplies only
those. The rows are chunk-major (row j * group + g), so with a group that
does not divide the lean rows (Falcon-H1's 20 query heads on 4 KV heads:
5) a lean tile ends inside a query. The Pallas kernel runs under the
interpreter, with the branch on and off, against the reference, over a
decode lane, a lane of three valid tokens, a prefill lane of a whole
chunk and an idle lane: float32 pages (float32 products), bfloat16 pages
(float32 products in three bfloat16 parts, one pass), and products
rounded to bfloat16 (``stored_products``).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.ragged_paged_attention import (
    _ragged_pallas, _reference_ragged, lean_decode_rows)

KVH, D, PS, C, MAXP = 2, 32, 16, 16, 6


def _case(group, pages_dtype, seed=0):
    rng = np.random.RandomState(seed)
    H, B = KVH * group, 4
    pages = B * MAXP + 1
    k = jnp.asarray(rng.randn(KVH, pages, PS, D), pages_dtype)
    v = jnp.asarray(rng.randn(KVH, pages, PS, D), pages_dtype)
    q = rng.randn(B, C, H, D).astype("f")
    # lane 0 decodes at 37, lane 1 holds 3 tokens at 20, lane 2 a whole
    # chunk at 32, lane 3 is idle; every lane owns pages of its own
    start = np.array([37, 20, 32, 0], np.int32)
    nvalid = np.array([1, 3, C, 0], np.int32)
    tables = (1 + np.arange(B * MAXP, dtype=np.int32)).reshape(B, MAXP)
    return (jnp.asarray(q), k, v) + tuple(
        jnp.asarray(a) for a in (start, nvalid, tables))


@pytest.mark.parametrize("mode", ["f32", "bf16_pages", "stored"])
@pytest.mark.parametrize("group", [4, 5, 8, 16])
def test_lean_branch_any_group(group, mode):
    """Float32 products: the decode lane takes the branch. Products
    rounded to bfloat16 (16 lean rows): with groups 4 and 5 the lane of
    three tokens does too, its last token's heads split across the tile.
    Over bfloat16 pages the float32 products keep the float32 bound."""
    stored = mode == "stored"
    args = _case(group, jnp.bfloat16 if mode == "bf16_pages"
                 else jnp.float32)
    scale = 1.0 / np.sqrt(D)
    want = np.asarray(_reference_ragged(*args, scale, None, None))
    rows = lean_decode_rows(group, C, stored)
    assert group <= rows < C * group          # the prefill lane never
    assert (3 * group <= rows) == (stored and group <= 5)
    for lean in (False, True):
        got = np.asarray(_ragged_pallas(
            *args, scale, None, None, interpret=True, lean_decode=lean,
            stored_products=stored))
        # stored_products rounds q, k, v and the probabilities to bfloat16
        np.testing.assert_allclose(got, want, atol=3e-2 if stored else 2e-6)
    assert not want[3].any() and not got[3].any()          # the idle lane
    assert not got[0, 1:].any() and not got[1, 3:].any()   # invalid rows


@pytest.mark.parametrize("group,stored,rows", [
    (1, False, 8), (4, False, 8), (5, False, 8), (8, False, 8),
    (16, False, 16), (5, True, 16), (8, True, 16), (16, True, 16),
    (20, True, 32)])
def test_lean_rows_whole_operand_tiles(group, stored, rows):
    """Rows cut in float32 tile by 8, rounded to bfloat16 by 16."""
    assert lean_decode_rows(group, C, stored) == rows
    assert lean_decode_rows(group, 1, stored) == min(rows, 8 * group)
