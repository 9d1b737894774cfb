"""The absorbed latent attention kernel (kernels/latent_attention.py)
under the Pallas interpreter against its own jnp reference and against
the definition, over a pool written by ``latent_cache_write`` chunk by
chunk: decode lanes (one row), prefill lanes (sixteen rows), idle lanes
and lanes whose rows are padding, contexts that cross a page boundary
and that end on one, float32 and bfloat16 pages. And the absorbed form
against the published, expanded one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation.kvcache import key_page_shape
from paddle_tpu.kernels.latent_attention import (
    _latent_pallas, _reference_latent, absorbed_latent_attention,
    expanded_latent_attention, latent_cache_write)

H, L, R, C, P = 8, 16, 8, 16, 40
SCALE = 1.0 / np.sqrt(24.0)


def _definition(q_lat, q_pe, ckv, kpe):
    """One sequence, its last len(q) positions: q [n, H, L] / [n, H, R]
    against every earlier entry, float64."""
    T, n = ckv.shape[0], q_lat.shape[0]
    s = (np.einsum("nhl,tl->nht", q_lat, ckv)
         + np.einsum("nhr,tr->nht", q_pe, kpe)) * SCALE
    pos = np.arange(T - n, T)[:, None, None]
    s = np.where(np.arange(T)[None, None, :] <= pos, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("nht,tl->nhl", p / p.sum(-1, keepdims=True), ckv)


def _case(lens, nv, dtype, seed=0):
    """Lanes with ``lens`` tokens cached before the step and ``nv`` new
    rows, the pool written through ``latent_cache_write`` a chunk at a
    time (the step's own rows included, as the program does)."""
    rng = np.random.RandomState(seed)
    B = len(lens)
    T = [a + b for a, b in zip(lens, nv)]
    ent = [rng.randn(t, L + R).astype("f") for t in T]
    pages = jnp.zeros((1, P) + key_page_shape(L, L + R, L), dtype)
    maxp = max(-(-t // L) for t in T) + 1
    tables = np.zeros((B, maxp), np.int32)
    fresh = 1
    for b, t in enumerate(T):
        for j in range(-(-t // L)):
            tables[b, j], fresh = fresh, fresh + 1
    pos = [0] * B
    while any(pos[b] < T[b] for b in range(B)):
        new = np.zeros((B, C, L + R), "f")
        st, n = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for b in range(B):
            c = min(C, T[b] - pos[b])
            if c > 0:
                new[b, :c] = ent[b][pos[b]:pos[b] + c]
                st[b], n[b] = pos[b], c
                pos[b] += c
        pages = latent_cache_write(pages, jnp.asarray(new),
                                   jnp.asarray(tables), jnp.asarray(st),
                                   jnp.asarray(n))
    q_lat = rng.randn(B, C, H, L).astype("f")
    q_pe = rng.randn(B, C, H, R).astype("f")
    want = np.zeros((B, C, H, L))
    for b in range(B):
        if nv[b]:
            e = np.asarray(jnp.asarray(ent[b]).astype(dtype), np.float64)
            ql = np.asarray(jnp.asarray(q_lat[b, :nv[b]]).astype(dtype),
                            np.float64)
            qp = np.asarray(jnp.asarray(q_pe[b, :nv[b]]).astype(dtype),
                            np.float64)
            want[b, :nv[b]] = _definition(ql, qp, e[:, :L], e[:, L:])
    args = (jnp.asarray(q_lat, dtype), jnp.asarray(q_pe, dtype), pages,
            jnp.asarray(lens, jnp.int32), jnp.asarray(nv, jnp.int32),
            jnp.asarray(tables))
    return args, want, ent, tables


# lanes: decode crossing into a fresh page (pos 16), decode ending on a
# page's last key (pos 31), a prefill chunk of 16 over 2 pages + the
# chunk's own (ends on a page boundary at 48), a prefill chunk from 0,
# an idle lane, a decode deep in the context, a short prefill chunk
LENS = [16, 31, 32, 0, 0, 70, 21]
NV = [1, 1, 16, 16, 0, 1, 5]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_equals_reference_and_definition(dtype):
    args, want, _ent, _tables = _case(LENS, NV, dtype)
    ref = np.asarray(_reference_latent(*args, SCALE), np.float64)
    got = np.asarray(_latent_pallas(*args, SCALE, interpret=True),
                     np.float64)
    # float32 pages: float32 round-off. bfloat16 pages: both give their
    # result in bfloat16 (half a unit: 2^-9 relative), and the kernel
    # rounds the probabilities to bfloat16 for its second product
    if dtype == jnp.float32:
        out, kernel = dict(atol=2e-5), dict(atol=2e-5)
    else:
        out, kernel = dict(rtol=2 ** -8, atol=1e-6), dict(atol=2e-2)
    np.testing.assert_allclose(ref, want, **out)
    np.testing.assert_allclose(got, want, **kernel)
    np.testing.assert_allclose(got, ref, **kernel)
    valid = np.arange(C)[None, :] < np.asarray(NV)[:, None]
    assert not got[~valid].any() and not ref[~valid].any()


def test_cache_write_lays_out_latent_then_rope_transposed():
    """A page holds its tokens' latents as rows over their rope keys
    transposed; what the write was not given stays zero."""
    (_ql, _qp, pages, *_), _want, ent, tables = _case(LENS, NV,
                                                      jnp.float32)
    pages = np.asarray(pages)
    for b, e in enumerate(ent):
        for t in range(e.shape[0]):
            page = pages[0, tables[b, t // L]]
            np.testing.assert_array_equal(page[t % L], e[t, :L])
            np.testing.assert_array_equal(page[L:, t % L], e[t, L:])
    assert not pages[0, 0].any()                    # the junk page


def test_absorbed_equals_expanded():
    """q_nope W_uk^T against the latents, then W_uv, equals expanding
    every key and value a head (the published form), over the pool a
    chunk of 16 tokens then a decode token wrote."""
    rng = np.random.RandomState(3)
    N, V, T = 12, 10, 17
    q = jnp.asarray(rng.randn(1, T, H * (N + R)), jnp.float32)
    entry = jnp.asarray(rng.randn(1, T, L + R), jnp.float32)
    kv_b = jnp.asarray(0.3 * rng.randn(L, H * (N + V)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(expanded_latent_attention(q, entry, kv_b, H, V))
        pages = jnp.zeros((1, 4) + key_page_shape(L, L + R, L), jnp.float32)
        tables = jnp.asarray([[1, 2, 0]], jnp.int32)
        got = []
        for at, n in ((0, 16), (16, 1)):
            chunk = slice(at, at + n)
            pages = latent_cache_write(
                pages, entry[:, chunk], tables, jnp.asarray([at]),
                jnp.asarray([n]))
            got.append(np.asarray(absorbed_latent_attention(
                q[:, chunk], pages, kv_b, jnp.asarray([at]),
                jnp.asarray([n]), tables, H, V)))
    np.testing.assert_allclose(np.concatenate(got, 1), want, atol=1e-4)
