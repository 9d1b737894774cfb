"""Grouped gated feed-forward of the held experts, one kernel.

``topk_moe`` (ops/moe.py) sorts its live (token, expert) pairs by expert;
for every held expert that has rows this kernel computes

    o = (silu(x W_in[e][:, :f]) * (x W_in[e][:, f:])) W_out[e]

and adds ``gate * o`` to the token's row of the output. At serving sizes
an expert sees a handful of rows, so the layer is a stream of weights:
what matters is how they are fetched. Here each visited expert's weights
cross HBM once, where they are stored (``[held, d, 2f]`` and ``[held, f,
d]``), in tiles of whole rows (``w_in[e, k0:k0 + tk, :]``, ``w_out[e,
r0:r0 + tr, :]``: contiguous, up to ``_TILE_BYTES``), copied by the kernel
itself into two buffers an array, the next tile (across the boundary the
next expert's first) in flight while the current one is multiplied.
XLA's ``ragged_dot`` kernel moves 256-512 KB a grid step out of rows cut
in 1 KB pieces and reaches 53-75% of the HBM's rate on a v5e.

The grid is one step a visit; a visit is an expert and up to ``ROWS`` of
its sorted rows, listed from the counts (scalar prefetch: expert, first
sorted row, rows). ``held + ceil(pairs / ROWS)`` visits cover any
routing; the dead ones at the end do nothing and fetch nothing. Inside a
visit the products run on sub-tiles of ``SUB`` rows, as many as the
visit holds. The tokens' rows ``x`` and the output ``[T, d]`` stay in
VMEM for the whole call (``x`` widened to float32 on arrival): rows are
gathered and put back there one at a time (exact), so ``a``, ``h`` and
``o`` never reach HBM and no ``[pairs, d]`` array exists.

Arithmetic as the ``ragged_dot`` path: operands in the weights' dtype,
float32 accumulation, ``h`` rounded to the weights' dtype, the gate
applied in float32 at the put-back.

``grouped_ffn`` is taken when ``_pallas_mode()`` says so and ``fits``;
otherwise ``topk_moe`` keeps its ``ragged_dot`` path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attention import _pallas_mode

LANES = 128
ROWS = 128            # sorted rows a visit takes at most
SUB = 32              # rows a product runs on
PACK = 16             # rows of a packed bfloat16 tile: x is widened by them
# a weight tile: whole rows of the stored array, this many bytes at most
# (4.9 us of HBM time a copy; 2 MB measured the same, megablox at 512 KB
# a fifth slower); two arrays x two buffers
_TILE_BYTES = 4 * 1024 * 1024
# what the call may hold in VMEM beside its weight tiles (x as it arrives
# and in float32, the float32 output, a visit's rows): a v5e has 128 MiB;
# 512 rows at d = 7680 hold 47.5 MiB (and the call ~80 MiB with its tiles)
_RESIDENT_BYTES = 56 * 1024 * 1024


def _tile_rows(rows: int, row_bytes: int):
    """Most rows of a weight tile: a divisor of ``rows``, whole lane
    tiles (the rows' and ``h``'s columns are cut by it), within
    _TILE_BYTES; None if there is none."""
    best = None
    for t in range(LANES, rows + 1, LANES):
        if rows % t == 0 and t * row_bytes <= _TILE_BYTES:
            best = t
    return best


def _plan(T: int, d: int, f: int, dtype):
    """(tk, tr, vmem bytes) for these shapes, or None where the tiles do
    not fit: d and f whole lane tiles, T whole packed tiles, a dtype the
    MXU takes, x and the output small enough to stay in VMEM."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    if d % LANES or f % LANES or T % PACK:
        return None
    size = dtype.itemsize
    tk = _tile_rows(d, 2 * f * size)
    tr = _tile_rows(f, d * size)
    resident = (T * d * (8 + size)             # x twice, out
                + ROWS * (2 * d * 4 + 2 * f * 4 + f * size))
    if tk is None or tr is None or resident > _RESIDENT_BYTES:
        return None
    tiles = 2 * (tk * 2 * f + tr * d) * size
    return tk, tr, resident + tiles + 16 * 1024 * 1024


def fits(T: int, d: int, f: int, dtype) -> bool:
    """Whether ``grouped_ffn`` runs these shapes here (the one routing
    decision: ``topk_moe`` and the engine's ``moe_kernel_layers`` gauge
    both ask it)."""
    return _pallas_mode() is not None and _plan(T, d, f, dtype) is not None


def visits(counts, pairs: int):
    """The visit list of a step from the held experts' row counts
    ``[held]``: (expert, first sorted row, rows) of each visit, ``[n]``
    int32 each with ``n = held + ceil(pairs / ROWS)``, and the number of
    live visits. An expert takes ``ceil(count / ROWS)`` visits, none if
    it has no rows; the dead visits past the live ones hold 0 rows."""
    held = counts.shape[0]
    n = held + -(-pairs // ROWS)
    per = -(-counts // ROWS)                               # [held]
    ends = jnp.cumsum(per)
    live = ends[-1]
    i = jnp.arange(n, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                    held - 1).astype(jnp.int32)
    j = i - (ends - per)[e]
    first = (jnp.cumsum(counts) - counts)[e] + j * ROWS
    rows = jnp.where(i < live, jnp.minimum(counts[e] - j * ROWS, ROWS), 0)
    return (e, first.astype(jnp.int32), rows.astype(jnp.int32),
            live.astype(jnp.int32).reshape(1))


def _kernel(n_visits: int, f: int, tk: int, tr: int, n_in: int, n_out: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def body(v_e, v_first, v_rows, v_live, tok, gate,       # SMEM
             x_hbm, w_in_hbm, w_out_hbm, out_hbm,
             x_in, x_v, out_v, xb, a_s, h_s, o_s, in_buf, out_buf,
             in_sem, out_sem, io_sem):
        v = pl.program_id(0)
        live = v_live[0]

        def in_copy(visit, j):
            return pltpu.make_async_copy(
                w_in_hbm.at[v_e[visit], pl.ds(j * tk, tk), :],
                in_buf.at[j % 2], in_sem.at[j % 2])

        def out_copy(visit, j):
            return pltpu.make_async_copy(
                w_out_hbm.at[v_e[visit], pl.ds(j * tr, tr), :],
                out_buf.at[j % 2], out_sem.at[j % 2])

        @pl.when(v == 0)
        def _():
            @pl.when(live > 0)
            def _():
                in_copy(0, 0).start()
            load = pltpu.make_async_copy(x_hbm, x_in, io_sem.at[0])
            load.start()
            out_v[...] = jnp.zeros_like(out_v)
            xb[...] = jnp.zeros_like(xb)        # rows past a visit's: finite
            load.wait()

            # rows are gathered one at a time, which float32 tiles allow
            def widen(i, c):
                at = pl.ds(pl.multiple_of(i * PACK, PACK), PACK)
                x_v[at, :] = x_in[at, :].astype(jnp.float32)
                return c
            jax.lax.fori_loop(0, x_v.shape[0] // PACK, widen, 0)

        @pl.when(v < live)
        def _():
            first, rows = v_first[v], v_rows[v]
            subs = (rows + SUB - 1) // SUB

            def each_sub(fn):
                def step(s, c):
                    fn(pl.ds(pl.multiple_of(s * SUB, SUB), SUB))
                    return c
                jax.lax.fori_loop(0, subs, step, 0)

            # the visit's rows, cut by weight tile: xb[j] meets w_in's
            # tile j (a tile loop may index a leading dimension only)
            def gather(r, c):
                at = pl.ds(tok[first + r], 1)
                for t in range(n_in):
                    xb[t, pl.ds(r, 1), :] = x_v[at, t * tk:(t + 1) * tk]
                return c
            jax.lax.fori_loop(0, rows, gather, 0)

            def clear(sub):
                a_s[sub, :] = jnp.zeros((SUB, a_s.shape[1]), jnp.float32)
                o_s[sub, :] = jnp.zeros((SUB, o_s.shape[1]), jnp.float32)
            each_sub(clear)

            # the tiles in the order they are needed, w_in's then w_out's:
            # before a tile is waited for, the one after it is started
            # (the last one starts the next visit's first)
            def gate_up(t, c):
                @pl.when(t + 1 < n_in)
                def _():
                    in_copy(v, t + 1).start()

                @pl.when(t + 1 == n_in)
                def _():
                    out_copy(v, 0).start()
                in_copy(v, t).wait()

                def product(sub):
                    a_s[sub, :] += jnp.dot(
                        xb[t, sub, :].astype(in_buf.dtype), in_buf[t % 2],
                        preferred_element_type=jnp.float32)
                each_sub(product)
                return c
            jax.lax.fori_loop(0, n_in, gate_up, 0)

            def act(sub):
                a = a_s[sub, :]
                h = (jax.nn.silu(a[:, :f]) * a[:, f:]).astype(h_s.dtype)
                for t in range(n_out):
                    h_s[t, sub, :] = h[:, t * tr:(t + 1) * tr]
            each_sub(act)

            def down(t, c):
                @pl.when(t + 1 < n_out)
                def _():
                    out_copy(v, t + 1).start()

                @pl.when((t + 1 == n_out) & (v + 1 < live))
                def _():
                    in_copy(v + 1, 0).start()
                out_copy(v, t).wait()

                def product(sub):
                    o_s[sub, :] += jnp.dot(
                        h_s[t, sub, :], out_buf[t % 2],
                        preferred_element_type=jnp.float32)
                each_sub(product)
                return c
            jax.lax.fori_loop(0, n_out, down, 0)

            def put(r, c):
                at = pl.ds(tok[first + r], 1)
                out_v[at, :] = (out_v[at, :]
                                + gate[first + r] * o_s[pl.ds(r, 1), :])
                return c
            jax.lax.fori_loop(0, rows, put, 0)

        @pl.when(v == n_visits - 1)
        def _():
            store = pltpu.make_async_copy(out_v, out_hbm, io_sem.at[1])
            store.start()
            store.wait()

    return body


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_ffn_pallas(x, w_in, w_out, tok_sorted, gate_sorted, counts,
                        interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, d = x.shape
    f = w_out.shape[1]
    tk, tr, vmem = _plan(T, d, f, w_in.dtype)
    v_e, v_first, v_rows, v_live = visits(counts, tok_sorted.shape[0])
    n = v_e.shape[0]
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    dt = w_in.dtype
    return pl.pallas_call(
        _kernel(n, f, tk, tr, d // tk, f // tr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(n,),
            in_specs=[any_space, any_space, any_space],
            out_specs=any_space,
            scratch_shapes=[
                pltpu.VMEM((T, d), dt),                    # x as it arrives
                pltpu.VMEM((T, d), jnp.float32),           # x
                pltpu.VMEM((T, d), jnp.float32),           # out
                pltpu.VMEM((d // tk, ROWS, tk), jnp.float32),  # a visit's rows
                pltpu.VMEM((ROWS, 2 * f), jnp.float32),    # a
                pltpu.VMEM((f // tr, ROWS, tr), dt),       # h
                pltpu.VMEM((ROWS, d), jnp.float32),        # o
                pltpu.VMEM((2, tk, 2 * f), dt),
                pltpu.VMEM((2, tr, d), dt),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="moe_grouped_ffn",
    )(v_e, v_first, v_rows, v_live, tok_sorted, gate_sorted,
      x.astype(dt), w_in, w_out)


def grouped_ffn(x, w_in, w_out, tok_sorted, gate_sorted, counts):
    """The held experts' part of ``sum_e gate_e o_e`` for pairs sorted
    by expert.

    x:           [T, d] the tokens' rows
    w_in, w_out: [held, d, 2f], [held, f, d] as stored
    tok_sorted:  [pairs] int32, the token of each sorted pair; the live
                 pairs come first, expert by expert
    gate_sorted: [pairs] float32
    counts:      [held] int32 live pairs of each held expert

    Returns [T, d] float32. The caller has asked ``fits``."""
    return _grouped_ffn_pallas(
        x, w_in, w_out, tok_sorted.astype(jnp.int32),
        gate_sorted.astype(jnp.float32), counts.astype(jnp.int32),
        interpret=(_pallas_mode() == "interpret"))
