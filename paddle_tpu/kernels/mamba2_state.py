"""Mamba-2 state step: read the carried state out and advance it by one
chunk, in ONE pass over it.

A lane's state of one layer is ``[H, P, N]`` float32 (4 MB at 128 heads
of 64 with 128 states) and a serving step touches it for every lane and
layer: it is the second largest thing a hybrid step moves after the
experts. Written as two einsums XLA reads it twice and writes it once,
and spends two more passes on layout copies between them (its
convolution emitter wants ``[R, P, H, N]``); here a block of heads is
read once, used for both products, and written back:

    y[t, (h, p)]  = sum_n S[h, p, n] * C[t, n]             (read-out)
    S'[h, p, n]   = decay[h] * S[h, p, n]
                    + sum_t xw[t, (h, p)] * B[t, n]          (advance)

``xw`` is the chunk's input already weighted by dt and by the decay from
its token to the chunk's end, ``decay`` the decay over the whole chunk
(zero for a row that starts a sequence: its stale state is dropped by
the multiplication, finite times nought). The products are float32 at
HIGHEST on the chip too: the state accumulates over a sequence.

``state_step`` routes like the other kernels (``_pallas_mode``): Mosaic
on a TPU, the interpreter under PADDLE_TPU_KERNEL_INTERPRET, the plain
einsums otherwise and for shapes the kernel's tiles do not fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attention import _pallas_mode

_HI = jax.lax.Precision.HIGHEST
LANES = 128
# bytes of state a grid step holds (in and out, double-buffered by the
# pipeline, plus the update of the same size): 512 KiB keeps the kernel
# well inside the default scoped VMEM
_BLOCK_BYTES = 512 * 1024


def _reference(ssm, c, b, xw, decay):
    """ssm [R, H, P, N]; c, b [R, G, T, N]; xw [R, T, H*P]; decay [R, H]
    -> (y [R, T, H*P], ssm')."""
    R, H, P, N = ssm.shape
    G, T = c.shape[1], c.shape[2]
    s = ssm.reshape(R, G, H // G, P, N)
    y = jnp.einsum("rgkpn,rgtn->rtgkp", s, c, precision=_HI)
    up = jnp.einsum("rtgkp,rgtn->rgkpn", xw.reshape(R, T, G, H // G, P), b,
                    precision=_HI)
    out = s * decay.reshape(R, G, H // G, 1, 1) + up
    return y.reshape(R, T, H * P), out.reshape(R, H, P, N)


def _head_block(heads_per_group: int, P: int, N: int):
    """Heads a grid step takes: a divisor of the group's heads whose
    state fits _BLOCK_BYTES and whose rows (heads x P) fill whole lane
    tiles of ``xw``; None if there is none."""
    best = None
    for hb in range(1, heads_per_group + 1):
        if heads_per_group % hb or hb * P * N * 4 > _BLOCK_BYTES:
            continue
        if (hb * P) % LANES == 0:
            best = hb
    return best


def _kernel(hb: int, P: int):
    def body(decay_ref, s_ref, c_ref, b_ref, xw_ref, y_ref, o_ref):
        from jax.experimental import pallas as pl

        r, h0 = pl.program_id(0), pl.program_id(1) * hb
        s = s_ref[0].reshape(hb * P, s_ref.shape[-1])      # [hb*P, N]
        c, b, xw = c_ref[0, 0], b_ref[0, 0], xw_ref[0]     # [T,N] [T,N] [T,hb*P]
        y_ref[0] = jax.lax.dot_general(
            c, s, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)            # [T, hb*P]
        up = jax.lax.dot_general(
            xw, b, (((0,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)            # [hb*P, N]
        for j in range(hb):
            o_ref[0, j] = (s_ref[0, j] * decay_ref[r, h0 + j]
                           + up[j * P:(j + 1) * P])
    return body


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_step_pallas(ssm, c, b, xw, decay, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, H, P, N = ssm.shape
    G, T = c.shape[1], c.shape[2]
    k = H // G
    hb = _head_block(k, P, N)
    per_group = k // hb
    grid = (R, H // hb)
    cb_spec = pl.BlockSpec((1, 1, T, N),
                           lambda r, h: (r, h // per_group, 0, 0))
    state_spec = pl.BlockSpec((1, hb, P, N), lambda r, h: (r, h, 0, 0))
    row_spec = pl.BlockSpec((1, T, hb * P), lambda r, h: (r, 0, h))
    return pl.pallas_call(
        _kernel(hb, P),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), state_spec,
                  cb_spec, cb_spec, row_spec],
        out_specs=[row_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((R, T, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((R, H, P, N), jnp.float32)],
        interpret=interpret,
        name="mamba2_state_step",
    )(decay, ssm, c, b, xw)


def state_step(ssm, c, b, xw, decay):
    """One chunk of every row's recurrence against its carried state.

    ssm:   [R, H, P, N] float32 (heads of group g: g*H/G .. (g+1)*H/G - 1)
    c, b:  [R, G, T, N] the chunk's C and B
    xw:    [R, T, H*P]  inputs weighted by dt and the decay to chunk end
    decay: [R, H]       decay over the whole chunk (0: drop the state)

    Returns (y [R, T, H*P]: ``S C_t`` for the state as it ARRIVED, which
    the caller scales by the decay up to t; ssm' [R, H, P, N]).
    """
    R, H, P, N = ssm.shape
    G, T = c.shape[1], c.shape[2]
    mode = _pallas_mode()
    fits = (ssm.dtype == jnp.float32 and T % 8 == 0 and P % 8 == 0
            and N % LANES == 0 and _head_block(H // G, P, N) is not None)
    if mode is None or not fits:
        return _reference(ssm, c, b, xw, decay)
    return _state_step_pallas(ssm, c, b, xw, decay,
                              interpret=(mode == "interpret"))
