"""Mamba-1's selective scan (arXiv:2312.00752), per sequence, over a
float32 state ``S`` of ``d_state`` x ``d_inner`` (channels last: on the
lanes):

    S_t = exp(dt_t A) * S_{t-1} + B_t (dt_t x_t)^T      elementwise decay
    y_t = C_t^T S_t                                     a channel each

with ``A`` [N, I] negative, ``dt`` [I] the step size and ``u = dt * x``
[I] the write, ``B``, ``C`` [N] a token. The decay differs per
(state, channel) pair, so no matmul form applies (the delta rule's in
``ops/kda.py`` does not): the scan is sequential in time, elementwise.

Three forms of the same mathematics:

- ``step``: one token a sequence, XLA (decode's fallback, the oracle).
- ``scan``: a run of tokens; ``lax.scan`` of ``step`` (the CPU, the
  tests) or, on the TPU, ``scan_kernel``: a Pallas kernel holding a
  block of channels' state in VMEM, sequential over the run's tokens,
  writing every token's ``y`` and the final state. A naive XLA form
  would materialise [tokens, I, N] float32 terms (84 MB a 256-token
  piece of a 5,120-channel layer).
- ``step_in_place``: one token a slot on the stacked state of every
  slot, a Pallas kernel that reads and writes the live slots' state
  where it lies (``ops/kda.py``'s ``recurrent_step_in_place`` is the
  pattern).

A token with ``dt = 0`` and ``u = 0`` leaves the state as it was
(padding). Every product is float32 and elementwise.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

SCAN_KERNEL_NAME = 'mamba_scan'
STEP_KERNEL_NAME = 'mamba_recurrent_step'
_ROWS = 8               # tokens a scan-kernel loop iteration takes
_LANE_BLOCK = 512       # channels a grid step / an inner pass holds


def step(state: jax.Array, dt: jax.Array, u: jax.Array, B: jax.Array,
         C: jax.Array, A: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token a sequence. state [b,N,I] f32; dt, u [b,I]; B, C [b,N];
    A [N,I]; all float32. Returns (y [b,I], the new state)."""
    state = (jnp.exp(dt[:, None, :] * A) * state
             + u[:, None, :] * B[:, :, None])
    return jnp.sum(state * C[:, :, None], axis=1), state


def scan_xla(state, dt, u, B, C, A):
    """A run of tokens as a ``lax.scan`` of ``step``. dt, u [b,s,I]; B, C
    [b,s,N]. Returns (y [b,s,I], the state after the run)."""
    def one(S, t):
        y, S = step(S, *t, A)
        return S, y

    state, y = lax.scan(one, state, tuple(jnp.moveaxis(a, 1, 0)
                                          for a in (dt, u, B, C)))
    return jnp.moveaxis(y, 0, 1), state


def _lane_block(I: int) -> int:
    for blk in (_LANE_BLOCK, 256, 128):
        if I % blk == 0:
            return blk
    raise ValueError(f'{I} channels do not tile by 128 lanes')


def scan_kernel(state, dt, u, B, C, A, *, interpret: bool = False):
    """``scan_xla`` as a Pallas kernel; I a multiple of 128. Grid (rows,
    channel blocks): a grid step holds one row's state of ``_LANE_BLOCK``
    channels in registers while it runs through the run's tokens,
    ``_ROWS`` a loop iteration (their ``y`` rows stored as one tile),
    and writes its final state. B and C come as [rows, s/8, N, 8]: a
    token's column of either is a static lane slice of the iteration's
    block. Any s (padded here with tokens that change nothing)."""
    from jax.experimental import pallas as pl
    b, s, I = dt.shape
    N = B.shape[-1]
    pad = -s % _ROWS
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        dt, u, B, C = map(widen, (dt, u, B, C))
    sp = s + pad
    groups = sp // _ROWS
    bi = _lane_block(I)

    def columns(a):             # [b, sp, N] -> [b, groups, N, 8]
        return a.reshape(b, groups, _ROWS, N).transpose(0, 1, 3, 2)

    def kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s_ref):
        A_blk = a_ref[...]                                  # [N, bi]
        rows = lax.broadcasted_iota(jnp.int32, (_ROWS, bi), 0)

        def group(g, S):
            t0 = pl.multiple_of(g * _ROWS, _ROWS)
            dts = dt_ref[0, pl.ds(t0, _ROWS), :]            # [8, bi]
            us = u_ref[0, pl.ds(t0, _ROWS), :]
            Bg, Cg = b_ref[0, g], c_ref[0, g]               # [N, 8]
            ys = jnp.zeros((_ROWS, bi), jnp.float32)
            for r in range(_ROWS):
                S = (jnp.exp(dts[r:r + 1] * A_blk) * S
                     + us[r:r + 1] * Bg[:, r:r + 1])
                y = jnp.sum(S * Cg[:, r:r + 1], axis=0, keepdims=True)
                ys = jnp.where(rows == r, y, ys)
            y_ref[0, pl.ds(t0, _ROWS), :] = ys
            return S

        s_ref[0] = lax.fori_loop(0, groups, group, s0_ref[0])

    tok = pl.BlockSpec((1, sp, bi), lambda r, j: (r, 0, j))
    col = pl.BlockSpec((1, groups, N, _ROWS), lambda r, j: (r, 0, 0, 0))
    st = pl.BlockSpec((1, N, bi), lambda r, j: (r, 0, j))
    y, state = pl.pallas_call(
        kernel,
        grid=(b, I // bi),
        in_specs=[tok, tok, col, col,
                  pl.BlockSpec((N, bi), lambda r, j: (0, j)), st],
        out_specs=[tok, st],
        out_shape=[jax.ShapeDtypeStruct((b, sp, I), jnp.float32),
                   jax.ShapeDtypeStruct((b, N, I), jnp.float32)],
        interpret=interpret,
        name=SCAN_KERNEL_NAME,
    )(dt, u, columns(B), columns(C), A, state)
    return y[:, :s], state


def scan(state, dt, u, B, C, A):
    """A run of tokens: ``scan_kernel`` on the TPU where the channels
    tile, else ``scan_xla``."""
    if jax.default_backend() == 'tpu' and dt.shape[-1] % 128 == 0:
        return scan_kernel(state, dt, u, B, C, A)
    return scan_xla(state, dt, u, B, C, A)


def step_in_place(states: jax.Array, layer, dt: jax.Array, u: jax.Array,
                  B: jax.Array, C: jax.Array, A: jax.Array,
                  active: jax.Array, *, interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """``step`` on layer ``layer`` of the stacked state of every slot,
    [layers, slots, 1, N, I] float32, for the slots that are ``active``
    ([slots] bool): each live slot's state is read once and written
    once, in place (the stack is aliased to the first output); a slot
    that is not active is neither read nor written. dt, u [slots, I]; B,
    C [slots, N]; A [N, I]. Returns (the stack, y [slots, I], zeros for
    the slots that are not active).

    Grid (slots,): a dead slot's blocks are mapped to those of the live
    slot before it (the first live one where there is none), so the
    pipeline sees an unchanged block index, moves nothing, and the body
    is skipped. The layer rides the block index of the whole stack. The
    body runs over ``_LANE_BLOCK`` channels at a time."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n_layers, slots, _, N, I = states.shape
    bi = _lane_block(I)
    idx = jnp.where(active, jnp.arange(slots), -1)
    before = lax.cummax(idx, axis=0)
    remap = jnp.where(before >= 0, before, jnp.argmax(active)).astype(
        jnp.int32)
    rows = jnp.stack([dt, u], axis=1)                       # [slots, 2, I]
    cols = jnp.stack([B, C], axis=-1)                       # [slots, N, 2]

    def kernel(layer_ref, remap_ref, live_ref, s_ref, rows_ref, cols_ref,
               a_ref, s_out_ref, y_ref):
        del layer_ref, remap_ref

        @pl.when(live_ref[pl.program_id(0)] != 0)
        def _():
            b_col, c_col = cols_ref[0, :, 0:1], cols_ref[0, :, 1:2]
            for c0 in range(0, I, bi):
                ch = slice(c0, c0 + bi)
                S = (jnp.exp(rows_ref[0, 0:1, ch] * a_ref[:, ch])
                     * s_ref[0, 0, 0, :, ch]
                     + rows_ref[0, 1:2, ch] * b_col)
                s_out_ref[0, 0, 0, :, ch] = S
                y_ref[0, :, ch] = jnp.sum(S * c_col, axis=0, keepdims=True)

    def state_block(b, layer, remap, live):
        del live
        return (layer[0], remap[b], 0, 0, 0)

    def slot_block(b, layer, remap, live):
        del layer, live
        return (remap[b], 0, 0)

    new_states, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,               # layer, remap, live
            grid=(slots,),
            in_specs=[
                pl.BlockSpec((1, 1, 1, N, I), state_block),
                pl.BlockSpec((1, 2, I), slot_block),
                pl.BlockSpec((1, N, 2), slot_block),
                pl.BlockSpec((N, I), lambda b, layer, remap, live: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, N, I), state_block),
                pl.BlockSpec((1, 1, I), slot_block),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, states.dtype),
            jax.ShapeDtypeStruct((slots, 1, I), jnp.float32),
        ],
        input_output_aliases={3: 0},    # the state stack, in place
        interpret=interpret,
        name=STEP_KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), remap,
      active.astype(jnp.int32), states, rows, cols, A)
    return new_states, jnp.where(active[:, None], y[:, 0], 0.0)
