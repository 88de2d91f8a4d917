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
- ``decode_mix``: the decode program's one token a slot, a Pallas
  kernel that takes the whole mixer between its two projections (conv,
  ``x_proj``, norms, ``dt_proj``, softplus, the step, gate) and reads
  and writes the live slots' state and conv tail where they lie.

A token with ``dt = 0`` and ``u = 0`` leaves the state as it was
(padding). Every product is float32 and elementwise.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

SCAN_KERNEL_NAME = 'mamba_scan'
DECODE_KERNEL_NAME = 'mamba_decode_mix'
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




# The stacked leaves ``decode_mix`` reads at its layer, in operand order
# ('conv_bias' where the layer has one).
DECODE_LEAVES = ('conv', 'conv_bias', 'x_proj', 'dt_norm', 'b_norm',
                 'c_norm', 'dt_proj', 'dt_bias', 'A_log', 'D')


def decode_mix(xz: jax.Array, leaves, states: jax.Array, tails: jax.Array,
               layer, active: jax.Array, *, eps: float,
               interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A Mamba mixer's one token a slot between its two projections, as
    one kernel: the causal conv over the tail and SiLU, ``x_proj``, the
    three RMSNorms, ``dt_proj`` and softplus, ``step`` and the gate
    ``(y + D x~) SiLU(z)``; the float32 math and the casts of
    ``models/mamba.py``'s ``mixer``.

    xz [slots, 2I] the in_proj output; ``leaves`` every Mamba layer's
    leaves stacked [layers, ...] (those of ``DECODE_LEAVES`` are read,
    at ``layer``, where they lie); states [layers, slots, 1, N, I] float32 and tails
    [layers, slots, taps-1, I], both aliased in place; ``active``
    [slots] bool. Returns (the state stack, the tail stack, the gated y
    [slots, I] in xz's dtype, zeros for the slots that are not active).

    Each live slot's state is read once and written once; a dead slot's
    is neither read nor written. The tails lie tap-major on the TPU (a
    [slots, I] plane a tap and layer: XLA's layout for [.., 3, I]), so
    they move in tiles of ``_tail_rows`` slots: the tiles that hold a
    live slot are read and written back, a dead slot's rows in them as
    they came. ``x_proj`` and the B / C norms are read in their layouts
    too ([R+2N, I] and [N, layers]): other views would be copies of the
    stacks.

    One grid step: the live tiles of the tails and the first live state
    are on their way while A is formed; the conv, ``x_proj`` and
    ``dt_proj`` run over every slot's row at once (a weight goes through
    the MXU once, not once a slot); then the live slots' states,
    double-buffered, each written back as the next one computes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    slots, I = xz.shape[0], xz.shape[1] // 2
    N, T = states.shape[3], tails.shape[2]
    R = leaves['dt_proj'].shape[1]
    names = [k for k in DECODE_LEAVES if k in leaves]
    views = dict(leaves, x_proj=jnp.swapaxes(leaves['x_proj'], 1, 2),
                 b_norm=leaves['b_norm'].T, c_norm=leaves['c_norm'].T)
    planes = jnp.swapaxes(tails, 1, 2)          # [layers, T, slots, I]
    rows = _tail_rows(tails.dtype)
    rows = rows if slots % rows == 0 else slots     # else one whole tile
    bi = _lane_block(I)
    chans = [slice(c0, c0 + bi) for c0 in range(0, I, bi)]

    def kernel(layer_ref, live_ref, x_ref, z_ref, *refs):
        w = dict(zip(names, refs))
        (s_hbm, t_hbm, y_ref, s_out, t_out, xc_v, dt_v, y_v, dbc_v, a_v,
         mask_v, tail_v, s_buf, ids_ref, t_sem, s_sem) = refs[len(names):]
        li = layer_ref[0]

        def collect(slot, n):           # the live slots, ascending
            ids_ref[n] = slot
            return n + live_ref[slot]

        n = lax.fori_loop(0, slots, collect, jnp.int32(0))

        def vec(name, ch=slice(None)):      # the layer's row [1, width]
            return w[name][pl.ds(li % 8, 1), ch]

        def over_live(body, init=0):
            return lax.fori_loop(0, n, body, init)

        def tile_dmas(start, out=False):    # a tile's rows of each tap
            hbm = t_out if out else t_hbm
            window = pl.ds(pl.multiple_of(start, rows), rows)
            pairs = [(tail_v.at[j, window], hbm.at[li, j, window])
                     for j in range(T)]
            return [pltpu.make_async_copy(*((a, b) if out else (b, a)),
                                          t_sem.at[int(out)])
                    for a, b in pairs]

        def over_live_tiles(body):
            """body(start) once for each tile holding a live slot (the
            ids ascend); returns how many."""
            def one(k, carry):
                seen, last = carry
                start = ids_ref[k] // rows * rows

                @pl.when(start != last)
                def _():
                    body(start)
                return seen + (start != last).astype(jnp.int32), start
            return over_live(one, (jnp.int32(0), jnp.int32(-1)))[0]

        def state_dma(k, buf, out=False):
            hbm = s_out if out else s_hbm
            src, dst = s_buf.at[buf], hbm.at[li, ids_ref[k]]
            return pltpu.make_async_copy(*((src, dst) if out else (dst, src)),
                                         s_sem.at[int(out), buf])

        def start_all(dmas):
            for dma in dmas:
                dma.start()

        tiles = over_live_tiles(lambda start: start_all(tile_dmas(start)))

        @pl.when(n > 0)
        def _first_state():
            state_dma(0, 0).start()

        mask_v[...] = jnp.zeros(mask_v.shape, f32)

        def mark(k, carry):
            mask_v[pl.ds(ids_ref[k], 1), :] = jnp.ones((1, mask_v.shape[1]),
                                                       f32)
            return carry

        over_live(mark)
        live = mask_v[:, 0:1] > 0                          # [slots, 1]
        a_v[...] = -jnp.exp(w['A_log'][...])

        def wait_tiles(count, out=False):
            def one(i, carry):
                for dma in tile_dmas(0, out):
                    dma.wait()
                return carry
            lax.fori_loop(0, count, one, 0)

        wait_tiles(tiles)
        # The conv over every row (a dead tile's rows are left unread),
        # and each live row's tail moved up by one token.
        for ch in chans:
            old = [tail_v[j, :, ch] for j in range(T)]
            x = x_ref[:, ch]
            taps = w['conv'][:, ch].astype(f32)             # [T + 1, bi]
            acc = old[0].astype(f32) * taps[0:1]
            for j in range(1, T):
                acc = acc + old[j].astype(f32) * taps[j:j + 1]
            acc = acc + x.astype(f32) * taps[T:T + 1]
            if 'conv_bias' in w:
                acc = acc + vec('conv_bias', ch)
            xc_v[:, ch] = jax.nn.silu(acc)
            for j, new in enumerate(old[1:] + [x.astype(tail_v.dtype)]):
                tail_v[j, :, ch] = jnp.where(live, new, old[j])
        over_live_tiles(lambda start: start_all(tile_dmas(start, out=True)))

        # [d; B; C] = x~ W_x over every slot's row (W_x lies as its
        # transpose); dt from d.
        dbc = lax.dot_general(xc_v[...].astype(x_ref.dtype), w['x_proj'][...],
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=f32)   # [slots, R+2N]
        dbc_v[...] = dbc
        d = dbc[:, :R]
        d = (d * lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps)
             * vec('dt_norm')).astype(x_ref.dtype)
        for ch in chans:
            dt = jnp.dot(d, w['dt_proj'][:, ch], preferred_element_type=f32)
            dt_v[:, ch] = jax.nn.softplus(dt + vec('dt_bias', ch))

        # B and C of a slot as columns [N, 1]: its row's lanes R.. and
        # R+N.., picked out exactly (one term a sum), then normed.
        def column(pick, a):
            return jnp.sum(jnp.where(pick, a, 0.0), axis=1, keepdims=True)

        lane = lax.broadcasted_iota(jnp.int32, (N, R + 2 * N), 1)
        row = lax.broadcasted_iota(jnp.int32, (N, R + 2 * N), 0)
        layer_lane = lax.broadcasted_iota(jnp.int32, w['b_norm'].shape, 1)
        norm_cols = [column(layer_lane == li, w[k][...])
                     for k in ('b_norm', 'c_norm')]

        def update(k, carry):           # a live slot's state, in place
            buf = k % 2
            slot = ids_ref[k]

            @pl.when(k + 1 < n)
            def _next_state():
                @pl.when(k >= 1)
                def _buffer_free():
                    state_dma(0, 1 - buf, out=True).wait()
                state_dma(k + 1, 1 - buf).start()

            dbc_row = dbc_v[pl.ds(slot, 1), :]
            Bc, Cc = [
                c * lax.rsqrt(jnp.mean(c * c, 0, keepdims=True) + eps) * wc
                for c, wc in zip((column(lane == R + row, dbc_row),
                                  column(lane == R + N + row, dbc_row)),
                                 norm_cols)]
            state_dma(k, buf).wait()
            for ch in chans:
                dt = dt_v[pl.ds(slot, 1), ch]               # [1, bi]
                u = dt * xc_v[pl.ds(slot, 1), ch]
                S = (jnp.exp(dt * a_v[:, ch]) * s_buf[buf, 0, :, ch]
                     + u * Bc)
                s_buf[buf, 0, :, ch] = S
                y_v[pl.ds(slot, 1), ch] = jnp.sum(S * Cc, axis=0,
                                                  keepdims=True)
            state_dma(k, buf, out=True).start()
            return carry

        over_live(update)

        @pl.when(n >= 2)
        def _second_last():
            state_dma(0, n % 2, out=True).wait()

        @pl.when(n >= 1)
        def _last():
            state_dma(0, (n - 1) % 2, out=True).wait()

        wait_tiles(tiles, out=True)
        for ch in chans:
            y = ((y_v[:, ch] + vec('D', ch) * xc_v[:, ch])
                 * jax.nn.silu(z_ref[:, ch].astype(f32)))
            y_ref[:, ch] = jnp.where(live, y, 0.0).astype(y_ref.dtype)

    def at_layer(name):
        a = views[name]
        if name in ('b_norm', 'c_norm'):        # [N, layers], whole
            return pl.BlockSpec(a.shape, lambda i, l, live: (0, 0))
        if a.ndim == 2:         # a vector a layer: the 8 rows round it
            return pl.BlockSpec((min(8, a.shape[0]), a.shape[1]),
                                lambda i, l, live: (l[0] // 8, 0))
        return pl.BlockSpec((None,) + a.shape[1:],
                            lambda i, l, live, nd=a.ndim: (l[0],)
                            + (0,) * (nd - 1))

    def half(j):
        return pl.BlockSpec((slots, I), lambda i, l, live: (0, j))

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    first = 4 + len(names)              # the state stack's operand index
    y, states, planes = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,               # layer, live flags
            grid=(1,),
            in_specs=[half(0), half(1)] + [at_layer(k) for k in names]
            + [any_spec, any_spec],
            out_specs=[half(0), any_spec, any_spec],
            scratch_shapes=[
                pltpu.VMEM((slots, I), f32),                # x~
                pltpu.VMEM((slots, I), f32),                # dt
                pltpu.VMEM((slots, I), f32),                # C^T S
                pltpu.VMEM((slots, R + 2 * N), f32),        # [d; B; C]
                pltpu.VMEM((N, I), f32),                    # A
                pltpu.VMEM((slots, 128), f32),              # live rows
                pltpu.VMEM((T, slots, I), tails.dtype),     # tail planes
                pltpu.VMEM((2, 1, N, I), f32),              # live states
                pltpu.SMEM((slots,), jnp.int32),            # live ids
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((slots, I), xz.dtype),
                   jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct(planes.shape, planes.dtype)],
        input_output_aliases={first: 1, first + 1: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name=DECODE_KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
      xz, xz,
      *[views[k] for k in names], states, planes)
    return states, jnp.swapaxes(planes, 1, 2), y


def _tail_rows(dtype) -> int:
    """Slots a DMA of a tail plane moves at once: a tile's rows (8 of
    32 bits: 16 of a bf16 plane)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
