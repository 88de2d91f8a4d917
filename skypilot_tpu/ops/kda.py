"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692), per head, ``S`` a ``[dk, dv]`` float32 state:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

in two forms of the same mathematics, both in XLA: ``recurrent_step``
(one token a sequence: decode) and ``chunked`` (prefill: the WY / UT
transform over sub-chunks of ``SUB`` tokens; sub-chunk to sub-chunk the
state is carried by a scan). ``g`` is the LOG decay (<= 0) and every
decay is applied as ``exp`` of a difference of cumulative logs that is
never positive, so nothing overflows however strong the decay: inside a
block of ``BLOCK`` tokens the differences are taken pair by pair, and
between blocks against the cumulative log at the later block's start.
A token with ``g = 0`` and ``beta = 0`` leaves the state as it was
(padding). The state is KEPT in float32. The chunked form's products
take the backend's default precision (on the TPU: operands rounded to
bfloat16 for the product, sums in float32, as the published kernels
compute them), all but the triangular inverse, whose rounding the
recursion would multiply; the one-step form is elementwise float32.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

SUB = 64        # tokens a WY transform covers
BLOCK = 16      # tokens whose decays are differenced pair by pair
_HI = lax.Precision.HIGHEST      # the triangular inverse


def recurrent_step(state: jax.Array, q: jax.Array, k: jax.Array,
                   v: jax.Array, g: jax.Array, beta: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """One token a sequence. state [b,h,dk,dv] f32; q, k, g [b,h,dk];
    v [b,h,dv]; beta [b,h]; all float32. Returns (o [b,h,dv], the new
    state). Products and sums over the state as it streams: two reads
    and one write of it, no matmul."""
    decayed = state * jnp.exp(g)[..., None]
    kS = jnp.sum(decayed * k[..., None], axis=-2)          # S~^T k
    qS = jnp.sum(decayed * q[..., None], axis=-2)          # S~^T q
    u = beta[..., None] * (v - kS)
    new = decayed + k[..., None] * u[..., None, :]
    o = qS + jnp.sum(q * k, axis=-1, keepdims=True) * u    # S_t^T q
    return o, new


def _pair_products(rows_q, rows_k, cols, G):
    """Within blocks: sum_c rows[s,c] cols[r,c] exp(G[s,c] - G[r,c]) for
    r <= s of one block, for two sets of rows at once. All [..., n,
    BLOCK, dk] -> two [..., n, BLOCK, BLOCK]."""
    diff = G[..., :, None, :] - G[..., None, :, :]         # [.., s, r, dk]
    tri = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))
    decay = jnp.exp(jnp.where(tri[..., None], diff, -jnp.inf))
    kd = cols[..., None, :, :] * decay
    return (jnp.sum(rows_q[..., :, None, :] * kd, -1),
            jnp.sum(rows_k[..., :, None, :] * kd, -1))


def _unit_lower_inverse(N):
    """(I + N)^-1 for N [..., n, n] strictly lower triangular, n a
    multiple of ``BLOCK`` by a power of two. The diagonal blocks of
    ``BLOCK`` rows by forward substitution, every block at once (a loop
    of ``BLOCK`` rows, not of n); then the inverse of a diagonal block of
    size 2m is put together from those of its halves, [[A, 0], [C,
    B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]], for m = BLOCK, 2 BLOCK,
    ... n / 2: two batched products a round. Exact but for rounding (the
    forward substitution's arithmetic in another order)."""
    n = N.shape[-1]
    lead = N.shape[:-2]

    def diagonal(a, size):      # [.., n, n] -> its diagonal blocks
        nb = n // size
        return jnp.einsum('...iaib->...iab',
                          a.reshape(lead + (nb, size, nb, size)))

    def embed(t):               # diagonal blocks -> [.., n, n]
        nb, size = t.shape[-3:-1]
        return jnp.einsum('...iab,ij->...iajb', t,
                          jnp.eye(nb, dtype=N.dtype)).reshape(N.shape)

    small = diagonal(N, BLOCK)                      # [.., n/B, B, B]
    unit = jnp.eye(BLOCK, dtype=N.dtype)

    def row(t, T):
        n_t = lax.dynamic_index_in_dim(small, t, axis=small.ndim - 2,
                                       keepdims=False)
        new = unit[t] - jnp.einsum('...r,...rc->...c', n_t, T,
                                   precision=_HI)
        return lax.dynamic_update_index_in_dim(T, new, t, T.ndim - 2)

    t = lax.fori_loop(0, BLOCK, row, jnp.zeros_like(small))
    m = BLOCK
    while m < n:
        halves = diagonal(embed(t), 2 * m)
        low = -jnp.einsum('...ij,...jk,...kl->...il', halves[..., m:, m:],
                          diagonal(N, 2 * m)[..., m:, :m],
                          halves[..., :m, :m], precision=_HI)
        t = halves.at[..., m:, :m].set(low)
        m *= 2
    return embed(t)


def _sub_chunk_terms(q, k, v, g, beta):
    """Everything of a sub-chunk that does not need the state it starts
    from. q, k, g [.., SUB, dk]; v [.., SUB, dv]; beta [.., SUB].
    Returns (Wk [.., SUB, dk], Wv [.., SUB, dv], Aqk [.., SUB, SUB],
    q_in [.., SUB, dk], k_out [.., SUB, dk], decay_all [.., dk])."""
    nb = SUB // BLOCK
    lead = q.shape[:-2]
    dk = q.shape[-1]
    G = jnp.cumsum(g, axis=-2)                              # inclusive
    blk = lambda a: a.reshape(lead + (nb, BLOCK) + a.shape[-1:])
    Gb, qb, kb = blk(G), blk(q), blk(k)
    # The cumulative log at each block's start (0 for the first).
    start = jnp.concatenate(
        [jnp.zeros(lead + (1, dk), G.dtype), Gb[..., :-1, -1, :]], -2)
    # Same block: pair by pair.
    qk_in, kk_in = _pair_products(qb, kb, kb, Gb)           # [.., nb, B, B]
    eye = jnp.eye(nb, dtype=q.dtype)
    embed = lambda a: jnp.einsum('...iab,ij->...iajb', a, eye).reshape(
        lead + (SUB, SUB))
    # Earlier blocks: rows decayed from their block's start, columns up
    # to it; both exponents <= 0.
    rows_decay = jnp.exp(Gb - start[..., :, None, :])       # [.., nb, B, dk]
    cols_decay = jnp.exp(jnp.minimum(
        start[..., :, None, :] - G[..., None, :, :], 0.0))  # [.., nb, SUB, dk]
    cols = k[..., None, :, :] * cols_decay
    earlier = (jnp.arange(SUB)[None, :]
               < (jnp.arange(nb) * BLOCK)[:, None])          # [nb, SUB]
    cols = jnp.where(earlier[..., None], cols, 0.0)
    off = lambda rows: jnp.einsum(
        '...iak,...irk->...iar', rows * rows_decay, cols).reshape(
            lead + (SUB, SUB))
    Aqk = embed(qk_in) + off(qb)                            # r <= s
    Akk = embed(kk_in) + off(kb)
    T = _unit_lower_inverse(beta[..., None] * jnp.tril(Akk, -1))
    k_in = k * jnp.exp(G)                                   # from the start
    Wk = jnp.einsum('...sr,...rk->...sk', T, beta[..., None] * k_in)
    Wv = jnp.einsum('...sr,...rv->...sv', T, beta[..., None] * v)
    total = G[..., -1:, :]
    return (Wk, Wv, Aqk, q * jnp.exp(G), k * jnp.exp(total - G),
            jnp.exp(total[..., 0, :]))


def chunked(state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
            g: jax.Array, beta: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A run of tokens a sequence. state [b,h,dk,dv] f32; q, k, g
    [b,s,h,dk]; v [b,s,h,dv]; beta [b,s,h]; float32; any s (padded here
    to a multiple of ``SUB`` with tokens that change nothing). Returns
    (o [b,s,h,dv], the state after the run)."""
    b, s, h, dk = q.shape
    pad = -s % SUB
    n = (s + pad) // SUB

    def split(a):               # [b,s,h,..] -> [n, b, h, SUB, ..]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((b, n, SUB) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    terms = _sub_chunk_terms(split(q), split(k), split(v), split(g),
                             split(beta[..., None])[..., 0])

    def sub_chunk(S, t):
        Wk, Wv, Aqk, q_in, k_out, decay_all = t
        u = Wv - jnp.einsum('bhsk,bhkv->bhsv', Wk, S)
        o = (jnp.einsum('bhsk,bhkv->bhsv', q_in, S)
             + jnp.einsum('bhsr,bhrv->bhsv', Aqk, u))
        S = (decay_all[..., None] * S
             + jnp.einsum('bhsk,bhsv->bhkv', k_out, u))
        return S, o

    state, o = lax.scan(sub_chunk, state, terms)            # o [n,b,h,SUB,dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        b, n * SUB, h, o.shape[-1])
    return o[:, :s], state


# --------------------------------------------------------------------------
# One step, as a kernel over the live slots' state
# --------------------------------------------------------------------------
KERNEL_NAME = 'kda_recurrent_step'
_HEAD_BLOCK = 16        # heads a grid step holds: 1 MiB of float32 state


def recurrent_step_in_place(states: jax.Array, layer, q: jax.Array,
                            k: jax.Array, v: jax.Array, g: jax.Array,
                            beta: jax.Array, active: jax.Array, *,
                            interpret: bool = False
                            ) -> Tuple[jax.Array, jax.Array]:
    """``recurrent_step`` on layer ``layer`` of the stacked state of every
    slot, [layers, slots, h, dk, dv] float32, for the slots that are
    ``active`` ([slots] bool): each live slot's state is read once and
    written once, in place (the stack is aliased to the first output);
    a slot that is not active is neither read nor written. q, k, g
    [slots,h,dk]; v [slots,h,dv]; beta [slots,h]. Returns (the stack, o
    [slots,h,dv], zeros for the slots that are not active).

    Grid (head blocks, slots), slots innermost: a dead slot's blocks are
    mapped to those of the live slot before it (the first live one where
    there is none), so the pipeline sees an unchanged block index, moves
    nothing, and the body is skipped. The layer rides the block index of
    the whole stack (no layer of it is sliced out as a copy). Vectors
    that scale the state's rows (q, k, exp g: along dk, the sublanes)
    come in column form [dk, heads of the block], those along dv (v, the
    write strength) as rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n_layers, slots, h, dk, dv = states.shape
    hb = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 else h
    nb = h // hb
    # the live slot whose blocks slot b's grid steps name
    idx = jnp.where(active, jnp.arange(slots), -1)
    before = lax.cummax(idx, axis=0)
    remap = jnp.where(before >= 0, before, jnp.argmax(active)).astype(
        jnp.int32)

    def columns(a):             # [slots,h,dk] -> [slots, nb, dk, hb]
        return a.reshape(slots, nb, hb, dk).transpose(0, 1, 3, 2)

    cols = jnp.stack([columns(q), columns(k), columns(jnp.exp(g))], axis=2)
    rows = jnp.stack([v.reshape(slots, nb, hb, dv),
                      jnp.broadcast_to(beta.reshape(slots, nb, hb, 1),
                                       (slots, nb, hb, dv))], axis=2)

    def kernel(layer_ref, remap_ref, live_ref, s_ref, cols_ref, rows_ref,
               s_out_ref, o_ref):
        del layer_ref, remap_ref

        @pl.when(live_ref[pl.program_id(1)] != 0)
        def _():
            for i in range(hb):
                qc = cols_ref[0, 0, 0, :, i:i + 1]          # [dk, 1]
                kc = cols_ref[0, 0, 1, :, i:i + 1]
                decayed = s_ref[0, 0, i] * cols_ref[0, 0, 2, :, i:i + 1]
                kS = jnp.sum(decayed * kc, axis=0, keepdims=True)
                qS = jnp.sum(decayed * qc, axis=0, keepdims=True)
                u = rows_ref[0, 0, 1, i:i + 1, :] * (
                    rows_ref[0, 0, 0, i:i + 1, :] - kS)      # [1, dv]
                s_out_ref[0, 0, i] = decayed + kc * u
                o_ref[0, 0, i:i + 1, :] = qS + jnp.sum(
                    qc * kc, axis=0, keepdims=True) * u

    def state_block(j, b, layer, remap, live):
        del live
        return (layer[0], remap[b], j, 0, 0)

    def slot_block(j, b, layer, remap, live):
        del layer, live
        return (remap[b], j, 0, 0, 0)

    new_states, o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,               # layer, remap, live
            grid=(nb, slots),
            in_specs=[
                pl.BlockSpec((1, 1, hb, dk, dv), state_block),
                pl.BlockSpec((1, 1, 3, dk, hb), slot_block),
                pl.BlockSpec((1, 1, 2, hb, dv), slot_block),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, dk, dv), state_block),
                pl.BlockSpec((1, 1, hb, dv),
                             lambda j, b, layer, remap, live:
                             (remap[b], j, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, states.dtype),
            jax.ShapeDtypeStruct((slots, nb, hb, dv), jnp.float32),
        ],
        input_output_aliases={3: 0},    # the state stack, in place
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), remap,
      active.astype(jnp.int32), states, cols, rows)
    o = jnp.where(active[:, None, None], o.reshape(slots, h, dv), 0.0)
    return new_states, o
