"""Pallas TPU flash attention (FlashAttention-2 style), fwd + bwd.

The training hot op. Blockwise online-softmax attention that never
materializes the [sq, skv] score matrix in HBM: each (batch, head, q-block)
streams kv-blocks through VMEM with running max/denominator in f32 scratch;
the MXU sees [block_q, head_dim] x [head_dim, block_k] matmuls.

Conventions:
- Public entry takes the model layout [batch, seq, heads, head_dim] and
  handles GQA natively in the forward (kv BlockSpec index-maps q-head ->
  kv-head, no materialized repeat).
- Backward follows FA-2: recompute p from q,k and the saved logsumexp, one
  kernel for dk/dv (loop over q blocks) and one for dq (loop over kv
  blocks). For GQA the backward expands kv to query heads and sums dk/dv
  over the group afterwards (read-only expansion would race on writes).
- All softmax math in f32; inputs/outputs keep their dtype (bf16 typical).

Grid iteration on TPU is sequential with the last dimension innermost, so
f32 scratch accumulators persist across the kv-block loop — the standard
Pallas flash pattern.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128


# --------------------------------------------------------------------------
# Forward kernel
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scratch, l_scratch, acc_scratch,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                num_k_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, -jnp.inf)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # For causal attention, kv blocks strictly above the diagonal contribute
    # nothing; skip their compute (the grid still visits them).
    q_start = iq * block_q
    k_start = ik * block_k
    if causal:
        needed = k_start <= q_start + block_q - 1
        last_needed_ik = jnp.minimum((q_start + block_q - 1) // block_k,
                                     num_k_blocks - 1)
    else:
        needed = jnp.bool_(True)
        last_needed_ik = num_k_blocks - 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)

        m_prev = m_scratch[:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                # [bq, 1]
        l_new = alpha * l_scratch[:, :1] + jnp.sum(p, -1, keepdims=True)

        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, d]
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(ik == last_needed_ik)
    def _finalize():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        lse = m_scratch[:, :1] + jnp.log(l_safe)       # [bq, 1]
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret):
    """q: [b, hq, sq, d]; k/v: [b, hkv, skv, d] -> (out, lse)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(skv, block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


# --------------------------------------------------------------------------
# Chunked-prefill forward (nonzero cache offset)
# --------------------------------------------------------------------------
def _fwd_chunk_kernel(cl_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scratch, l_scratch, acc_scratch,
                      *, scale: float, split: int, block_q: int,
                      block_k: int, num_k_blocks: int):
    """Forward-only flash for a prefill CHUNK against a cache prefix.

    kv rows [0, split) are the per-row cache prefix (valid iff their
    index < cl_ref[b], the row's live cache length); rows [split, skv)
    are the chunk itself, causal against the chunk-local q positions.
    The chunk's absolute positions are cl_b + [0..sq) so every valid
    cache row strictly precedes every q row — only the length mask
    applies to the cache region."""
    ib = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    cl = cl_ref[ib]              # this row's live cache length (SMEM)

    @pl.when(ik == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, -jnp.inf)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q_start = iq * block_q
    k_start = ik * block_k
    # A block is needed when it holds live cache rows (any index < cl)
    # or overlaps the chunk's causal triangle; blocks straddling
    # ``split`` evaluate both.
    needed = ((jnp.logical_and(k_start < split, k_start < cl))
              | jnp.logical_and(k_start + block_k > split,
                                k_start - split <= q_start + block_q - 1))
    # The diagonal chunk block is always needed and always has the
    # largest needed ik (chunk rows come after cache rows), so the
    # finalize index depends only on the q block.
    last_needed_ik = jnp.minimum((split + q_start + block_q - 1)
                                 // block_k, num_k_blocks - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_idx = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = jnp.where(k_idx < split, k_idx < cl,
                          k_idx - split <= q_pos)
        s = jnp.where(valid, s, DEFAULT_MASK_VALUE)

        m_prev = m_scratch[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scratch[:, :1] + jnp.sum(p, -1, keepdims=True)

        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(ik == last_needed_ik)
    def _finalize():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)


def _fwd_chunk(q, k, v, cache_len, *, scale, split, block_q, block_k,
               interpret):
    """q: [b, hq, sq, d]; k/v: [b, hkv, skv, d] laid out as
    [cache(:split); chunk]; cache_len: [b] int32 live cache rows."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(skv, block_k)

    kernel = functools.partial(
        _fwd_chunk_kernel, scale=scale, split=split, block_q=block_q,
        block_k=block_k, num_k_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,               # cache lengths [b]
            grid=(b, hq, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda ib, ih, iq, ik, cl: (ib, ih, iq, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, iq, ik, cl:
                             (ib, ih // group, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, iq, ik, cl:
                             (ib, ih // group, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda ib, ih, iq, ik, cl:
                                   (ib, ih, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q, k, v)
    return out


# --------------------------------------------------------------------------
# Backward kernels
# --------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scratch, dv_scratch,
                    *, scale: float, causal: bool, block_q: int,
                    block_k: int, num_q_blocks: int):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    q_start = iq * block_q
    k_start = ik * block_k
    needed = (jnp.bool_(True) if not causal
              else q_start + block_q - 1 >= k_start)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        do = do_ref[0, 0].astype(jnp.float32)          # [bq, d]
        lse = lse_ref[0, 0][:, :1]                     # [bq, 1]
        delta = delta_ref[0, 0][:, :1]                 # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)                           # [bq, bk]

        # dv += p^T @ do
        dv_scratch[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]
        # ds = p * (do @ v^T - delta)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = p * (dp - delta) * scale
        # dk += ds^T @ q
        dk_scratch[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, d]

    @pl.when(iq == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scratch,
                   *, scale: float, causal: bool, block_q: int,
                   block_k: int, num_k_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    q_start = iq * block_q
    k_start = ik * block_k
    needed = (not causal) or (k_start <= q_start + block_q - 1)
    last_needed_ik = (num_k_blocks - 1 if not causal else
                      jnp.minimum((q_start + block_q - 1) // block_k,
                                  num_k_blocks - 1))

    @pl.when(needed if isinstance(needed, bool) else needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scratch[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, d]

    @pl.when(ik == last_needed_ik)
    def _finalize():
        dq_ref[0, 0] = dq_scratch[:].astype(dq_ref.dtype)


def _bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    do = g
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    # Expand kv to query heads for the backward (write-race-free).
    ke = jnp.repeat(k, group, axis=1) if group > 1 else k
    ve = jnp.repeat(v, group, axis=1) if group > 1 else v

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(skv, bk)

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                           # [b, hq, sq]
    lse_b = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda ib, ih, a, b_: (ib, ih, b_, 0))
    k_spec = pl.BlockSpec((1, 1, bk, d), lambda ib, ih, a, b_: (ib, ih, a, 0))
    r_spec = pl.BlockSpec((1, 1, bq, _LANES),
                          lambda ib, ih, a, b_: (ib, ih, b_, 0))

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, num_q_blocks=nq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hq, nk, nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, a, b_: (ib, ih, a, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, a, b_: (ib, ih, a, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=interpret,
    )(q, ke, ve, do, lse_b, delta_b)

    q_spec2 = pl.BlockSpec((1, 1, bq, d), lambda ib, ih, a, b_: (ib, ih, a, 0))
    k_spec2 = pl.BlockSpec((1, 1, bk, d), lambda ib, ih, a, b_: (ib, ih, b_, 0))
    r_spec2 = pl.BlockSpec((1, 1, bq, _LANES),
                           lambda ib, ih, a, b_: (ib, ih, a, 0))
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, num_k_blocks=nk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, hq, nq, nk),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda ib, ih, a, b_: (ib, ih, a, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=interpret,
    )(q, ke, ve, do, lse_b, delta_b)

    if group > 1:
        dk = dk.reshape(b, hkv, group, skv, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, skv, d).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# Public entry ([b, s, h, d] layout, custom VJP)
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, res, g):
    return _bwd(scale, causal, block_q, block_k, interpret, res, g)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    cache_len: Optional[jax.Array] = None,
                    kv_split: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Flash attention over model-layout tensors.

    q: [b, sq, hq, d]; k/v: [b, skv, hkv, d] (GQA: hkv divides hq).
    Returns [b, sq, hq, d].

    Chunked prefill against a cache (nonzero cache offset): pass
    ``kv_split`` and ``cache_len`` with k/v laid out as
    ``[cache(:kv_split); chunk]``. Row b's cache prefix is valid up to
    ``cache_len[b]`` rows; the chunk (rows ``kv_split:``) is causal
    against q, whose absolute positions are ``cache_len[b] + [0..sq)``.
    This path is FORWARD-ONLY (inference prefill — no VJP).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(f'GQA requires hkv ({hkv}) to divide hq ({hq})')
    if cache_len is not None or kv_split is not None:
        if cache_len is None or kv_split is None:
            raise ValueError('cache_len and kv_split must be passed '
                             'together')
        if not causal:
            raise ValueError('chunked-prefill flash is causal only')
        if skv != kv_split + sq:
            raise ValueError(
                f'kv must be [cache({kv_split}); chunk({sq})] rows, got '
                f'skv={skv}')
    elif causal and sq != skv:
        raise ValueError(
            f'causal flash kernel assumes sq == skv (got {sq} vs {skv}); '
            'use ops.attention with q_offset for cached prefill/decode')
    # Shrink blocks (512 -> 256 -> 128) until they divide the sequence:
    # 512 is the throughput sweet spot, but seq lengths like 640/768 are
    # only 128-divisible and must still route through the kernel.
    block_q = min(block_q, sq)
    while block_q > 128 and sq % block_q:
        block_q //= 2
    block_k = min(block_k, skv)
    while block_k > 128 and skv % block_k:
        block_k //= 2
    # Mosaic needs sublane-aligned tiles: a sequence like 300 or 129
    # would otherwise sail through with block==sq and die in the kernel
    # compile with an opaque error. Short power-of-two sequences
    # (block == sq, multiple of 8) remain valid, as before.
    if (sq % block_q != 0 or skv % block_k != 0
            or block_q % 8 != 0 or block_k % 8 != 0):
        raise ValueError(
            f'seq lengths must be divisible by 8-aligned block sizes: '
            f'sq={sq} (block_q={block_q}), skv={skv} (block_k={block_k})')
    if scale is None:
        scale = q.shape[-1] ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if cache_len is not None:
        out = _fwd_chunk(qt, kt, vt, cache_len, scale=scale,
                         split=kv_split, block_q=block_q,
                         block_k=block_k, interpret=interpret)
    else:
        out = _flash_bhsd(qt, kt, vt, scale, causal, block_q, block_k,
                          interpret)
    return out.transpose(0, 2, 1, 3)
