"""Attention ops: reference jnp implementation + dispatcher.

The dispatcher routes to the Pallas flash-attention kernel on TPU for long
sequences (see ``skypilot_tpu/ops/flash_attention.py``) and falls back to the
XLA einsum path elsewhere (CPU tests, tiny shapes, decode).

Shapes follow the [batch, seq, heads, head_dim] convention throughout.
GQA: kv heads are broadcast to query heads here (the kernel keeps them
folded to save bandwidth).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[b, s, kv_heads, d] -> [b, s, kv_heads * n_rep, d]."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def reference_attention(
    q: jax.Array,                      # [b, sq, h, d]
    k: jax.Array,                      # [b, skv, hkv, d]
    v: jax.Array,                      # [b, skv, hkv, d]
    *,
    causal: bool = True,
    q_offset: Optional[jax.Array] = None,   # position of q[0] within kv seq
    kv_len: Optional[jax.Array] = None,     # valid kv length (decode masking)
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain XLA attention in fp32 accumulation.

    GQA is computed in grouped form ([b, s, hkv, group, d] einsums) so kv is
    never materialized at query-head width — in decode the kv cache read IS
    the bandwidth bill, a 4x broadcast would quadruple it.

    ``q_offset``/``kv_len`` support the decode path: q positions are
    ``q_offset + [0..sq)``, kv positions beyond ``kv_len`` are masked out.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, sq, hkv, group, d)

    logits = jnp.einsum('bqhgd,bkhd->bhgqk', qg, k,
                        preferred_element_type=jnp.float32) * scale

    skv = k.shape[1]
    kv_pos = jnp.arange(skv)[None, None, None, None, :]        # [1,1,1,1,k]
    mask = jnp.ones((1, 1, 1, sq, skv), dtype=bool)
    if causal:
        q_pos = jnp.arange(sq)[None, None, None, :, None]      # [1,1,1,q,1]
        if q_offset is not None:
            q_pos = q_pos + jnp.reshape(q_offset, (-1, 1, 1, 1, 1))
        mask = mask & (kv_pos <= q_pos)
    if kv_len is not None:
        mask = mask & (kv_pos < jnp.reshape(kv_len, (-1, 1, 1, 1, 1)))
    logits = jnp.where(mask, logits, -1e30)

    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum('bhgqk,bkhd->bqhgd', probs, v)
    return out.reshape(b, sq, h, d)


def _scale_bhk(s: Optional[jax.Array]) -> Optional[jax.Array]:
    """[b, S, hkv, 1] fp32 per-row KV scales -> [b, hkv, 1, 1, S] for
    folding into 'bhgqk' logits/probs."""
    if s is None:
        return None
    return jnp.transpose(s[..., 0], (0, 2, 1))[:, :, None, None, :]


def _unpack_kv(cache_k: jax.Array, cache_v: jax.Array):
    """int4 KV caches arrive as packed uint8 nibble rows ([..., d//2]);
    unpack to int8 CODES so the downstream contraction + scale-fold
    math is byte-for-byte the int8 path's (absmax/7 scales instead of
    absmax/127 — the fold is scale-agnostic). The unpack is VPU work
    XLA fuses into the operand read; the HBM stream stays packed."""
    if cache_k.dtype != jnp.uint8:
        return cache_k, cache_v
    from skypilot_tpu.models import quantization
    return (quantization.unpack_int4(cache_k, axis=-1),
            quantization.unpack_int4(cache_v, axis=-1))


def cached_attention(
    q: jax.Array,                      # [b, s, h, d] new-token queries
    k_new: jax.Array,                  # [b, s, hkv, d] new-token keys
    v_new: jax.Array,                  # [b, s, hkv, d]
    cache_k: jax.Array,                # [b, S, hkv, d] cache WITHOUT new rows
    cache_v: jax.Array,                # [b, S, hkv, d]
    cache_len: jax.Array,              # [b] valid cache entries
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,   # [b, S, hkv, 1] fp32: cache_k/v
    v_scale: Optional[jax.Array] = None,   # are int8 CODES when given
) -> jax.Array:
    """Decode/prefill attention against a KV cache without materializing
    the concatenated [cache; new] sequence.

    Two score blocks share one numerically-stable softmax: the cache block
    (positions < cache_len; all strictly precede the new tokens, so only
    the length mask applies) and the new-token block (standard causal
    within the s new positions). The cache is only READ here — the caller
    scatters the new rows in afterwards — so a decode step's cache traffic
    is one streaming read plus an s-token write, not a full rewrite.
    fp32 logits/softmax; GQA stays in grouped form (no kv broadcast).

    int8 caches pass CODES + per-row scales: the codes are contracted
    directly (int8 stays int8 across HBM — a pre-dequantized operand
    streams ~30% slower, see quantization.qeinsum) and the row scales
    fold into the fp32 logits (K) / probabilities (V) exactly. int4
    caches pass PACKED uint8 nibble rows (see ``_unpack_kv``)."""
    cache_k, cache_v = _unpack_kv(cache_k, cache_v)
    b, s, h, d = q.shape
    hkv = k_new.shape[2]
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, s, hkv, group, d)

    lc = jnp.einsum('bqhgd,bkhd->bhgqk', qg, cache_k,
                    preferred_element_type=jnp.float32) * scale
    ks = _scale_bhk(k_scale)
    if ks is not None:
        lc = lc * ks
    ls = jnp.einsum('bqhgd,bkhd->bhgqk', qg, k_new,
                    preferred_element_type=jnp.float32) * scale

    S = cache_k.shape[1]
    kv_pos = jnp.arange(S)[None, None, None, None, :]
    lc = jnp.where(kv_pos < jnp.reshape(cache_len, (-1, 1, 1, 1, 1)),
                   lc, -1e30)
    q_pos = jnp.arange(s)[None, None, None, :, None]
    new_pos = jnp.arange(s)[None, None, None, None, :]
    ls = jnp.where(new_pos <= q_pos, ls, -1e30)

    m = jnp.maximum(jnp.max(lc, -1, keepdims=True),
                    jnp.max(ls, -1, keepdims=True))
    ec = jnp.exp(lc - m)
    es = jnp.exp(ls - m)
    denom = jnp.sum(ec, -1, keepdims=True) + jnp.sum(es, -1, keepdims=True)
    pc = ec / denom
    vs = _scale_bhk(v_scale)
    if vs is not None:
        pc = pc * vs
        out = jnp.einsum('bhgqk,bkhd->bqhgd', pc.astype(jnp.bfloat16),
                         cache_v, preferred_element_type=jnp.float32
                         ).astype(q.dtype)
    else:
        out = jnp.einsum('bhgqk,bkhd->bqhgd', pc.astype(cache_v.dtype),
                         cache_v)
    out = out + jnp.einsum('bhgqk,bkhd->bqhgd',
                           (es / denom).astype(v_new.dtype), v_new)
    return out.reshape(b, s, h, d)


def ring_decode_attention(
    q: jax.Array,                      # [b, 1, h, d] current-token queries
    k_self: jax.Array,                 # [b, 1, hkv, d] current-token keys
    v_self: jax.Array,                 # [b, 1, hkv, d]
    cache_k: jax.Array,                # [b, S, hkv, d] read-only main cache
    cache_v: jax.Array,
    cache_len: jax.Array,              # [b] valid main-cache entries (fixed
                                       #     for the whole fused horizon)
    ring_k: jax.Array,                 # [b, H, hkv, d] this horizon's rows
    ring_v: jax.Array,
    ring_len: jax.Array,               # scalar: rows < ring_len are valid
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,   # [b, S, hkv, 1] fp32: cache_k/v
    v_scale: Optional[jax.Array] = None,   # are int8 CODES when given
) -> jax.Array:
    """Single-token decode attention over three blocks sharing one
    softmax: the main cache (read-only inside a fused multi-step decode —
    its mask depends only on the horizon-start lengths), the ring of rows
    produced by the previous steps of this horizon, and the current
    token. Keeping the main cache out of the loop carry is the point:
    XLA then streams it instead of re-materializing it every step.
    int8 caches pass codes + scales (see cached_attention); int4
    caches pass packed uint8 nibble rows (see ``_unpack_kv``)."""
    cache_k, cache_v = _unpack_kv(cache_k, cache_v)
    b, _, h, d = q.shape
    hkv = k_self.shape[2]
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, 1, hkv, group, d)

    lc = jnp.einsum('bqhgd,bkhd->bhgqk', qg, cache_k,
                    preferred_element_type=jnp.float32) * scale
    ks = _scale_bhk(k_scale)
    if ks is not None:
        lc = lc * ks
    lr = jnp.einsum('bqhgd,bkhd->bhgqk', qg, ring_k,
                    preferred_element_type=jnp.float32) * scale
    lself = jnp.einsum('bqhgd,bqhd->bhgq', qg, k_self,
                       preferred_element_type=jnp.float32)[..., None] * scale

    S = cache_k.shape[1]
    pos = jnp.arange(S)[None, None, None, None, :]
    lc = jnp.where(pos < jnp.reshape(cache_len, (-1, 1, 1, 1, 1)), lc, -1e30)
    rpos = jnp.arange(ring_k.shape[1])[None, None, None, None, :]
    lr = jnp.where(rpos < ring_len, lr, -1e30)

    m = jnp.maximum(jnp.max(lc, -1, keepdims=True),
                    jnp.max(lr, -1, keepdims=True))
    m = jnp.maximum(m, lself)
    ec, er, es = jnp.exp(lc - m), jnp.exp(lr - m), jnp.exp(lself - m)
    denom = (jnp.sum(ec, -1, keepdims=True) +
             jnp.sum(er, -1, keepdims=True) + es)
    pc = ec / denom
    vs = _scale_bhk(v_scale)
    if vs is not None:
        out = jnp.einsum('bhgqk,bkhd->bqhgd',
                         (pc * vs).astype(jnp.bfloat16), cache_v,
                         preferred_element_type=jnp.float32
                         ).astype(q.dtype)
    else:
        out = jnp.einsum('bhgqk,bkhd->bqhgd', pc.astype(cache_v.dtype),
                         cache_v)
    out = out + jnp.einsum('bhgqk,bkhd->bqhgd',
                           (er / denom).astype(ring_v.dtype), ring_v)
    w_self = (es / denom)[..., 0].transpose(0, 3, 1, 2)   # [b, 1, hkv, g]
    out = out + w_self.astype(v_self.dtype)[..., None] * \
        v_self[:, :, :, None, :]
    return out.reshape(b, 1, h, d)


def flash_selected(impl: str, sq: int, head_dim: int, *,
                   causal: bool = True, masked: bool = False) -> bool:
    """Whether :func:`attention` runs the Pallas flash kernel for these
    shapes ('flash' forces it; 'auto' picks it on TPU where the shape
    fits the kernel's tiling; else the XLA reference). The entry points
    print this, so which attention ran is not a guess."""
    if impl == 'flash':
        return True
    return (impl == 'auto' and jax.default_backend() == 'tpu' and causal
            and sq >= 256 and sq % 128 == 0 and head_dim % 128 == 0
            and not masked)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: Optional[jax.Array] = None,
    kv_len: Optional[jax.Array] = None,
    impl: str = 'auto',
) -> jax.Array:
    """Dispatching attention entry point used by the models.

    impl: 'auto' | 'xla' | 'flash' | 'ring' | 'ulysses'. 'auto' picks
    flash on TPU when the shape fits the kernel's tiling (training-style
    full-sequence causal attention); decode (sq==1) always uses the XLA
    path, which fuses into a single-pass softmax anyway. 'ring' shards
    the sequence over the sp mesh axis with ppermute KV rotation;
    'ulysses' shards it with all-to-all head scatter — two collectives
    total; needs (n_heads/tp) divisible by sp.

    Deliberately NOT wrapped in jax.jit: the 'ring' dispatch reads the
    ambient mesh context at trace time, and a jit cache here is not keyed
    on that context — a cached no-mesh trace would silently serve the
    non-ring path inside a mesh. Callers jit the surrounding computation.
    """
    if impl in ('ring', 'ulysses'):
        # Sequence-parallel exact attention over the sp mesh axis
        # (training/prefill; decode never shards its single query).
        assert q_offset is None and kv_len is None, (
            'sequence-parallel attention is a full-sequence path; '
            'decode masking args are not supported')
        from skypilot_tpu.ops import ring_attention as ring
        mesh = ring.current_mesh()
        if mesh is not None and mesh.shape.get('sp', 1) > 1:
            if impl == 'ulysses':
                from skypilot_tpu.ops.ulysses import ulysses_attention
                return ulysses_attention(q, k, v, mesh, causal=causal)
            return ring.ring_attention(q, k, v, mesh, causal=causal)
        return reference_attention(q, k, v, causal=causal)
    if impl not in ('auto', 'xla', 'flash'):
        raise ValueError(f'unknown attention impl {impl!r}')
    use_flash = flash_selected(
        impl, q.shape[1], q.shape[-1], causal=causal,
        masked=q_offset is not None or kv_len is not None)
    if use_flash:
        from skypilot_tpu.ops import flash_attention
        return flash_attention.flash_attention(q, k, v, causal=causal)
    return reference_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_len=kv_len)
