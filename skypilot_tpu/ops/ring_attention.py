"""Ring attention: exact attention over a sequence sharded across the
``sp`` mesh axis (long-context / context parallelism).

SURVEY §5 mandate (absent from the reference, which delegates long-context
to external engines): each sp-rank holds one contiguous block of the
sequence; KV blocks rotate around the ring via ``lax.ppermute`` (ICI
neighbor hops) while a numerically-stable blockwise softmax accumulates —
the same online (m, l, acc) recurrence as flash attention, so the full
[s, s] score matrix never materializes and per-device memory stays
O(s_local). After sp_size hops every rank has attended to the whole
sequence exactly once.

Causal masking uses global positions (rank * s_local + local offset).
Blocks strictly in the future (fully masked) are SKIPPED via lax.cond —
roughly half the causal FLOPs.

**Zigzag layout** (``layout='zigzag'``, the default for causal): the
contiguous layout leaves rank r computing r+1 blocks — rank sp-1 does sp
times rank 0's work and sets the wall clock. Zigzag splits each local
block into two halves and re-deals them so rank r holds halves r and
2*sp-1-r (one early, one late): every rank then computes exactly 2
half-block pairs per ring step (+ the diagonal tick) — balanced to
within one diagonal. The re-deal happens INSIDE this op via two static
ppermute permutations (rope/positions are applied by the caller before
the ring, so no token-permutation contract leaks out).

**Flash block body** (``block_impl='flash'``, auto-selected on aligned
shapes): each (q-block, k-block) pair runs the Pallas FlashAttention
kernel, whose (out, lse) merges into the running softmax — block logits
never materialize in fp32. The kernel forward has no lse-cotangent
rule, so the block is wrapped in a custom_vjp whose backward
re-derives the block with the einsum reference (same rematerialization
trade flash itself makes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Block bodies: einsum accumulate vs flash (out, lse) merge
# ---------------------------------------------------------------------------
def _block_ref(q, k, v, scale: float, causal: bool):
    """Reference block attention returning (normalized out, lse) — the
    differentiable twin of the flash kernel's forward contract."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, group, d)
    logits = jnp.einsum('bqhgd,bkhd->bhgqk', qg, k.astype(jnp.float32))
    if causal:
        sk = k.shape[1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    m = jnp.max(logits, -1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum('bhgqk,bkhd->bqhgd', p, v.astype(jnp.float32))
    o = (o / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2, 4)
         ).reshape(b, sq, h, d)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]   # [b,hkv,g,sq]
    return o, lse.reshape(b, hkv * group, sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_block(q, k, v, scale: float, causal: bool):
    """Pallas flash forward returning (out, lse); backward re-derives
    through the einsum reference (correct lse cotangents — the kernel's
    own vjp has none)."""
    from skypilot_tpu.ops import flash_attention as fa
    interpret = jax.default_backend() != 'tpu'
    sq, sk = q.shape[1], k.shape[1]
    bq = min(512, sq)
    bk = min(512, sk)
    out, lse = fa._fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                       v.transpose(0, 2, 1, 3), scale=scale,
                       causal=causal, block_q=bq, block_k=bk,
                       interpret=interpret)
    return out.transpose(0, 2, 1, 3), lse


def _flash_block_fwd(q, k, v, scale, causal):
    return _flash_block(q, k, v, scale, causal), (q, k, v)


def _flash_block_bwd(scale, causal, res, cts):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: _block_ref(q, k, v, scale, causal),
                     q, k, v)
    do, dlse = cts
    return vjp((do.astype(jnp.float32), dlse.astype(jnp.float32)))


_flash_block.defvjp(_flash_block_fwd, _flash_block_bwd)


def _merge_block(m, l, acc, q, k_blk, v_blk, *, scale, causal,
                 use_flash):
    """Merge one (q, k_blk) pair into the running (m, l, acc) softmax.
    m, l: [b, hkv, g, s, 1]; acc: [b, s, hkv, g, d]."""
    b, s, h, d = q.shape
    hkv = m.shape[1]
    group = h // hkv
    if use_flash:
        o_n, lse = _flash_block(q, k_blk, v_blk, scale, causal)
        lse = lse.reshape(b, hkv, group, s)[..., None]
        o_n = o_n.reshape(b, s, hkv, group, d).astype(jnp.float32)
        m_new = jnp.maximum(m, lse)
        corr = jnp.exp(m - m_new)
        w = jnp.exp(lse - m_new)                # block's Σexp rebased
        l_new = l * corr + w
        acc_new = (acc * corr.transpose(0, 3, 1, 2, 4)
                   + o_n * w.transpose(0, 3, 1, 2, 4))
        return m_new, l_new, acc_new
    qg = (q.astype(jnp.float32) * scale).reshape(b, s, hkv, group, d)
    logits = jnp.einsum('bqhgd,bkhd->bhgqk', qg,
                        k_blk.astype(jnp.float32))
    if causal:
        sk = k_blk.shape[1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(s)[:, None]
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(logits, -1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, -1, keepdims=True)
    acc_new = acc * corr.transpose(0, 3, 1, 2, 4) + jnp.einsum(
        'bhgqk,bkhd->bqhgd', p, v_blk.astype(jnp.float32))
    return m_new, l_new, acc_new


def _init_softmax_state(b, hkv, group, s, d):
    m = jnp.full((b, hkv, group, s, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, hkv, group, s, 1), jnp.float32)
    acc = jnp.zeros((b, s, hkv, group, d), jnp.float32)
    return m, l, acc


def _finish_softmax(m, l, acc, b, s, h, d, dtype):
    del m
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s, h, d).astype(dtype)


def _ring_body(q: jax.Array, k: jax.Array, v: jax.Array, *,
               axis_name: str, axis_size: int, causal: bool,
               scale: float, use_flash: bool) -> jax.Array:
    """Contiguous-layout per-shard computation (runs under shard_map).

    q: [b, s, h, d]; k, v: [b, s, hkv, d] — the LOCAL sequence blocks.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    m, l, acc = _init_softmax_state(b, hkv, group, s, d)
    my_rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # Step 0 hoisted: the diagonal block (own k/v) is plain causal.
    m, l, acc = _merge_block(m, l, acc, q, k, v, scale=scale,
                             causal=causal, use_flash=use_flash)
    k_blk = lax.ppermute(k, axis_name, perm)
    v_blk = lax.ppermute(v, axis_name, perm)

    def step(carry, step_idx):
        m, l, acc, k_blk, v_blk = carry
        # After `step_idx` forward rotations we hold the block that
        # originated at rank (my_rank - step_idx) — strictly past or
        # strictly future at block granularity, never diagonal.
        blk_rank = (my_rank - step_idx) % axis_size

        def compute(operand):
            m, l, acc, k_blk, v_blk = operand
            return _merge_block(m, l, acc, q, k_blk, v_blk, scale=scale,
                                causal=False, use_flash=use_flash)

        if causal:
            # Blocks from HIGHER ranks are entirely in the future: skip
            # their matmuls (lax.cond executes one branch) — the ring
            # still rotates, but ~half the causal FLOPs disappear. (The
            # permute below depends only on k/v, so XLA forwards blocks
            # through skipping ranks without waiting on compute.)
            m, l, acc = lax.cond(
                blk_rank < my_rank, compute,
                lambda operand: (operand[0], operand[1], operand[2]),
                (m, l, acc, k_blk, v_blk))
        else:
            m, l, acc = compute((m, l, acc, k_blk, v_blk))
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (m, l, acc, k_nxt, v_nxt), None

    if axis_size > 1:
        (m, l, acc, _, _), _ = lax.scan(
            step, (m, l, acc, k_blk, v_blk),
            jnp.arange(1, axis_size))
    return _finish_softmax(m, l, acc, b, s, h, d, q.dtype)


# ---------------------------------------------------------------------------
# Zigzag layout
# ---------------------------------------------------------------------------
def _zigzag_perms(sp: int):
    """Static ppermute permutations dealing contiguous half-blocks into
    the zigzag layout. Rank r's contiguous block = global halves
    (2r, 2r+1); zigzag wants halves (r, 2sp-1-r). Half h's target rank
    is min(h, 2sp-1-h); restricted to even (resp. odd) source halves
    this is a rank permutation, so two ppermutes re-deal everything."""
    t0 = {r: (2 * r if 2 * r < sp else 2 * sp - 1 - 2 * r)
          for r in range(sp)}
    t1 = {r: (2 * r + 1 if 2 * r + 1 < sp else 2 * sp - 2 - 2 * r)
          for r in range(sp)}
    perm0 = [(r, t0[r]) for r in range(sp)]
    perm1 = [(r, t1[r]) for r in range(sp)]
    inv0 = [(t0[r], r) for r in range(sp)]
    inv1 = [(t1[r], r) for r in range(sp)]
    return perm0, perm1, inv0, inv1


def _zigzag_deal(x, axis_name: str, sp: int, rank):
    """[b, s, ...] contiguous local block -> (lo, hi) zigzag halves
    ([b, s/2, ...] each): lo = global half `rank`, hi = `2sp-1-rank`."""
    half = x.shape[1] // 2
    perm0, perm1, _, _ = _zigzag_perms(sp)
    r0 = lax.ppermute(x[:, :half], axis_name, perm0)
    r1 = lax.ppermute(x[:, half:], axis_name, perm1)
    even = (rank % 2 == 0)
    lo = jnp.where(even, r0, r1)
    hi = jnp.where(even, r1, r0)
    return lo, hi


def _zigzag_undeal(lo, hi, axis_name: str, sp: int, rank):
    """Inverse of _zigzag_deal: back to the contiguous local block."""
    _, _, inv0, inv1 = _zigzag_perms(sp)
    even = (rank % 2 == 0)
    via0 = jnp.where(even, lo, hi)
    via1 = jnp.where(even, hi, lo)
    b0 = lax.ppermute(via0, axis_name, inv0)
    b1 = lax.ppermute(via1, axis_name, inv1)
    return jnp.concatenate([b0, b1], axis=1)


def _zigzag_body(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 axis_name: str, axis_size: int, scale: float,
                 use_flash: bool) -> jax.Array:
    """Balanced causal ring: every rank computes exactly 2 half-block
    pairs per off-diagonal step (contiguous layout: rank r computes r+1
    — rank sp-1 sets the wall clock at sp*rank0's work). Causal only —
    non-causal is already balanced in the contiguous layout."""
    sp = axis_size
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    half = s // 2
    rank = lax.axis_index(axis_name)

    q_lo, q_hi = _zigzag_deal(q, axis_name, sp, rank)
    k_lo, k_hi = _zigzag_deal(k, axis_name, sp, rank)
    v_lo, v_hi = _zigzag_deal(v, axis_name, sp, rank)

    m_lo, l_lo, a_lo = _init_softmax_state(b, hkv, group, half, d)
    m_hi, l_hi, a_hi = _init_softmax_state(b, hkv, group, half, d)

    def kw(causal):
        return dict(scale=scale, causal=causal, use_flash=use_flash)

    # Diagonal tick (src == rank): q_lo·k_lo diag, q_hi·k_lo full,
    # q_hi·k_hi diag.
    m_lo, l_lo, a_lo = _merge_block(m_lo, l_lo, a_lo, q_lo, k_lo, v_lo,
                                    **kw(True))
    m_hi, l_hi, a_hi = _merge_block(m_hi, l_hi, a_hi, q_hi, k_lo, v_lo,
                                    **kw(False))
    m_hi, l_hi, a_hi = _merge_block(m_hi, l_hi, a_hi, q_hi, k_hi, v_hi,
                                    **kw(True))

    perm = [(i, (i + 1) % sp) for i in range(sp)]
    kl = lax.ppermute(k_lo, axis_name, perm)
    vl = lax.ppermute(v_lo, axis_name, perm)
    kh = lax.ppermute(k_hi, axis_name, perm)
    vh = lax.ppermute(v_hi, axis_name, perm)

    def step(carry, t):
        m_lo, l_lo, a_lo, m_hi, l_hi, a_hi, kl, vl, kh, vh = carry
        src = (rank - t) % sp

        def past_src(op):
            # src < rank: its k_lo half (global idx src) is past BOTH
            # our halves; its k_hi half (2sp-1-src) is future for both.
            m_lo, l_lo, a_lo, m_hi, l_hi, a_hi = op
            m_lo, l_lo, a_lo = _merge_block(m_lo, l_lo, a_lo, q_lo,
                                            kl, vl, **kw(False))
            m_hi, l_hi, a_hi = _merge_block(m_hi, l_hi, a_hi, q_hi,
                                            kl, vl, **kw(False))
            return m_lo, l_lo, a_lo, m_hi, l_hi, a_hi

        def future_src(op):
            # src > rank: its k_lo half is future for q_lo but past for
            # q_hi; its k_hi half (2sp-1-src < 2sp-1-rank) is past for
            # q_hi only.
            m_lo, l_lo, a_lo, m_hi, l_hi, a_hi = op
            m_hi, l_hi, a_hi = _merge_block(m_hi, l_hi, a_hi, q_hi,
                                            kl, vl, **kw(False))
            m_hi, l_hi, a_hi = _merge_block(m_hi, l_hi, a_hi, q_hi,
                                            kh, vh, **kw(False))
            return m_lo, l_lo, a_lo, m_hi, l_hi, a_hi

        # Both branches: exactly 2 half-block fulls — balanced.
        m_lo, l_lo, a_lo, m_hi, l_hi, a_hi = lax.cond(
            src < rank, past_src, future_src,
            (m_lo, l_lo, a_lo, m_hi, l_hi, a_hi))
        kl2 = lax.ppermute(kl, axis_name, perm)
        vl2 = lax.ppermute(vl, axis_name, perm)
        kh2 = lax.ppermute(kh, axis_name, perm)
        vh2 = lax.ppermute(vh, axis_name, perm)
        return (m_lo, l_lo, a_lo, m_hi, l_hi, a_hi,
                kl2, vl2, kh2, vh2), None

    if sp > 1:
        (m_lo, l_lo, a_lo, m_hi, l_hi, a_hi, *_), _ = lax.scan(
            step, (m_lo, l_lo, a_lo, m_hi, l_hi, a_hi, kl, vl, kh, vh),
            jnp.arange(1, sp))

    out_lo = _finish_softmax(m_lo, l_lo, a_lo, b, half, h, d, q.dtype)
    out_hi = _finish_softmax(m_hi, l_hi, a_hi, b, half, h, d, q.dtype)
    return _zigzag_undeal(out_lo, out_hi, axis_name, sp, rank)


def ring_schedule_cost(sp: int, rank: int, layout: str) -> float:
    """Static per-rank compute cost in full-block-pair units (an s x s
    score block = 1.0; a half-pair = 0.25; a half-diag = 0.125) — what
    the balance tests assert on."""
    if layout == 'contiguous':
        return 0.5 + rank                      # diag + `rank` past blocks
    # zigzag: diagonal tick = 2 half-diags + 1 half-full = 0.5; every
    # other step = 2 half-fulls = 0.5. Rank-independent == balanced,
    # and equal to the ideal total/sp (sp^2/2 work over sp ranks).
    del rank
    return 0.5 + (sp - 1) * 0.5


def seq_parallel_call(q, k, v, mesh, body, *, axis_name: str = 'sp',
                      rules=None, kv_head_modulus: Optional[int] = None):
    """Shared scaffolding for sequence-parallel attention variants
    (ring, ulysses): seq-divisibility check, GQA kv expansion when local
    kv heads wouldn't pair positionally with local q heads, spec_for +
    shard_map plumbing. ``kv_head_modulus`` is what the GLOBAL kv head
    count must divide by to stay in grouped form (tp for ring, tp*sp
    for ulysses); ``body(q, k, v)`` runs in the manual region."""
    from skypilot_tpu.parallel.mesh import spec_for
    sp = mesh.shape[axis_name]
    if q.shape[1] % sp:
        raise ValueError(
            f'sequence-parallel attention needs seq ({q.shape[1]}) '
            f'divisible by {axis_name}={sp}')
    # The manual bodies pair local q heads with local kv heads
    # positionally, so kv heads must shard exactly like q heads. For
    # MQA/GQA below the modulus, materialize the per-q-head kv (repeat)
    # instead of replicating — replicated kv with sharded q would
    # silently mis-pair GQA groups.
    tp = mesh.shape.get('tp', 1)
    modulus = kv_head_modulus if kv_head_modulus is not None else tp
    if k.shape[2] % modulus:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qspec = spec_for(('batch', 'seq', 'heads', 'head_dim'), rules)
    kspec = (qspec if k.shape[2] == q.shape[2] else
             spec_for(('batch', 'seq', 'kv_heads', 'head_dim'), rules))
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qspec, kspec, kspec),
        out_specs=qspec,
        check_vma=False,
    )
    return fn(q, k, v)


def ring_attention(
    q: jax.Array,                      # [b, S, h, d] global (sharded) arrays
    k: jax.Array,                      # [b, S, hkv, d]
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    axis_name: str = 'sp',
    rules=None,
    layout: str = 'auto',              # 'auto' | 'zigzag' | 'contiguous'
    block_impl: str = 'auto',          # 'auto' | 'flash' | 'einsum'
) -> jax.Array:
    """Exact attention with the sequence dimension sharded over
    ``axis_name``. Call inside (or outside) jit with a mesh whose
    ``axis_name`` size divides the sequence length.

    ``layout='zigzag'`` (auto default for causal, sp>1, even local
    halves) balances causal work across ranks; ``block_impl='flash'``
    (auto on 128-aligned shapes) runs each block pair through the
    Pallas kernel."""
    sp = mesh.shape[axis_name]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if sp == 1:
        from skypilot_tpu.ops.attention import reference_attention
        return reference_attention(q, k, v, causal=causal, scale=scale)
    s_local = q.shape[1] // sp
    if layout == 'auto':
        layout = ('zigzag' if causal and s_local % 2 == 0 else
                  'contiguous')
    if block_impl == 'auto':
        blk = s_local // 2 if layout == 'zigzag' else s_local
        block_impl = ('flash' if blk % 128 == 0
                      and q.shape[3] % 128 == 0 else 'einsum')
    use_flash = block_impl == 'flash'
    if layout == 'zigzag' and causal:
        body = functools.partial(_zigzag_body, axis_name=axis_name,
                                 axis_size=sp, scale=scale,
                                 use_flash=use_flash)
    else:
        body = functools.partial(_ring_body, axis_name=axis_name,
                                 axis_size=sp, causal=causal,
                                 scale=scale, use_flash=use_flash)
    return seq_parallel_call(q, k, v, mesh, body, axis_name=axis_name,
                             rules=rules)


def current_mesh():
    """The (abstract) mesh of the enclosing ``jax.set_mesh`` context, if
    any."""
    from skypilot_tpu.models.llama import _ambient_mesh
    return _ambient_mesh()
