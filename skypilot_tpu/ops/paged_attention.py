"""Pallas paged-attention decode kernel.

The gather-based paged decode (``inference/paged.py``) materializes a
contiguous copy of each slot's pages per layer — that copy is a full
extra read+write of the KV stream (on an earlier chip it ran at 0.37x a
contiguous cache's decode). This kernel is the vLLM/JetStream answer
built the TPU way (SURVEY §7 step 8 "paged KV in Pallas"): the page
table rides the grid as a SCALAR-PREFETCH operand, each grid step DMAs
one page of K/V straight from the pool in HBM into VMEM (no
intermediate copy), and a flash-style online softmax accumulates per
slot. Reads are LENGTH-EXACT per slot: a slot visits only
ceil(len/page) pages (the XLA gather path had to read the bucketed max
over all slots).

The pool stores pages HEAD-MAJOR: ``[L, n_pages, hkv, page, d]`` (and
scales ``[L, n_pages, hkv, page]``). Both attention contractions then
run straight off the DMA'd block — logits contract d (the minor dim of
q AND k, the MXU's native A.B^T form) and the p.v dot contracts page —
so the kernel performs NO in-kernel relayout. The previous token-major
``[page, hkv, d]`` layout needed k.transpose(1, 2, 0) / v.transpose(1,
0, 2) per page visit: a VPU lane-shuffle of every streamed byte that
capped the kernel well below a contiguous read (docs/perf.md, "The
page pool's layout"). Head-major costs the WRITE side a strided
row append ([hkv, 1, d] slices, 32 runs x 128 B) — decode writes one
row per slot per step vs reading hundreds, so the read side wins.

The kernel computes the CACHE part of decode attention and returns the
partial-softmax triple (acc, m, l); the caller merges the current
token + fused-horizon ring rows (tiny tensors) in XLA — one softmax
across all three blocks, exactly like ``ops.attention.
ring_decode_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Cost-model annotation (analysis/costmodel.py): these KERNEL BODIES
# (the names pallas_call eqns carry) take the FULL layer-stacked pool
# ([L, n_pages, ...]) with the layer as a scalar-prefetch block index
# and DMA one layer's pages per call — so the static analyzer prices
# their kv_pool/kv_scale operands at aval_bytes / L, not the whole
# stacked aval. ``_kernel_all`` (the all-layers sweep) is deliberately
# absent: it really does read every layer. A kernel that starts
# reading more than its layer must drop itself from this map (and eat
# the byte budget it then owes).
COST_KERNEL_KV_TRAFFIC = {
    '_kernel': 'one_layer_per_call',          # paged_decode_attention
    '_kernel_manual': 'one_layer_per_call',
    '_kernel_fused': 'one_layer_per_call',    # ..._fused (cross-layer)
}

_NEG_INF = -1e30


def _dequantize_unpack_int4(x):
    """In-kernel int4 unpack: uint8 nibble bytes -> sign-extended int8
    codes with the minor dim doubled (low nibble first — the exact
    inverse of ``quantization.pack_int4(axis=-1)``). VPU bit-ops the
    compiler folds into the operand read; the HBM/VMEM stream stays
    packed at head_dim/2 bytes per row."""
    lo = (x & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = (x >> 4).astype(jnp.int8)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(
        x.shape[:-1] + (x.shape[-1] * 2,))


def _flash_page_update(qg, k_raw, v_raw, ks, vs, pos0, length,
                       m_s, l_s, acc_s, *, page: int, quantized: bool,
                       packed: bool):
    """One page block's online-softmax update against the VMEM scratch
    triple (m_s, l_s, acc_s) — the body shared by the per-layer,
    all-layer and fused-merge grid kernels.

    qg: [hkv, g, d] f32 PRE-SCALED queries; k_raw/v_raw: the DMA'd
    head-major page block ([hkv, page, d]; packed int4 pools arrive as
    [hkv, page, d/2] uint8 nibbles and unpack HERE, so the HBM stream
    stays packed); ks/vs: [hkv, page] f32 scale rows or None; pos0:
    the block's first absolute cache position (for the length mask)."""
    if packed:
        k_raw = _dequantize_unpack_int4(k_raw)
        v_raw = _dequantize_unpack_int4(v_raw)
    k = k_raw.astype(jnp.float32)                     # [hkv, page, d]
    v = v_raw.astype(jnp.float32)
    hkv, g, d = qg.shape
    hq = hkv * g
    # logits[h, g, p] = sum_d q[h,g,d] * k[h,p,d]: batched (over
    # hkv) A.B^T dots, both operands contracting their MINOR dim —
    # the head-major page layout feeds the MXU with no relayout.
    # Quantized pools: the per-row scales ride HEAD-MAJOR [hkv, page]
    # blocks and fold into the LOGITS (and into p for the v side).
    logits = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)           # [hkv, g, page]
    if quantized:
        logits = logits * ks[:, None, :]
    logits = logits.reshape(hq, page)
    pos = pos0 + jax.lax.broadcasted_iota(
        jnp.int32, (hq, page), 1)
    logits = jnp.where(pos < length, logits, _NEG_INF)
    m_prev = m_s[:, :1]                               # [hq, 1]
    m_page = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_page)
    p = jnp.exp(logits - m_new)                       # [hq, page]
    p = jnp.where(pos < length, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                    # [hq, 1]
    l_s[:] = l_s[:] * corr + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_s.shape)
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    # pv[h,g,d] = sum_p p[h,g,p] * v[h,p,d]: batched over hkv.
    pg = p.reshape(hkv, g, page)
    if quantized:
        pg = pg * vs[:, None, :]
    pv = jax.lax.dot_general(
        pg, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)           # [hkv, g, d]
    acc_s[:] = acc_s[:] * corr + pv.reshape(hq, d)


def _kernel(li_ref, table_ref, lens_ref,         # scalar prefetch
            q_ref, k_ref, v_ref,                 # inputs (VMEM blocks)
            *refs,                               # [ks, vs,] outs, scratch
            page: int, pages_per_slot: int, scale: float,
            quantized: bool, packed: bool = False):
    # li_ref carries the layer index: the pool stays [L, ...] and the
    # block specs index straight into it, so the per-layer slice is a
    # DMA address, never a materialized copy (feeding
    # dynamic_index_in_dim output into pallas_call would copy the whole
    # layer's pool per step: over half a 7B's step, an earlier reading).
    # Quantized pools carry two extra scale operands; the bf16 variant
    # omits them entirely (a dummy scale pool would cost a real HBM DMA
    # per page on the decode hot path).
    del li_ref                                   # consumed by index maps
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    else:
        ks_ref = vs_ref = None
    acc_ref, m_ref, l_ref, m_s, l_s, acc_s = refs
    i = pl.program_id(0)                         # slot
    j = pl.program_id(1)                         # page index within slot

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    length = lens_ref[i]
    # Number of pages this slot actually needs. Pages past that are
    # compute-masked here AND their DMA collapses: the index maps clamp
    # j to the last needed page, and Pallas skips the copy when a grid
    # step's block index repeats the previous step's — so a
    # short-context slot in a long-bucket table pays no extra HBM
    # traffic.
    needed = (length + page - 1) // page

    @pl.when(j < needed)
    def _compute():
        # Layout note: all Refs/values stay >=2D with the LANE dim last
        # (Mosaic rejects trailing size-1 ref dims: "unsupported output
        # implicit dimension"); m/l ride [hq, LANES] broadcast columns,
        # the same trick the flash kernel's lse uses.
        q = q_ref[0].astype(jnp.float32) * scale          # [hq, d]
        hq, d = q.shape
        hkv = k_ref.shape[2]
        g = hq // hkv
        _flash_page_update(
            q.reshape(hkv, g, d), k_ref[0, 0], v_ref[0, 0],
            ks_ref[0, 0].astype(jnp.float32) if quantized else None,
            vs_ref[0, 0].astype(jnp.float32) if quantized else None,
            j * page, length, m_s, l_s, acc_s,
            page=page, quantized=quantized, packed=packed)

    @pl.when(j == pages_per_slot - 1)
    def _finish():
        acc_ref[0] = acc_s[:]
        m_ref[0] = m_s[:]
        l_ref[0] = l_s[:]


def _kernel_all(table_ref, lens_ref,             # scalar prefetch
                q_ref, k_ref, v_ref,             # inputs (VMEM blocks)
                *refs,                           # [ks, vs,] outs, scratch
                page: int, pages_per_slot: int, scale: float,
                quantized: bool, packed: bool = False):
    """All-layer variant of ``_kernel``: the layer axis rides the GRID
    (``(slots, L, pages)``) instead of scalar prefetch, so ONE
    pallas_call streams every layer's pages — the per-call dispatch
    and pipeline-warmup cost is paid once instead of L times per step.
    Queries for ALL layers must exist up front (stacked
    [L, slots, hq, d]); the decode layer chain cannot provide that
    (layer l's query depends on layer l-1's output), so the decode hot
    path keeps per-layer calls — this kernel serves the paths where
    the full query stack IS known: the kv_round2 bandwidth probe and
    any cross-layer scoring pass."""
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    else:
        ks_ref = vs_ref = None
    acc_ref, m_ref, l_ref, m_s, l_s, acc_s = refs
    i = pl.program_id(0)                         # slot
    j = pl.program_id(2)                         # page index within slot

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    length = lens_ref[i]
    needed = (length + page - 1) // page

    @pl.when(j < needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [hq, d]
        hq, d = q.shape
        hkv = k_ref.shape[2]
        g = hq // hkv
        _flash_page_update(
            q.reshape(hkv, g, d), k_ref[0, 0], v_ref[0, 0],
            ks_ref[0, 0].astype(jnp.float32) if quantized else None,
            vs_ref[0, 0].astype(jnp.float32) if quantized else None,
            j * page, length, m_s, l_s, acc_s,
            page=page, quantized=quantized, packed=packed)

    @pl.when(j == pages_per_slot - 1)
    def _finish():
        acc_ref[0, 0] = acc_s[:]
        m_ref[0, 0] = m_s[:]
        l_ref[0, 0] = l_s[:]


def _kernel_fused(li_ref, rl_ref, table_ref, lens_ref,  # scalar prefetch
                  q_ref, ksf_ref, vsf_ref, rk_ref, rv_ref,
                  k_ref, v_ref,
                  *refs,                         # [ks, vs,] out, scratch
                  page: int, pages_per_slot: int, scale: float,
                  quantized: bool, packed: bool = False):
    """Fused-merge variant of ``_kernel``: after the cache pages, the
    final grid step folds the fused-horizon ring rows and the current
    token into the SAME online softmax and emits the normalized
    per-layer attention output directly — the separate XLA
    ``merge_partial_with_ring_self`` program (and its [b, hq, d] f32
    partial triple round-tripping through HBM every layer of every
    decode step) disappears. The merge replicates the XLA three-block
    softmax op-for-op, so greedy decode stays byte-identical."""
    del li_ref                                   # consumed by index maps
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    else:
        ks_ref = vs_ref = None
    out_ref, m_s, l_s, acc_s = refs
    i = pl.program_id(0)                         # slot
    j = pl.program_id(1)                         # page index within slot

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    length = lens_ref[i]
    needed = (length + page - 1) // page

    @pl.when(j < needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [hq, d]
        hq, d = q.shape
        hkv = k_ref.shape[2]
        g = hq // hkv
        _flash_page_update(
            q.reshape(hkv, g, d), k_ref[0, 0], v_ref[0, 0],
            ks_ref[0, 0].astype(jnp.float32) if quantized else None,
            vs_ref[0, 0].astype(jnp.float32) if quantized else None,
            j * page, length, m_s, l_s, acc_s,
            page=page, quantized=quantized, packed=packed)

    @pl.when(j == pages_per_slot - 1)
    def _finish():
        # Ring + self merge: the exact op sequence of
        # ``merge_partial_with_ring_self`` on this slot's row, with the
        # kernel scratch standing in for the cache partial.
        q = q_ref[0].astype(jnp.float32) * scale          # [hq, d]
        hq, d = q.shape
        hkv = rk_ref.shape[2]
        g = hq // hkv
        qg = q.reshape(hkv, g, d)
        rk = rk_ref[0].astype(jnp.float32)                # [H, hkv, d]
        rv = rv_ref[0].astype(jnp.float32)
        H = rk.shape[0]
        ring_len = rl_ref[0]
        # lr[h, g, kk] = sum_d qg[h,g,d] * rk[kk,h,d]
        lr = jax.lax.dot_general(
            qg, rk, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)           # [hkv, g, H]
        ridx = jax.lax.broadcasted_iota(jnp.int32, (hkv, g, H), 2)
        lr = jnp.where(ridx < ring_len, lr, _NEG_INF)
        ksf = ksf_ref[0].astype(jnp.float32)              # [hkv, d]
        vsf = vsf_ref[0].astype(jnp.float32)
        lself = jnp.sum(qg * ksf[:, None, :], axis=-1,
                        keepdims=True)                    # [hkv, g, 1]
        m_rs = jnp.maximum(jnp.max(lr, -1, keepdims=True), lself)
        p_r = jnp.exp(lr - m_rs)
        p_s = jnp.exp(lself - m_rs)
        l_rs = jnp.sum(p_r, -1, keepdims=True) + p_s
        # acc_rs[h,g,d] = sum_kk p_r[h,g,kk] * rv[kk,h,d] + p_s * v_self
        acc_rs = jax.lax.dot_general(
            p_r, rv, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) \
            + p_s * vsf[:, None, :]                       # [hkv, g, d]
        m_cg = m_s[:, :1].reshape(hkv, g, 1)
        l_cg = l_s[:, :1].reshape(hkv, g, 1)
        acc_cg = acc_s[:].reshape(hkv, g, d)
        m = jnp.maximum(m_cg, m_rs)
        c_c = jnp.exp(m_cg - m)
        c_rs = jnp.exp(m_rs - m)
        l = l_cg * c_c + l_rs * c_rs
        acc = acc_cg * c_c + acc_rs * c_rs
        out = acc / jnp.maximum(l, 1e-30)                 # [hkv, g, d]
        out_ref[0] = out.reshape(hq, d).astype(out_ref.dtype)


def _kernel_manual(li_ref, table_ref, lens_ref,   # scalar prefetch
                   q_ref, k_hbm, v_hbm,           # q VMEM; pools in HBM
                   *refs,
                   page: int, pages_per_block: int, scale: float,
                   quantized: bool):
    """Manual-DMA variant: grid is (slots,) and the kernel loops over
    the slot's pages itself with double-buffered async copies — block
    j+1 streams from HBM while block j computes. This beats the
    grid-per-page formulation (which pays per-grid-step pipeline
    overhead on hundreds of tiny steps per layer: 0.71x a contiguous
    cache's decode on a 7B, an earlier reading) and reads length-exact.

    ``pages_per_block`` (K) pages are fetched per loop iteration into
    per-page VMEM buffers (async copies issued back-to-back, one wait
    each); the final block's tail pages SKIP their DMA entirely
    (conditional issue + wait on the same predicate), so reads are
    length-exact at page granularity for any K. With the head-major
    pool every DMA (data AND scales) lands contiguously in its [kk]
    buffer and the flash update runs per page (unrolled online-softmax
    updates — exp over [hq, page] is VPU noise next to the stream).
    Measured on the 7B int8 decode at batch 48, K=1 beats K=2/4/8 by
    4-10% (1790 vs 1724/1625/1620 tok/s/chip): with no in-loop
    relayout to hide, per-iteration overhead is small and the K>1
    double-buffer granularity only delays the first compute."""
    if quantized:
        ks_hbm, vs_hbm = refs[0], refs[1]
        refs = refs[2:]
    else:
        ks_hbm = vs_hbm = None
    acc_ref, m_ref, l_ref = refs[:3]
    scratch = refs[3:]
    if quantized:
        kb, vb, ksb, vsb, sem = scratch
    else:
        kb, vb, sem = scratch
        ksb = vsb = None
    i = pl.program_id(0)
    li = li_ref[0]
    length = lens_ref[i]
    K = pages_per_block
    blk = K * page
    P = table_ref.shape[1]
    needed = (length + blk - 1) // blk            # K-page blocks
    hq, d = q_ref.shape[1], q_ref.shape[2]
    hkv = kb.shape[2]
    g = hq // hkv

    # Pages the slot actually holds: the final K-block's tail pages
    # (j*K + kk >= needed_pages) are SKIPPED, not clamped — their DMA
    # never issues and the compute mask zeroes their positions, so
    # reads are length-exact at page granularity instead of rounding
    # up to K*page per slot (at K=4/page=128 the rounding cost ~25%
    # extra KV stream on ~380-token average contexts).
    needed_pages = (length + page - 1) // page

    def dma_ops(buf, j, kk):
        pid = table_ref[i, jnp.minimum(j * K + kk, P - 1)]
        s0, s1 = 2 * kk, 2 * kk + 1
        out = [pltpu.make_async_copy(
                   k_hbm.at[li, pid],
                   kb.at[buf, kk],
                   sem.at[buf, s0]),
               pltpu.make_async_copy(
                   v_hbm.at[li, pid],
                   vb.at[buf, kk],
                   sem.at[buf, s1])]
        if quantized:
            out += [pltpu.make_async_copy(
                        ks_hbm.at[li, pid],
                        ksb.at[buf, kk],
                        sem.at[buf, 2 * K + s0]),
                    pltpu.make_async_copy(
                        vs_hbm.at[li, pid],
                        vsb.at[buf, kk],
                        sem.at[buf, 2 * K + s1])]
        return out

    def start_dmas(buf, j):
        for kk in range(K):
            if K == 1:
                # j*K+kk < needed_pages is the fori_loop bound itself:
                # no predicate, no skip machinery on the hot path.
                for dma in dma_ops(buf, j, kk):
                    dma.start()
                continue

            @pl.when(j * K + kk < needed_pages)
            def _go(buf=buf, j=j, kk=kk):
                for dma in dma_ops(buf, j, kk):
                    dma.start()

    def wait_dmas(buf, j):
        for kk in range(K):
            if K == 1:
                for dma in dma_ops(buf, j, kk):
                    dma.wait()
                continue

            @pl.when(j * K + kk < needed_pages)
            def _wait(buf=buf, j=j, kk=kk):
                for dma in dma_ops(buf, j, kk):
                    dma.wait()

    if K > 1:
        @pl.when(i == 0)
        def _zero_scratch():
            # Skipped tail pages never DMA; their buffers are read
            # (then compute-masked) anyway. Stale FINITE data from
            # earlier slots is harmless (p is zeroed at masked
            # positions before the v dot), but UNINITIALIZED f32/bf16
            # scratch can be NaN and 0 * NaN = NaN would poison acc —
            # so zero everything once. (At K=1 every executed
            # iteration DMAs its page: nothing stale is ever read.)
            kb[...] = jnp.zeros_like(kb)
            vb[...] = jnp.zeros_like(vb)
            if quantized:
                ksb[...] = jnp.zeros_like(ksb)
                vsb[...] = jnp.zeros_like(vsb)

    @pl.when(needed > 0)
    def _prefetch_first():
        start_dmas(0, 0)

    q = q_ref[0].astype(jnp.float32) * scale              # [hq, d]
    qg = q.reshape(hkv, g, d)

    def page_step(j, carry):
        carry_in = carry
        buf = j % 2

        @pl.when(j + 1 < needed)
        def _prefetch_next():
            start_dmas((j + 1) % 2, j + 1)

        wait_dmas(buf, j)
        acc, m_prev, l_prev = carry_in
        for kk in range(K):                       # unrolled: static K
            k = kb[buf, kk].astype(jnp.float32)           # [hkv, page, d]
            v = vb[buf, kk].astype(jnp.float32)
            # Batched A.B^T: both operands contract their minor dim
            # straight off the DMA'd head-major block — no relayout.
            logits = jax.lax.dot_general(
                qg, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)       # [hkv, g, page]
            if quantized:
                # head-major [hkv, page] scale blocks fold into the
                # logits (k side) and p (v side).
                logits = logits * ksb[buf, kk].astype(
                    jnp.float32)[:, None, :]
            logits = logits.reshape(hq, page)
            pos = (j * K + kk) * page + jax.lax.broadcasted_iota(
                jnp.int32, (hq, page), 1)
            logits = jnp.where(pos < length, logits, _NEG_INF)
            m_page = jnp.max(logits, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_page)
            p = jnp.exp(logits - m_new)
            p = jnp.where(pos < length, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_prev = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            m_prev = m_new
            pg = p.reshape(hkv, g, page)
            if quantized:
                pg = pg * vsb[buf, kk].astype(jnp.float32)[:, None, :]
            pv = jax.lax.dot_general(
                pg, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)       # [hkv, g, d]
            acc = acc * corr + pv.reshape(hq, d)
        return acc, m_prev, l_prev

    acc0 = jnp.zeros((hq, d), jnp.float32)
    m0 = jnp.full((hq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((hq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, needed, page_step, (acc0, m0, l0))
    acc_ref[0] = acc
    m_ref[0] = jnp.broadcast_to(m, m_ref.shape[1:])
    l_ref[0] = jnp.broadcast_to(l, l_ref.shape[1:])


def paged_decode_attention(
    q: jax.Array,                      # [slots, hq, d] current-token queries
    pool_k: jax.Array,                 # [L, n_pages, hkv, page, d]
    pool_v: jax.Array,
    table_p: jax.Array,                # [slots, P] page ids
    lengths: jax.Array,                # [slots] valid cache rows
    k_scale: Optional[jax.Array] = None,  # [L, n_pages, hkv, page]
    v_scale: Optional[jax.Array] = None,  # (HEAD-MAJOR; see caller)
    *,
    layer: jax.Array | int = 0,        # which pool layer to attend over
    scale: Optional[float] = None,
    interpret: bool = False,
    pages_per_block: int = 1,          # K pages DMA'd/computed per loop
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial softmax of each slot's query against its OWN pages of
    pool layer ``layer``. The full stacked pool is taken (with the
    layer as a scalar-prefetch index into the block specs) so the
    caller's per-layer scan never materializes a pool slice — a sliced
    operand would cost a whole extra read+write of the KV stream per
    decode step.

    Returns (acc [slots, hq, d] f32 — UNnormalized, rebased at m;
    m [slots, hq] f32; l [slots, hq] f32). Rows past ``lengths`` are
    masked; slots with length 0 return (0, -inf, 0) — merging is a
    no-op for them.
    """
    slots, hq, d = q.shape
    _, n_pages, hkv, page, dc = pool_k.shape
    P = table_p.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    quantized = k_scale is not None
    # Packed int4 pools: uint8 nibble rows, dc == d/2 — the grid
    # kernel unpacks in VMEM (the HBM stream stays packed). The manual
    # path is excluded: its per-page DMA buffers would need a 64-lane
    # minor dim, below Mosaic's 128-lane tile.
    packed = pool_k.dtype == jnp.uint8

    LANES = 128
    li = jnp.asarray(layer, jnp.int32).reshape(1)
    out_shape_m = [
        jax.ShapeDtypeStruct((slots, hq, d), jnp.float32),
        jax.ShapeDtypeStruct((slots, hq, LANES), jnp.float32),
        jax.ShapeDtypeStruct((slots, hq, LANES), jnp.float32),
    ]
    # Manual path constraint: the per-page scale DMA slices a
    # [hkv, page] block whose minor dim (page) must be 128-aligned for
    # Mosaic — int8 pools need page % 128 == 0 (the engine's default
    # page is 128 for exactly this reason); bf16 pools have no scale
    # operand and run at any page size.
    if not interpret and not packed \
            and (k_scale is None or page % 128 == 0):
        # Compiled path: manual double-buffered K-page block DMA, one
        # grid step per slot (the per-page grid pays pipeline overhead
        # on hundreds of tiny steps; interpret mode has no DMA
        # emulation guarantee, so CPU tests ride the grid variant
        # below).
        # Clamp K so the double-buffered K/V blocks stay within ~16MB
        # of VMEM regardless of page size (page=256 at K=4 would need
        # 67MB of buffers alone and fail Mosaic's scoped-vmem checks).
        page_buf_bytes = 4 * page * hkv * d * pool_k.dtype.itemsize
        K = max(1, min(pages_per_block, P,
                       (16 * 1024 * 1024) // page_buf_bytes))
        kernel = functools.partial(_kernel_manual, page=page,
                                   pages_per_block=K, scale=scale,
                                   quantized=quantized)
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [
            pl.BlockSpec((1, hq, d),
                         lambda i, li, tab, lens: (i, 0, 0)),
            any_spec, any_spec,
        ]
        args = [li, table_p, lengths, q, pool_k, pool_v]
        n_sems = 2 * K
        scratch = [
            pltpu.VMEM((2, K, hkv, page, d), pool_k.dtype),
            pltpu.VMEM((2, K, hkv, page, d), pool_v.dtype),
        ]
        if quantized:
            in_specs += [any_spec, any_spec]
            args += [k_scale, v_scale]
            scratch += [pltpu.VMEM((2, K, hkv, page), jnp.float32),
                        pltpu.VMEM((2, K, hkv, page), jnp.float32)]
            n_sems = 4 * K
        scratch.append(pltpu.SemaphoreType.DMA((2, n_sems)))
        acc, m, l = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,           # layer, table, lengths
                grid=(slots,),
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec((1, hq, d),
                                 lambda i, li, tab, lens: (i, 0, 0)),
                    pl.BlockSpec((1, hq, LANES),
                                 lambda i, li, tab, lens: (i, 0, 0)),
                    pl.BlockSpec((1, hq, LANES),
                                 lambda i, li, tab, lens: (i, 0, 0)),
                ],
                scratch_shapes=scratch,
            ),
            out_shape=out_shape_m,
            # MHA shapes (hq=32, d=128, K-page blocks) put outputs +
            # double buffers a few MB past Mosaic's default 16M scoped
            # vmem; the v5e has 128M physical VMEM.
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=48 * 1024 * 1024),
        )(*args)
        return acc, m[..., 0], l[..., 0]

    grid = (slots, P)
    kernel = functools.partial(_kernel, page=page, pages_per_slot=P,
                               scale=scale, quantized=quantized,
                               packed=packed)
    out_shape = out_shape_m

    def page_idx(i, j, lens):
        # Clamp past-needed steps to the last needed page: a repeated
        # block index skips the DMA (see kernel note).
        needed = (lens[i] + page - 1) // page
        return jnp.minimum(j, jnp.maximum(needed - 1, 0))

    in_specs = [
        pl.BlockSpec((1, hq, d), lambda i, j, li, tab, lens: (i, 0, 0)),
        pl.BlockSpec((1, 1, hkv, page, dc), lambda i, j, li, tab, lens:
                     (li[0], tab[i, page_idx(i, j, lens)], 0, 0, 0)),
        pl.BlockSpec((1, 1, hkv, page, dc), lambda i, j, li, tab, lens:
                     (li[0], tab[i, page_idx(i, j, lens)], 0, 0, 0)),
    ]
    args = [li, table_p, lengths, q, pool_k, pool_v]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, hkv, page),
                         lambda i, j, li, tab, lens:
                         (li[0], tab[i, page_idx(i, j, lens)], 0, 0)),
            pl.BlockSpec((1, 1, hkv, page),
                         lambda i, j, li, tab, lens:
                         (li[0], tab[i, page_idx(i, j, lens)], 0, 0)),
        ]
        args += [k_scale, v_scale]
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,               # layer, table, lengths
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, hq, d), lambda i, j, li, tab, lens:
                             (i, 0, 0)),
                pl.BlockSpec((1, hq, LANES), lambda i, j, li, tab, lens:
                             (i, 0, 0)),
                pl.BlockSpec((1, hq, LANES), lambda i, j, li, tab, lens:
                             (i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((hq, LANES), jnp.float32),
                pltpu.VMEM((hq, LANES), jnp.float32),
                pltpu.VMEM((hq, d), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    return acc, m[..., 0], l[..., 0]


def paged_decode_attention_all_layers(
    q: jax.Array,                      # [L, slots, hq, d] stacked queries
    pool_k: jax.Array,                 # [L, n_pages, hkv, page, d]
    pool_v: jax.Array,
    table_p: jax.Array,                # [slots, P] page ids
    lengths: jax.Array,                # [slots] valid cache rows
    k_scale: Optional[jax.Array] = None,  # [L, n_pages, hkv, page]
    v_scale: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ALL layers' cache partials in ONE pallas_call: the layer axis
    rides the grid (``(slots, L, P)``) so per-call dispatch and
    pipeline warmup are paid once per step instead of once per layer
    — the cross-layer batching front (a) of the KV round. Requires
    the full query stack up front, so the decode layer chain (where
    layer l's query depends on layer l-1) cannot use it; callers with
    all queries in hand (the kv_round2 bandwidth probe, cross-layer
    scoring) get L-for-1 dispatch amortization. Byte-identical to L
    stacked :func:`paged_decode_attention` calls.

    Returns (acc [L, slots, hq, d] f32 unnormalized, m, l
    [L, slots, hq] f32)."""
    L, slots, hq, d = q.shape
    _, n_pages, hkv, page, dc = pool_k.shape
    P = table_p.shape[1]
    if scale is None:
        scale = d ** -0.5
    quantized = k_scale is not None
    packed = pool_k.dtype == jnp.uint8
    LANES = 128

    kernel = functools.partial(_kernel_all, page=page, pages_per_slot=P,
                               scale=scale, quantized=quantized,
                               packed=packed)

    def page_idx(i, j, lens):
        needed = (lens[i] + page - 1) // page
        return jnp.minimum(j, jnp.maximum(needed - 1, 0))

    in_specs = [
        pl.BlockSpec((1, 1, hq, d),
                     lambda i, l, j, tab, lens: (l, i, 0, 0)),
        pl.BlockSpec((1, 1, hkv, page, dc),
                     lambda i, l, j, tab, lens:
                     (l, tab[i, page_idx(i, j, lens)], 0, 0, 0)),
        pl.BlockSpec((1, 1, hkv, page, dc),
                     lambda i, l, j, tab, lens:
                     (l, tab[i, page_idx(i, j, lens)], 0, 0, 0)),
    ]
    args = [table_p, lengths, q, pool_k, pool_v]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, hkv, page),
                         lambda i, l, j, tab, lens:
                         (l, tab[i, page_idx(i, j, lens)], 0, 0)),
            pl.BlockSpec((1, 1, hkv, page),
                         lambda i, l, j, tab, lens:
                         (l, tab[i, page_idx(i, j, lens)], 0, 0)),
        ]
        args += [k_scale, v_scale]
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,               # table, lengths
            grid=(slots, L, P),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, hq, d),
                             lambda i, l, j, tab, lens: (l, i, 0, 0)),
                pl.BlockSpec((1, 1, hq, LANES),
                             lambda i, l, j, tab, lens: (l, i, 0, 0)),
                pl.BlockSpec((1, 1, hq, LANES),
                             lambda i, l, j, tab, lens: (l, i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((hq, LANES), jnp.float32),
                pltpu.VMEM((hq, LANES), jnp.float32),
                pltpu.VMEM((hq, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((L, slots, hq, d), jnp.float32),
            jax.ShapeDtypeStruct((L, slots, hq, LANES), jnp.float32),
            jax.ShapeDtypeStruct((L, slots, hq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(*args)
    return acc, m[..., 0], l[..., 0]


def paged_decode_attention_fused(
    q: jax.Array,                      # [slots, hq, d] current-token queries
    k_self: jax.Array,                 # [slots, hkv, d] current-token rows
    v_self: jax.Array,
    ring_k: jax.Array,                 # [slots, H, hkv, d] fused-horizon ring
    ring_v: jax.Array,
    ring_len,                          # scalar: valid ring rows
    pool_k: jax.Array,                 # [L, n_pages, hkv, page, d]
    pool_v: jax.Array,
    table_p: jax.Array,                # [slots, P] page ids
    lengths: jax.Array,                # [slots] valid cache rows
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    *,
    layer: jax.Array | int = 0,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """The complete decode attention for one layer in ONE kernel:
    cache pages (online softmax, length-exact) THEN the ring + current
    token folded into the same accumulator on the final grid step —
    the normalized [slots, hq, d] output comes back in q's dtype and
    the XLA merge program (``merge_partial_with_ring_self``) plus its
    HBM round-trip of the f32 partial triple disappears from the layer
    scan. This is ``decode_impl='cross_layer'``'s kernel."""
    slots, hq, d = q.shape
    _, n_pages, hkv, page, dc = pool_k.shape
    P = table_p.shape[1]
    H = ring_k.shape[1]
    if scale is None:
        scale = d ** -0.5
    quantized = k_scale is not None
    packed = pool_k.dtype == jnp.uint8
    LANES = 128
    li = jnp.asarray(layer, jnp.int32).reshape(1)
    rl = jnp.asarray(ring_len, jnp.int32).reshape(1)

    kernel = functools.partial(_kernel_fused, page=page,
                               pages_per_slot=P, scale=scale,
                               quantized=quantized, packed=packed)

    def page_idx(i, j, lens):
        needed = (lens[i] + page - 1) // page
        return jnp.minimum(j, jnp.maximum(needed - 1, 0))

    in_specs = [
        pl.BlockSpec((1, hq, d),
                     lambda i, j, li, rl, tab, lens: (i, 0, 0)),
        pl.BlockSpec((1, hkv, d),
                     lambda i, j, li, rl, tab, lens: (i, 0, 0)),
        pl.BlockSpec((1, hkv, d),
                     lambda i, j, li, rl, tab, lens: (i, 0, 0)),
        pl.BlockSpec((1, H, hkv, d),
                     lambda i, j, li, rl, tab, lens: (i, 0, 0, 0)),
        pl.BlockSpec((1, H, hkv, d),
                     lambda i, j, li, rl, tab, lens: (i, 0, 0, 0)),
        pl.BlockSpec((1, 1, hkv, page, dc),
                     lambda i, j, li, rl, tab, lens:
                     (li[0], tab[i, page_idx(i, j, lens)], 0, 0, 0)),
        pl.BlockSpec((1, 1, hkv, page, dc),
                     lambda i, j, li, rl, tab, lens:
                     (li[0], tab[i, page_idx(i, j, lens)], 0, 0, 0)),
    ]
    args = [li, rl, table_p, lengths, q, k_self, v_self,
            ring_k, ring_v, pool_k, pool_v]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, hkv, page),
                         lambda i, j, li, rl, tab, lens:
                         (li[0], tab[i, page_idx(i, j, lens)], 0, 0)),
            pl.BlockSpec((1, 1, hkv, page),
                         lambda i, j, li, rl, tab, lens:
                         (li[0], tab[i, page_idx(i, j, lens)], 0, 0)),
        ]
        args += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,          # layer, ring_len, table, lens
            grid=(slots, P),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, hq, d),
                             lambda i, j, li, rl, tab, lens: (i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((hq, LANES), jnp.float32),
                pltpu.VMEM((hq, LANES), jnp.float32),
                pltpu.VMEM((hq, d), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((slots, hq, d), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(*args)[0]
    return out


def merge_partial_with_ring_self(
    partial: Tuple[jax.Array, jax.Array, jax.Array],
    q: jax.Array,                      # [b, 1, hq, d]
    k_self: jax.Array,                 # [b, 1, hkv, d]
    v_self: jax.Array,
    ring_k: jax.Array,                 # [b, H, hkv, d]
    ring_v: jax.Array,
    ring_len,                          # scalar: valid ring rows
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Complete the decode softmax: merge the kernel's cache partial
    with the fused-horizon ring rows and the current token (tiny
    tensors — plain XLA). Mirrors ``ring_decode_attention``'s
    three-block softmax; returns [b, 1, hq, d]."""
    acc_c, m_c, l_c = partial
    b, _, hq, d = q.shape
    hkv = k_self.shape[2]
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    qg = (q.astype(jnp.float32) * scale).reshape(b, hkv, g, d)

    lr = jnp.einsum('bhgd,bkhd->bhgk', qg,
                    ring_k.astype(jnp.float32))            # [b,hkv,g,H]
    H = ring_k.shape[1]
    ridx = jnp.arange(H)[None, None, None, :]
    lr = jnp.where(ridx < ring_len, lr, _NEG_INF)
    lself = jnp.einsum('bhgd,bhd->bhg', qg,
                       k_self[:, 0].astype(jnp.float32))[..., None]

    m_rs = jnp.maximum(jnp.max(lr, -1, keepdims=True), lself)
    p_r = jnp.exp(lr - m_rs)
    p_s = jnp.exp(lself - m_rs)
    l_rs = jnp.sum(p_r, -1, keepdims=True) + p_s
    acc_rs = (jnp.einsum('bhgk,bkhd->bhgd', p_r,
                         ring_v.astype(jnp.float32))
              + p_s * v_self[:, 0].astype(jnp.float32)[:, :, None, :])

    m_cg = m_c.reshape(b, hkv, g)[..., None]
    l_cg = l_c.reshape(b, hkv, g)[..., None]
    acc_cg = acc_c.reshape(b, hkv, g, d)

    m = jnp.maximum(m_cg, m_rs)
    c_c = jnp.exp(m_cg - m)
    c_rs = jnp.exp(m_rs - m)
    l = l_cg * c_c + l_rs * c_rs
    acc = acc_cg * c_c + acc_rs * c_rs
    out = acc / jnp.maximum(l, 1e-30)          # [b, hkv, g, d]
    return out.reshape(b, 1, hq, d).astype(q.dtype)
