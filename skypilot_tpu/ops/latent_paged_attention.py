"""Pallas paged decode attention over a latent (MLA) cache.

The absorbed decode form (``ops/latent_attention.py``) reads one shared
row a cached token under every query head: ``kv_lora_rank`` latent values
that are key AND value, and ``qk_rope_head_dim`` roped key values. The
XLA form gathers every slot's page bucket into ``[slots, P * page, ...]``
in every layer of every step, whatever the slots hold: 32 padded slots x
64 pages where two rows of ~4k tokens were live was two thirds of a
GLM-4.7-Flash decode step (``PERF.md``, PR 30). This kernel takes the two
stacked pools whole and DMAs each live slot's own pages from HBM,
length-exact; a slot of length 0 reads nothing. It is
``ops/paged_attention.py``'s ``_kernel_manual`` for the other cache
shape, kept apart from it on purpose: GQA contracts K and V rows of one
width under per-head groups with scales, MLA one shared key row whose
first ``kv_lora_rank`` values are also the value row.

One page's update: logits ``q_lat . c^T + q_rope . kr^T`` over the page
(operands in the pools' dtype into the MXU, float32 out), scaled, masked
by position, folded into a running max / sum / ``[heads, rank]``
accumulator; the weighted sum is ``p . c`` over the SAME latent page
already in VMEM, so one DMA serves keys and values.

The rope pool is stored ``128 / d_rope`` tokens to a 128-lane row
(``PagedKVCache.create``), and a DMA narrower than the lanes is not to be
had. The page is brought back to token order inside VMEM, exactly:
a one-hot ``[page, lane_rows]`` matmul hands token ``t`` its lane row
``t // per`` (each output is one stored value times 1.0), a lane mask
keeps the token's own ``d_rope`` lanes of it, and the rope queries arrive
tiled ``per`` times across the lanes, so ``q_tiled . rows^T`` contracts
each token with its own part only.

The kernel returns the cache partial ``(acc, m, l)``;
``merge_latent_partial_with_ring_self`` folds the fused horizon's ring
rows and the current token into the same softmax in XLA (tiny tensors),
as ``merge_partial_with_ring_self`` does for GQA rows.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops.latent_attention import _NEG, _latent_logits

# Cost-model annotation (analysis/costmodel.py), as in
# ``ops/paged_attention.py``, by the name the pallas_call carries: the
# kernel takes the layer-stacked pools with the layer as a
# scalar-prefetch index and reads one layer's pages.
KERNEL_NAME = 'latent_paged_decode'
COST_KERNEL_KV_TRAFFIC = {KERNEL_NAME: 'one_layer_per_call'}

_LANES = 128


def _latent_kernel(li_ref, table_ref, lens_ref,   # scalar prefetch
                   ql_ref, qr_ref,                # queries (VMEM blocks)
                   c_hbm, r_hbm,                  # the pools, whole, in HBM
                   acc_ref, m_ref, l_ref,         # outputs
                   cb, rb, sem,                   # scratch
                   *, page: int, per: int, scale: float):
    """Grid ``(slots,)``: the slot loops over its own ``ceil(length /
    page)`` pages with double-buffered async copies, page j + 1 on its
    way while page j computes. One page a loop iteration: 2, 4 and 8
    were 1-5 % slower on the chip (``PERF.md``, PR 31), as for
    ``_kernel_manual``; a page's update is bound by its matmuls' weight
    loads, not by the loop."""
    i = pl.program_id(0)
    li = li_ref[0]
    length = lens_ref[i]
    needed = (length + page - 1) // page
    hq, r = ql_ref.shape[1], ql_ref.shape[2]
    lane_rows, lanes = rb.shape[1], rb.shape[2]
    d_rope = lanes // per

    def page_dmas(buf, j):
        pid = table_ref[i, jnp.minimum(j, table_ref.shape[1] - 1)]
        return [pltpu.make_async_copy(c_hbm.at[li, pid, 0], cb.at[buf],
                                      sem.at[buf, 0]),
                pltpu.make_async_copy(r_hbm.at[li, pid, 0], rb.at[buf],
                                      sem.at[buf, 1])]

    @pl.when(needed > 0)
    def _prefetch_first():
        for dma in page_dmas(0, 0):
            dma.start()

    ql = ql_ref[0]                                    # [hq, r]
    qr = qr_ref[0]                                    # [hq, lanes], tiled
    if per > 1:
        # Token t of a page is lanes (t % per) * d_rope ... of lane row
        # t // per.
        dup = (jax.lax.broadcasted_iota(jnp.int32, (page, lane_rows), 1)
               == jax.lax.broadcasted_iota(
                   jnp.int32, (page, lane_rows), 0) // per
               ).astype(rb.dtype)
        own = (jax.lax.broadcasted_iota(jnp.int32, (page, lanes), 1)
               // d_rope
               == jax.lax.broadcasted_iota(jnp.int32, (page, lanes), 0)
               % per)

    def page_step(j, carry):
        acc, m_prev, l_prev = carry
        buf = j % 2

        @pl.when(j + 1 < needed)
        def _prefetch_next():
            for dma in page_dmas(1 - buf, j + 1):
                dma.start()

        for dma in page_dmas(buf, j):
            dma.wait()
        c = cb[buf]                                   # [page, r]
        kr = rb[buf]                                  # [lane_rows, lanes]
        if per > 1:
            rows = jnp.dot(dup, kr, preferred_element_type=jnp.float32)
            kr = jnp.where(own, rows, 0.0).astype(kr.dtype)
        # A.B^T: both operands contract their minor dim.
        logits = (jax.lax.dot_general(
            ql, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                qr, kr, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * scale  # [hq, page]
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (hq, page), 1)
        logits = jnp.where(pos < length, logits, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.where(pos < length, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.dot(p.astype(c.dtype), c,
                     preferred_element_type=jnp.float32)      # [hq, r]
        return acc * corr + pv, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        0, needed, page_step,
        (jnp.zeros((hq, r), jnp.float32),
         jnp.full((hq, 1), _NEG, jnp.float32),
         jnp.zeros((hq, 1), jnp.float32)))
    acc_ref[0] = acc
    m_ref[0] = jnp.broadcast_to(m, m_ref.shape[1:])
    l_ref[0] = jnp.broadcast_to(l, l_ref.shape[1:])


def latent_paged_decode_attention(
    q_lat: jax.Array,                  # [slots, hq, r] absorbed queries
    q_rope: jax.Array,                 # [slots, hq, d_rope]
    pool_c: jax.Array,                 # [L, n_pages, 1, page, r]
    pool_r: jax.Array,                 # [L, n_pages, 1, page * d_rope
                                       #  / 128, 128], or [.., page, d_rope]
    table_p: jax.Array,                # [slots, P] page ids
    lengths: jax.Array,                # [slots] cached rows; 0: read nothing
    *,
    layer: jax.Array | int = 0,
    scale: float,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial softmax of each slot's one query row against its OWN
    pages of pool layer ``layer``. Both pools are taken whole, the layer
    a scalar-prefetch index: a sliced or reshaped pool as the operand is
    a copy of it in every layer step (``PERF.md``, PRs 28 and 30). The
    page bucket ``P`` only sizes the table.

    Returns (acc [slots, hq, r] f32, unnormalised and rebased at m; m
    [slots, hq] f32; l [slots, hq] f32); a slot of length 0 returns
    (0, -1e30, 0), which the merge weighs at nothing."""
    slots, hq, r = q_lat.shape
    d_rope = q_rope.shape[-1]
    page = pool_c.shape[3]
    lane_rows, lanes = pool_r.shape[3:]
    per = lanes // d_rope
    assert lane_rows * per == page and per * d_rope == lanes, (
        pool_c.shape, pool_r.shape, d_rope)
    kernel = functools.partial(_latent_kernel, page=page, per=per,
                               scale=scale)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def slot_block(width):
        return pl.BlockSpec((1, hq, width),
                            lambda i, li, tab, lens: (i, 0, 0))

    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,               # layer, table, lengths
            grid=(slots,),
            in_specs=[slot_block(r), slot_block(lanes), any_spec,
                      any_spec],
            out_specs=[slot_block(r), slot_block(_LANES),
                       slot_block(_LANES)],
            scratch_shapes=[
                pltpu.VMEM((2, page, r), pool_c.dtype),
                pltpu.VMEM((2, lane_rows, lanes), pool_r.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, hq, r), jnp.float32),
            jax.ShapeDtypeStruct((slots, hq, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((slots, hq, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), table_p, lengths,
      q_lat.astype(pool_c.dtype),
      jnp.tile(q_rope, (1, 1, per)).astype(pool_r.dtype),
      pool_c, pool_r)
    return acc, m[..., 0], l[..., 0]


def merge_latent_partial_with_ring_self(
    partial: Tuple[jax.Array, jax.Array, jax.Array],
    q_lat: jax.Array,                  # [b, 1, hq, r]
    q_rope: jax.Array,                 # [b, 1, hq, d_rope]
    c_self: jax.Array,                 # [b, 1, r] the current token's rows
    kr_self: jax.Array,                # [b, 1, d_rope]
    ring_c: jax.Array,                 # [b, H, r] the horizon's earlier rows
    ring_kr: jax.Array,                # [b, H, d_rope]
    ring_len,                          # scalar: valid ring rows
    *,
    scale: float,
) -> jax.Array:
    """Complete the decode softmax: the kernel's cache partial, the
    fused horizon's ring rows and the current token under one softmax
    (``absorbed_ring_decode_attention``'s three blocks). Returns the
    output in latent space [b, 1, hq, r]."""
    acc_c, m_c, l_c = partial
    lr = _latent_logits(q_lat, q_rope, ring_c, ring_kr, scale)[:, :, 0]
    lself = _latent_logits(q_lat, q_rope, c_self, kr_self,
                           scale)[:, :, 0]                 # [b, hq, 1]
    lr = jnp.where(jnp.arange(ring_c.shape[1]) < ring_len, lr, _NEG)
    m_rs = jnp.maximum(jnp.max(lr, -1, keepdims=True), lself)
    p_r, p_s = jnp.exp(lr - m_rs), jnp.exp(lself - m_rs)
    l_rs = jnp.sum(p_r, -1, keepdims=True) + p_s
    acc_rs = (jnp.einsum('bhk,bkr->bhr', p_r.astype(ring_c.dtype), ring_c,
                         preferred_element_type=jnp.float32)
              + p_s.astype(c_self.dtype).astype(jnp.float32)
              * c_self.astype(jnp.float32))
    m_cg, l_cg = m_c[..., None], l_c[..., None]
    m = jnp.maximum(m_cg, m_rs)
    w_c, w_rs = jnp.exp(m_cg - m), jnp.exp(m_rs - m)
    out = (acc_c * w_c + acc_rs * w_rs) / jnp.maximum(
        l_cg * w_c + l_rs * w_rs, 1e-30)
    return out[:, None].astype(q_lat.dtype)
