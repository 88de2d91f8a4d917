"""Paged KV cache with prefix caching and chunked prefill.

The one serving cache (a contiguous cache would reserve ``max_seq`` rows
per slot and re-prefill shared prefixes). This is the vLLM-class
capability (the reference's serving recipes lean on vLLM's paged
attention, ``llm/vllm/README.md:10``) for XLA's static-shape world:

- **Page pool**: one ``[L, n_pages, hkv, page, d]`` tensor shared by all
  slots; a slot holds a host-side list of page ids. HBM is proportional
  to LIVE tokens (rounded to pages), not slots × max_seq — longer
  contexts / more slots fit the same chip. Pages are HEAD-MAJOR so the
  Pallas decode kernel contracts straight off each DMA'd page with no
  in-kernel relayout (see ``ops/paged_attention.py``'s layout note).
- **Static shapes everywhere**: decode gathers each slot's first ``P``
  pages where ``P`` is a power-of-two bucket of the live maximum,
  which bounds the count of compiled programs.
  Unused table entries point at page 0, a reserved null/trash page.
- **Prefix caching**: full pages are content-addressed by the hash of
  the token prefix they complete; a new request reuses the longest
  cached chain (no recompute, no duplicate storage — TTFT win for
  shared system prompts). Freed registered pages retire into an LRU
  that allocation evicts last.
- **Chunked prefill**: prompts prefill in fixed ``chunk`` slices against
  the pages written so far — one compiled program regardless of prompt
  length, bounded scratch memory (long-prompt serving).

int8 (``kv_cache_dtype='int8'``, its own knob — decoupled from the
weight quantize mode, which it follows only when left on auto): the
pool quantizes each row by its own absmax (``k_scale``
[L, n_pages, hkv, page] fp32, head-major like the pool — the kernel
DMAs scale pages contiguously and the old per-horizon-call relayout
of the whole scale pool is gone). Every capacity decision — auto pool
sizing, preemption pressure, prefill stack caps, telemetry — costs
tokens at the QUANTIZED per-token byte width, so int8 KV ~doubles pool
token capacity as well as halving the decode KV stream.
"""
from __future__ import annotations

import functools
import contextlib
import hashlib
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from skypilot_tpu.models import llama
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.ops.attention import cached_attention, ring_decode_attention
from skypilot_tpu.telemetry import clock
from skypilot_tpu.utils.host import device_upload, host_sync

Params = Dict[str, Any]


_LANES = 128


class PagedKVCache(NamedTuple):
    """Device state. Page 0 is reserved (null/trash target for masked
    writes); the allocator never hands it out. Per-slot lengths are
    HOST state (the engine controls every admit/advance), passed as a
    small per-call argument — no device length bookkeeping."""
    pool_k: jax.Array                      # [L, n_pages, hkv, page, d],
                                           # L = cfg.n_cache_layers
    pool_v: jax.Array
    k_scale: Optional[jax.Array] = None    # [L, n_pages, hkv, page]
    v_scale: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def packed(self) -> bool:
        """int4 pools: two nibble codes per byte along head_dim
        (uint8, head_dim/2); scales ride the int8 layout."""
        return self.pool_k.dtype == jnp.uint8

    @property
    def quant_mode(self):
        """False | True (int8) | 'int4' — the mode every write-side
        quantizer keys on (``_maybe_quantize_rows``)."""
        if self.packed:
            return 'int4'
        return self.quantized

    @property
    def page_size(self) -> int:
        return self.pool_k.shape[3]

    @property
    def n_pages(self) -> int:
        return self.pool_k.shape[1]

    @classmethod
    def create(cls, cfg: ModelConfig, *, n_pages: int,
               page_size: int = 128, quantized: bool = False,
               kv_dtype: Optional[str] = None) -> 'PagedKVCache':
        if kv_dtype is None:
            kv_dtype = 'int8' if quantized else 'bf16'
        spec = cfg.kv_spec
        shape = (cfg.n_cache_layers, n_pages, spec.heads, page_size,
                 spec.k_dim)
        if spec.v_dim != spec.k_dim:
            # A latent cache: the normed latent rows in the K pool, the
            # roped key part in the V pool; bf16 only (refuse_unsupported).
            # Rope rows narrower than the 128 lanes lie several tokens to
            # a lane row ([page * v_dim / 128, 128]: the same bytes in the
            # same order). A [page, 64] page makes the row scatter relay
            # the whole pool (a 341 MB copy in every chunk and ring merge,
            # 16 s more to compile each; compiler, PR 30).
            v_shape = shape[:-1] + (spec.v_dim,)
            if _LANES % spec.v_dim == 0 and \
                    (page_size * spec.v_dim) % _LANES == 0:
                v_shape = shape[:-2] + (page_size * spec.v_dim // _LANES,
                                        _LANES)
            return cls(pool_k=jnp.zeros(shape, cfg.dtype),
                       pool_v=jnp.zeros(v_shape, cfg.dtype))
        if kv_dtype == 'int4':
            if cfg.head_dim % 2:
                raise ValueError('int4 KV needs an even head_dim')
            pshape = shape[:-1] + (cfg.head_dim // 2,)
            sshape = shape[:-1]
            return cls(pool_k=jnp.zeros(pshape, jnp.uint8),
                       pool_v=jnp.zeros(pshape, jnp.uint8),
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))
        if kv_dtype == 'int8' or quantized:
            sshape = shape[:-1]
            return cls(pool_k=jnp.zeros(shape, jnp.int8),
                       pool_v=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))
        return cls(pool_k=jnp.zeros(shape, cfg.dtype),
                   pool_v=jnp.zeros(shape, cfg.dtype))


class RecurrentState(NamedTuple):
    """What the layers that cache no rows keep instead, a slot: the
    sequence's whole past in a fixed size (``cfg.state_spec``), beside
    the pool in the one engine. It has no pages, no ring and no length:
    the programs that advance a slot's tokens advance its state in place
    (donated), a slot's first chunk starts it from zeros, and nothing
    can rebuild it from the pool's rows."""
    state: jax.Array       # [recurrent layers, slots, heads, dk, dv] f32
    conv: jax.Array        # [recurrent layers, slots, taps - 1, channels]

    @classmethod
    def create(cls, cfg: ModelConfig, slots: int) -> 'RecurrentState':
        spec, L = cfg.state_spec, cfg.n_recurrent_layers
        return cls(
            state=jnp.zeros((L, slots, spec.heads, spec.k_dim, spec.v_dim),
                            jnp.float32),
            conv=jnp.zeros((L, slots, spec.conv_rows, spec.conv_dim),
                           cfg.dtype))

    def rows(self, slot_ids: jax.Array, fresh: jax.Array
             ) -> 'RecurrentState':
        """The state of ``slot_ids`` [n] (an id past the slots: any
        row), zeros where ``fresh``: a sequence's start."""
        def take(a):
            a = jnp.take(a, slot_ids, axis=1, mode='clip')
            keep = ~fresh.reshape((1, -1) + (1,) * (a.ndim - 2))
            return jnp.where(keep, a, jnp.zeros((), a.dtype))
        return RecurrentState(take(self.state), take(self.conv))

    def with_rows(self, slot_ids: jax.Array, rows: 'RecurrentState'
                  ) -> 'RecurrentState':
        """``rows`` written back at ``slot_ids``; an id past the slots
        writes nothing."""
        return RecurrentState(
            self.state.at[:, slot_ids].set(rows.state, mode='drop'),
            self.conv.at[:, slot_ids].set(rows.conv, mode='drop'))


def _recurrent_layer(layer: Params, li, x: jax.Array, rec: RecurrentState,
                     cfg: ModelConfig, positions: jax.Array, live,
                     kernel: bool = False, params: Optional[Params] = None):
    """One layer whose mixer keeps a state and no rows: row ``li`` of
    ``rec`` in, the state after these tokens written back in its place
    (``rec`` is a loop carry: in place). ``kernel`` (one token a slot):
    the state is advanced where it lies by the kind's kernel, the live
    slots' alone: a KDA layer's recurrence (``ops.kda``), a Mamba layer's
    whole mixer between its projections (``ops.mamba.decode_mix``, which
    reads the Mamba stacks of ``params`` at ``li`` and keeps the conv
    tail in place too); else by XLA on every row it is given. Returns
    ((x, rec), the layer's expert counters, or zeros for a dense FFN)."""
    if kernel and 'mamba' in layer:
        from skypilot_tpu.models import mamba
        active, advanced = _live_slots(live, x.shape[0]), {}

        def mix(p, h):
            y, advanced['state'], advanced['conv'] = mamba.decode_mixer(
                p, params[mamba.STACKS['mamba']]['mamba'], h, li, rec.state,
                rec.conv, active, cfg)
            return y, None

        x, _, aux = llama._layer_core(layer, x, cfg, positions, mix,
                                      live=live, rec=rec)
        return (x, RecurrentState(advanced['state'], advanced['conv'])), aux
    tail = lax.dynamic_index_in_dim(rec.conv, li, 0, keepdims=False)
    if kernel:
        from skypilot_tpu.ops.kda import recurrent_step_in_place
        advanced, active = {}, _live_slots(live, x.shape[0])

        def step_fn(state, *qkvgb):
            del state                   # it stays in the stack
            advanced['state'], o = recurrent_step_in_place(
                rec.state, li, *qkvgb, active,
                interpret=jax.default_backend() != 'tpu')
            return o, None

        x, (_, tail), aux = llama._layer_core(
            layer, x, cfg, positions, step_fn, live=live, rec=(None, tail))
        state = advanced['state']
    else:
        before = lax.dynamic_index_in_dim(rec.state, li, 0, keepdims=False)
        x, (after, tail), aux = llama._layer_core(
            layer, x, cfg, positions, None, live=live, rec=(before, tail))
        state = lax.dynamic_update_index_in_dim(rec.state, after, li, 0)
    conv = lax.dynamic_update_index_in_dim(
        rec.conv, tail.astype(rec.conv.dtype), li, 0)
    return (x, RecurrentState(state, conv)), aux


def _live_slots(live, slots: int) -> jax.Array:
    """[slots] bool: the slots whose row carries a token (``live``
    [slots, 1], None = all)."""
    return (jnp.ones((slots,), bool) if live is None
            else jnp.broadcast_to(live, (slots, 1))[:, 0])


def _carrying_state(kv_layer_body, cfg: ModelConfig, positions: jax.Array,
                    live, kernel: bool = False,
                    params: Optional[Params] = None):
    """The layer body of a model with recurrent layers, from the body of
    a layer that caches rows: the carry is (x, every recurrent layer's
    state); a recurrent layer advances its row of the state, any other
    runs ``kv_layer_body`` on x and hands the state through. ``params``:
    the whole tree, where ``kernel`` reads a kind's stacks itself."""
    def body(carry, layer_and_idx):
        x, rec = carry
        layer, li = layer_and_idx
        if 'kda' in layer or 'mamba' in layer:
            return _recurrent_layer(layer, li, x, rec, cfg, positions, live,
                                    kernel=kernel, params=params)
        x, ys = kv_layer_body(x, layer_and_idx)
        return (x, rec), ys
    return body


def _state_kernel_tiles(spec) -> bool:
    """Whether a state's blocks tile the lanes for its kernel: a KDA
    head's key and value widths, a Mamba layer's channels."""
    if spec.kind == 'mamba':
        return spec.v_dim % _LANES == 0
    return spec.k_dim % _LANES == 0 and spec.v_dim % _LANES == 0


def _decode_state_kernel(cfg: ModelConfig, decode_impl: str) -> bool:
    """Whether the decode program advances the recurrent layers in their
    kind's kernel ('pallas', where the blocks tile; ops/kda.py,
    ops/mamba.py) rather than XLA."""
    return decode_impl == 'pallas' and _state_kernel_tiles(cfg.state_spec)


def _rows_need_live(cfg: ModelConfig) -> bool:
    """Whether a layer reads which rows carry a token: routed experts
    (a dead row routes nowhere) and recurrent mixers (it moves no
    state)."""
    return cfg.latent or cfg.ffn_kind == 'routed_shared' or cfg.recurrent


def paged_cache_logical_axes(quantized: bool = False) -> PagedKVCache:
    pool = ('layers', None, 'kv_heads', None, 'head_dim')
    if quantized:
        scale = ('layers', None, 'kv_heads', None)
        return PagedKVCache(pool_k=pool, pool_v=pool,
                            k_scale=scale, v_scale=scale)
    return PagedKVCache(pool_k=pool, pool_v=pool)


# ---------------------------------------------------------------------------
# Device functions
# ---------------------------------------------------------------------------
def _flat_write_indices(table: jax.Array, starts: jax.Array, n: int,
                        valid_len: jax.Array, page: int) -> jax.Array:
    """Flat pool row index for each of ``n`` tokens appended per slot:
    token j of slot b lands at table[b, (starts_b+j)//page]*page +
    (starts_b+j)%page. Tokens past ``valid_len_b`` are redirected to the
    trash rows of page 0. Returns [slots, n] int32."""
    j = jnp.arange(n)[None, :]
    pos = starts[:, None] + j
    page_idx = pos // page
    page_id = jnp.take_along_axis(table, page_idx, axis=1)
    flat = page_id * page + pos % page
    return jnp.where(j < valid_len[:, None], flat,
                     j % page)                 # page 0 = trash


def _scatter_rows(pool: jax.Array, rows: jax.Array,
                  flat_idx: jax.Array) -> jax.Array:
    """pool [L, n_pages, hkv, page] (+ optional trailing [d]); rows
    [L, slots, n, hkv] (+ the same tail); flat_idx [slots, n] logical
    token indices (page_id * page + pos). The pool's head-major layout
    interleaves heads between a page's token rows, so each (LAYER,
    token, head) triple scatters to its own flattened row:
    layer * n_pages*hkv*page + (tok // page) * hkv*page + head * page
    + tok % page.

    Why fully flat (the layer axis folded into the scatter indices
    rather than ridden as a batch dim): every batched formulation that
    leaves L as a window dim makes XLA's layout assignment relay the
    whole pool around the scatter (a measured 3.77 GB HLO-temp copy of
    the 7B pool — an instant OOM with the pool + weights resident),
    because the scatter windows [L, ..] span the operand's major dim.
    Fully flat windows are [page-row] = the operand's own minor layout,
    the scatter runs IN PLACE (0-byte temps, donation holds), at the
    price of a slower per-row scatter: ~70 ns a row on a v5e, which was
    15 % of ``ouro-2.6b.reason``'s device time while every slot's rows
    were written (``PERF.md``, PR 35). ``_write_live_rows`` calls this
    for live rows only."""
    if pool.ndim == 5 and rows.shape[-1] != pool.shape[-1]:
        return _scatter_rows_lane_packed(pool, rows, flat_idx)
    L, n_pages, hkv, page = pool.shape[:4]
    tail = pool.shape[4:]
    rows_per_layer = n_pages * hkv * page
    flat_pool = pool.reshape((L * rows_per_layer,) + tail)
    f = flat_idx.reshape(-1)                            # [slots*n]
    tok = ((f[:, None] // page) * (hkv * page)
           + jnp.arange(hkv)[None, :] * page
           + f[:, None] % page)                         # [slots*n, hkv]
    idx = (jnp.arange(L)[:, None, None] * rows_per_layer
           + tok[None]).reshape(-1)                     # [L*slots*n*hkv]
    flat_rows = rows.reshape((idx.size,) + tail)
    flat_pool = flat_pool.at[idx].set(
        flat_rows.astype(flat_pool.dtype), mode='drop')
    return flat_pool.reshape(pool.shape)


def _scatter_rows_lane_packed(pool: jax.Array, rows: jax.Array,
                              flat_idx: jax.Array) -> jax.Array:
    """``_scatter_rows`` for a pool whose rows of ``w`` < 128 values lie
    ``128 / w`` tokens to a lane row (``PagedKVCache.create``): pool [L,
    n_pages, 1, page * w / 128, 128], rows [L, slots, n, 1, w]. Token
    ``t`` of a page is lanes ``(t % per) * w ...`` of lane row ``t //
    per``. Flat and in place like its twin, and like it one scatter of
    WHOLE lane rows: each token writes its full 128-lane row, whose
    other places hold what this call writes there (a slot's tokens are
    consecutive, so a lane row's tokens are neighbours along ``n``) or
    else what the pool holds (one gather of the touched lane rows).
    Tokens of one lane row so write the same bytes, and duplicate
    indices are harmless; a lane row never straddles a page, and a page
    is written by one slot (the trash page excepted: it may hold
    anything).

    Not a [w] window at (row, lane): XLA lowers that 2-D scatter to a
    serial loop of one trip a row, 2,048 trips of 3.9 us in every ring
    merge and every prefill chunk of GLM-4.7-Flash (``PERF.md``, PR
    33)."""
    L, n_pages, hkv, lane_rows, lanes = pool.shape
    w = rows.shape[-1]
    per = lanes // w
    assert hkv == 1, 'lane-packed rows have no head axis'
    slots, n = flat_idx.shape
    flat_pool = pool.reshape(L * n_pages * lane_rows, lanes)
    place = flat_idx % per                              # [slots, n]
    idx = (jnp.arange(L)[:, None] * (n_pages * lane_rows)
           + (flat_idx // per).reshape(-1)[None, :]).reshape(-1)
    # Place k of token j's lane row is token j + k - place_j of the same
    # slot, if this call writes it there (the redirect of rows past
    # ``valid_len`` to the trash page breaks the run of indices). Every
    # index below is in bounds; ``mode='clip'`` spares the programs the
    # code of the default's fill (a quarter of this function's, in every
    # prefill and merge program).
    src = jnp.arange(n)[None, :, None] + jnp.arange(per) - place[..., None]
    want = (flat_idx - place)[..., None] + jnp.arange(per)
    near = jnp.clip(src, 0, n - 1)                      # [slots, n, per]
    written = (src == near) & (jnp.take_along_axis(
        flat_idx, near.reshape(slots, n * per), axis=1, mode='clip'
    ).reshape(slots, n, per) == want)
    new = jnp.take_along_axis(
        rows.reshape(L, slots, n, w).astype(flat_pool.dtype),
        near.reshape(1, slots, n * per, 1), axis=2, mode='clip')
    old = flat_pool.at[idx].get(mode='clip').reshape(L, slots, n, per, w)
    full = jnp.where(written[None, ..., None],
                     new.reshape(L, slots, n, per, w), old)
    flat_pool = flat_pool.at[idx].set(full.reshape(-1, lanes), mode='drop')
    return flat_pool.reshape(pool.shape)


# Rows a trip of ``_write_live_rows`` scatters into a pool at the least.
# Alone on a v5e (``PERF.md``, PR 36): into bf16 pools a row costs its
# 70 ns at 24,576 rows a trip as in one scatter of 786,432, and a trip's
# own cost is ~17 us; into int8 pools with their f32 scale pools a row
# costs twice as much in trips of 7,168 rows and below as in trips of
# 14,336 and above (the compiler stages a scale pool through fast memory
# only for the larger scatter).
_UNIT_ROWS_MIN = 16384


def _write_unit(n_layers: int, hkv: int, n: int) -> int:
    """Tokens of one slot that a trip of ``_write_live_rows`` writes: the
    smallest power of two >= 8 whose rows (``n_layers * hkv`` a token)
    reach ``_UNIT_ROWS_MIN``, if it divides the slot's run of ``n``
    tokens into more than one unit; else the whole run. From static
    shapes only: small enough that a chunk's padding is skipped where a
    token is many rows (8 tokens on ``ouro-2.6b``: 3,072 rows a token),
    the whole run where it is few (``qwen2-7b``: 112; a latent cache: a
    row a layer), so that a live slot costs what it cost."""
    unit = 8
    while n_layers * hkv * unit < _UNIT_ROWS_MIN:
        unit *= 2
    return unit if unit < n and n % unit == 0 else n


def _write_live_rows(pools, rows, table: jax.Array, starts: jax.Array,
                     valid_len: jax.Array, page: int):
    """Scatter the rows that something will read: ``pools`` and ``rows``
    are matching tuples (each pool as ``_scatter_rows`` takes it, each
    row array [L, slots, n, hkv] + tail), ``table`` [slots, P], ``starts``
    and ``valid_len`` [slots]. Returns the pools.

    A slot's ``n`` new tokens are ``n / unit`` units of consecutive
    tokens, and a unit is live iff its first token lies before the
    slot's ``valid_len``. ONE loop runs over the live units, live-first,
    and a trip scatters one unit's rows into every pool, in place: its
    trip count is the live work. A dead slot of a ring merge and the
    padding of a prefill chunk cost nothing, where every row used to be
    scattered at the full price, the dead ones to the trash page. Rows
    of a live unit past ``valid_len`` (at most ``unit - 1``) still go
    there. Trips run one after another, so a lane row that two units
    share is read back by the second as the first left it
    (``_scatter_rows_lane_packed``)."""
    n_layers, slots, n, hkv = rows[0].shape[:4]
    unit = _write_unit(n_layers, hkv, n)
    per_slot = n // unit
    # Every unit's first token, tokens due and write indices, [slots *
    # per_slot] of each: a few hundred at the most. (``repeat`` and a
    # stable ``argsort`` of the flags, not gathers and ``nonzero``: these
    # trace and lower in a third of the time, in each of a server's ~100
    # programs that write rows.)
    def per_unit(x):                       # [slots, ...] -> [units, ...]
        return jnp.repeat(x, per_slot, axis=0)
    off = jnp.tile(jnp.arange(per_slot, dtype=starts.dtype) * unit, slots)
    due = per_unit(valid_len) - off
    live = due > 0
    flat_idx = _flat_write_indices(per_unit(table), per_unit(starts) + off,
                                   unit, due, page)
    order = jnp.argsort(~live, stable=True)             # live units first
    # [L, slots, n] -> [L, units, unit]: a slot's tokens are consecutive
    units = [r.reshape((n_layers, slots * per_slot, unit) + r.shape[3:])
             for r in rows]

    def write_unit(i, pools):
        u = order[i]
        idx = lax.dynamic_slice_in_dim(flat_idx, u, 1)
        return tuple(
            _scatter_rows(pool, lax.dynamic_slice_in_dim(r, u, 1, axis=1),
                          idx)
            for pool, r in zip(pools, units))

    return lax.fori_loop(0, jnp.sum(live, dtype=jnp.int32), write_unit,
                         tuple(pools))


def merge_rows_into_pool(cache: PagedKVCache, k_rows, v_rows,
                         table: jax.Array, starts: jax.Array,
                         valid_len: jax.Array,
                         mesh=None) -> PagedKVCache:
    """Scatter [L, slots, n, hkv, d] new rows into the pool through the
    page table: token j of slot b, if j < ``valid_len_b``, lands at
    position ``starts_b + j`` of the slot's pages; rows past
    ``valid_len`` are written nowhere a request reads
    (``_write_live_rows``). For int8 pools the rows arrive PRE-quantized
    as ``(codes, scales)`` tuples — quantizing per layer inside the
    caller's scan keeps the stacked transient int8 (a 7B prefill chunk's
    bf16 [L, n, chunk] rows alone are ~4 GB; int8 is ~1 GB).

    ``mesh``: REQUIRED whenever the pool is tp-sharded. The fully-flat
    scatter below folds the head dim into its indices, which GSPMD
    cannot keep sharded — left to propagation it ALL-GATHERS the whole
    pool every merge (measured on the CPU tp=2 audit: a pool-shaped
    all-gather per decode step — the exact resharding collective the
    paged-tp audit preset exists to ban). With a mesh the merge runs
    under ``shard_map`` instead: each tp shard scatters its local head
    slice of the rows into its local pool shard (indices are
    head-uniform, so the flat in-place scatter is unchanged per
    shard), and a dp-sharded row batch is first all-gathered over dp
    INSIDE the body — ring-rows-sized, the one known dp collective —
    so every dp shard's pool replica stays identical."""
    axes = _pool_shard_axes(cache, table, mesh)
    if axes is not None:
        return _merge_rows_sharded(cache, k_rows, v_rows, table, starts,
                                   valid_len, mesh, *axes)
    pools, rows = _pools_and_rows(cache, k_rows, v_rows)
    return PagedKVCache(*_write_live_rows(pools, rows, table, starts,
                                          valid_len, cache.page_size))


def _pools_and_rows(cache: PagedKVCache, k_rows, v_rows):
    """The cache's pools and the row array bound for each, in
    ``PagedKVCache``'s field order."""
    if cache.quantized:
        (kq, ks), (vq, vs) = k_rows, v_rows
        return tuple(cache), (kq, vq, ks, vs)
    return (cache.pool_k, cache.pool_v), (k_rows, v_rows)


def _pool_shard_axes(cache: PagedKVCache, table: jax.Array, mesh):
    """(tp_axis, dp_axes) the sharded merge should map over, or None
    for the plain local path (no mesh, or nothing actually shards).
    Mirrors the divisibility rules the cache shardings were built
    with: tp only when it divides the head dim, dp only when the data
    axes divide the row batch (``table``'s slot dim)."""
    if mesh is None:
        return None
    import math as _math
    hkv = cache.pool_k.shape[2]
    tp = ('tp' if mesh.shape['tp'] > 1 and hkv % mesh.shape['tp'] == 0
          else None)
    data = tuple(a for a in ('slice', 'dp', 'fsdp') if mesh.shape[a] > 1)
    dp = (data if data and table.shape[0] % _math.prod(
        mesh.shape[a] for a in data) == 0 else None)
    if tp is None and dp is None:
        return None
    return tp, dp


def _merge_rows_sharded(cache: PagedKVCache, k_rows, v_rows,
                        table: jax.Array, starts: jax.Array,
                        valid_len: jax.Array, mesh, tp, dp
                        ) -> PagedKVCache:
    """``merge_rows_into_pool`` under ``shard_map``: per-shard flat
    scatters (in place, zero cross-shard traffic for tp) plus one
    ring-rows-sized all-gather over dp when the row batch is
    dp-sharded. See the caller's docstring for why GSPMD alone cannot
    do this without all-gathering the pool."""
    from jax.sharding import PartitionSpec as P
    pools, rows = _pools_and_rows(cache, k_rows, v_rows)
    pool_specs = tuple(P(None, None, tp, *(None,) * (pool.ndim - 3))
                       for pool in pools)      # scale pools are rank 4
    rows_s = P(None, dp, None, tp, None)      # codes AND rank-5 scales

    def body(*flat):
        pools, rows = flat[:len(pool_specs)], flat[len(pool_specs):-3]
        tbl, st, vl = flat[-3:]
        if dp is not None:
            # Regroup the dp-sharded row batch so EVERY dp shard
            # applies every slot's updates — the pool replicates over
            # dp and must not diverge. Ring-rows-sized: the one known
            # dp collective of the decode chain.
            rows = [lax.all_gather(r, dp, axis=1, tiled=True)
                    for r in rows]
            tbl = lax.all_gather(tbl, dp, axis=0, tiled=True)
            st = lax.all_gather(st, dp, axis=0, tiled=True)
            vl = lax.all_gather(vl, dp, axis=0, tiled=True)
        return _write_live_rows(pools, rows, tbl, st, vl,
                                cache.page_size)

    # Replication checking is off: with a dp-sharded row batch the pool
    # outputs ARE replicated over dp — every shard gathers the full row
    # set before scattering — but the checker cannot see through the
    # explicit all_gather.
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=pool_specs + (rows_s,) * len(rows)
        + (P(dp, None), P(dp), P(dp)),
        out_specs=pool_specs, check_vma=False)(
            *pools, *rows, table, starts, valid_len)
    return PagedKVCache(*out)


def _maybe_quantize_rows(new_kv, quantized):
    """(k_rows, v_rows) bf16 -> ((kq, ks), (vq, vs)) when the pool is
    quantized (``quantized``: False | True/int8 | 'int4' — the cache's
    ``quant_mode``); identity otherwise. Runs INSIDE the per-layer
    scan."""
    if not quantized:
        return new_kv
    quant = (llama.quantize_kv_rows4 if quantized == 'int4'
             else llama.quantize_kv_rows)
    k_rows, v_rows = new_kv
    return (quant(k_rows), quant(v_rows))


def _gather_layer(pool: jax.Array, scale_pool, li, table_p: jax.Array):
    """Layer ``li``'s pages of ``table_p`` out of the STACKED pool
    [L, n_pages, hkv, page, d] -> ([slots, P*page, hkv, d], scales or
    None): contiguous token-major view of each slot's first P pages
    (the XLA attention ops are token-major; the permute fuses into the
    gather's copy — the Pallas decode kernel reads the head-major pool
    directly). int8 / packed-int4 pools return CODES + gathered scales
    — the gathered copy stays quantized (half the write+read traffic
    of a dequantized gather) and the attention op folds the scales
    into logits/probs.

    ``li`` (traced, from the layer scan) rides in the gather's INDEX:
    the two major axes merge (a bitcast — no layout changes, and the
    head axis a tp mesh shards is untouched) and page ``p`` of layer
    ``li`` is row ``li * n_pages + p``. A layer sliced out first
    (``dynamic_index_in_dim``) is a copy of that layer's WHOLE pool in
    every layer step: the entire KV pool read once per program, growing
    with the pool and not with the prompt (GC121; ``PERF.md``, PR 28).
    Every id in ``table_p`` is an in-bounds page id (padding is page
    0), so the flat row stays inside layer ``li``."""
    n_layers, n_pages = pool.shape[:2]
    slots, P = table_p.shape
    rows = li * n_pages + table_p               # [slots, P] flat page rows
    # Never a ONE-row gather: XLA turns it into a dynamic-slice, fuses
    # that into the p.v dot and gives the dot's transposed layout to the
    # operand — a transposed copy of the whole V pool in every program
    # run (2.66 GB of temp, 8.8 ms a chunk on the 7B; PERF.md, PR 28).
    # A second copy of the row keeps it a gather, which reads the pool
    # as it lies; the extra page is dropped before the permute.
    if rows.size == 1:
        rows = jnp.concatenate([rows, rows], axis=1)

    def pages(stacked):
        flat = stacked.reshape((n_layers * n_pages,) + stacked.shape[2:])
        return flat[rows][:, :P]

    g = pages(pool)                             # [slots, P, hkv, page, d]
    hkv, page = g.shape[2:4]
    g = g.transpose(0, 1, 3, 2, 4).reshape(
        (slots, P * page, hkv) + g.shape[4:])
    if scale_pool is None:
        return g, None
    s = pages(scale_pool)                       # [slots, P, hkv, page]
    s = s.transpose(0, 1, 3, 2).reshape(slots, P * page, hkv, 1)
    return g, s


def paged_decode_horizon(
    params: Params,
    cache: PagedKVCache,
    table_p: jax.Array,                # [slots, P] first-P page ids (static P)
    tokens: jax.Array,                 # [slots]
    lengths: jax.Array,                # [slots] live tokens (host truth)
    cfg: ModelConfig,
    *,
    horizon: int,
    sample_fn=None,
    rngs: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,
    decode_impl: str = 'gather',       # 'gather' | 'pallas' | 'cross_layer'
    pages_per_block: int = 1,          # pallas path: K pages per DMA loop
    mlora_idx: Optional[jax.Array] = None,   # [slots] adapter slot per
                                       # row (-1 = none): multi-LoRA
                                       # bank gather inside the scan
    vocab_mask: Optional[jax.Array] = None,  # [slots, vocab] bool
                                       # constrained-decoding mask
    rec: Optional[RecurrentState] = None,    # the recurrent layers'
                                       # state, every slot's
):
    """``horizon`` fused decode steps over the paged pool: the cached
    rows are read by either a per-layer page gather or the Pallas
    paged-attention kernel (``ops/paged_attention.py``: page table as
    scalar prefetch, pages DMA'd straight from HBM, length-exact per
    slot; the gather materializes a full KV copy per layer, which is
    why a TPU takes the kernel), this horizon's rows from a small ring
    merged into the pool once after the scan. table_p must cover
    lengths+horizon for active slots.

    READ-ONLY on the cache: returns (tokens [slots, horizon],
    ring_k, ring_v [L, slots, horizon, hkv, d]); the caller scatters
    the ring into the pool via ``merge_ring_into_pool`` in a separate
    donated program (see its docstring for why). A recurrent model's
    ``rec`` has no ring: it is carried through the steps, each active
    slot's state advanced a token a step (the others' left as they
    were), and returned as a fourth output for the caller to donate."""
    b = tokens.shape[0]
    n_layers, spec = cfg.n_cache_layers, cfg.kv_spec
    len0 = lengths
    pool_k, pool_v = cache.pool_k, cache.pool_v
    ks_pool, vs_pool = cache.k_scale, cache.v_scale
    # Scales are STORED head-major [L, n_pages, hkv, page] (like the
    # pool): the kernel DMAs them per page with no relayout — the old
    # token-major storage cost one full scale-pool relayout (~0.5 GB
    # on a 7B) per horizon program, scaling with pool capacity.
    ring_k = jnp.zeros((n_layers, b, horizon, spec.heads, spec.k_dim),
                       cfg.dtype)
    ring_v = jnp.zeros((n_layers, b, horizon, spec.heads, spec.v_dim),
                       cfg.dtype)
    live = (None if not _rows_need_live(cfg) or active is None
            else active[:, None])
    if rngs is None:
        rngs = jnp.zeros((horizon, 2), jnp.uint32)

    def one_step(carry, step_in):
        ring_k, ring_v, tok, rec = carry
        i, rng = step_in
        x = llama._embed_tokens(params, tok[:, None], cfg)
        positions = (len0 + i)[:, None]

        def layer_body(xc, layer_and_idx):
            layer, li = layer_and_idx
            rk = lax.dynamic_index_in_dim(ring_k, li, 0, keepdims=False)
            rv = lax.dynamic_index_in_dim(ring_v, li, 0, keepdims=False)

            if cfg.latent and decode_impl == 'pallas':
                # The cached rows through the latent paged kernel, each
                # live slot's own pages DMA'd from the pools as they
                # lie; ring and current token merged in XLA
                # (ops/latent_paged_attention.py).
                from skypilot_tpu.ops.latent_paged_attention import (
                    latent_paged_decode_attention,
                    merge_latent_partial_with_ring_self)
                interp = jax.default_backend() != 'tpu'
                # A slot the host has freed reads nothing, whatever
                # length it was left with.
                read_len = (len0 if active is None
                            else jnp.where(active, len0, 0))

                def attn_fn(q_lat, q_rope, c, kr, scale):
                    partial = latent_paged_decode_attention(
                        q_lat[:, 0], q_rope[:, 0], pool_k, pool_v,
                        table_p, read_len, layer=li, scale=scale,
                        interpret=interp)
                    return merge_latent_partial_with_ring_self(
                        partial, q_lat, q_rope, c, kr, rk[:, :, 0],
                        rv[:, :, 0], i, scale=scale)
            elif cfg.latent:
                # The XLA fallback (CPU, a rope width that does not
                # pack) and the kernel's oracle: the absorbed form over
                # the gathered latent and rope rows, one shared row a
                # token under every query head (ops/latent_attention.py).
                from skypilot_tpu.ops.latent_attention import (
                    absorbed_ring_decode_attention)
                with jax.named_scope('mla_attn'):   # the gather is its cost
                    ck, _ = _gather_layer(pool_k, None, li, table_p)  # graftcheck: disable=GC121
                    cv, _ = _gather_layer(pool_v, None, li, table_p)  # graftcheck: disable=GC121
                cv = cv.reshape(cv.shape[0], -1, spec.v_dim)  # lane rows

                def attn_fn(q_lat, q_rope, c, kr, scale):
                    return absorbed_ring_decode_attention(
                        q_lat, q_rope, c, kr, ck[:, :, 0], cv, len0,
                        rk[:, :, 0], rv[:, :, 0], i, scale=scale)
            elif decode_impl == 'pallas':
                # The kernel takes the FULL stacked pool with the layer
                # as a scalar-prefetch block index: slicing the pool
                # here (dynamic_index_in_dim) would force XLA to
                # materialize a copy of the layer's pool as the
                # pallas_call operand — one extra read+write of the
                # whole KV stream per decode step (a CPU-era reading
                # had it cost over half the step; not re-measured).
                from skypilot_tpu.ops.paged_attention import (
                    merge_partial_with_ring_self, paged_decode_attention)
                interp = jax.default_backend() != 'tpu'

                def attn_fn(q, k, v):
                    partial = paged_decode_attention(
                        q[:, 0], pool_k, pool_v, table_p, len0,
                        ks_pool, vs_pool, layer=li, interpret=interp,
                        pages_per_block=pages_per_block)
                    return merge_partial_with_ring_self(
                        partial, q, k, v, rk, rv, i)
            elif decode_impl == 'cross_layer':
                # Fused-merge kernel: the ring + current-token blocks
                # fold into the cache softmax INSIDE the kernel, so the
                # per-layer XLA merge program (and its f32 partial
                # triple bouncing through HBM every layer of every
                # step) disappears from the scan. Same scalar-prefetch
                # pool discipline as 'pallas'.
                from skypilot_tpu.ops.paged_attention import (
                    paged_decode_attention_fused)
                interp = jax.default_backend() != 'tpu'

                def attn_fn(q, k, v):
                    out = paged_decode_attention_fused(
                        q[:, 0], k[:, 0], v[:, 0], rk, rv, i,
                        pool_k, pool_v, table_p, len0,
                        ks_pool, vs_pool, layer=li, interpret=interp)
                    return out[:, None]
            else:
                # The ONE grandfathered per-layer gather on the decode
                # path (GC121): the XLA-only fallback for backends /
                # head_dims the kernels don't cover. The suppression
                # below is deliberate — a new gather-per-layer site
                # anywhere else on the decode path hard-fails
                # graftcheck.
                ck, sck = _gather_layer(pool_k, ks_pool, li, table_p)  # graftcheck: disable=GC121
                cv, scv = _gather_layer(pool_v, vs_pool, li, table_p)  # graftcheck: disable=GC121

                def attn_fn(q, k, v):
                    return ring_decode_attention(q, k, v, ck, cv, len0,
                                                 rk, rv, i, k_scale=sck,
                                                 v_scale=scv)

            xc, new_kv, aux = llama._layer_core(
                layer, xc, cfg, positions, attn_fn, mlora_idx=mlora_idx,
                live=live)
            return xc, (new_kv, aux)

        if rec is None:
            x, ((k_rows, v_rows), aux), _ = llama.run_loops(
                layer_body, x, params, cfg)
        else:
            (x, rec), ys, _ = llama.run_loops(_carrying_state(
                layer_body, cfg, positions, live,
                kernel=_decode_state_kernel(cfg, decode_impl),
                params=params), (x, rec), params, cfg)
            (k_rows, v_rows), aux = ys['gqa']
            aux = jnp.concatenate([aux, ys[cfg.recurrent_kind]])
        ring_k = lax.dynamic_update_slice(
            ring_k, k_rows.astype(ring_k.dtype), (0, 0, i, 0, 0))
        ring_v = lax.dynamic_update_slice(
            ring_v, v_rows.astype(ring_v.dtype), (0, 0, i, 0, 0))
        logits = llama._unembed_logits(params, x, cfg)[:, 0]
        # Constrained decoding at logits production (covers the raw
        # greedy argmax branch too).
        logits = llama.apply_vocab_mask(logits, vocab_mask)
        if sample_fn is None:
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        else:
            nxt = sample_fn(logits, rng)
        # NaN blast-radius isolation: poisoned rows emit the sentinel
        # (host evicts exactly that request at readback; co-batched
        # slots continue) — see llama.mask_nonfinite_tokens.
        nxt = llama.mask_nonfinite_tokens(logits, nxt)
        return (ring_k, ring_v, nxt, rec), (nxt, jnp.sum(aux, axis=0))

    (ring_k, ring_v, _, rec), (toks, step_aux) = lax.scan(
        one_step, (ring_k, ring_v, tokens, rec),
        (jnp.arange(horizon), rngs))
    toks = toks.T
    if cfg.ffn_kind == 'routed_shared':
        # One more row under the slots' tokens: the distinct experts each
        # step read, summed over its expert layers (and, where the model
        # holds a share of its experts, a second: the assignments that
        # were held). They ride the one readback the call has
        # (PagedInferenceEngine._process_one).
        counted = step_aux.astype(toks.dtype)
        toks = jnp.concatenate(
            [toks, counted[None] if counted.ndim == 1 else counted.T])
    if rec is not None:
        return toks, ring_k, ring_v, rec
    return toks, ring_k, ring_v


def merge_ring_into_pool(cache: PagedKVCache, ring_k, ring_v,
                         table_p: jax.Array, lengths: jax.Array,
                         active: Optional[jax.Array],
                         mesh=None) -> PagedKVCache:
    """Scatter a decode horizon's ring rows into the pool — a SEPARATE
    jitted program from the token computation (engine donates the cache
    here). Keeping the pool update out of the program whose layer scan
    feeds the pool to pallas_call is what lets XLA alias the donated
    pool buffers in place; fused, the pool double-buffers (+4.4 GB on
    a 7B — an OOM)."""
    horizon = ring_k.shape[2]
    act = (active.astype(jnp.int32) if active is not None
           else jnp.ones_like(lengths))
    rk, rv = _maybe_quantize_rows((ring_k, ring_v), cache.quant_mode)
    return merge_rows_into_pool(cache, rk, rv, table_p, lengths,
                                valid_len=act * horizon, mesh=mesh)


def paged_prefill_chunk(
    params: Params,
    cache: PagedKVCache,
    table_p: jax.Array,                # [n, P] pages covering ctx+chunk
    tokens: jax.Array,                 # [n, chunk] (padded)
    lengths: jax.Array,                # [n] context already in the pool
    valid: jax.Array,                  # [n] tokens of this chunk in use
    want_idx: jax.Array,               # [n] in-chunk index of the row whose
                                       #     next token the caller needs
                                       #     (-1: none)
    cfg: ModelConfig,
    temps: jax.Array = None,           # [n] per-row sampling params
    topks: jax.Array = None,
    topps: jax.Array = None,
    rng: jax.Array = None,
    w8a8: bool = False,
    mesh=None,
    mlora_idx: Optional[jax.Array] = None,   # [n] adapter slot per row
    vocab_mask: Optional[jax.Array] = None,  # [n, vocab] bool mask for
                                       # the completing rows' first token
    rec: Optional[RecurrentState] = None,    # the recurrent layers'
                                       # state, every slot's
    slot_ids: Optional[jax.Array] = None,    # [n] each row's slot (past
                                       # the slots: a padding row)
):
    """One fixed-size prefill chunk for ``n`` slots: attends against the
    pages written so far (each slot's ``lengths``) plus causal
    self-attention within the chunk, scatters the new rows into the
    pool, and SAMPLES each completing row's next token ON DEVICE with
    that row's params (temperature/top-k/top-p; ``engine.
    sample_tokens`` — greedy rows take temp<=0's argmax path).

    Returns (first_tokens [n] int32, new cache). Device-side sampling
    is what lets the engine feed a completing slot's token straight
    into the device token vector at ENQUEUE time: the slot starts
    decoding on the very next horizon instead of idling 1-2 pipelined
    calls for a host logits readback + host sampling (measured: that
    idle was a double-digit share of sustained-serving slot time once
    decode itself got fast). ``w8a8`` quantizes the layer-matmul
    activations per token (prefill is compute-bound; see
    ``quantization.w8a8_region``) — the unembed stays W8A16.

    A recurrent model's ``rec`` carries each slot's state from chunk to
    chunk: the rows' states are taken out (zeros for a row with no
    context yet: a new request's first chunk), advanced over the row's
    ``valid`` tokens (padding moves nothing) and written back; the
    return is then (first_tokens, new cache, new rec)."""
    n, chunk = tokens.shape
    len0 = lengths
    pool_k, pool_v = cache.pool_k, cache.pool_v
    ks_pool, vs_pool = cache.k_scale, cache.v_scale
    x = llama._embed_tokens(params, tokens, cfg)
    positions = len0[:, None] + jnp.arange(chunk)[None, :]
    # Padding rows of a piece route nowhere (a latent model's experts).
    live = (jnp.arange(chunk)[None, :] < valid[:, None]
            if _rows_need_live(cfg) else None)
    rec_rows = None if rec is None else rec.rows(slot_ids, len0 == 0)

    def layer_body(xc, layer_and_idx):
        layer, li = layer_and_idx
        with (jax.named_scope('mla_attn') if cfg.latent
              else contextlib.nullcontext()):       # the gather is its cost
            ck, sck = _gather_layer(pool_k, ks_pool, li, table_p)
            cv, scv = _gather_layer(pool_v, vs_pool, li, table_p)

        if cfg.latent:
            from skypilot_tpu.ops.latent_attention import (
                absorbed_cached_attention)
            cv = cv.reshape(n, -1, cfg.kv_spec.v_dim)   # lane rows

            def attn_fn(q_lat, q_rope, c, kr, scale):
                return absorbed_cached_attention(
                    q_lat, q_rope, c, kr, ck[:, :, 0], cv, len0,
                    scale=scale)
        else:
            def attn_fn(q, k, v):
                return cached_attention(q, k, v, ck, cv, len0,
                                        k_scale=sck, v_scale=scv)

        xc, new_kv, _ = llama._layer_core(layer, xc, cfg, positions,
                                          attn_fn, mlora_idx=mlora_idx,
                                          live=live)
        # Quantize inside the scan: the stacked [L, n, chunk] ys stay
        # int8 (the bf16 stack is the 7B prefill's biggest transient).
        return xc, _maybe_quantize_rows(new_kv, cache.quant_mode)

    from skypilot_tpu.models.quantization import w8a8_region
    with (w8a8_region() if w8a8 else contextlib.nullcontext()):
        if rec is None:
            x, (k_rows, v_rows), _ = llama.run_loops(layer_body, x, params,
                                                     cfg)
        else:
            (x, rec_rows), ys, _ = llama.run_loops(
                _carrying_state(layer_body, cfg, positions, live),
                (x, rec_rows), params, cfg)
            k_rows, v_rows = ys['gqa']
    idx = jnp.clip(want_idx, 0, chunk - 1)
    last_x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = llama._unembed_logits(params, last_x, cfg)[:, 0]
    logits = llama.apply_vocab_mask(logits, vocab_mask)
    # All-greedy batches (the common case) take the argmax path
    # STATICALLY: sample_tokens sorts the [n, vocab] logits, and a TPU
    # sort over vocab=32k costs hundreds of ms — compiled into every
    # admission step, it halved sustained serving before this gate.
    if temps is None:                      # static: all rows greedy
        first = jnp.argmax(logits, -1).astype(jnp.int32)
    else:
        from skypilot_tpu.inference.engine import sample_tokens
        first = sample_tokens(logits, rng, temps, topks, topps)
    # NaN guard on the first-token sample too: a prompt that blows up
    # in prefill must evict at readback, not stream argmax-of-NaN.
    first = llama.mask_nonfinite_tokens(logits, first)

    new_cache = merge_rows_into_pool(cache, k_rows, v_rows, table_p,
                                     len0, valid_len=valid, mesh=mesh)
    if rec is not None:
        return first, new_cache, rec.with_rows(slot_ids, rec_rows)
    return first, new_cache


def paged_spec_verify(
    params: Params,
    cache: PagedKVCache,
    table_p: jax.Array,                # [n, P] pages covering len+k+1
    tokens: jax.Array,                 # [n] current token per slot (t0)
    proposals: jax.Array,              # [n, k] drafted continuations
    n_prop: jax.Array,                 # [n] valid drafts per slot
    lengths: jax.Array,                # [n] context already in the pool
    active: jax.Array,                 # [n] bool decodable mask
    cfg: ModelConfig,
    *,
    sample: bool,
    temps: jax.Array = None,
    topks: jax.Array = None,
    topps: jax.Array = None,
    rng: jax.Array = None,
    w8a8: bool = False,
    mesh=None,
    mlora_idx: Optional[jax.Array] = None,   # [n] adapter slot per row
    vocab_mask: Optional[jax.Array] = None,  # [n, vocab] bool mask
                                       # (broadcast over the k+1 verify
                                       # positions)
):
    """Speculative verify over the paged pool: one forward over the
    ``k+1`` positions ``[t0, d1..dk]`` per slot against the pages
    written so far (``paged_prefill_chunk``'s attention math with
    every position's logits kept), device-side acceptance
    (``speculative.verify_tokens``), and a MASKED merge of the accepted
    rows — ``merge_rows_into_pool``'s ``valid_len`` keeps rows past each
    slot's commit count out of every page a request reads, so per-slot
    variable acceptance never changes a program shape.

    Returns ``(commit [n, k+1], n_commit [n], new_tok [n], new_cache)``
    where ``new_tok`` is each slot's next-round current token (the last
    committed one; unchanged for inactive slots)."""
    from skypilot_tpu.inference import speculative
    n, k = proposals.shape
    seq = jnp.concatenate([tokens[:, None], proposals], axis=1)
    len0 = lengths
    pool_k, pool_v = cache.pool_k, cache.pool_v
    ks_pool, vs_pool = cache.k_scale, cache.v_scale
    x = llama._embed_tokens(params, seq, cfg)
    positions = len0[:, None] + jnp.arange(k + 1)[None, :]

    def layer_body(xc, layer_and_idx):
        layer, li = layer_and_idx
        ck, sck = _gather_layer(pool_k, ks_pool, li, table_p)
        cv, scv = _gather_layer(pool_v, vs_pool, li, table_p)

        def attn_fn(q, kk, vv):
            return cached_attention(q, kk, vv, ck, cv, len0,
                                    k_scale=sck, v_scale=scv)

        xc, new_kv, _ = llama._layer_core(layer, xc, cfg, positions,
                                          attn_fn, mlora_idx=mlora_idx)
        return xc, _maybe_quantize_rows(new_kv, cache.quant_mode)

    import contextlib
    from skypilot_tpu.models.quantization import w8a8_region
    with (w8a8_region() if w8a8 else contextlib.nullcontext()):
        x, (k_rows, v_rows) = lax.scan(
            layer_body, x, (params['layers'], jnp.arange(cfg.n_layers)))
    x = llama.rms_norm(x, params['final_norm'], cfg.norm_eps,
                       cfg.norm_plus_one)
    logits = llama._unembed_logits(params, x, cfg)      # [n, k+1, v]
    # Constrained rows verify against the MASKED distribution: both the
    # acceptance test and the bonus/resample draw obey the grammar.
    logits = llama.apply_vocab_mask(logits, vocab_mask)
    commit, n_commit = speculative.verify_tokens(
        logits, proposals, n_prop, rng, temps, topks, topps,
        sample=sample)
    n_commit = jnp.where(active, n_commit, 0)
    new_cache = merge_rows_into_pool(cache, k_rows, v_rows, table_p,
                                     len0, valid_len=n_commit, mesh=mesh)
    nxt = jnp.take_along_axis(
        commit, jnp.maximum(n_commit - 1, 0)[:, None], axis=1)[:, 0]
    new_tok = jnp.where(active, nxt, tokens)
    return commit, n_commit, new_tok, new_cache


def _chip_generation():
    """The TPU generation of the first device (its peaks), or None off a
    TPU: the CPU has no peaks here, and its chunk keeps its width."""
    from skypilot_tpu import accelerators
    return accelerators.generation_of_device(jax.devices()[0].device_kind)


def wide_piece_width(params: Params, cfg: ModelConfig, chunk: int,
                     gen) -> int:
    """The width of a piece that a prompt prefilled alone takes: ``chunk``
    times the largest power of two up to 4 that the piece's balance width
    holds. A chunk program reads every stored weight but the embedding
    table whatever its width (the head too, for the completing row), at
    ``gen``'s HBM rate; each token's matmuls touch each weight but the
    embedding and the head once, at ``gen``'s bf16 MXU rate, and of a
    routed layer's held experts the share a token reaches (top-k / the
    experts routed over). The balance width is where the two take
    equally long: 120-240 tokens for a dense model (a weight is 1-2
    bytes and 2 FLOPs a token), thousands where most stored bytes are
    experts a token does not reach. A looped model reads and computes
    every pass alike, so its passes cancel. ``gen`` None keeps
    ``chunk``."""
    if gen is None:
        return chunk
    from skypilot_tpu.models.quantization import per_device_bytes
    read = per_device_bytes(params) - per_device_bytes(params['embed'])
    touched = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        keys = {getattr(k, 'key', None) for k in path}
        if keys & {'embed', 'unembed'}:
            continue
        share = (cfg.n_experts_per_token / cfg.n_routed_experts
                 if 'experts' in keys else 1.0)
        touched += per_device_bytes(leaf) / leaf.dtype.itemsize * share
    flop_per_byte = gen.peak_bf16_tflops * 1e12 / (gen.hbm_bw_gbps * 1e9)
    balance = flop_per_byte * read / (2 * touched)
    width = chunk
    while width < 4 * chunk and 2 * width <= balance:
        width *= 2
    return width


# ---------------------------------------------------------------------------
# Host-side allocator + prefix index
# ---------------------------------------------------------------------------
class PageAllocator:
    """Free-list + refcount + content-addressed prefix index.

    Pages: 1..n_pages-1 allocatable (0 reserved). A *registered* page
    completes a full token prefix and carries its hash; when its
    refcount hits 0 it retires into an LRU (``retained``) that stays
    hit-able for prefix reuse until pool pressure evicts it."""

    def __init__(self, n_pages: int, page_size: int):
        self.page_size = page_size
        self.n_pages = n_pages
        self.free: List[int] = list(range(n_pages - 1, 0, -1))
        self.refcount = np.zeros(n_pages, np.int32)
        self.page_hash: Dict[int, bytes] = {}      # page -> prefix hash
        self.by_hash: Dict[bytes, int] = {}        # prefix hash -> page
        # insertion-ordered dict as LRU: oldest first
        self.retained: Dict[int, None] = {}
        self.prefix_hits = 0
        self.prefix_misses = 0

    # -------------------------------------------------- alloc/free
    @property
    def available(self) -> int:
        return len(self.free) + len(self.retained)

    def alloc(self) -> int:
        if self.free:
            page = self.free.pop()
        elif self.retained:
            page = next(iter(self.retained))       # LRU victim
            del self.retained[page]
            self._forget(page)
        else:
            raise MemoryError('KV page pool exhausted')
        self.refcount[page] = 1
        return page

    def retain(self, page: int) -> None:
        if page in self.retained:                  # revive from LRU
            del self.retained[page]
        self.refcount[page] += 1

    def release(self, page: int) -> None:
        self.refcount[page] -= 1
        assert self.refcount[page] >= 0, page
        if self.refcount[page] == 0:
            if page in self.page_hash:
                self.retained[page] = None         # prefix-reusable, LRU
            else:
                self.free.append(page)

    def _forget(self, page: int) -> None:
        h = self.page_hash.pop(page, None)
        if h is not None and self.by_hash.get(h) == page:
            del self.by_hash[h]

    # -------------------------------------------------- prefix index
    def _chain_hashes(self, prompt: List[int], upto: int):
        """Rolling per-page chain digests: h_i = sha1(h_{i-1} ||
        tokens of page i). O(len) total — hashing full prefixes per
        boundary would be O(len^2) on long prompts."""
        ps = self.page_size
        h = b''
        arr = np.asarray(prompt, np.int32)
        for i in range(upto):
            h = hashlib.sha1(h + arr[i * ps:(i + 1) * ps].tobytes()
                             ).digest()
            yield i, h

    def match_prefix(self, prompt: List[int]) -> List[int]:
        """Longest chain of cached full pages covering the prompt's
        *reusable* prefix (never the final token — its logits must be
        computed). Retains every matched page for the caller."""
        matched: List[int] = []
        max_full = (len(prompt) - 1) // self.page_size
        for _, h in self._chain_hashes(prompt, max_full):
            page = self.by_hash.get(h)
            if page is None or (self.refcount[page] == 0
                                and page not in self.retained):
                break
            matched.append(page)
        for p in matched:
            self.retain(p)
        if matched:
            self.prefix_hits += 1
        else:
            self.prefix_misses += 1
        return matched

    def register_prefix(self, prompt: List[int], pages: List[int],
                        n_matched: int) -> None:
        """Content-address the full pages a prefill just wrote (pages
        beyond ``n_matched``); an existing entry for the same hash keeps
        the older page (already shared).

        Validates BEFORE touching the index that ``pages`` actually
        covers every full page of ``prompt``: callers used to be
        locally-written pages only (count always matched by
        construction), but a KV-handoff ingest registers pages built
        from wire bytes — a truncated row batch whose token length
        claims more pages than were landed would otherwise
        content-address pages that hold other (or no) data, silently
        poisoning every future prefix hit on that chain."""
        max_full = (len(prompt) - 1) // self.page_size
        if len(pages) < max_full:
            raise ValueError(
                f'register_prefix: {len(pages)} page(s) cannot cover '
                f'the {max_full} full page(s) of a {len(prompt)}-token '
                'context (truncated row batch?)')
        for i, h in self._chain_hashes(prompt, max_full):
            if i < n_matched:
                continue
            page = pages[i]
            if h not in self.by_hash:
                self.by_hash[h] = page
                self.page_hash[page] = h


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------
from skypilot_tpu.inference.engine import _EngineBase
from skypilot_tpu.inference.speculative import SpeculativeMixin


class PagedInferenceEngine(SpeculativeMixin, _EngineBase):
    """Continuous-batching engine over the paged pool: the one serving
    engine. The host-side request lifecycle is ``_EngineBase``'s
    (``inference/engine.py``); the cache and the programs are here:

    - admission matches cached prefix pages, then chunk-prefills only
      the uncached tail (one compiled program per (n, P) bucket pair,
      any prompt length);
    - decode gathers pages instead of slicing a per-slot reservation;
    - HBM = page pool sized by TOTAL live tokens, not slots x max_seq;
    - ``speculate_k > 0``: decode runs the speculative
      propose→verify→commit loop (``inference/speculative.py``) with
      masked page-pool commits.
    """

    _PREFILL_N_BUCKETS = (1, 2, 4, 8, 16, 32)
    _HORIZON_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)
    # Stacked-chunk KV transient budget. Sized so n_max reaches 16 on a
    # 7B (chunk 256, ~270 KB/token): with decode at ~1800 tok/s/chip a
    # 32-step horizon completes ~9.5 req/s, and the old 8-wide chunk
    # batches (~9.4 admits/s) were the sustained-serving bottleneck —
    # slots idled waiting on admission while decode ran 2x faster than
    # round 4. The pool auto-size reserves this same constant, so the
    # pool shrinks ~0.75 GB (~22 pages) to pay for it.
    _PREFILL_STACK_BUDGET = int(1.5e9)
    # Ring-buffer byte cap. At batch 48 on a 7B this admits horizon 32
    # (ring 1.6 GB, k+v): _auto_n_pages reserves 2*row*h_max so the
    # pool shrinks to pay for it — a LONGER horizon halves the
    # admission interleaves and fixed per-call costs per token, which
    # measured as ~40% of sustained-serving device time at h=16. (The
    # old 512 MB cap predates the reserve accounting: h=32 at batch 48
    # OOM'd when the pool was sized ignoring the ring.)
    _RING_BYTES_CAP_PAGED = int(1.7e9)

    def __init__(self, cfg: ModelConfig, params=None, *,
                 max_batch: int = 8, max_seq: int = 1024,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 chunk: int = 256,
                 prefill_chunk_tokens: Optional[int] = None,
                 decode_priority_ratio: Optional[float] = None,
                 decode_steps_per_call: Optional[int] = None,
                 mesh=None, rng_seed: int = 0, attn_impl: str = 'auto',
                 quantize: Optional[str] = None,
                 kv_cache_dtype: Optional[str] = None,
                 donate_params: bool = False,
                 decode_impl: str = 'auto',
                 prefill_w8a8: bool = False,
                 pages_per_block: int = 1,
                 speculate_k: int = 0,
                 adapter_slots: int = 0,
                 adapter_dir: Optional[str] = None,
                 adapter_rank: int = 8,
                 adapter_targets: Optional[Any] = None,
                 telemetry: bool = True):
        from skypilot_tpu.inference.engine import (prepare_params,
                                                   refuse_unsupported)
        from skypilot_tpu.parallel import mesh as mesh_lib
        refuse_unsupported(cfg, quantize=quantize,
                           speculate_k=speculate_k,
                           adapter_slots=adapter_slots, mesh=mesh,
                           decode_impl=decode_impl)
        self._init_telemetry(telemetry)
        self.max_batch = max_batch
        self.max_seq = max_seq
        # page_size=None auto-selects a FAST-PATH size after the
        # quantize mode is known (see below); explicit values are the
        # user's to keep (with the misalignment warning).
        self._page_user = page_size is not None
        # ``prefill_chunk_tokens`` is the serve layer's spelling of the
        # chunk knob (``--prefill-chunk-tokens``); it wins over
        # ``chunk`` when given.
        if prefill_chunk_tokens is not None:
            chunk = prefill_chunk_tokens
        self.chunk = chunk
        # Decode share of the interleaved token budget while prompts
        # are mid-prefill (see _EngineBase._interleave_horizon). None
        # keeps this engine's measured-best fixed interleave horizon.
        self.decode_priority_ratio = decode_priority_ratio
        # Multi-step on-device decode (see _EngineBase): pin every
        # decode call at exactly k fused steps.
        self.decode_steps_per_call = self._validate_decode_steps(
            decode_steps_per_call)
        self.mesh = mesh
        self.attn_impl = attn_impl
        # Opt-in W8A8 prefill (int8 activations on the compute-bound
        # chunk prefill; decode unaffected) — see quantization.w8a8_region.
        self.prefill_w8a8 = prefill_w8a8
        # Pallas decode: K pages DMA'd/computed per loop iteration.
        # With the kernel's conditional tail-page DMAs reads are
        # length-exact at ANY K, so K only trades fori_loop/DMA-issue
        # overhead against double-buffer granularity. Measured on the
        # 7B int8 at batch 48 (anchor workload, steady): K=1 1790,
        # K=2 1724, K=4 1625, K=8 1620 tok/s/chip — single-page blocks
        # win now that no transpose hides in the loop body.
        self.pages_per_block = pages_per_block
        self._rng = jax.random.PRNGKey(rng_seed)
        self._host_rng = np.random.default_rng(rng_seed)
        cfg, self.params, quantize = prepare_params(
            cfg, params, quantize=quantize, mesh=mesh,
            donate_params=donate_params)
        self.cfg = cfg
        # KV storage dtype is its OWN knob, decoupled from the weight
        # quantize mode (None/'auto' follows it — the historical
        # coupling). Resolved AFTER prepare_params so pre-quantized
        # param trees (load_checkpoint(quantize='int8')) resolve 'auto'
        # correctly too. The resulting flag drives the pool dtype,
        # page-size selection, pool sizing, and every capacity surface.
        from skypilot_tpu.inference.engine import resolve_kv_cache_dtype
        self.kv_cache_dtype = resolve_kv_cache_dtype(kv_cache_dtype,
                                                     quantize)
        refuse_unsupported(cfg, kv_cache_dtype=self.kv_cache_dtype,
                           quantize=quantize)
        self._note_kv_layout()
        kv_int8 = self.kv_cache_dtype == 'int8'
        if page_size is None:
            page_size = self._auto_page_size(cfg, max_seq, kv_int8,
                                             mesh)
        if self._page_user and page_size % 128 != 0 and kv_int8 \
                and self._int8_fast_path_reachable(cfg, mesh):
            # The manual-DMA int8 kernel's per-page scale blocks need a
            # 128-aligned minor dim; off the fast path decode drops to
            # the per-page-grid kernel (~0.71x measured). Where that
            # kernel is actually reachable, an explicit misaligned size
            # is a pure footgun (the multichip dryrun's page_size=8 int8
            # pool shipped the 0.7x path for weeks) — so it is ROUNDED
            # UP to the next fast-path size, loudly. Elsewhere (CPU
            # tests, gather path, meshes) alignment is free and the
            # explicit size is the user's to keep.
            adjusted = self._fast_path_page_size(page_size)
            import warnings
            warnings.warn(
                f'page_size={page_size} is not a multiple of 128: int8 '
                'paged decode would fall off the manual-DMA fast path '
                f'(~0.7x throughput). Auto-adjusted to {adjusted}; '
                'pass a multiple of 128 to silence this.')
            page_size = adjusted
        self.page = page_size
        from skypilot_tpu.models import quantization
        # PER-DEVICE stored parameter bytes (sharded leaves count their
        # local shard; dp-replicated leaves count in full) — the floor
        # pool auto-sizing subtracts and the weight stream the ring cap
        # is sized against. Dividing global bytes by mesh.size was
        # wrong in both directions once dp>1 exists: dp REPLICATES the
        # weights, so a (tp=1, dp=2) mesh would have claimed half the
        # resident bytes and oversized the pool into an OOM.
        self._param_bytes = quantization.per_device_bytes(self.params)

        # Auto-sized pools reserve HBM for the long-horizon ring (see
        # _auto_n_pages); an EXPLICIT n_pages made no such bargain, so
        # its ring budget stays at the conservative cap — a user pool
        # sized to fill HBM under the old 512 MB assumption must not
        # suddenly meet a 3x ring at runtime.
        # The recurrent layers' per-slot state (None: every layer caches
        # rows). Its shape depends on ``max_batch`` alone, so it adds no
        # program and no key; it is made before the pool is sized, which
        # takes what is left.
        self.rec = (RecurrentState.create(cfg, max_batch)
                    if cfg.recurrent else None)
        self._state_slot_bytes = (
            cfg.n_recurrent_layers * cfg.state_spec.slot_bytes(
                jnp.dtype(cfg.dtype).itemsize) if cfg.recurrent else 0)
        # A state cannot be rebuilt from pages: a recurrent model's
        # requests match and register no prefix (no snapshot is kept at
        # page boundaries yet), and a preempted one prefills again from
        # its first token.
        self._prefix_reuse = not cfg.recurrent
        # The half-width chunk program (every pending piece <= 128
        # tokens) halves a chunk's FLOPs. A recurrent model's chunk
        # mostly reads weights (its mixers' matrices and every held
        # expert): a 100-token piece alone takes 15.7-16.0 ms through
        # the 128-wide program and 17.9 through the 256-wide one (my
        # chip run, PR 37: 2 ms of 18 to win, for a request whose last
        # piece is prefilled alone). Each of its programs is 6.3-7.3 MB
        # in the compile cache and the second width doubles their count
        # (18 more at max_batch 32 and 2k prompts: 6 minutes of
        # compiles, 115 MB): it keeps the one width until its programs
        # are smaller (ROADMAP B6 e).
        self._narrow_chunk = not cfg.recurrent
        self._note_state_layout()
        self._pool_auto_sized = n_pages is None
        if n_pages is None:
            n_pages = self._auto_n_pages(cfg, max_batch, max_seq,
                                         page_size)
        self.alloc = PageAllocator(n_pages, page_size)
        create_cache = functools.partial(
            PagedKVCache.create, cfg, n_pages=n_pages,
            page_size=page_size, kv_dtype=self.kv_cache_dtype)
        # Pre-partitioned pool + pinned output shardings: the pool is
        # placed ONCE (kv heads over tp; pages replicated — the
        # page table indexes them dynamically, so a page-sharded pool
        # would turn every gather into a collective), and every jitted
        # step that returns it pins this same tree as out_shardings.
        # The decode ring rows are pinned too (``_ring_sh``): the
        # decode program's ring OUTPUT sharding is exactly the merge
        # program's ring INPUT sharding, so the decode→merge chain has
        # no resharding between programs.
        self._cache_sh = None
        self._ring_sh = None
        if mesh is None:
            self.cache = create_cache()
        else:
            # Each shard is born on its own device. The pool is sized
            # per device (tp x the pages one chip would hold), so built
            # whole on the first device and resharded it does not fit:
            # the tp=4 server died here the first time it met four
            # chips.
            self._cache_sh = mesh_lib.tree_shardings(
                paged_cache_logical_axes(self.kv_cache_dtype != 'bf16'),
                mesh, shapes=jax.eval_shape(create_cache))
            self.cache = jax.jit(create_cache,
                                 out_shardings=self._cache_sh)()
            from jax.sharding import NamedSharding
            self._ring_sh = NamedSharding(mesh, mesh_lib.spec_for(
                ('layers', 'batch', None, 'kv_heads', 'head_dim'),
                shape=(cfg.n_cache_layers, max_batch, 1,
                       cfg.kv_spec.heads,
                       cfg.kv_spec.k_dim),
                mesh=mesh))

        on_tpu = jax.default_backend() == 'tpu'
        if decode_impl == 'auto':
            # The Pallas kernel needs 128-lane head_dim; on CPU its
            # interpret mode is correct but slow, so auto picks it only
            # on a real TPU backend (tests opt in explicitly). int4
            # pools stay on the gather path: the packed uint8 page
            # blocks halve the minor dim below the 128-lane tile.
            # A latent cache has its own kernel
            # (ops/latent_paged_attention.py): a bf16 pool of
            # 128-lane latent rows beside the lane-packed rope pool.
            if cfg.latent:
                fits = (cfg.kv_lora_rank % _LANES == 0
                        and self.cache.pool_k.dtype == jnp.bfloat16
                        and self.cache.pool_v.shape[-1] == _LANES)
            else:
                fits = (cfg.head_dim % 128 == 0
                        and self.kv_cache_dtype != 'int4')
            decode_impl = ('pallas' if fits and on_tpu and mesh is None
                           else 'gather')
        elif (on_tpu and self.kv_cache_dtype == 'int4'
              and decode_impl in ('pallas', 'cross_layer')):
            # Mosaic refuses the in-kernel nibble unpack of a packed
            # int4 pool (infer-vector-layout: unsupported shape cast,
            # tests/test_tpu_compile.py records it), so on the chip the
            # combination would die at the first decode. Interpret mode
            # on CPU runs it; the kernel layout is ROADMAP A4's work.
            raise ValueError(
                f'decode_impl={decode_impl!r} does not compile for a '
                "TPU with kv_cache_dtype='int4': use decode_impl="
                "'gather' (what 'auto' picks), or an int8/bf16 KV "
                'cache')
        self.decode_impl = decode_impl

        # host slot state (queue/slots/finish from _EngineBase)
        self._init_slots(max_batch)
        self._pages: List[List[int]] = [[] for _ in range(max_batch)]
        # slot -> tokens of its prompt TAIL prefilled so far; a slot in
        # this dict is assigned but not yet decodable (continuous
        # admission interleaves its remaining chunks with decode).
        self._prefill_off: Dict[int, int] = {}
        # Extra async-pipeline state beyond _EngineBase's (_tok_dev /
        # _pending live there): slots whose DEVICE-sampled first token
        # hasn't surfaced to the host yet sit in _await_first. They
        # DECODE meanwhile (the token merged into the device token
        # vector at prefill enqueue); membership only gates the
        # first-token event + finish bookkeeping, and the preemption
        # path drains the pipeline before acting so requeued contexts
        # stay complete.
        self._await_first: set = set()
        self._slot_inflight = np.zeros(max_batch, np.int64)
        # Fixed-shape first-token merge: padding entries scatter to the
        # out-of-range sentinel max_batch and are dropped.
        self._merge_tokens_drop = jax.jit(
            lambda tok, slots, vals: tok.at[slots].set(vals,
                                                       mode='drop'))
        # Early-recycled requests whose tail tokens are still in the
        # pipeline: in neither _queue nor _slots, so has_work() and
        # cancel() must consult this registry (a serve loop that slept
        # on queue+slots alone stranded the final tokens forever, and
        # a disconnecting client's request leaked uncancellable).
        self._lagging: Dict[int, Any] = {}
        self._eager_drain = True       # see step()'s opportunistic drain
        # Bumped when a slot is freed: an in-flight call enqueued for a
        # previous occupant must not decrement the NEW occupant's
        # inflight count at processing time.
        self._slot_epoch = np.zeros(max_batch, np.int64)
        self._deferred_events: List[Tuple[int, int, bool]] = []
        # Multi-tenant adapter bank (adapter_slots > 0): the stacked
        # multi-LoRA bank installs into params['layers']['mlora']
        # BEFORE any program traces; adapter_slots=0 leaves the params
        # tree — and every traced program — byte-identical to before.
        self.adapters = None
        if adapter_slots > 0:
            from skypilot_tpu.inference import adapters as adapters_lib
            self.adapters = adapters_lib.AdapterRegistry(
                self, slots=adapter_slots, rank=adapter_rank,
                adapter_dir=adapter_dir, targets=adapter_targets)
        self._decode_fn = self._build_decode()
        self._prefill_fns: Dict[Tuple[int, int], Any] = {}
        # A prefill chunk-batch stacks [L, n, chunk] KV rows as a scan
        # transient; cap n so the stack stays within
        # _PREFILL_STACK_BUDGET (at n=32 x chunk=256 on a 7B the two
        # stacks alone are 2 GB — the compile OOM'd the chip).
        # _auto_n_pages reserves the same budget.
        tok_bytes = self._page_bytes(self.cfg, 1, self.kv_cache_dtype,
                                     mesh=self.mesh)
        n_fit = int(self._PREFILL_STACK_BUDGET // max(1, chunk *
                                                      tok_bytes))
        self._prefill_n_max = 1
        for b in self._PREFILL_N_BUCKETS:
            if b <= n_fit:
                self._prefill_n_max = b
        # A prompt prefilled alone takes pieces this wide while its tail
        # lasts (``_prefill_chunk_batch``): where a chunk's time is the
        # weights it reads, as a routed model's is (GLM-4.7-Flash re-read
        # 8.5 GB of experts for each 256 tokens), a wider piece reads them
        # once for more tokens. A dense model's balance width lies below
        # its chunk, so its width is ``chunk`` and its programs are as
        # they were. The piece is held to the transients a batch of
        # width / chunk prompts at the longest context's page bucket may
        # take, and halved until it fits.
        from skypilot_tpu.inference.engine import _bucket_len
        wide = wide_piece_width(self.params, cfg, chunk, _chip_generation())
        pages_max = _bucket_len(self._pages_needed(max_seq), minimum=1)
        while wide > chunk and (
                wide // chunk > self._prefill_n_max
                or self._chunk_piece_bytes(pages_max, wide)
                > self._CHUNK_TRANSIENT_BUDGET):
            wide //= 2
        self._wide_chunk = wide
        self._prof.note_wide_width(wide)
        # Expert layers a decode step runs (0: the model routes nothing),
        # for the expert counters of the profiler.
        self._moe_layers = (cfg.n_layers - cfg.n_dense_layers
                            if cfg.ffn_kind == 'routed_shared' else 0)
        self.chunks_prefilled = 0          # diagnostics (prefix-hit wins)
        self.preemptions = 0               # pool-pressure recomputes
        # KV handoff programs (disaggregated serving): export page
        # gathers keyed by P bucket, ingest merges keyed by (rows, P).
        self._export_fns: Dict[int, Any] = {}
        self._ingest_fns: Dict[Tuple[int, int], Any] = {}
        # Hot-prefix heat tracker (spot resilience): chain digest ->
        # {'tokens', 'hits'} for recently registered/matched prefix
        # chains, bounded LRU-by-heat. The preemption checkpoint
        # exports the hottest chains' page bytes so a replacement
        # replica boots near-warm (export_prefix_snapshots /
        # warm_prefix).
        self._prefix_heat: Dict[bytes, Dict[str, Any]] = {}
        self._PREFIX_HEAT_MAX = 64
        # Speculative decoding (0 = off): n-gram propose + batched
        # verify with masked page-pool commits.
        self._init_spec(speculate_k)
        # Where the state sits (shapes and shardings are fixed from here
        # on, so this is taken once and never touches a donated buffer).
        from skypilot_tpu.telemetry import device as device_lib
        self._bytes_by_device = {
            'params': device_lib.bytes_by_device(self.params),
            'kv_pool': device_lib.bytes_by_device(self.cache)}
        if self.rec is not None:
            self._bytes_by_device['recurrent_state'] = \
                device_lib.bytes_by_device(self.rec)

    @staticmethod
    def _int8_fast_path_reachable(cfg: ModelConfig, mesh) -> bool:
        """True when ``decode_impl='auto'`` would pick the Pallas
        manual-DMA int8 kernel — the one condition under which page
        alignment matters (its per-page scale blocks need a 128-aligned
        minor dim)."""
        return (cfg.head_dim % 128 == 0
                and jax.default_backend() == 'tpu' and mesh is None)

    @staticmethod
    def _fast_path_page_size(page_size: int) -> int:
        """Smallest fast-path-compatible (128-multiple) page size that
        holds at least ``page_size`` tokens."""
        return max(128, -(-page_size // 128) * 128)

    @classmethod
    def _auto_page_size(cls, cfg: ModelConfig, max_seq: int,
                        kv_int8: bool, mesh) -> int:
        """Default page size: stay on the decode fast path. Wherever
        the Pallas manual-DMA int8 kernel is reachable (the same
        condition ``decode_impl='auto'`` uses to pick it), pages must
        be 128-aligned — the multichip dryrun's explicit page_size=8
        int8 pool tripped the ~0.7x per-page-grid fallback this guard
        exists to catch (explicit misaligned sizes are now auto-rounded
        up in ``__init__`` under the same condition). Elsewhere (bf16
        pools, CPU tests, gather path) alignment is free, so
        short-context configs get smaller pages instead of one page per
        slot."""
        if kv_int8 and cls._int8_fast_path_reachable(cfg, mesh):
            return 128
        from skypilot_tpu.inference.engine import _bucket_len
        return min(128, _bucket_len(max(8, max_seq // 8), minimum=8))

    @staticmethod
    def _page_bytes(cfg: ModelConfig, page_size: int,
                    quantized, mesh=None) -> int:
        """Stored bytes of one page; with ``mesh``, PER-DEVICE bytes
        (kv heads shard over tp — the pool's pages replicate over dp,
        so dp never divides). HBM sizing passes the mesh; reporting
        surfaces keep the global cost."""
        from skypilot_tpu.inference.engine import kv_token_bytes
        return kv_token_bytes(cfg, quantized, mesh=mesh) * page_size

    def _auto_n_pages(self, cfg: ModelConfig, max_batch: int,
                      max_seq: int, page_size: int) -> int:
        """Size the pool from FREE HBM after the weights landed, not
        from ``max_batch x max_seq``: the pool is the paged engine's whole
        advantage (HBM proportional to live tokens -> more concurrent
        long contexts on the same chip), so idle HBM is wasted
        capacity. A reserve covers decode transients (the horizon ring,
        unembed logits, prefill activations) and XLA workspace. Off the
        TPU (CPU tests, interpret mode) it is ``max_batch x max_seq``."""
        parity = max_batch * -(-max_seq // page_size) + 1
        # Per-page byte cost follows the KV CACHE dtype, not the weight
        # dtype — with the flags decoupled (int8 weights + bf16 KV or
        # vice versa) sizing the pool off the params would mis-state
        # capacity by 2x in either direction.
        quantized = self.kv_cache_dtype
        if jax.default_backend() != 'tpu':
            # CPU (tests, interpret mode) reports no device memory:
            # max_batch x max_seq. That reserves NOTHING for the ring, so
            # decode keeps the conservative ring budget.
            self._pool_auto_sized = False
            return parity
        # On the chip the live stats are the only source: a TPU that
        # reports none is an error, not a reason to guess.
        stats = jax.devices()[0].memory_stats()
        if not stats or 'bytes_limit' not in stats \
                or 'bytes_in_use' not in stats:
            raise RuntimeError(
                f'{jax.devices()[0]} reports no bytes_limit/bytes_in_use '
                f'in memory_stats() ({stats!r}); the paged pool cannot '
                'be sized. Pass n_pages explicitly.')
        limit = stats['bytes_limit']
        used = stats['bytes_in_use']
        # bytes_in_use can lag async transfers (observed right after the
        # parallel checkpoint puts: the pool then oversized by ~3 GB and
        # decode OOM'd at runtime); the weights are a known floor —
        # _param_bytes is already the exact PER-DEVICE resident bytes
        # (sharded leaves count their local shard, dp-replicated leaves
        # in full — dividing by mesh.size here was the dp>1 oversizing
        # bug).
        used = max(used, self._param_bytes + int(0.15e9)
                   + self._state_slot_bytes * max_batch)
        # The reserve must cover the decode transients at the LONGEST
        # horizon the ring budget allows — sizing the pool without
        # them compiled programs past HBM at batch=48 on a 7B. The
        # ring (decode program) and the stacked prefill-chunk KV
        # (prefill program) are transients of DIFFERENT programs and
        # never peak together, so the reserve takes their MAX on top
        # of a fixed workspace: summing them shrank a 7B pool to 65
        # pages (2.2 GB) where 170 pages ran h=32 clean — the
        # empirically-safe reserve on that config is ~3.1 GB. h_max
        # rounds DOWN to the horizon bucket decode will actually pick.
        from skypilot_tpu.inference.engine import _ring_row_bytes
        row = _ring_row_bytes(cfg, max_batch, self.mesh)
        h_max = self._ring_horizon_bucket(self._RING_BYTES_CAP_PAGED)
        reserve = (int(1.6e9) + max(2 * row * h_max,
                                    self._PREFILL_STACK_BUDGET))
        # Per-DEVICE page cost: a tp-sharded pool stores 1/tp of each
        # page's rows per chip, so the same free HBM fits tp x the
        # pages (the whole point of sharding the pool) — while a dp>1
        # mesh replicates the pool and gets NO page-count credit.
        page_bytes = self._page_bytes(cfg, page_size, quantized,
                                      mesh=self.mesh)
        fit = max(0, (limit - used - reserve)) // page_bytes
        # Take what fits, capped at 4x slot parity (prefix-cache
        # headroom without letting a tiny model grab the whole chip);
        # under pool pressure admission backs off, so a sub-parity fit
        # still serves. Never below 2 (page 0 is reserved).
        return int(max(min(fit, 4 * parity), 2))

    @classmethod
    def from_pretrained(cls, path: str, *, dtype=None,
                        **kwargs) -> 'PagedInferenceEngine':
        """Build a paged engine from an HF checkpoint directory (see
        ``models/weights.py``; quantization happens host-side during
        load, int8 cache reused)."""
        from skypilot_tpu.models import weights
        cfg, params = weights.load_checkpoint(
            path, dtype=dtype if dtype is not None else jnp.bfloat16,
            quantize=kwargs.get('quantize'))
        kwargs.setdefault('donate_params', True)
        return cls(cfg, params, **kwargs)

    # ---------------------------------------------------------- compiled
    def _build_decode(self):
        """Two programs per horizon, enqueued back to back with ONE host
        sync: token computation reads the pool (pallas blocks DMA from
        it directly), then the ring scatter runs with the cache donated
        so the pool updates in place — see merge_ring_into_pool."""
        cfg = self.cfg
        decode_impl = self.decode_impl
        # Pinned ring output shardings: the decode program emits the
        # ring rows in exactly the layout the merge program consumes
        # them in (out_axis_resources == next in_axis_resources), and
        # the merge returns the pool in its own resident sharding —
        # the decode→merge chain reshards nothing in steady state.
        ring_kwargs = ({'out_shardings': (None, self._ring_sh,
                                          self._ring_sh)}
                       if self._ring_sh is not None else {})
        merge_kwargs = ({'out_shardings': self._cache_sh}
                        if self._cache_sh is not None else {})

        # A recurrent model's state is advanced by this program (it has
        # no ring to merge later): donated, updated in place.
        rec_kwargs = ({'donate_argnames': ('rec',)}
                      if self.rec is not None else {})

        @functools.partial(jax.jit,
                           static_argnames=('horizon', 'sample'),
                           **ring_kwargs, **rec_kwargs)
        def decode_steps(params, cache, table_p, tokens, lengths, rng,
                         temps, topks, topps, active, adp, vmask,
                         horizon, sample, rec=None):
            if sample:
                def sample_fn(logits, step_rng):
                    from skypilot_tpu.inference.engine import sample_tokens
                    return sample_tokens(logits, step_rng, temps, topks,
                                         topps)
                rngs = jax.random.split(rng, horizon)
            else:
                sample_fn, rngs = None, None
            return paged_decode_horizon(
                params, cache, table_p, tokens, lengths, cfg,
                horizon=horizon, sample_fn=sample_fn, rngs=rngs,
                active=active, decode_impl=decode_impl,
                pages_per_block=self.pages_per_block,
                mlora_idx=adp, vocab_mask=vmask, rec=rec)

        # A named function, not a jitted ``functools.partial`` (which
        # has no name: the program was ``jit__unknown`` in every device
        # trace): the program is ``jit_merge_ring_into_pool``.
        mesh = self.mesh

        def merge_ring(cache, ring_k, ring_v, table_p, lengths, active):
            return merge_ring_into_pool(cache, ring_k, ring_v, table_p,
                                        lengths, active, mesh=mesh)
        merge_ring.__name__ = merge_ring_into_pool.__name__
        merge = jax.jit(merge_ring, donate_argnums=(0,), **merge_kwargs)

        def decode_and_merge(params, cache, table_p, tokens, lengths,
                             rng, temps, topks, topps, active, adp,
                             vmask, horizon, sample):
            toks, ring_k, ring_v, *rec = decode_steps(
                params, cache, table_p, tokens, lengths, rng, temps,
                topks, topps, active, adp, vmask, horizon, sample,
                rec=self.rec)
            if rec:
                self.rec, = rec
            new_cache = merge(cache, ring_k, ring_v, table_p, lengths,
                              active)
            return toks, new_cache

        return decode_and_merge

    def _get_prefill(self, n: int, P: int, sample: bool,
                     chunk_w: Optional[int] = None):
        key = (n, P, sample, chunk_w or self.chunk)
        if key not in self._prefill_fns:
            cfg = self.cfg
            w8a8 = self.prefill_w8a8

            mesh = self.mesh

            @functools.partial(jax.jit, donate_argnums=(1,),
                               donate_argnames=('rec',),
                               **self._step_out_shardings(1))
            def prefill(params, cache, table_p, tokens, lengths, valid,
                        want_idx, adp, vmask, temps, topks, topps, rng,
                        rec=None, slot_ids=None):
                return paged_prefill_chunk(
                    params, cache, table_p, tokens, lengths, valid,
                    want_idx, cfg, temps=temps if sample else None,
                    topks=topks, topps=topps, rng=rng, w8a8=w8a8,
                    mesh=mesh, mlora_idx=adp, vocab_mask=vmask, rec=rec,
                    slot_ids=slot_ids)

            self._prefill_fns[key] = prefill
        return self._prefill_fns[key]

    # ---------------------------------------------------------- public
    def _validate_request(self, prompt: List[int],
                          max_new_tokens: int) -> None:
        super()._validate_request(prompt, max_new_tokens)
        # A prompt the pool can NEVER hold must fail loudly here — a
        # silent requeue would spin run_to_completion forever.
        need = self._pages_needed(len(prompt) + max_new_tokens)
        if need > self.alloc.n_pages - 1:
            raise ValueError(
                f'request needs {need} pages but the pool has only '
                f'{self.alloc.n_pages - 1}; raise n_pages')

    def memory_stats(self) -> Dict[str, Any]:
        page_bytes = self._page_bytes(self.cfg, self.page,
                                      self.kv_cache_dtype)
        used = self.alloc.n_pages - 1 - len(self.alloc.free) \
            - len(self.alloc.retained)
        return {
            'n_pages': self.alloc.n_pages,
            'pages_in_use': used,
            'pages_retained_prefix': len(self.alloc.retained),
            'pages_free': len(self.alloc.free),
            'page_bytes': page_bytes,
            'pool_bytes': page_bytes * self.alloc.n_pages,
            'kv_cache_dtype': self.kv_cache_dtype,
            # Allocatable tokens (page 0 is the reserved trash page).
            'pool_token_capacity': (self.alloc.n_pages - 1) * self.page,
            # What the layers that cache no rows keep, beside the pool.
            'recurrent_kind': self.cfg.recurrent_kind,
            'recurrent_layers': self.cfg.n_recurrent_layers,
            'recurrent_state_slot_bytes': self._state_slot_bytes,
            'recurrent_state_bytes': (self._state_slot_bytes
                                      * self.max_batch),
            'prefix_hits': self.alloc.prefix_hits,
            'prefix_misses': self.alloc.prefix_misses,
        }

    def recurrent_state_of(self, req) -> Optional[np.ndarray]:
        """The recurrent state the slot of ``req`` (a request this
        engine ran) holds now, on the host: float32 [recurrent layers,
        heads, k_dim, v_dim]; None for a model with no recurrent layer.
        A finished request's state lies where it was left until another
        request's first chunk starts the slot from zeros, so a caller
        that ran ``req`` alone reads the state after every token of its
        context but the last one produced (a budget-bound request takes
        no step past its budget: ``_maybe_early_free``). It is a
        reading, for a comparison with a reference; no snapshot is kept
        for reuse (ROADMAP B6)."""
        if self.rec is None:
            return None
        return np.asarray(self.rec.state[:, req._slot], np.float32)

    def resolved_path(self) -> Dict[str, Any]:
        """What construction resolved (kernel path, pool) and where the
        bytes sit, for ``/metrics?format=json`` and ``chip_smoke.py``: a
        drop to ``gather``, to interpret mode or to a parity-sized pool
        shows from outside the process. Host-side state only."""
        compiles = self._prof.compile_events
        return {
            'decode_impl': self.decode_impl,
            'decode_interpret': (
                self.decode_impl in ('pallas', 'cross_layer')
                and jax.default_backend() != 'tpu'),
            # paged_prefill_chunk always takes cached_attention, or a
            # latent model its absorbed form: two-block XLA either way.
            'prefill_attn': ('xla_absorbed_two_block' if self.cfg.latent
                             else 'xla_two_block'),
            'page_size': self.page,
            'kv_pool_pages': self.alloc.n_pages,
            'pool_auto_sized': bool(self._pool_auto_sized),
            'bytes_by_device': self._bytes_by_device,
            # The recurrent mixers' q/k/v leaf as the programs read it
            # (``kda.serving_layout``); None without such a stack.
            'kda_qkv': (list(self.params['kda_layers']['kda']['wqkv'].shape)
                        if 'kda_layers' in self.params else None),
            # A Mamba layer's decode step: 'fused', the one kernel
            # between its projections (``ops.mamba.decode_mix``), or
            # 'xla'; None for a model without Mamba layers.
            'mamba_decode': (
                None if self.cfg.recurrent_kind != 'mamba'
                else 'fused' if _decode_state_kernel(self.cfg,
                                                     self.decode_impl)
                else 'xla'),
            # First dispatch per jit static key (the profiler's compile
            # events): steady state over repeated shapes adds none.
            'jit_first_calls': len(compiles),
            'jit_first_call_seconds': round(
                sum(e['seconds'] for e in compiles), 3),
        }

    def kv_token_capacity(self) -> int:
        """Token rows the pool arrays physically hold (trash page
        included — the cost model divides pool AVAL bytes, and page 0
        is part of the aval). Distinct from ``memory_stats``'s
        allocatable capacity, which excludes the reserved page."""
        return self.alloc.n_pages * self.page

    def kv_pool_stats(self) -> Dict[str, Any]:
        """KV capacity/pressure in TOKENS (page-granular: a partially
        filled page counts as used) — the schema of the telemetry
        gauges and the JSON ``kv_pool`` block. Prefix-retained
        pages count as FREE: allocation evicts them on demand."""
        from skypilot_tpu.inference.engine import (kv_shard_degree,
                                                   kv_token_bytes)
        stats = self.memory_stats()
        cap = stats['pool_token_capacity']
        used = stats['pages_in_use'] * self.page
        return {
            'kv_cache_dtype': self.kv_cache_dtype,
            'pool_token_capacity': cap,
            'tokens_used': used,
            'tokens_free': cap - used,
            'preemptions': int(self.preemptions),
            'kv_token_bytes': kv_token_bytes(self.cfg,
                                             self.kv_cache_dtype),
            # Per-DEVICE byte view (kv heads shard over tp; pages
            # replicate over dp): token counts above stay GLOBAL so
            # scheduler bounds and preemption pressure mean the same
            # thing at any mesh shape.
            'kv_token_bytes_per_shard': kv_token_bytes(
                self.cfg, self.kv_cache_dtype, mesh=self.mesh),
            'kv_shards': kv_shard_degree(self.cfg, self.mesh),
        }

    def _refuse_kv_transfer(self) -> None:
        if self.cfg.recurrent:
            raise NotImplementedError(
                f'{self.cfg.name}: KV export/ingest (handoff, prefix '
                'snapshots) moves cache rows; '
                f'{self.cfg.n_recurrent_layers} of its layers keep a '
                'per-slot state that no row holds, and the wire format '
                'has no state record yet (kv_transfer.py)')
        if self.cfg.latent:
            raise NotImplementedError(
                f'{self.cfg.name}: KV export/ingest (handoff, prefix '
                'snapshots) moves [pages, kv_heads, head_dim] rows; the '
                "wire format has no latent row yet (kv_transfer.py)")
        if self.cfg.n_loops > 1:
            raise NotImplementedError(
                f'{self.cfg.name}: KV export/ingest (handoff, prefix '
                'snapshots) checks [n_layers, rows, kv_heads] blocks; a '
                f'looped model caches {self.cfg.n_cache_layers} layers a '
                'token (kv_transfer.py)')

    # What one chunk program's transients may take beside the weights and
    # the pool, and what a score element of its attention costs at the
    # peak (the f32 logits with their exponentials and the cast
    # probabilities: 0.378 GB for 84 M more elements between two
    # compiles of GLM-4.7-Flash's program; compiler, PR 30). By this
    # count qwen2-7b.chat's largest program (32 prompts x 16 pages: 2.11
    # GB, which runs in the 2.9 GB that deployment leaves) is inside the
    # budget and the 32 x 32 that does not fit (compiler, PR 28) outside.
    _ATTN_SCORE_BYTES = 4.5
    _CHUNK_TRANSIENT_BUDGET = int(2.2e9)
    # float32 arrays of [heads, k_dim] a token that a recurrent mixer's
    # chunked form holds at once (its q, k, v, log decays, their
    # decayed forms and the WY terms): the compile for a described v5e
    # has 131 MB a 256-token piece of 64 heads x 128 (compiler, PR 37).
    _RECURRENT_TOKEN_ARRAYS = 16
    # ... of [d_inner] a token of a Mamba mixer's chunk (conv output,
    # step size, write, scan output, gate): jamba2-3b's chunk programs
    # grow by 72 MB a 256-token piece at 16 pages, 47 MB of it attention
    # scores (compiled for a described v5e).
    _MAMBA_TOKEN_ARRAYS = 5

    def _chunk_piece_bytes(self, pages: int,
                           width: Optional[int] = None) -> int:
        """What one prompt's piece of ``width`` tokens (``chunk``) adds
        to a chunk program's transients at the page bucket ``pages``: its
        attention scores [heads, width, pages x page]; routed experts'
        sorted rows (top-k copies of a token through gate, up and down);
        a recurrent mixer's float32 terms a token (of its kind)."""
        cfg = self.cfg
        width = width or self.chunk
        piece = pages * (cfg.n_heads * width * self.page
                         * self._ATTN_SCORE_BYTES)
        if cfg.ffn_kind == 'routed_shared':
            piece += width * cfg.n_experts_per_token * (
                6 * cfg.dim + 8 * cfg.moe_ffn_dim)
        if cfg.recurrent:
            spec = cfg.state_spec
            piece += width * spec.token_width * 4 * (
                self._MAMBA_TOKEN_ARRAYS if spec.kind == 'mamba'
                else self._RECURRENT_TOKEN_ARRAYS)
        return int(piece)

    def _chunk_batch_cap(self, batch: List[int]) -> int:
        """How many of ``batch``'s pieces one chunk program may hold. Its
        transients (``_chunk_piece_bytes``) are counted at the page
        bucket of the longest context in it and the prompt bucket above
        the count, and nothing else capped them: 8k-token prompts
        batched 32 wide ended the server with RESOURCE_EXHAUSTED.
        Slot-parity pools (the CPU) were sized against nothing and are
        not capped."""
        if not self._pool_auto_sized:
            return len(batch)
        from skypilot_tpu.inference.engine import _bucket_len
        pages, fit = 1, 1
        for count, slot in enumerate(batch, 1):
            req = self._slots[slot]
            rest = (len(req._ctx) - req._n_matched * self.page
                    - self._prefill_off[slot])
            pages = max(pages, _bucket_len(self._pages_needed(
                int(self._slot_len[slot]) + min(self.chunk, rest)),
                minimum=1))
            n = next(b for b in self._PREFILL_N_BUCKETS if b >= count)
            if n * self._chunk_piece_bytes(pages) \
                    > self._CHUNK_TRANSIENT_BUDGET:
                break
            fit = count
        return fit

    # ---------------------------------------------------------- admission
    def _pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page)

    def _ensure_pages(self, slot: int, upto_tokens: int) -> bool:
        """Grow the slot's page list to cover ``upto_tokens``; False if
        the pool is exhausted (caller backs off)."""
        need = self._pages_needed(upto_tokens)
        pages = self._pages[slot]
        grabbed = []
        try:
            while len(pages) < need:
                p = self.alloc.alloc()
                pages.append(p)
                grabbed.append(p)
            return True
        except MemoryError:
            for p in grabbed:
                pages.remove(p)
                self.alloc.release(p)
            return False

    def _free_slot(self, slot: int) -> None:
        for p in self._pages[slot]:
            self.alloc.release(p)
        self._pages[slot] = []
        self._prefill_off.pop(slot, None)        # cancel mid-prefill
        self._await_first.discard(slot)
        self._slot_inflight[slot] = 0
        self._slot_epoch[slot] += 1
        super()._free_slot(slot)

    def has_work(self) -> bool:
        self._purge_lagging()
        return super().has_work() or bool(self._lagging)

    def has_runnable_work(self) -> bool:
        # Purge finished early-freed stragglers FIRST: a finished
        # request parked in _lagging is not runnable work, and
        # counting it busy-spins the serve loop after every
        # budget-bound completion (and floods the gang op log with
        # no-op steps) until something else happened to call
        # has_work() and purge. The base check then sees the live
        # truth.
        self._purge_lagging()
        return super().has_runnable_work()

    def cancel(self, request_id: int) -> bool:
        if super().cancel(request_id):
            return True
        req = self._lagging.pop(request_id, None)
        if req is not None and req.finish_time is None:
            # Early-recycled: the slot/pages are already released; the
            # pipeline's remaining tail tokens are dropped at readback
            # by the finish_time check. NOT recorded as finished —
            # same contract as a slot cancel.
            req.finish_time = clock.now()
            self._trace_finish(req, cancelled=True)
            return True
        return False

    def _slot_remaining_prefill(self, slot: int) -> int:
        """Uncached prompt-tail tokens still to prefill: the context
        minus prefix-matched pages minus the chunk cursor."""
        off = self._prefill_off.get(slot)
        if off is None:
            return 0
        req = self._slots[slot]
        tail = len(req._ctx) - req._n_matched * self.page
        return max(0, tail - off)

    def _purge_lagging(self) -> None:
        if self._lagging:
            for rid in [rid for rid, r in self._lagging.items()
                        if r.finish_time is not None]:
                del self._lagging[rid]

    def _ring_horizon_bucket(self, ring_bytes: int) -> int:
        """The horizon BUCKET the ring budget admits — the one place
        this is computed: _auto_n_pages sizes the pool reserve with it
        and _enqueue_decode caps live horizons with it, and the two
        drifting apart re-creates the under/over-reserve OOMs (see the
        reserve note in _auto_n_pages)."""
        from skypilot_tpu.inference.engine import (_ring_horizon_cap,
                                                   _ring_row_bytes)
        row = _ring_row_bytes(self.cfg, self.max_batch, self.mesh)
        cap = min(self._HORIZON_BUCKETS[-1],
                  _ring_horizon_cap(self.cfg, self.max_batch,
                                    self._param_bytes, self.mesh),
                  max(8, ring_bytes // row))
        return next((b for b in reversed(self._HORIZON_BUCKETS)
                     if b <= cap), 8)

    def _maybe_early_free(self, slot: int, req) -> None:
        """Recycle the slot the moment the request's whole output is
        covered by ENQUEUED device calls. Only budget-bound requests
        qualify — stop sequences / eos make completion data-dependent,
        so those free at readback like before. The tail tokens surface
        later through the pipeline (entries hold the request object;
        ``_finish_req`` never touches a recycled slot), and the pages
        released here are only ever re-written by programs enqueued
        AFTER the in-flight reads/merges — the single device stream
        orders them. Without this, a finished slot decoded garbage for
        ~PIPELINE_DEPTH more horizons and then idled until readback:
        measured at 1790 tok/s steady, that waste held the sustained
        token YIELD (counted / issued slot-steps) at 0.44."""
        if req.stop or req.eos_id is not None or req._early_freed:
            return
        budget = min(req.max_new_tokens,
                     max(1, self.max_seq - len(req.prompt)))
        if req._enq_out >= budget:
            req._early_freed = True
            self._lagging[req.request_id] = req
            self._free_slot(slot)

    def _preempt_slot(self, slot: int) -> None:
        """Pool pressure: push a live request back to the FRONT of the
        queue, releasing its pages. It re-enters through _assign_slots
        with prompt+output as context (recompute) — generated tokens
        are kept, TTFT is not reset."""
        req = self._slots[slot]
        self.preemptions += 1
        # Content-address the full pages ALREADY WRITTEN for this
        # context before releasing them: re-admission then re-matches
        # them (refcount-0 registered pages retire into the LRU, which
        # allocation evicts only on demand) instead of recomputing the
        # whole context. Besides the work saved, the resumed KV is the
        # ORIGINAL bytes — a full recompute re-derives the generated
        # tokens' rows through the chunk-prefill program, whose bf16
        # rounding differs from the decode ring's by a few ULPs, enough
        # to flip near-tie argmaxes on resume. Rows are written for
        # ctx[:_slot_len] only (the current token's row rides the next
        # decode call), so registration is capped there — a mid-prefill
        # victim must not register pages it never filled.
        written = (req.prompt + req.output)[:int(self._slot_len[slot]) + 1]
        if self._pages[slot] and self._prefix_reuse:
            self.alloc.register_prefix(written, self._pages[slot],
                                       getattr(req, '_n_matched', 0))
        if req.trace is not None:
            # Close the in-slot spans; the re-admission re-opens
            # queue → prefill → decode, preserving the real timeline.
            req.trace.end('decode')
            req.trace.end('prefill')
            req.trace.begin('queue', preempted=True)
        self._free_slot(slot)
        self._requeue_front([req])

    def _admit(self) -> List[Tuple[int, int, bool]]:
        """Continuous admission: assign free slots immediately, then run
        at most ONE prefill chunk-batch before decode resumes. The
        round-4 wave-synchronous admission ran *every* chunk of a wave
        before any decode step — running requests stalled for the whole
        wave (the measured 7.8 s burst TTFT was this architecture).
        Interleaving one chunk per step bounds active-request TPOT at
        one chunk time while prompts stream in (the JetStream/vLLM
        continuous-batching admission contract, the capability the
        reference serves through those engines).

        BURST exception: while the batch is mostly EMPTY (cold start /
        arrival burst), the one-chunk-per-step TPOT bound protects
        almost nobody — so admission keeps running chunk batches until
        the DECODING population reaches a QUARTER of the batch. A
        2x-batch burst's median TTFT was ~7 s with strictly one
        chunk-batch per ~0.8 s horizon; filling the first slots
        back-to-back cuts the queue wait for everyone, while the low
        threshold keeps the loop from stalling a half-full batch of
        live streams behind a run of long prompts."""
        self._assign_slots()
        events = self._prefill_chunk_batch()
        while (self._prefill_off
               and sum(r is not None for r in self._slots)
               - len(self._prefill_off) < self.max_batch // 4):
            events += self._prefill_chunk_batch()
        return events

    def _assign_slots(self) -> None:
        for slot in range(self.max_batch):
            if self._slots[slot] is not None:
                continue
            req = self._queue_pop()
            if req is None:
                return
            # A preempted request re-enters with its generated tokens as
            # part of the context (preemption-by-recompute): prefilling
            # prompt+output resumes generation exactly where it stopped,
            # and the completed-prefill logits ARE its next token.
            ctx = req.prompt + req.output
            matched = (self.alloc.match_prefix(ctx) if self._prefix_reuse
                       else [])
            if self.rec is not None:
                # The slot's state starts from zeros at the first chunk
                # (``lengths == 0``); a resumed request's whole context
                # is prefilled again, because no state was kept.
                self._prof.note_state_reset(
                    recompute_tokens=len(ctx) if req.output else 0)
            # Quantize the resume point to the canonical chunk grid.
            # A cold prefill chunks from offset 0, so its boundaries are
            # exact multiples of the wide width while its pieces are wide
            # and of ``self.chunk`` after (``_on_cold_grid``); resuming a
            # prefix hit at
            # an arbitrary page boundary regroups the same attention
            # terms across cached_attention's two softmax blocks
            # (cache-sum + in-chunk-sum), and the few-ULP denominator
            # difference flips greedy argmax on near-tie logits — the
            # hit path would emit different bytes than the cold path for
            # the SAME request. Keeping only matched pages up to a
            # chunk-multiple boundary makes every hit-path chunk run the
            # byte-identical program on byte-identical operands (same
            # rationale as _preempt_slot registering original bytes).
            # ``alloc.prefix_hits`` still counts the match; surplus
            # pages return to the retained LRU, not the free list.
            # Preemption re-entry (req.output non-empty) is exempt: it
            # resumes from its OWN pages registered by _preempt_slot
            # with the original bytes, so the restore is exact and the
            # uninterrupted-run contract needs the mid-grid resume.
            if not req.output:
                keep = len(matched)
                while keep and not self._on_cold_grid(keep * self.page,
                                                      len(ctx)):
                    keep -= 1
                for p in matched[keep:]:
                    self.alloc.release(p)
                matched = matched[:keep]
            self._pages[slot] = list(matched)
            if not self._ensure_pages(slot, len(ctx)):
                # Pool pressure: back to the FRONT of the queue (tail
                # requeue would let later small requests starve it) and
                # stop admitting.
                for p in self._pages[slot]:
                    self.alloc.release(p)
                self._pages[slot] = []
                self._requeue_front([req])
                return
            self._slots[slot] = req
            self._slot_len[slot] = len(matched) * self.page
            req._n_matched = len(matched)        # host-only annotations
            req._ctx = ctx
            req._slot = slot
            if matched:
                # A prefix HIT is the strongest heat signal — shared
                # prefixes are exactly what the preemption checkpoint
                # should carry.
                self._note_hot_prefix(ctx)
            self._prefill_off[slot] = 0          # tail tokens done so far
            self._trace_sched(req)
            if req.trace is not None and matched:
                req.trace.instant('prefix_cache_hit',
                                  pages=len(matched))

    def _on_cold_grid(self, at: int, length: int) -> bool:
        """Whether a cold prefill of ``length`` tokens, alone from offset
        0, starts a piece at ``at``: it takes wide pieces while its tail
        holds one, so its boundaries are the multiples of the wide width
        up to the last of them within ``length`` and the multiples of
        ``chunk`` from there (every multiple of ``chunk`` where the two
        widths are one)."""
        wide = self._wide_chunk
        return at % self.chunk == 0 and (
            at % wide == 0 or at >= length // wide * wide)

    def _prefill_chunk_batch(self) -> List[Tuple[int, int, bool]]:
        """One fixed-size chunk across up to a compiled n-bucket of
        mid-prefill slots (the wide width for one slot alone whose tail
        holds it, ``wide_piece_width``). ALWAYS returns [] — completing slots'
        first tokens are sampled ON DEVICE (per-request params) and
        merged into the device token vector before this returns, so
        they decode next horizon; the first-token EVENT surfaces via
        ``_process_one`` up to ``_PIPELINE_DEPTH`` calls later."""
        pending = sorted(self._prefill_off)
        if not pending:
            return []
        batch = pending[:self._prefill_n_max]
        batch = batch[:self._chunk_batch_cap(batch)]
        n = next(b for b in self._PREFILL_N_BUCKETS if b >= len(batch))
        # Chunk-width variant: when every pending piece fits 128
        # tokens (the common case with a prefix-cache hit — e.g. a
        # 220-token prompt whose first page is cached leaves a <=92
        # token tail), the half-width program does half the prefill
        # FLOPs. Mixed batches fall back to the full chunk. Pure
        # arithmetic — no tail slicing here (a list copy per slot per
        # chunk made long-prompt prefill O(len^2/chunk) host work).
        rest_max = max(
            len(self._slots[s]._ctx)
            - self._slots[s]._n_matched * self.page
            - self._prefill_off[s]
            for s in batch)
        # A prompt alone whose tail holds a whole wide piece takes one:
        # the piece reads the weights once for that many tokens, and
        # several prompts of one batch already share that read.
        wide = (self._wide_chunk > self.chunk and len(batch) == 1
                and rest_max >= self._wide_chunk)
        if wide:
            chunk_w = self._wide_chunk
        else:
            chunk_w = (128 if self.chunk > 128 and rest_max <= 128
                       and self._narrow_chunk else self.chunk)
        tokens = np.zeros((n, chunk_w), np.int32)
        lengths = np.zeros(n, np.int32)
        valid = np.zeros(n, np.int32)
        want = np.full(n, -1, np.int32)
        P_needed = 1
        pieces: List[List[int]] = []
        for i, slot in enumerate(batch):
            req = self._slots[slot]
            tail = req._ctx[req._n_matched * self.page:]
            off = self._prefill_off[slot]
            piece = tail[off:off + chunk_w]
            pieces.append(piece)
            lengths[i] = self._slot_len[slot]
            tokens[i, :len(piece)] = piece
            valid[i] = len(piece)
            if off + len(piece) == len(tail):
                want[i] = len(piece) - 1
            P_needed = max(P_needed, self._pages_needed(
                int(lengths[i]) + int(valid[i])))
        for i in range(len(batch), n):           # padding rows: valid=0
            lengths[i] = self._slot_len[batch[0]]   # no row is written
        from skypilot_tpu.inference.engine import _bucket_len
        P = _bucket_len(P_needed, minimum=1)
        table_p = np.zeros((n, P), np.int32)
        for i, slot in enumerate(batch):
            ps = self._pages[slot][:P]
            table_p[i, :len(ps)] = ps
        # Per-row sampling params: completing rows sample their first
        # token ON DEVICE inside the prefill program (padding and
        # mid-prompt rows run greedy on garbage logits — discarded).
        temps = np.zeros(n, np.float32)
        topks = np.zeros(n, np.int32)
        topps = np.ones(n, np.float32)
        adp_h = (np.full(n, -1, np.int32)
                 if self.adapters is not None else None)
        vm_h = (np.ones((n, self.cfg.vocab_size), bool)
                if self._vmask_any else None)
        for i, slot in enumerate(batch):
            req = self._slots[slot]
            temps[i] = req.temperature
            topks[i] = req.top_k or 0
            topps[i] = req.top_p
            if adp_h is not None:
                adp_h[i] = req._adapter_slot
            if vm_h is not None and req._vocab_mask is not None:
                vm_h[i] = req._vocab_mask
        with self._prof.phase('eager', site='rng_split'):
            self._rng, prng = jax.random.split(self._rng)
        # ONE batched host->device transfer for every host-built
        # operand: each separate jnp.asarray is its own dispatch round
        # trip, nine of them per admission otherwise.
        # A recurrent model's rows name their slots (padding: past them).
        slots_h = None
        if self.rec is not None:
            slots_h = np.full(n, self.max_batch, np.int32)
            slots_h[:len(batch)] = batch
        extras = tuple(x for x in (adp_h, vm_h, slots_h) if x is not None)
        # Query-key pairs under the causal mask that the chunk needs, a
        # layer: each valid row against its context and the piece up to
        # itself; and its valid tokens. On the upload's annotation too,
        # so that a trace holds the counts of exactly the chunks it holds.
        pairs = sum(int(v) * int(c) + int(v) * (int(v) + 1) // 2
                    for v, c in zip(valid[:len(batch)],
                                    lengths[:len(batch)]))
        chunk_tokens = int(sum(valid[:len(batch)]))
        with self._prof.phase('admit_upload', pairs=pairs,
                              tokens=chunk_tokens), \
                self._prof.phase('upload'):
            uploaded = device_upload(
                (table_p, tokens, lengths, valid, want, temps, topks,
                 topps) + extras)
        (table_d, tokens_d, lengths_d, valid_d, want_d, temps_d,
         topks_d, topps_d) = uploaded[:8]
        rest = list(uploaded[8:])
        adp_d = rest.pop(0) if adp_h is not None else None
        vm_d = rest.pop(0) if vm_h is not None else None
        state_kwargs = ({} if slots_h is None
                        else {'rec': self.rec, 'slot_ids': rest.pop(0)})
        # Sampling variant only when a row COMPLETING this chunk needs
        # it: sample_tokens sorts the [n, vocab] logits (hundreds of ms
        # on TPU at vocab 32k) — mid-prompt chunks and greedy
        # completions must not pay it.
        sample = any(self._slots[s].temperature > 0
                     for i, s in enumerate(batch) if want[i] >= 0)
        prefill = self._get_prefill(n, P, sample, chunk_w)
        chunk_t0 = clock.monotonic()
        with self._prof.phase('prefill_chunk', prompts=n, pages=P,
                              width=chunk_w, sample=sample, wide=wide), \
                self._prof.phase('dispatch'), \
                self._prof.jit_key('prefill', (n, P, sample, chunk_w)):
            first, self.cache, *rec = prefill(
                self.params, self.cache, table_d, tokens_d, lengths_d,
                valid_d, want_d, adp_d, vm_d, temps_d, topks_d,
                topps_d, prng, **state_kwargs)
            if rec:
                self.rec, = rec
        chunk_t1 = clock.monotonic()
        self.chunks_prefilled += 1
        self._prof.note_prefill_pairs(
            pairs, tokens=chunk_tokens, wide=wide,
            scan=self.cfg.recurrent_kind == 'mamba')
        self._prof.note_pool_write(chunk_tokens, n * chunk_w)
        for i, slot in enumerate(batch):
            r = self._slots[slot]
            if r.trace is not None:
                r.trace.add('prefill_chunk', chunk_t0, chunk_t1,
                            offset=self._prefill_off[slot],
                            tokens=int(valid[i]))
        # Async: host bookkeeping advances NOW (the device writes are
        # program-ordered). Completing slots' sampled tokens merge into
        # the device token vector IMMEDIATELY (device-to-device, no
        # sync) so they decode on the very next horizon; _await_first
        # now gates only the first-token EVENT (host readback of the
        # token value rides the pipeline).
        done_rows: List[Tuple[int, int]] = []    # (row i, slot)
        for i, slot in enumerate(batch):
            req = self._slots[slot]
            self._slot_len[slot] += int(valid[i])
            self._prefill_off[slot] += int(valid[i])
            if want[i] < 0:
                continue                         # more chunks to go
            del self._prefill_off[slot]
            self._await_first.add(slot)
            if self._prefix_reuse:
                self.alloc.register_prefix(req._ctx, self._pages[slot],
                                           req._n_matched)
                self._note_hot_prefix(req._ctx)
            done_rows.append((i, slot))
        if done_rows:
            # FIXED [n] shapes for the token gather + merge: a
            # len(done_rows)-shaped array would compile a fresh tiny
            # gather/scatter program per distinct count (measured:
            # ~0.9 s per remote compile, dozens across a serving run —
            # the dominant admission cost). Padding rows point at row
            # 0 and scatter to the out-of-range sentinel max_batch,
            # which mode='drop' discards.
            rows_p = np.zeros(n, np.int32)
            slots_p = np.full(n, self.max_batch, np.int32)
            for j, (i, slot) in enumerate(done_rows):
                rows_p[j], slots_p[j] = i, slot
            with self._prof.phase('admit_token_merge'):
                with self._prof.phase('upload'):
                    rows_d, slots_d = device_upload((rows_p, slots_p))
                with self._prof.phase('eager', site='first_token_take'):
                    picked = jnp.take(first, rows_d)
                with self._prof.phase('dispatch'):
                    self._tok_dev = self._merge_tokens_drop(
                        self._tok_dev, slots_d, picked)
            self._meta_dirty = True          # slots become decodable
            self._pending.append({
                'kind': 'prefill', 'toks': first,
                'batch': [(slot, self._slots[slot], i)
                          for i, slot in done_rows]})
            for i, slot in done_rows:
                req = self._slots[slot]
                # re-admission resumes with output already present
                req._enq_out = len(req.output) + 1
                self._maybe_early_free(slot, req)
        return []

    # ------------------------------------------------ prefix heat
    def _note_hot_prefix(self, tokens: List[int]) -> None:
        """Record one use (registration or future-worthy context) of
        the prefix chain covering ``tokens``' full pages — the
        preemption checkpoint exports the hottest. Host-side dict ops
        only; bounded at _PREFIX_HEAT_MAX entries (coldest evicted)."""
        full = (len(tokens) - 1) // self.page
        if full < 1:
            return
        covered = full * self.page
        key = hashlib.sha1(np.asarray(
            tokens[:covered], np.int32).tobytes()).digest()
        rec = self._prefix_heat.get(key)
        if rec is not None:
            rec['hits'] += 1
            return
        if len(self._prefix_heat) >= self._PREFIX_HEAT_MAX:
            coldest = min(self._prefix_heat,
                          key=lambda k: self._prefix_heat[k]['hits'])
            del self._prefix_heat[coldest]
        self._prefix_heat[key] = {'tokens': list(tokens[:covered + 1]),
                                  'hits': 1}

    def hot_prefix_digest(self, max_entries: int = 16):
        """The hottest prefix chains as a bounded, wire-cheap digest:
        ``[{'hash': <sha1 hex of the page-grid token bytes>, 'len':
        <covered token count>, 'hits': n}, ...]`` hottest-first, at
        most ``max_entries``. Built from the host-side heat tracker
        ONLY — no allocator matching, no device gather, zero d2h —
        so the /metrics probe path can ship it on every scrape. The
        LB recomputes the same sha1 over a prompt's page-grid
        prefixes to find the longest match (prefix-affinity
        routing). A hash may name a chain the allocator has since
        evicted; affinity is a routing hint, not a guarantee."""
        by_heat = sorted(self._prefix_heat.items(),
                         key=lambda kv: -kv[1]['hits'])
        return [{'hash': key.hex(),
                 'len': len(rec['tokens']) - 1,
                 'hits': int(rec['hits'])}
                for key, rec in by_heat[:max_entries]]

    def drain_pipeline(self):
        """Gang ``flush`` op (see ``_EngineBase.drain_pipeline``): on
        top of syncing the in-flight device calls, the paged engine
        must also surface its pool-pressure deferred-event stash —
        otherwise a leader that flushed before a checkpoint and a
        follower that didn't would emit the same tokens in different
        step batches and the finished-digest comparison would be
        comparing mid-stream states."""
        events = super().drain_pipeline()
        if self._deferred_events:
            events.extend(self._deferred_events)
            self._deferred_events = []
        return events

    def export_prefix_snapshots(self, max_entries: int = 8):
        """The hottest still-cached prefix chains as prefix entries
        (``kv_transfer`` SKPF dicts): per chain, re-match its pages in
        the allocator (a chain evicted since it was hot exports
        nothing) and gather the page rows in the pool's STORED dtype
        through the same compiled gather the KV handoff uses. Returns
        ``(entries, drained_events)`` — the async pipeline is drained
        first so the pool rows are final; the caller routes the events
        exactly like ``step()`` events."""
        from skypilot_tpu.inference.engine import _bucket_len
        events: List[Tuple[int, int, bool]] = []
        while self._pending:
            events.extend(self._process_one())
        entries: List[Dict[str, Any]] = []
        by_heat = sorted(self._prefix_heat.values(),
                         key=lambda r: -r['hits'])
        for rec in by_heat:
            if len(entries) >= max_entries:
                break
            entry = self._export_prefix_record(rec)
            if entry is not None:
                entries.append(entry)
        return entries, events

    def _export_prefix_record(self, rec: Dict[str, Any]
                              ) -> Optional[Dict[str, Any]]:
        """Gather one heat record's still-cached chain as a prefix
        entry (None if the allocator evicted it). Callers own pipeline
        draining."""
        from skypilot_tpu.inference.engine import _bucket_len
        cfg = self.cfg
        tokens = rec['tokens']
        pages = self.alloc.match_prefix(tokens)
        if not pages:
            return None
        n_rows = len(pages) * self.page
        try:
            P = _bucket_len(len(pages), minimum=1)
            table = np.zeros((P,), np.int32)
            table[:len(pages)] = pages
            with self._prof.phase('upload'):
                table_d = device_upload(table)
            out = self._get_export(P)(self.cache, table_d)
            # Sanctioned d2h: the checkpoint export IS a host
            # readback by design (the rows leave on the wire or
            # land in a checkpoint file).
            host = host_sync(out)
        finally:
            for p in pages:
                self.alloc.release(p)
        if self.cache.quantized:
            k, v, ks, vs = host
            k, v = k[:, :n_rows], v[:, :n_rows]
            ks, vs = ks[:, :n_rows], vs[:, :n_rows]
        else:
            k, v = host
            k, v = k[:, :n_rows], v[:, :n_rows]
            ks = vs = None
        return {
            'kv_cache_dtype': self.kv_cache_dtype,
            'n_rows': n_rows,
            'model': {'n_layers': cfg.n_layers,
                      'n_kv_heads': cfg.n_kv_heads,
                      'head_dim': cfg.head_dim},
            'tokens': list(tokens[:n_rows + 1]),
            'k': k, 'v': v, 'k_scale': ks, 'v_scale': vs,
        }

    def export_prefix_entry(self, hash_hex: str):
        """One hot chain — named by its digest hash — as a prefix
        entry: ``(entry_or_None, drained_events)``. The proactive
        affinity migration path: the LB asks the source replica for
        exactly the chain whose digest match lost to load, ships the
        blob to the target's warmup endpoint, and the prefix is warm
        there without a single recomputed token. None when the heat
        record or its pages are gone (the digest was a stale hint)."""
        try:
            key = bytes.fromhex(hash_hex)
        except ValueError:
            return None, []
        rec = self._prefix_heat.get(key)
        if rec is None:
            return None, []
        events: List[Tuple[int, int, bool]] = []
        while self._pending:
            events.extend(self._process_one())
        return self._export_prefix_record(rec), events

    def warm_prefix(self, entry: Dict[str, Any]) -> int:
        """Land a prefix entry into the prefix cache without seating a
        request: allocate pages, scatter the rows at their exact
        original bytes, ``register_prefix`` the chain, then release
        the pages into the reusable LRU — future prompts sharing the
        prefix hit the ORIGINAL KV. Idempotent: a chain already fully
        cached lands nothing. Returns rows landed; raises
        ``ValueError`` on mismatch (permanent) and
        ``HandoffCapacityError`` on pool pressure (retryable)."""
        from skypilot_tpu.inference.engine import HandoffCapacityError
        if 'tokens' not in entry:
            from skypilot_tpu.inference import kv_transfer
            entry = kv_transfer.as_prefix_entry(entry)
        n_rows = int(entry['n_rows'])
        tokens = [int(t) for t in entry['tokens']]
        if len(tokens) < n_rows + 1:
            raise ValueError(
                f'prefix entry carries {len(tokens)} token(s) for '
                f'{n_rows} row(s); need n_rows + 1')
        self._validate_kv_entry(entry, n_rows)
        # Land whole pages only (this engine's page size — normally
        # identical to the exporter's, but a partial tail page cannot
        # be content-addressed either way).
        full = n_rows // self.page
        if full < 1:
            return 0
        rows_used = full * self.page
        prefix_tokens = tokens[:rows_used + 1]
        matched = self.alloc.match_prefix(prefix_tokens)
        already = len(matched)
        for p in matched:
            self.alloc.release(p)
        if already >= full:
            return 0                       # already warm
        if self.alloc.available < full:
            raise HandoffCapacityError(
                f'KV page pool exhausted ({self.alloc.available} '
                f'page(s) free, {full} needed for prefix warmup)')
        pages = [self.alloc.alloc() for _ in range(full)]
        try:
            self._scatter_snapshot_rows(pages, entry, rows_used)
            self.alloc.register_prefix(prefix_tokens, pages, 0)
        except Exception:
            for p in pages:
                self.alloc.release(p)
            raise
        # refcount -> 0: freshly hashed pages retire into the
        # prefix-reusable LRU (warm); pages whose hash already existed
        # (shared with a cached chain) recycle to the free list.
        for p in pages:
            self.alloc.release(p)
        self._note_hot_prefix(prefix_tokens)
        return rows_used

    # ---------------------------------------------------- KV handoff
    def _get_export(self, P: int):
        """Compiled page gather for one slot's handoff export: the
        first ``P`` pages as token-major [L, P*page, hkv, d] rows (+
        [L, P*page, hkv] scales), in the pool's STORED dtype — int8
        codes and fp32 scales leave exactly as resident, never
        dequantized (the int8-on-the-wire contract GC114 gates)."""
        if P in self._export_fns:
            return self._export_fns[P]
        self._refuse_kv_transfer()
        page = self.page
        quantized = self.cache.quantized

        @jax.jit
        def export(cache, table):          # table [P] page ids
            def tok_major(pool):
                g = pool[:, table]         # [L, P, hkv, page(, d)]
                if g.ndim == 5:
                    g = g.transpose(0, 1, 3, 2, 4)
                else:
                    g = g.transpose(0, 1, 3, 2)
                return g.reshape((g.shape[0], P * page) + g.shape[3:])

            k, v = tok_major(cache.pool_k), tok_major(cache.pool_v)
            if quantized:
                return (k, v, tok_major(cache.k_scale),
                        tok_major(cache.v_scale))
            return k, v

        self._export_fns[P] = export
        return export

    def _gather_kv_rows(self, slot: int, n_rows: int):
        from skypilot_tpu.inference.engine import _bucket_len
        P = _bucket_len(self._pages_needed(max(1, n_rows)), minimum=1)
        table = np.zeros((P,), np.int32)
        ps = self._pages[slot][:P]
        table[:len(ps)] = ps
        with self._prof.phase('upload'):
            table_d = device_upload(table)
        out = self._get_export(P)(self.cache, table_d)
        # Sanctioned d2h: the handoff export IS a host readback by
        # design (the rows leave this process on the wire).
        host = host_sync(out)
        if self.cache.quantized:
            k, v, ks, vs = host
            return (k[:, :n_rows], v[:, :n_rows], ks[:, :n_rows],
                    vs[:, :n_rows])
        k, v = host
        return k[:, :n_rows], v[:, :n_rows], None, None

    def _get_ingest(self, nb: int, P: int):
        """Compiled handoff merge: land a [L, 1, nb, hkv(, d)] row
        batch into the pool through a [1, P] page table (padding rows
        past ``valid`` reach no page a request reads). Donates the pool —
        the scatter runs in place like every other merge."""
        key = (nb, P)
        if key in self._ingest_fns:
            return self._ingest_fns[key]
        self._refuse_kv_transfer()
        quantized = self.cache.quantized
        mesh = self.mesh

        if quantized:
            @functools.partial(jax.jit, donate_argnums=(0,),
                               **self._step_out_shardings(0))
            def ingest(cache, kq, ks, vq, vs, table, starts, valid):
                return merge_rows_into_pool(cache, (kq, ks), (vq, vs),
                                            table, starts, valid,
                                            mesh=mesh)
        else:
            @functools.partial(jax.jit, donate_argnums=(0,),
                               **self._step_out_shardings(0))
            def ingest(cache, kr, vr, table, starts, valid):
                return merge_rows_into_pool(cache, kr, vr, table,
                                            starts, valid, mesh=mesh)

        self._ingest_fns[key] = ingest
        return ingest

    def _scatter_snapshot_rows(self, pages: List[int], snap,
                               n_rows: int) -> None:
        """Compiled scatter of ``n_rows`` stored-dtype snapshot rows
        into ``pages`` (shared by the KV-handoff land and the prefix
        warmup — both land wire bytes at their exact original
        values)."""
        from skypilot_tpu.inference.engine import _bucket_len
        cfg = self.cfg
        P = _bucket_len(len(pages), minimum=1)
        # Row bucket: bounded compiled-program count. nb may exceed
        # P*page for non-power-of-two page sizes; padding rows past
        # ``valid`` are skipped or masked to the trash page (their
        # clamped table lookups are discarded), so the overshoot is
        # harmless.
        nb = _bucket_len(n_rows, minimum=8)
        table = np.zeros((1, P), np.int32)
        table[0, :len(pages)] = pages

        def pad(arr, tail):
            out = np.zeros((cfg.n_layers, 1, nb, cfg.n_kv_heads)
                           + tail, dtype=arr.dtype)
            out[:, 0, :n_rows] = np.asarray(arr, dtype=arr.dtype)[
                :, :n_rows].reshape(
                (cfg.n_layers, n_rows, cfg.n_kv_heads) + tail)
            return out

        starts = np.zeros(1, np.int32)
        valid = np.array([n_rows], np.int32)
        ingest = self._get_ingest(nb, P)
        # Packed int4 rows carry head_dim/2 code bytes per row — the
        # scatter is tail-shape-generic, only the pad buffer cares.
        code_d = cfg.head_dim // 2 if self.cache.packed else cfg.head_dim
        if self.cache.quantized:
            with self._prof.phase('upload'):
                (kq, ks, vq, vs, table_d, starts_d,
                 valid_d) = device_upload(
                    (pad(snap['k'], (code_d,)),
                     pad(snap['k_scale'], (1,)),
                     pad(snap['v'], (code_d,)),
                     pad(snap['v_scale'], (1,)), table, starts, valid))
            self.cache = ingest(self.cache, kq, ks, vq, vs,
                                table_d, starts_d, valid_d)
        else:
            with self._prof.phase('upload'):
                kr, vr, table_d, starts_d, valid_d = device_upload(
                    (pad(snap['k'], (cfg.head_dim,)),
                     pad(snap['v'], (cfg.head_dim,)), table, starts,
                     valid))
            self.cache = ingest(self.cache, kr, vr, table_d,
                                starts_d, valid_d)

    def _land_kv_rows(self, slot: int, req, snap) -> None:
        from skypilot_tpu.inference.engine import HandoffCapacityError
        n_rows = int(snap['n_rows'])
        ctx = req.prompt + req.output
        self._pages[slot] = []
        if not self._ensure_pages(slot, max(1, n_rows)):
            raise HandoffCapacityError(
                f'KV page pool exhausted ({self.alloc.available} '
                f'page(s) free, {self._pages_needed(n_rows)} needed)')
        try:
            self._scatter_snapshot_rows(self._pages[slot], snap, n_rows)
            # Content-address the landed full pages: future prompts
            # sharing the prefix hit them, and a preempt/resume of
            # THIS request re-matches the original bytes.
            # register_prefix validates page-count vs token-length —
            # the truncated-handoff guard.
            self.alloc.register_prefix(ctx, self._pages[slot], 0)
            self._note_hot_prefix(ctx)
        except Exception:
            for p in self._pages[slot]:
                self.alloc.release(p)
            self._pages[slot] = []
            raise
        req._ctx = ctx
        req._n_matched = 0

    # ------------------------------------------------------- speculative
    def _spec_room(self, slot: int) -> int:
        """Proposal cap from page availability: reserve pages for
        len + k + 1 rows; under pool pressure shrink the cover (masked
        commits write at most that many rows) down to 1; -1 when even
        one more token has no page (the mixin then routes the slot
        through ``_spec_starved``)."""
        base = int(self._slot_len[slot])
        for cover in range(self.speculate_k + 1, 0, -1):
            if self._ensure_pages(slot, base + cover):
                return cover - 1
        return -1

    def _spec_starved(self, slots: List[int]) -> None:
        """Pool exhausted for these slots even at one token: preempt
        them back to the queue (vLLM-style recompute — same contract as
        the decode path's pool-pressure preemption). The oldest live
        request is never in this set in practice: ``_spec_room`` is
        called in slot order after earlier slots reserved their pages,
        and ``_validate_request`` guarantees any single request fits
        the pool alone once the others release."""
        for slot in slots:
            if self._slots[slot] is not None:
                with self._prof.phase('preempt'):
                    self._preempt_slot(slot)

    def _get_spec_verify(self, n: int, P: int, sample: bool):
        key = (self.speculate_k, sample, P)
        if key not in self._spec_verify_fns:
            cfg = self.cfg
            w8a8 = self.prefill_w8a8

            mesh = self.mesh

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._step_out_shardings(3))
            def verify(params, cache, table_p, tokens, proposals,
                       n_prop, lengths, active, adp, vmask, temps,
                       topks, topps, rng):
                return paged_spec_verify(
                    params, cache, table_p, tokens, proposals, n_prop,
                    lengths, active, cfg, sample=sample,
                    temps=temps, topks=topks, topps=topps, rng=rng,
                    w8a8=w8a8, mesh=mesh, mlora_idx=adp,
                    vocab_mask=vmask)

            self._spec_verify_fns[key] = verify
        return self._spec_verify_fns[key]

    def _spec_verify_call(self, ready, proposals, n_prop):
        from skypilot_tpu.inference.engine import _bucket_len
        temps_d, topks_d, topps_d, active_d, sample = \
            self._slot_meta(ready)
        P_needed = max(max((len(self._pages[s])
                            for s, r in enumerate(ready)
                            if r is not None), default=1), 1)
        P = _bucket_len(P_needed, minimum=1)
        table_p = np.zeros((self.max_batch, P), np.int32)
        for s in range(self.max_batch):
            ps = self._pages[s][:P]
            table_p[s, :len(ps)] = ps
        lengths = self._slot_len.astype(np.int32)
        with self._prof.phase('eager', site='rng_split'):
            self._rng, rng = jax.random.split(self._rng)
        with self._prof.phase('upload'):
            table_d, prop_d, n_prop_d, lengths_d = device_upload(
                (table_p, proposals, n_prop, lengths))
        verify = self._get_spec_verify(self.max_batch, P, sample)
        with self._prof.phase('dispatch'), \
                self._prof.jit_key('spec_verify',
                                   (self.speculate_k, sample, P)):
            commit, n_commit, self._tok_dev, self.cache = verify(
                self.params, self.cache, table_d, self._tok_dev, prop_d,
                n_prop_d, lengths_d, active_d, self._adp_dev,
                self._vmask_dev, temps_d, topks_d, topps_d, rng)
        return commit, n_commit

    def _spec_can_fuse(self, slot: int, rounds: int) -> bool:
        """Up-front page reservation for the fused in-scan rounds: the
        device commits up to ``rounds * (k + 1)`` rows with no host
        between rounds, so every covering page must exist BEFORE
        dispatch. Returning False sends the mixin to the single-round
        ``_spec_step`` (which shrinks its cover per round under pool
        pressure). Pages reserved here stay with the slot either way
        and release at slot free."""
        base = int(self._slot_len[slot])
        return self._ensure_pages(
            slot, base + rounds * (self.speculate_k + 1))

    def _get_spec_fused(self, n: int, P: int, sample: bool,
                        rounds: int):
        """Compiled in-scan speculative rounds over the paged pool:
        ``rounds`` x (device n-gram propose → ``paged_spec_verify`` →
        masked merge) fused into ONE program via lax.scan, with the
        per-slot lengths, history window, and remaining-token budgets
        carried between rounds. jit key: (k, sample, P, rounds)."""
        key = ('fused', self.speculate_k, sample, P, rounds)
        if key not in self._spec_verify_fns:
            from skypilot_tpu.inference import speculative
            cfg = self.cfg
            w8a8 = self.prefill_w8a8
            mesh = self.mesh
            k = self.speculate_k
            max_ngram = self.spec_max_ngram
            H = self.spec_hist_window

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._step_out_shardings(4))
            def fused(params, cache, table_p, tokens, hist, rem,
                      lengths, active, adp, vmask, temps, topks, topps,
                      rngs):
                def round_body(carry, rng):
                    cache, tok, hist, rem, lens = carry
                    prop, n_prop = speculative.ngram_propose_device(
                        hist, k, max_ngram=max_ngram)
                    # Budget carry: _spec_build_proposals's cap,
                    # applied round by round on device (n_commit <=
                    # n_prop + 1 <= rem never overshoots).
                    n_prop = jnp.minimum(n_prop,
                                         jnp.maximum(rem - 1, 0))
                    act = active & (rem >= 1)
                    commit, n_commit, new_tok, new_cache = \
                        paged_spec_verify(
                            params, cache, table_p, tok, prop, n_prop,
                            lens, act, cfg, sample=sample, temps=temps,
                            topks=topks, topps=topps, rng=rng,
                            w8a8=w8a8, mesh=mesh, mlora_idx=adp,
                            vocab_mask=vmask)
                    # History carry: append the commit row and
                    # re-right-align (shift left by n_commit).
                    combined = jnp.concatenate([hist, commit], axis=1)
                    gidx = (jnp.arange(H, dtype=jnp.int32)[None, :]
                            + n_commit[:, None])
                    new_hist = jnp.take_along_axis(combined, gidx,
                                                   axis=1)
                    return ((new_cache, new_tok, new_hist,
                             rem - n_commit, lens + n_commit),
                            (commit, n_commit, n_prop))

                (cache, tokens, hist, rem, lengths), stacked = \
                    lax.scan(round_body,
                             (cache, tokens, hist, rem, lengths), rngs)
                commits, n_commits, n_props = stacked
                return commits, n_commits, n_props, tokens, cache

            self._spec_verify_fns[key] = fused
        return self._spec_verify_fns[key]

    def _spec_fused_call(self, ready, rounds):
        """Dispatch ``rounds`` fused propose→verify→commit rounds in
        one jitted call (``_spec_step_fused``). ``_spec_can_fuse``
        already reserved pages covering the worst-case growth, so the
        page table built here spans every in-scan commit."""
        from skypilot_tpu.inference.engine import _bucket_len
        temps_d, topks_d, topps_d, active_d, sample = \
            self._slot_meta(ready)
        P_needed = max(max((len(self._pages[s])
                            for s, r in enumerate(ready)
                            if r is not None), default=1), 1)
        P = _bucket_len(P_needed, minimum=1)
        table_p = np.zeros((self.max_batch, P), np.int32)
        for s in range(self.max_batch):
            ps = self._pages[s][:P]
            table_p[s, :len(ps)] = ps
        lengths = self._slot_len.astype(np.int32)
        hist, rem = self._spec_hist_state(ready)
        with self._prof.phase('eager', site='rng_split'):
            keys = jax.random.split(self._rng, rounds + 1)
            self._rng, round_keys = keys[0], keys[1:]
        with self._prof.phase('upload'):
            table_d, hist_d, rem_d, lengths_d = device_upload(
                (table_p, hist, rem, lengths))
        fused = self._get_spec_fused(self.max_batch, P, sample, rounds)
        with self._prof.phase('dispatch'), \
                self._prof.jit_key('spec_fused',
                                   (self.speculate_k, sample, P, rounds)):
            commits, n_commits, n_props, self._tok_dev, self.cache = \
                fused(self.params, self.cache, table_d, self._tok_dev,
                      hist_d, rem_d, lengths_d, active_d, self._adp_dev,
                      self._vmask_dev, temps_d, topks_d, topps_d,
                      round_keys)
        return commits, n_commits, n_props

    def step(self, horizon: int = 1) -> List[Tuple[int, int, bool]]:
        """Admit (one chunk max), then enqueue decode through the async
        pipeline (_EngineBase semantics: results lag enqueues by up to
        _PIPELINE_DEPTH calls). While prompts are still streaming in,
        the decode horizon is capped at ``interleave_horizon`` so the
        next chunk runs within a bounded number of decode steps
        (admission latency), and capped at a medium bucket while the
        queue is non-empty so freed slots are noticed promptly. Steady
        state (no queue, no prefill) runs the caller's full horizon.
        ``speculate_k > 0`` replaces the fused decode horizon with one
        synchronous propose→verify→commit round per step; adding
        ``decode_steps_per_call > 1`` fuses that many rounds into one
        dispatch instead (in-scan speculative verify)."""
        events: List[Tuple[int, int, bool]] = []
        with self._prof.phase('readback'):
            while len(self._pending) >= self._PIPELINE_DEPTH:
                events.extend(self._process_one())
        with self._prof.phase('admit'):
            events.extend(self._admit())
        if self.speculate_k:
            if (self.decode_steps_per_call or 0) > 1:
                events.extend(self._spec_step_fused())
            else:
                events.extend(self._spec_step())
            if self._deferred_events:
                events.extend(self._deferred_events)
                self._deferred_events = []
            return events
        if self.decode_steps_per_call:
            # Multi-step pin: exactly k fused steps per call (the
            # dispatch-amortization knob wins over interleave/queue
            # shrinks; capacity caps still apply in _enqueue_decode).
            horizon = self.decode_steps_per_call
        elif self._prefill_off:
            # decode_priority_ratio switches the fixed interleave
            # horizon to the Sarathi-style token-budget split
            # (``_EngineBase._interleave_horizon``); None keeps the
            # fixed cap below.
            horizon = min(horizon,
                          self.interleave_horizon
                          if self.decode_priority_ratio is None
                          else self._interleave_horizon())
        elif self._queue:
            horizon = min(horizon, 32)
        with self._prof.phase('decode_enqueue'):
            enqueued = self._enqueue_decode(horizon)
        if not enqueued and self._pending:
            with self._prof.phase('readback'):
                events.extend(self._process_one())
        # Opportunistic drain: surface any entry whose device results
        # are ALREADY ready (non-blocking probe) instead of letting it
        # age up to _PIPELINE_DEPTH calls — at a 32-step horizon that
        # lag added ~1.5 s to every first-token/finish event. (Tests
        # pinning recycle-window behavior turn it off: on CPU every
        # result is instantly ready and the window collapses.)
        if self._eager_drain:
            with self._prof.phase('readback'):
                while self._pending:
                    probe = getattr(self._pending[0]['toks'],
                                    'is_ready', None)
                    # Probe OUTSIDE any except: an exception from
                    # result processing itself must propagate (the
                    # entry is already popped — swallowing it would
                    # drop tokens and strand inflight counts).
                    if probe is None or not probe():
                        break
                    events.extend(self._process_one())
        if self._deferred_events:        # pool-pressure pipeline drain
            events.extend(self._deferred_events)
            self._deferred_events = []
        return events

    interleave_horizon = 8

    # ---------------------------------------------------------- decode
    def _enqueue_decode(self, horizon: int = 1) -> bool:
        # _await_first slots DO decode: their device-sampled first
        # token was merged into the token vector at prefill enqueue;
        # only the first-token EVENT is still in flight. Held slots
        # (disaggregated handoff pending) never decode.
        active_slots = [s for s in range(self.max_batch)
                        if self._slots[s] is not None
                        and s not in self._prefill_off
                        and not self._slots[s].hold]
        if not active_slots:
            return False
        cap = int(self.max_seq - 1 -
                  max(self._slot_len[s] + self._slot_inflight[s]
                      for s in active_slots))
        if cap < 1:
            return False
        horizon = max(1, min(horizon, cap))
        from skypilot_tpu.inference.engine import (_ring_horizon_cap,
                                                   _ring_row_bytes)
        # Ring budget: auto-sized pools reserved HBM for the full
        # _RING_BYTES_CAP_PAGED ring (see _auto_n_pages — horizon 32
        # on the 7B config), so they take it; explicit pools keep the
        # historical conservative 512 MB cap, since nothing shrank
        # them to pay for a bigger ring (h=32 at batch 48 on a 7B
        # OOM'd at runtime against a full-HBM pool where h=16 ran).
        ring_bytes = (self._RING_BYTES_CAP_PAGED
                      if self._pool_auto_sized else int(512e6))
        horizon = min(horizon, self._ring_horizon_bucket(ring_bytes))
        if self.decode_steps_per_call is None:
            for b in reversed(self._HORIZON_BUCKETS):
                if b <= horizon:
                    horizon = b
                    break
        # else: multi-step pin — run EXACTLY k (capacity-clamped) so
        # the jit key stays (k, sample, P) and the audit's
        # one-dispatch-per-k-tokens contract holds.
        # page capacity: every active slot must hold pages for
        # len+inflight+horizon; shrink the horizon under pool pressure,
        # and when even horizon=1 cannot fit, PREEMPT the newest request
        # back to the queue (vLLM-style recompute: it re-enters with
        # prompt+output as its context) instead of crashing — the
        # auto-sized pool may legitimately be smaller than
        # slots x max_seq. Preemption must see COMPLETE outputs (the
        # requeued context is prompt+output), so with calls in flight
        # the pipeline drains first and the step retries.
        def covered(s, extra):
            return self._ensure_pages(
                s, int(self._slot_len[s] + self._slot_inflight[s]) +
                extra)

        while True:
            while horizon > 1:
                if all(covered(s, horizon) for s in active_slots):
                    break
                horizon //= 2
            if horizon > 1 or all(covered(s, 1) for s in active_slots):
                break
            if self._pending:
                # In-flight tokens would be lost by preempting now:
                # drain into the deferred stash (step() flushes it into
                # its returned events) and retry next step.
                drained = list(self._deferred_events)
                self._deferred_events = []
                with self._prof.phase('pressure_drain'):
                    while self._pending:
                        drained.extend(self._process_one())
                self._deferred_events = drained
                return False
            # Victim pool: every occupied slot (mid-prefill ones hold
            # pages too) EXCEPT the oldest decodable request — keeping
            # that one guarantees progress, and _validate_request
            # guarantees it fits the pool alone.
            oldest = min(active_slots,
                         key=lambda s: self._slots[s].request_id)
            cands = [s for s in range(self.max_batch)
                     if self._slots[s] is not None and s != oldest]
            if not cands:
                raise MemoryError(
                    'KV page pool exhausted even at horizon=1 with one '
                    'active request; raise n_pages')
            victim = max(cands, key=lambda s: self._slots[s].request_id)
            with self._prof.phase('preempt'):
                self._preempt_slot(victim)
            if victim in active_slots:
                active_slots.remove(victim)

        ready = self._decode_ready()
        temps_d, topks_d, topps_d, active_d, sample = \
            self._slot_meta(ready)
        from skypilot_tpu.inference.engine import _bucket_len
        max_pages_live = max(
            self._pages_needed(int(self._slot_len[s] +
                                   self._slot_inflight[s]) + horizon)
            for s in active_slots)
        P = _bucket_len(max_pages_live, minimum=1)
        table_p = np.zeros((self.max_batch, P), np.int32)
        for s in range(self.max_batch):
            ps = self._pages[s][:P]
            table_p[s, :len(ps)] = ps
        # Device-truth lengths at this call = processed + in-flight.
        lengths = (self._slot_len + self._slot_inflight).astype(np.int32)
        with self._prof.phase('eager', site='rng_split'):
            self._rng, rng = jax.random.split(self._rng)
        with self._prof.phase('upload'):
            table_dd, lengths_dd = device_upload((table_p, lengths))
        # Per-substep attribution: one dispatch covers ``horizon``
        # decode substeps (multi-step amortization; the profiler's
        # per_substep_ms split makes it visible).
        self._prof.note_substeps('decode_enqueue', horizon,
                                 live_rows=len(active_slots),
                                 moe_layers=self._moe_layers,
                                 top_k=self.cfg.n_experts_per_token)
        if self.cfg.latent:
            # What the latent paged kernel reads of the padded table.
            self._prof.note_decode_attn_pages(
                horizon * sum(self._pages_needed(int(lengths[s]))
                              for s in active_slots),
                horizon * self.max_batch * P)
        self._prof.note_pool_write(horizon * len(active_slots),
                                   horizon * self.max_batch)
        self._prof.tag(horizon=horizon, pages=P)
        with self._prof.phase('dispatch'), \
                self._prof.jit_key('decode', (horizon, sample, P)):
            toks, self.cache = self._decode_fn(
                self.params, self.cache, table_dd,
                self._tok_dev, lengths_dd, rng,
                temps_d, topks_d, topps_d, active_d, self._adp_dev,
                self._vmask_dev, horizon, sample)
        self._note_decode_step(
            int(sum(int(lengths[s]) for s in active_slots)))
        with self._prof.phase('eager', site='token_slice'):
            self._tok_dev = toks[:self.max_batch, -1]
        # Snapshot the epochs BEFORE any early free below bumps them:
        # the entry must record the epochs its tokens were produced
        # under, or a recycled slot's stale entry would pass the epoch
        # check at readback and decrement the NEW tenant's in-flight
        # count (understated lengths -> decode overwrites in-flight KV
        # positions).
        epochs = self._slot_epoch.copy()
        for s in range(self.max_batch):
            if ready[s] is not None:
                self._slot_inflight[s] += horizon
                ready[s]._enq_out += horizon
                self._maybe_early_free(s, ready[s])
        self._pending.append({'kind': 'decode', 'toks': toks,
                              'horizon': horizon,
                              'snapshot': list(ready),
                              'epochs': epochs})
        return True

    def _process_one(self) -> List[Tuple[int, int, bool]]:
        """Sync the oldest in-flight call into events. Prefill entries
        carry the DEVICE-sampled first tokens (already merged into the
        device token vector at enqueue — the slot has been decoding
        since the next horizon); this readback only surfaces the token
        VALUE for the first-token event, host bookkeeping, and finish
        checks."""
        events: List[Tuple[int, int, bool]] = []
        entry = self._pending.popleft()
        # THE sanctioned device->host readback of the async pipeline
        # (jaxpr-audit-gated; see engine.py._process_one).
        vals = host_sync(entry['toks'])
        now = clock.now()
        if entry['kind'] == 'prefill':
            for slot, req, row in entry['batch']:
                if req.finish_time is not None:
                    continue
                tenant = self._slots[slot] is req
                if not tenant and not req._early_freed:
                    continue                     # cancelled/preempted
                token = int(vals[row])
                if token < 0:
                    # Non-finite sentinel from prefill: evict exactly
                    # this request (frees its slot + pages when it is
                    # still the tenant); the other rows land normally.
                    if tenant:
                        self._await_first.discard(slot)
                    events.append(self._evict_nonfinite(slot, req))
                    continue
                if tenant:
                    self._await_first.discard(slot)
                if req.first_token_time is None:  # not on re-admission
                    req.first_token_time = now
                self._trace_first_token(req)
                req.output.append(token)
                finished = self._finish_req(slot, req, token)
                events.append((req.request_id, token, finished))
            return events
        if self._moe_layers:
            # The row under the slots' tokens (paged_decode_horizon).
            self._prof.note_distinct_experts(
                int(vals[self.max_batch].sum()),
                entry['horizon'] * self._moe_layers,
                held_assignments=(
                    None if self.cfg.holds_every_expert
                    else int(vals[self.max_batch + 1].sum())))
        for slot, req in enumerate(entry['snapshot']):
            if req is None:
                continue
            if entry['epochs'][slot] == self._slot_epoch[slot]:
                self._slot_inflight[slot] = max(
                    0, self._slot_inflight[slot] - entry['horizon'])
            if req.finish_time is not None:
                continue
            tenant = self._slots[slot] is req
            if not tenant and not req._early_freed:
                continue                         # cancelled/preempted
            for i in range(entry['horizon']):
                token = int(vals[slot, i])
                if token < 0:
                    # Non-finite sentinel mid-horizon: evict exactly
                    # this request; co-batched slots keep their
                    # tokens (blast radius = one request).
                    events.append(self._evict_nonfinite(slot, req))
                    break
                req.output.append(token)
                if tenant:
                    self._slot_len[slot] += 1
                finished = self._finish_req(slot, req, token)
                events.append((req.request_id, token, finished))
                if finished:
                    break
        return events
