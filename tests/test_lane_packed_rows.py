"""``paged._scatter_rows_lane_packed``: rows of ``w`` < 128 values lying
``128 / w`` tokens to a lane row are written as WHOLE lane rows (one
scatter; ``PERF.md``, PR 33). Held to a NumPy oracle that places token by
token, and to the 2-D windowed ``lax.scatter`` it replaced (a serial loop
of one trip a row on the chip): every written token exact, every other
place of a touched lane row and every other page bit for bit what it
was. Page 0 is the trash page and may hold anything.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from skypilot_tpu.inference import paged

L, N_PAGES, PAGE, LANES = 2, 7, 16, 128
# Pages of three slots, none of them page 0; unused entries are page 0.
TABLE = np.array([[3, 5, 0], [1, 6, 0], [4, 2, 0]], np.int32)

# name -> (starts, n, valid_len): where each slot's n tokens begin, and
# how many of them are real.
CASES = {
    'even_starts_full': ([4, 0, 6], 4, [4, 4, 4]),
    'odd_starts_full': ([5, 3, 7], 4, [4, 4, 4]),
    'mixed_parity_odd_n': ([1, 2, 9], 5, [5, 5, 5]),
    'crosses_a_page': ([14, 13, 15], 5, [5, 5, 5]),
    'valid_len_zero': ([4, 5, 6], 4, [0, 0, 0]),
    'valid_len_partial': ([4, 5, 14], 4, [1, 3, 3]),
    'dead_slot_beside_live': ([7, 0, 12], 6, [6, 0, 5]),
    'one_token': ([5, 8, 0], 1, [1, 0, 1]),
}


def _distinct(shape, dtype, first):
    """An array of ``dtype`` no two of whose values are equal: float32
    counts up from ``first``; bfloat16 takes consecutive bit patterns of
    finite positive numbers."""
    size = int(np.prod(shape))
    if dtype == 'float32':
        return jnp.arange(first, first + size, dtype=jnp.float32
                          ).reshape(shape)
    bits = np.arange(first, first + size)
    assert bits[-1] < 0x7F00, 'past the finite bf16 patterns'
    return lax.bitcast_convert_type(
        jnp.asarray(bits, jnp.uint16), jnp.bfloat16).reshape(shape)


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _oracle(pool, rows, flat_idx):
    """Token by token: token ``t`` of the pool (page * PAGE + position)
    is values ``t * w ...`` of its layer's bytes."""
    w = rows.shape[-1]
    out = _bits(pool).copy().reshape(L, -1, w)
    for layer in range(L):
        for b in range(flat_idx.shape[0]):
            for j in range(flat_idx.shape[1]):
                out[layer, flat_idx[b, j]] = _bits(rows)[layer, b, j, 0]
    return out.reshape(pool.shape)


def windowed_scatter(pool, rows, flat_idx):
    """``paged._scatter_rows_lane_packed`` as it was before PR 33: a 2-D
    ``lax.scatter`` with a [w] window at (lane row, lane), which XLA
    lowers to a ``while`` of one trip a row on the chip
    (``tests/test_tpu_compile.py`` compiles it as its witness)."""
    n_layers, n_pages, _, lane_rows, lanes = pool.shape
    w = rows.shape[-1]
    per = lanes // w
    flat_pool = pool.reshape(n_layers * n_pages * lane_rows, lanes)
    f = flat_idx.reshape(-1)
    row = (jnp.arange(n_layers)[:, None] * (n_pages * lane_rows)
           + (f // per)[None, :]).reshape(-1)
    lane = jnp.broadcast_to(((f % per) * w)[None, :],
                            (n_layers, f.size)).reshape(-1)
    flat_pool = lax.scatter(
        flat_pool, jnp.stack([row, lane], axis=-1),
        rows.reshape(-1, w).astype(flat_pool.dtype),
        lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0, 1)),
        mode='drop')
    return flat_pool.reshape(pool.shape)


@pytest.mark.parametrize('case', list(CASES))
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('w', [64, 32, 8])        # 2, 4, 16 tokens a row
def test_whole_lane_row_writes_place_every_token(w, dtype, case):
    starts, n, valid = CASES[case]
    per = LANES // w
    pool = _distinct((L, N_PAGES, 1, PAGE // per, LANES), dtype, 0x0100)
    rows = _distinct((L, len(starts), n, 1, w), dtype, 0x4000)
    flat_idx = paged._flat_write_indices(
        jnp.asarray(TABLE), jnp.asarray(starts, jnp.int32), n,
        jnp.asarray(valid, jnp.int32), PAGE)
    got = _bits(paged._scatter_rows(pool, rows, flat_idx))
    assert got.shape == pool.shape

    want = _oracle(pool, rows, np.asarray(flat_idx))
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    old_form = _bits(windowed_scatter(pool, rows, flat_idx))
    np.testing.assert_array_equal(got[:, 1:], old_form[:, 1:])

    # The oracle is no tautology: exactly the real tokens' values moved,
    # and only on those tokens' pages.
    moved = got[:, 1:] != _bits(pool)[:, 1:]
    assert moved.sum() == L * sum(valid) * w
    pages = {int(TABLE[b, (s + j) // PAGE])
             for b, (s, v) in enumerate(zip(starts, valid))
             for j in range(v)}
    touched = {p + 1 for p in range(N_PAGES - 1) if moved[:, p].any()}
    assert touched == pages


def test_jitted_and_donated_like_the_merge_program():
    """Under ``jit`` with the pool donated, as ``merge_ring_into_pool``
    and the prefill chunk run it, through ``merge_rows_into_pool`` with
    the latent pool beside the rope pool."""
    w, n = 64, 4
    starts, valid = [5, 14, 2], [4, 3, 0]
    pool_k = _distinct((L, N_PAGES, 1, PAGE, 32), 'float32', 7)
    pool_v = _distinct((L, N_PAGES, 1, PAGE * w // LANES, LANES),
                       'float32', 100000)
    k_rows = _distinct((L, 3, n, 1, 32), 'float32', 300000)
    v_rows = _distinct((L, 3, n, 1, w), 'float32', 400000)
    cache = paged.PagedKVCache(pool_k=pool_k, pool_v=pool_v)
    flat_idx = np.asarray(paged._flat_write_indices(
        jnp.asarray(TABLE), jnp.asarray(starts, jnp.int32), n,
        jnp.asarray(valid, jnp.int32), PAGE))
    want_v = _oracle(pool_v, v_rows, flat_idx)
    want_k = _oracle(pool_k, k_rows, flat_idx)

    merge = jax.jit(paged.merge_rows_into_pool, donate_argnums=(0,))
    out = merge(cache, k_rows, v_rows, jnp.asarray(TABLE),
                jnp.asarray(starts, jnp.int32),
                jnp.asarray(valid, jnp.int32))
    np.testing.assert_array_equal(_bits(out.pool_v)[:, 1:], want_v[:, 1:])
    np.testing.assert_array_equal(_bits(out.pool_k)[:, 1:], want_k[:, 1:])
