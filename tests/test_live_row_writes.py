"""``paged._write_live_rows``: the ring merge, the prefill chunk, the
speculative verify and the KV ingest write rows into the paged pools in
ONE loop over live units of tokens (``PERF.md``, PR 36), where the
parent scattered every slot's rows and sent the dead ones to the trash
page. Held to that flat write (kept here as the oracle): every page a
request owns bit for bit the same, for bf16, int8 (codes + scales) and
lane-packed pools, under ``jit`` with the cache donated, at every unit
width the rule can pick. Page 0 is the trash page and may hold anything.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import paged

L, HKV, D, N_PAGES, PAGE, SLOTS, N = 3, 2, 16, 23, 16, 5, 16

# name -> valid_len of the five slots (of N = 16 new tokens each)
VALID = {
    'all_dead': [0, 0, 0, 0, 0],
    'all_live': [16, 16, 16, 16, 16],
    'ragged': [0, 16, 5, 0, 9],
    'one_live_last': [0, 0, 0, 0, 16],
    'one_token_each': [1, 1, 1, 1, 1],
}


def flat_write(cache, k_rows, v_rows, table, starts, valid_len):
    """``merge_rows_into_pool`` as it was before PR 36: one flat scatter
    a pool of every slot's rows, those past ``valid_len`` to page 0."""
    pools, rows = paged._pools_and_rows(cache, k_rows, v_rows)
    flat_idx = paged._flat_write_indices(table, starts, rows[0].shape[2],
                                         valid_len, cache.page_size)
    return paged.PagedKVCache(*(paged._scatter_rows(pool, r, flat_idx)
                                for pool, r in zip(pools, rows)))


def _cache_and_rows(kind, rng, slots=SLOTS, n=N):
    """A cache of ``kind`` holding random bits, and rows for it."""
    def vals(shape, dtype):
        if dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(shape), dtype)

    pool, rows = (L, N_PAGES, HKV, PAGE), (L, slots, n, HKV)
    if kind == 'int8':
        cache = paged.PagedKVCache(
            vals(pool + (D,), jnp.int8), vals(pool + (D,), jnp.int8),
            vals(pool, jnp.float32), vals(pool, jnp.float32))
        return cache, tuple(
            (vals(rows + (D,), jnp.int8), vals(rows + (1,), jnp.float32))
            for _ in range(2))
    if kind == 'lane_packed':          # latent rows + rope rows, 4 to a row
        pool, rows = (L, N_PAGES, 1, PAGE), (L, slots, n, 1)
        cache = paged.PagedKVCache(
            vals(pool + (D,), jnp.bfloat16),
            vals(pool[:3] + (PAGE * 32 // 128, 128), jnp.bfloat16))
        return cache, (vals(rows + (D,), jnp.bfloat16),
                       vals(rows + (32,), jnp.bfloat16))
    cache = paged.PagedKVCache(vals(pool + (D,), jnp.bfloat16),
                               vals(pool + (D,), jnp.bfloat16))
    return cache, (vals(rows + (D,), jnp.bfloat16),
                   vals(rows + (D,), jnp.bfloat16))


def _table_and_starts(rng, slots=SLOTS, n=N):
    """Four pages a slot, no page twice and none of them page 0; starts
    anywhere the run fits, so most runs straddle a page."""
    table = rng.permutation(np.arange(1, N_PAGES))[:slots * 4].reshape(
        slots, 4).astype(np.int32)
    starts = rng.integers(0, 4 * PAGE - n + 1, slots).astype(np.int32)
    return jnp.asarray(table), jnp.asarray(starts)


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[
        x.dtype.itemsize])


@pytest.mark.parametrize('unit', [2, 4, 8, 16])
@pytest.mark.parametrize('valid', list(VALID))
@pytest.mark.parametrize('kind', ['bf16', 'int8', 'lane_packed'])
def test_live_unit_loop_equals_the_flat_write_outside_the_trash_page(
        monkeypatch, kind, valid, unit):
    monkeypatch.setattr(paged, '_write_unit', lambda *shape: unit)
    rng = np.random.default_rng(
        [unit, list(VALID).index(valid), len(kind)])
    cache, (k_rows, v_rows) = _cache_and_rows(kind, rng)
    table, starts = _table_and_starts(rng)
    valid_len = jnp.asarray(VALID[valid], jnp.int32)
    before = [_bits(pool) for pool in jax.tree.leaves(cache)]
    want = flat_write(cache, k_rows, v_rows, table, starts, valid_len)
    want = [_bits(pool) for pool in jax.tree.leaves(want)]

    # a function of its own each time: ``jit`` caches the trace by
    # function and shapes, and the unit is in neither
    merge = jax.jit(lambda *args: paged.merge_rows_into_pool(*args),
                    donate_argnums=(0,))
    got = merge(cache, k_rows, v_rows, table, starts, valid_len)
    assert type(got) is paged.PagedKVCache
    assert got.quantized == (kind == 'int8')
    for pool, ref, old in zip(jax.tree.leaves(got), want, before):
        np.testing.assert_array_equal(_bits(pool)[:, 1:], ref[:, 1:])
        if not any(VALID[valid]):
            # nothing due: nothing written, the trash page included (the
            # flat write scattered all 80 rows there)
            np.testing.assert_array_equal(_bits(pool), old)
    if any(VALID[valid]):
        assert any((ref[:, 1:] != old[:, 1:]).any()
                   for ref, old in zip(want, before))


def test_the_loop_runs_one_trip_a_live_unit(monkeypatch):
    """The trip count is the live work: each trip's scatter is counted on
    the host through a callback in the loop's body."""
    trips = []
    scatter = paged._scatter_rows

    def counted(pool, rows, flat_idx):
        if pool.ndim == 5 and pool.shape[-1] == D:
            jax.debug.callback(lambda i: trips.append(np.asarray(i)),
                               flat_idx)
        return scatter(pool, rows, flat_idx)

    monkeypatch.setattr(paged, '_scatter_rows', counted)
    monkeypatch.setattr(paged, '_write_unit', lambda *shape: 4)
    rng = np.random.default_rng(7)
    cache, (k_rows, v_rows) = _cache_and_rows('bf16', rng)
    cache = cache._replace(pool_v=cache.pool_v[..., :8])   # one D-wide pool
    table, starts = _table_and_starts(rng)
    valid_len = jnp.asarray(VALID['ragged'], jnp.int32)     # 0 16 5 0 9
    out = jax.jit(lambda *a: paged.merge_rows_into_pool(*a))(
        cache, k_rows, v_rows[..., :8], table, starts, valid_len)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert len(trips) == 4 + 2 + 3              # ceil(valid / 4) a slot
    # and the live units' rows past valid_len are the only trash writes
    trash = sum(int((idx < PAGE).sum()) for idx in trips)
    assert trash == (8 - 5) + (12 - 9)


@pytest.mark.parametrize('shape,unit', [
    # ouro-2.6b.reason: 192 cache layers x 16 heads
    ((192, 16, 8), 8), ((192, 16, 256), 8), ((192, 16, 128), 8),
    # qwen2-7b.chat: 28 layers x 4 KV heads; rings of 8 and 32 steps
    ((28, 4, 8), 8), ((28, 4, 32), 32), ((28, 4, 256), 256),
    ((28, 4, 128), 128),
    # llama-3-8b: 32 layers x 8 KV heads
    ((32, 8, 256), 64), ((32, 8, 32), 32),
    # glm-4.7-flash.longctx: 8 layers, one latent row a token
    ((8, 1, 8), 8), ((8, 1, 256), 256),
    # a speculative verify's k + 1 rows, a width no unit divides
    ((28, 4, 5), 5), ((192, 16, 100), 100), ((192, 16, 96), 8),
    # the tiny models of the tests: the whole run
    ((2, 2, 16), 16),
])
def test_unit_is_a_function_of_static_shapes(shape, unit):
    assert paged._write_unit(*shape) == unit
    n = shape[2]
    assert n % unit == 0 and (unit == n or unit >= 8)


def test_sharded_merge_runs_the_same_loop():
    """Under a tp x dp mesh the ``shard_map`` body writes through the same
    loop: the pools equal the unsharded merge's, every page."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.serving_mesh(tp=2, dp=2)
    rng = np.random.default_rng(11)
    cache, (k_rows, v_rows) = _cache_and_rows('int8', rng, slots=4)
    table, starts = _table_and_starts(rng, slots=4)
    valid_len = jnp.asarray([0, 16, 5, 9], jnp.int32)
    want = paged.merge_rows_into_pool(cache, k_rows, v_rows, table,
                                      starts, valid_len)
    assert paged._pool_shard_axes(cache, table, mesh) is not None
    with jax.set_mesh(mesh):
        got = jax.jit(lambda *a: paged.merge_rows_into_pool(
            *a, mesh=mesh))(cache, k_rows, v_rows, table, starts,
                            valid_len)
    for pool, ref in zip(got, want):
        np.testing.assert_array_equal(_bits(pool), _bits(ref))


def test_counters_read_what_the_host_arithmetic_says():
    """After a served request: every prefill chunk offered prompts x width
    rows of which the prompt's own are live, every decode call offered
    slots x horizon of which the decoding slots' are."""
    from skypilot_tpu.models import configs
    from skypilot_tpu.telemetry import profiler as profiler_lib
    from skypilot_tpu.telemetry import registry as registry_lib
    reg = registry_lib.reset_registry()
    try:
        eng = paged.PagedInferenceEngine(
            configs.get_config('tiny'), max_batch=4, max_seq=96,
            page_size=16, chunk=16)
        calls = []
        note = eng.profiler.note_pool_write

        def note_and_keep(live, offered):
            calls.append((live, offered))
            note(live, offered)
        eng.profiler.note_pool_write = note_and_keep
        eng.add_request(list(range(1, 22)), max_new_tokens=6)   # 21 tokens
        eng.run_to_completion()
        live = reg.get(profiler_lib.POOL_ROWS_LIVE_METRIC).value
        offered = reg.get(profiler_lib.POOL_ROWS_OFFERED_METRIC).value
    finally:
        registry_lib.reset_registry()
    assert (live, offered) == tuple(map(sum, zip(*calls)))
    chunks, decodes = calls[:2], calls[2:]
    assert chunks == [(16, 16), (5, 16)]         # the prompt's two pieces
    # one slot of four decodes: a quarter of each ring merge is live
    assert decodes and all(4 * lv == off for lv, off in decodes)
    assert 0 < live < offered
