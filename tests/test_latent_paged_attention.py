"""The latent (MLA) paged decode kernel (``ops/latent_paged_attention.py``)
in interpret mode, against the XLA form it replaces on the chip:
``absorbed_ring_decode_attention`` over pages gathered by
``paged._gather_layer``. What the chip's compiler says of it is
``tests/test_tpu_compile.py``'s to ask.

Every page a slot must not read is NaN here: the pages of its table past
``ceil(length / page)`` (the bucket is wider than any row needs), the
pool's pages no table names, and every other layer. One read past
``length`` and the output is NaN.

Tolerances: float32 2e-5 on outputs of magnitude ~1 (only the order of
float32 accumulation differs: page by page against all at once);
bfloat16 0.04, two roundings of an output near 2.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import paged
from skypilot_tpu.models import configs, llama
from skypilot_tpu.ops import latent_attention
from skypilot_tpu.ops import latent_paged_attention as lpa

TOL = {'float32': 2e-5, 'bfloat16': 0.04}
HQ, R, DR, LAYERS, LAYER, RING = 4, 32, 8, 3, 1, 4


def lengths_for(page):
    """Nothing, one row, a page but one, a page, a page and one, several
    pages with an odd tail; a dead slot among the live ones."""
    return [0, 1, page - 1, page, 0, page + 1, 3 * page + 5]


def pools_and_table(dtype, page, lengths, P, rng):
    """(clean pools, poisoned pools, table): every slot's table names P
    pages of its own, and whatever it must not read is NaN in the
    poisoned pair. The rope pool lies as ``PagedKVCache.create`` lays
    it: lane-packed at page 16 (16 rows of 8 fill the 128 lanes), plain
    [page, 8] at page 8."""
    cfg = dataclasses.replace(configs.TINY_GLM, n_layers=LAYERS,
                              dtype=jnp.dtype(dtype))
    slots = len(lengths)
    n_pages = 1 + slots * P + 3
    cache = paged.PagedKVCache.create(cfg, n_pages=n_pages, page_size=page)
    assert cache.pool_k.shape == (LAYERS, n_pages, 1, page, R)
    assert cache.pool_v.shape[3:] == ((1, 128) if page == 16 else (8, DR))
    table = 1 + np.arange(slots * P, dtype=np.int32).reshape(slots, P)
    clean = [jnp.asarray(rng.standard_normal(pool.shape), dtype)
             for pool in cache[:2]]
    keep = np.zeros((LAYERS, n_pages), bool)
    for row, n in zip(table, lengths):
        keep[LAYER, row[:-(-n // page)]] = True
    poisoned = [jnp.where(keep[:, :, None, None, None], pool, jnp.nan)
                for pool in clean]
    return clean, poisoned, jnp.asarray(table)


@pytest.mark.parametrize('ring_len', [0, 2, RING])
@pytest.mark.parametrize('table_p', [4, 8])     # the longest row's, and wider
@pytest.mark.parametrize('dtype,page', [('float32', 16), ('float32', 8),
                                        ('bfloat16', 16)])
def test_kernel_and_merge_match_the_gathered_form(dtype, page, table_p,
                                                  ring_len):
    rng = np.random.default_rng(8)
    lengths = lengths_for(page)
    slots = len(lengths)
    clean, poisoned, table = pools_and_table(dtype, page, lengths, table_p,
                                             rng)

    def rows(*shape):
        return jnp.asarray(rng.standard_normal((slots,) + shape), dtype)

    q_lat, q_rope = rows(1, HQ, R), rows(1, HQ, DR)
    c_self, kr_self = rows(1, R), rows(1, DR)
    ring_c, ring_kr = rows(RING, R), rows(RING, DR)
    lens = jnp.asarray(lengths, jnp.int32)
    scale = (R + DR) ** -0.5

    partial = lpa.latent_paged_decode_attention(
        q_lat[:, 0], q_rope[:, 0], *poisoned, table, lens, layer=LAYER,
        scale=scale, interpret=True)
    acc, m, l = map(np.asarray, partial)
    dead = np.asarray(lengths) == 0
    assert (acc[dead] == 0).all() and (l[dead] == 0).all()
    assert (m[dead] < -1e29).all() and (l[~dead] > 0).all()
    got = lpa.merge_latent_partial_with_ring_self(
        partial, q_lat, q_rope, c_self, kr_self, ring_c, ring_kr,
        ring_len, scale=scale)

    ck, _ = paged._gather_layer(clean[0], None, LAYER, table)
    cv, _ = paged._gather_layer(clean[1], None, LAYER, table)
    want = latent_attention.absorbed_ring_decode_attention(
        q_lat, q_rope, c_self, kr_self, ck[:, :, 0],
        cv.reshape(slots, -1, DR), lens, ring_c, ring_kr, ring_len,
        scale=scale)
    assert got.shape == want.shape == (slots, 1, HQ, R)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert np.isfinite(err).all() and err.max() < TOL[dtype], err.max()


def test_a_slot_the_host_freed_reads_nothing():
    """``paged_decode_horizon`` hands the kernel ``lengths`` where
    ``active`` and 0 elsewhere: a freed slot whose stale length names
    pages that are NaN by now leaves the live slot's tokens as the
    gathered form serves them from a clean pool."""
    cfg = dataclasses.replace(configs.TINY_GLM, dtype=jnp.dtype('float32'))
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page, n_pages = 16, 9
    rng = np.random.default_rng(9)
    cache = paged.PagedKVCache(*[
        jnp.asarray(rng.standard_normal(pool.shape) * 0.3, pool.dtype)
        for pool in paged.PagedKVCache.create(
            cfg, n_pages=n_pages, page_size=page)[:2]])
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    freed = np.zeros(n_pages, bool)
    freed[5:] = True
    stale = paged.PagedKVCache(*[
        jnp.where(freed[None, :, None, None, None], jnp.nan, pool)
        for pool in cache[:2]])
    tokens = jnp.asarray([3, 5], jnp.int32)
    lengths = jnp.asarray([37, 50], jnp.int32)
    active = jnp.asarray([True, False])

    def decode(cache, decode_impl):
        toks, ring_c, _ = jax.jit(lambda c: paged.paged_decode_horizon(
            params, c, table, tokens, lengths, cfg, horizon=3,
            active=active, decode_impl=decode_impl))(cache)
        return np.asarray(toks), np.asarray(ring_c)

    want, want_rows = decode(cache, 'gather')
    got, got_rows = decode(stale, 'pallas')
    assert (got[0] == want[0]).all() and (got[0] >= 0).all()
    np.testing.assert_allclose(got_rows[:, 0], want_rows[:, 0], atol=2e-5)


def test_engine_serves_through_the_kernel():
    """``decode_impl='pallas'`` beside ``'gather'`` through chunked
    prefill and two decode calls of the engine over the lane-packed
    pool (the chip's form), by logits: every served token is the plain
    reference's best within the float32 tolerance
    (``tests/test_latent_moe.py``). The page counters move for a latent
    cache under either form: they count what the table holds."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models.reference import glm4_moe_lite as reference
    from skypilot_tpu.telemetry import profiler, registry
    cfg = dataclasses.replace(configs.TINY_GLM, dtype=jnp.dtype('float32'))
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page = 16
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (37, 9, 21)]
    reg = registry.get_registry()

    def counter(name):
        return reg.get(name).value if reg.get(name) else 0.0

    for decode_impl in ('gather', 'pallas'):
        eng = PagedInferenceEngine(cfg, params=params, max_batch=4,
                                   max_seq=96, page_size=page, chunk=16,
                                   decode_impl=decode_impl)
        assert eng.resolved_path()['decode_impl'] == decode_impl
        before = [counter(profiler.ATTN_PAGES_LIVE_METRIC),
                  counter(profiler.ATTN_PAGES_TABLE_METRIC)]
        ids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
        done = eng.run_to_completion(horizon=4)
        for rid, prompt in zip(ids, prompts):
            out = done[rid].output
            ref = np.asarray(reference.forward(
                params, jnp.asarray(prompt + out), cfg, q_block=7)[0])[
                    len(prompt) - 1:len(prompt) + len(out) - 1]
            deficit = ref.max(-1) - ref[np.arange(len(out)), out]
            assert len(out) == 9 and deficit.max() < 2e-4, decode_impl
        live = counter(profiler.ATTN_PAGES_LIVE_METRIC) - before[0]
        held = counter(profiler.ATTN_PAGES_TABLE_METRIC) - before[1]
        # At most 3 live rows in 4 slots, each reading at least one page
        # and at most its bucket, in each of the 8 decode steps or more.
        assert held >= 4 * 8 and held % 4 == 0
        assert 8 <= live <= held * 3 / 4


def test_auto_takes_the_kernel_where_the_pool_fits(monkeypatch):
    """``'auto'`` for a latent cache: the kernel on a TPU backend when
    the latent rows fill the lanes, the pool is bf16 and the rope pool is
    lane-packed; the XLA form on the CPU and for a pool that does not
    fit. The backend is steered here, in the test."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    wide = dataclasses.replace(configs.TINY_GLM, kv_lora_rank=128)

    def resolved(cfg, **kwargs):
        return PagedInferenceEngine(cfg, max_batch=2, max_seq=64,
                                    n_pages=8, **kwargs).decode_impl

    assert resolved(wide, page_size=16) == 'gather'         # the CPU
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert resolved(wide, page_size=16) == 'pallas'
    assert resolved(wide, page_size=8) == 'gather'          # [8, 8] rope rows
    assert resolved(configs.TINY_GLM, page_size=16) == 'gather'   # rank 32
    assert resolved(dataclasses.replace(wide, dtype=jnp.dtype('float32')),
                    page_size=16) == 'gather'
    assert resolved(wide, page_size=16, decode_impl='gather') == 'gather'
    assert resolved(configs.TINY_GLM, page_size=8,
                    decode_impl='pallas') == 'pallas'       # asked for
