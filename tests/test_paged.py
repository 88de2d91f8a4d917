"""Paged KV cache engine: greedy tokens against the plain forward pass
(``greedy_oracle``), prefix caching, chunked prefill, pool accounting
(VERDICT r4 task 3; reference capability anchor: vLLM paged attention,
llm/vllm/README.md:10)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import greedy_oracle
from skypilot_tpu.inference.paged import (PageAllocator, PagedKVCache,
                                          PagedInferenceEngine,
                                          _gather_layer,
                                          paged_prefill_chunk)
from skypilot_tpu.models import configs, llama

# Compile-heavy (jit of full models): most engine classes ride the slow
# tier — the fast sweep holds the greedy path to the oracle (below) and
# the pool-gather tests at the file's end.


@pytest.fixture(scope='module')
def setup():
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


SHORT_PROMPTS = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1, 8], [9]]


@pytest.mark.parametrize('chunked', [False, True],
                         ids=['single-chunk', 'chunked-150'])
@pytest.mark.parametrize('quantize,kv_dtype', [
    (None, 'bf16'), ('int8', 'bf16'), ('int8', 'int8'), ('int4', 'int4')])
def test_greedy_agrees_with_oracle(setup, quantize, kv_dtype, chunked):
    """Every token the paged engine emits greedily is the plain
    forward's choice (of the same, possibly quantized, weights) or within
    the KV precision's tolerance of it: prompts of one chunk decoding
    side by side, and a 150-token prompt prefilled in 32-token chunks."""
    cfg, params = setup
    if chunked:
        prompts, n_new = [[(i * 7 + 3) % cfg.vocab_size
                           for i in range(150)]], 6
        kw = dict(max_batch=2, chunk=32)
    else:
        prompts, n_new, kw = SHORT_PROMPTS, 8, dict(max_batch=4)
    eng = PagedInferenceEngine(cfg, params, max_seq=256, page_size=8,
                               attn_impl='xla', quantize=quantize,
                               kv_cache_dtype=kv_dtype, **kw)
    outs = greedy_oracle.greedy(eng, prompts, n_new)
    if chunked:
        assert eng.chunks_prefilled >= 5       # 150/32 -> 5 chunks
    greedy_oracle.assert_all_agree(
        cfg, eng.params, prompts, outs, f'{quantize}/{kv_dtype}',
        greedy_oracle.KV_KIND[kv_dtype], n_new)


def test_oracle_refuses_another_models_tokens(setup):
    """The oracle has teeth: tokens a different weight tree chose lie
    O(1) logits below this tree's choices, many times the tolerance a
    rounding difference is allowed."""
    cfg, params = setup
    other = llama.init_params(jax.random.PRNGKey(1), cfg)
    eng = PagedInferenceEngine(cfg, other, max_batch=4, max_seq=256,
                               page_size=8, attn_impl='xla')
    outs = greedy_oracle.greedy(eng, SHORT_PROMPTS, 8)
    for prompt, out in zip(SHORT_PROMPTS, outs):
        greedy_oracle.assert_agrees(cfg, other, prompt, out)
        with pytest.raises(AssertionError, match='below the reference'):
            greedy_oracle.assert_agrees(cfg, params, prompt, out)
        deficit, _ = greedy_oracle.score(cfg, params, prompt, out)
        assert deficit.max() > 10 * greedy_oracle.tolerance(cfg, 'bf16')


@pytest.mark.parametrize('kv_dtype', ['bf16', 'int8', 'int4'])
def test_prefix_hit_continuation_agrees_with_oracle(setup, kv_dtype):
    """A second request with the same long prefix reuses the shared
    pages (one hit, one chunk for the tail where a cold prompt takes
    five), whatever the pages' dtype, and what it then decodes is still
    the reference's choice."""
    cfg, params = setup
    shared = [(i * 5 + 2) % cfg.vocab_size for i in range(64)]
    p1 = shared + [11, 12]
    p2 = shared + [13, 14, 15]
    eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=256,
                               page_size=8, chunk=16, attn_impl='xla',
                               kv_cache_dtype=kv_dtype)
    greedy_oracle.greedy(eng, [p1], 4)
    chunks_before = eng.chunks_prefilled
    assert eng.alloc.prefix_misses == 1
    (out,) = greedy_oracle.greedy(eng, [p2], 6)
    # 64 shared tokens = 8 full pages reused; only the 3-token tail
    # prefills -> exactly 1 chunk vs 5 without reuse.
    assert eng.alloc.prefix_hits == 1
    assert eng.chunks_prefilled - chunks_before == 1
    assert len(out) == 6
    greedy_oracle.assert_agrees(cfg, params, p2, out,
                                greedy_oracle.KV_KIND[kv_dtype],
                                f'prefix hit, {kv_dtype} pages')


def test_preemption_by_recompute_agrees_with_oracle(setup):
    """Pool pressure preempts the newest request and recomputes it via
    prompt+output (a prefill where the uninterrupted run decoded:
    another program, so held to the oracle): every token of both
    outputs is still the reference's choice."""
    cfg, params = setup
    prompt = list(range(1, 30))
    # Tiny pool: 2 slots' growth collides mid-decode.
    eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=256,
                               page_size=8, n_pages=12,
                               decode_impl='gather')
    outs = greedy_oracle.greedy(eng, [prompt, prompt], 24)
    assert eng.preemptions >= 1
    greedy_oracle.assert_all_agree(cfg, params, [prompt, prompt], outs,
                                   'preempted + recomputed', n_new=24)


@pytest.mark.slow
class TestPagedEngine:

    def test_int8_paged_generates(self, setup):
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                   page_size=8, quantize='int8',
                                   attn_impl='xla')
        assert eng.cache.quantized
        rid = eng.add_request(list(range(1, 12)), max_new_tokens=6)
        done = eng.run_to_completion(horizon=4)
        assert len(done[rid].output) == 6

    def test_sampling_runs(self, setup):
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                   page_size=8, attn_impl='xla')
        rid = eng.add_request([1, 2, 3], max_new_tokens=16,
                              temperature=1.5, top_k=40)
        done = eng.run_to_completion(horizon=4)
        assert len(set(done[rid].output)) > 1

    def test_top_p_and_stop(self, setup):
        """top_p -> 0 equals greedy under hot sampling; stop sequences
        finish early with the matched suffix trimmed (paged engine)."""
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                   page_size=8, attn_impl='xla')
        g = eng.add_request([3, 1, 4], max_new_tokens=12)
        n = eng.add_request([3, 1, 4], max_new_tokens=12,
                            temperature=2.0, top_p=1e-6)
        done = eng.run_to_completion(horizon=4)
        assert done[g].output == done[n].output
        full = done[g].output
        eng2 = PagedInferenceEngine(cfg, params, max_batch=2,
                                    max_seq=128, page_size=8,
                                    attn_impl='xla')
        rid = eng2.add_request([3, 1, 4], max_new_tokens=12,
                               stop=[full[2:4]])
        req = eng2.run_to_completion(horizon=4)[rid]
        assert req.stop_hit and req.output == full[:2]


@pytest.mark.slow
class TestPrefixCache:

    def test_prefix_hit_byte_identical_to_cold(self, setup):
        """A prefix-cache hit must emit byte-identical output to a cold
        run of the same request. Resuming chunked prefill at an
        arbitrary page boundary (instead of the cold run's chunk grid)
        regroups cached_attention's two softmax partial sums, and the
        few-ULP denominator drift flips greedy argmax on near-tie
        logits — the engine quantizes resume points to chunk-multiple
        boundaries to keep both paths bitwise equal. Prompt [14]+S+[8]
        below is a known near-tie under TINY init: without the
        quantization its hit-path bytes diverge from cold."""
        cfg, _ = setup
        shared = [7 + (j % 50) for j in range(40)]
        for lead, tail in [(11, 5), (14, 8)]:
            prompt = [lead] + shared + [tail]
            eng = PagedInferenceEngine(cfg, max_batch=2, max_seq=256)
            r1 = eng.add_request(list(prompt), max_new_tokens=6)
            cold = eng.run_to_completion()[r1].output
            r2 = eng.add_request(list(prompt), max_new_tokens=6)
            hit = eng.run_to_completion()[r2].output
            assert eng.alloc.prefix_hits == 1
            assert hit == cold, (lead, hit, cold)

    def test_aligned_prefix_hit_keeps_reuse_and_identity(self, setup):
        """When the matched prefix covers whole chunk multiples, resume
        quantization keeps the pages: chunk work drops AND the output
        stays byte-identical to the cold run."""
        cfg, params = setup
        shared = [(i * 5 + 2) % cfg.vocab_size for i in range(64)]
        prompt = shared + [21, 22, 23]
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=256,
                                   page_size=8, chunk=16,
                                   attn_impl='xla')
        r1 = eng.add_request(list(prompt), max_new_tokens=6)
        cold = eng.run_to_completion(horizon=4)[r1].output
        before = eng.chunks_prefilled
        r2 = eng.add_request(list(prompt), max_new_tokens=6)
        done = eng.run_to_completion(horizon=4)
        # 64 shared tokens = 4 chunk-aligned boundaries survive
        # quantization; only the tail re-prefills.
        assert eng.chunks_prefilled - before <= 1
        assert done[r2].output == cold

    def test_prefix_pages_survive_slot_free_until_pressure(self, setup):
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   page_size=8, attn_impl='xla')
        prompt = list(range(1, 26))            # 3 full pages
        eng.add_request(prompt, max_new_tokens=2)
        eng.run_to_completion(horizon=2)
        stats = eng.memory_stats()
        assert stats['pages_retained_prefix'] >= 3
        # a re-submit hits the retained pages
        eng.add_request(prompt + [30], max_new_tokens=2)
        eng.run_to_completion(horizon=2)
        assert eng.alloc.prefix_hits == 1

    def test_memory_stats_accounting(self, setup):
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                   page_size=8, attn_impl='xla')
        s0 = eng.memory_stats()
        assert s0['pages_in_use'] == 0
        assert s0['pool_bytes'] > 0
        eng.add_request(list(range(1, 20)), max_new_tokens=64)
        eng.step(horizon=2)
        s1 = eng.memory_stats()
        assert s1['pages_in_use'] >= 3         # 19 tokens / 8 per page
        eng.run_to_completion(horizon=8)
        s2 = eng.memory_stats()
        assert s2['pages_in_use'] == 0         # all freed or retained
        assert (s2['pages_free'] + s2['pages_retained_prefix']
                == s2['n_pages'] - 1)


@pytest.mark.slow
class TestAllocator:

    def test_exhaustion_and_lru_eviction(self):
        a = PageAllocator(n_pages=5, page_size=4)     # 4 usable
        pages = [a.alloc() for _ in range(4)]
        with pytest.raises(MemoryError):
            a.alloc()
        # register 2 pages as prefix pages, then free them -> retained
        a.page_hash[pages[0]] = b'h0'
        a.by_hash[b'h0'] = pages[0]
        a.page_hash[pages[1]] = b'h1'
        a.by_hash[b'h1'] = pages[1]
        a.release(pages[0])
        a.release(pages[1])
        assert a.available == 2
        # allocation evicts the LRU retained page (pages[0] first)
        p = a.alloc()
        assert p == pages[0]
        assert b'h0' not in a.by_hash          # hash forgotten
        assert a.by_hash[b'h1'] == pages[1]    # newer one survives

    def test_refcount_sharing(self):
        a = PageAllocator(n_pages=4, page_size=4)
        p = a.alloc()
        a.retain(p)
        a.release(p)
        assert a.refcount[p] == 1              # still held by one user
        a.release(p)
        assert p in a.free                     # unregistered -> free list


@pytest.mark.slow
class TestPallasDecodeKernel:
    """Paged-attention Pallas kernel (interpret mode on CPU): the
    engine's pallas decode path matches the gather path exactly."""

    def test_pallas_decode_matches_gather(self, setup):
        cfg, params = setup
        prompts = [[3, 1, 4, 1, 5, 9, 2], [2, 7]]
        outs = {}
        for impl in ('gather', 'pallas'):
            eng = PagedInferenceEngine(cfg, params, max_batch=2,
                                       max_seq=64, page_size=8,
                                       attn_impl='xla',
                                       decode_impl=impl)
            rids = [eng.add_request(p, max_new_tokens=5)
                    for p in prompts]
            done = eng.run_to_completion(horizon=2)
            outs[impl] = [done[r].output for r in rids]
        assert outs['pallas'] == outs['gather'], outs

    def test_pallas_decode_int8(self, setup):
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=64,
                                   page_size=8, quantize='int8',
                                   attn_impl='xla',
                                   decode_impl='pallas')
        rid = eng.add_request(list(range(1, 12)), max_new_tokens=4)
        done = eng.run_to_completion(horizon=2)
        assert len(done[rid].output) == 4


@pytest.mark.slow
class TestContinuousAdmission:
    """Round-5: admission interleaves prefill chunks with decode (the
    wave-synchronous form stalled running requests for a whole wave)."""

    def test_active_request_decodes_between_chunks(self, setup):
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=256,
                                   page_size=8, chunk=16,
                                   decode_impl='gather')
        # Request A fully admitted and decoding.
        a = eng.add_request(list(range(1, 20)), max_new_tokens=64)
        while eng._prefill_off or eng._queue:
            eng.step(horizon=1)
        # Long prompt B needs ~10 chunks; each step runs at most ONE
        # chunk and then decodes — A must gain tokens while B prefill
        # is still in flight (bounded TPOT during admission).
        eng.add_request(list(range(1, 160)), max_new_tokens=4)
        saw_interleave = False
        for _ in range(6):
            events = eng.step(horizon=2)
            if eng._prefill_off and any(rid == a for rid, _, _ in events):
                saw_interleave = True
        assert saw_interleave
        eng.run_to_completion(horizon=4)

    def test_preemption_event_stream_complete(self, setup):
        """Every generated token must surface as a step() event even
        when pool pressure forces a pipeline drain + preemption (the
        serve layer streams from events; a dropped event is a lost
        streamed token or a hung client). Regression: the drain path
        once collected events into an aliased list and lost them."""
        cfg, params = setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=256,
                                   page_size=8, n_pages=12,
                                   decode_impl='gather')
        r1 = eng.add_request(list(range(1, 30)), max_new_tokens=24)
        r2 = eng.add_request(list(range(1, 30)), max_new_tokens=24)
        events = []
        while eng.has_work() or eng._pending:
            events.extend(eng.step(horizon=4))
        assert eng.preemptions >= 1
        for rid in (r1, r2):
            streamed = [t for r, t, _ in events if r == rid]
            out = eng.get_finished(rid).output
            # A preempted request's regenerated tokens stream twice
            # (recompute); the final output must be a SUFFIX of the
            # stream and every output token must have been streamed.
            assert streamed[-len(out):] == out


@pytest.mark.slow
class TestEarlyRecycle:
    """Host-known completion frees slots at ENQUEUE: a budget-bound
    request's slot recycles while its tail tokens are still riding the
    async pipeline. These pin the lifecycle contracts around that
    window (the serve loop and disconnecting clients both hit it)."""

    def _engine(self, cfg, params):
        eng = PagedInferenceEngine(cfg, params, max_batch=2,
                                   max_seq=256, page_size=8,
                                   n_pages=32, decode_impl='gather')
        # Pin the recycle WINDOW: on CPU every result is instantly
        # ready, so the opportunistic drain would collapse the lag
        # these tests exist to exercise.
        eng._eager_drain = False
        return eng

    def test_lagging_tail_tokens_surface(self, setup):
        cfg, params = setup
        eng = self._engine(cfg, params)
        rid = eng.add_request([1, 2, 3, 4] * 3, max_new_tokens=4)
        eng.step(horizon=8)            # prefill + covering decode call
        # Budget covered at enqueue: slot freed, tail still in flight.
        assert all(r is None for r in eng._slots)
        assert eng._pending
        assert eng.has_work()          # lagging request keeps it awake
        done = eng.run_to_completion(horizon=8)
        assert len(done[rid].output) == 4
        assert not eng.has_work() and not eng._lagging

    def test_cancel_in_recycle_window(self, setup):
        cfg, params = setup
        eng = self._engine(cfg, params)
        rid = eng.add_request([5, 6, 7, 8] * 3, max_new_tokens=4)
        eng.step(horizon=8)
        assert all(r is None for r in eng._slots)
        # Early-freed but unfinished: cancel must still find it (a
        # disconnecting client in this window once leaked the request
        # into _finished forever).
        assert eng.cancel(rid) is True
        eng.run_to_completion(horizon=8)
        assert eng.get_finished(rid) is None
        assert not eng.has_work() and not eng._lagging

    def test_stop_sequences_disable_early_free(self, setup):
        cfg, params = setup
        eng = self._engine(cfg, params)
        rid = eng.add_request([1, 2] * 4, max_new_tokens=4,
                              stop=[[99999]])
        eng.step(horizon=8)
        # Completion is data-dependent: the slot must NOT recycle early.
        assert any(r is not None for r in eng._slots)
        done = eng.run_to_completion(horizon=8)
        assert len(done[rid].output) == 4


# ---------------------------------------------------------------------
# The pool gather (tier-1): the layer rides in the gather's index.
# ---------------------------------------------------------------------
def _random_pool(kv_dtype, rng, shape):
    """A stacked pool [L, n_pages, hkv, page, d] (+ scales) whose every
    (layer, page) holds values of its own."""
    if kv_dtype == 'bf16':
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16), None
    if kv_dtype == 'int4':          # two nibble codes a byte along d
        pool = rng.integers(0, 256, shape[:-1] + (shape[-1] // 2,),
                            np.uint8)
    else:
        pool = rng.integers(-127, 128, shape, np.int8)
    return jnp.asarray(pool), jnp.asarray(
        rng.random(shape[:-1], np.float32))


@pytest.mark.parametrize('kv_dtype', ['bf16', 'int8', 'int4'])
def test_gather_layer_equals_numpy_take(kv_dtype):
    """``_gather_layer`` under jit with a TRACED layer equals, bit for
    bit, a plain take of that layer's pages (token-major) — for every
    layer, with padding (page 0), repeated ids and the last page in the
    table, and for a table of one page."""
    n_layers, n_pages, hkv, page, d = 3, 5, 2, 4, 8
    pool, scales = _random_pool(kv_dtype, np.random.default_rng(0),
                                (n_layers, n_pages, hkv, page, d))
    gather = jax.jit(_gather_layer)     # li is an argument: traced

    def take(stacked, li, table):
        g = np.asarray(stacked)[li][table]      # [slots, P, hkv, page, ..]
        g = np.moveaxis(g, 2, 3)                # token-major
        return g.reshape(table.shape[:1] + (-1, hkv) + g.shape[4:])

    # The second table is the one-row gather the helper never emits.
    for table in ([[1, 3, 0, 0], [4, 4, 2, 0]], [[4]]):
        table = np.array(table, np.int32)
        for li in range(n_layers):
            g, s = gather(pool, scales, jnp.int32(li), jnp.asarray(table))
            assert g.dtype == pool.dtype
            np.testing.assert_array_equal(np.asarray(g),
                                          take(pool, li, table))
            if scales is None:
                assert s is None
            else:
                np.testing.assert_array_equal(
                    np.asarray(s), take(scales, li, table)[..., None])


@pytest.mark.parametrize('kv_dtype', ['bf16', 'int8'])
def test_prefill_chunk_pages_at_pool_end(kv_dtype):
    """The index arithmetic's edge: a prompt whose pages sit at the
    pool's far end (so layer L-1 reads the pool's very last page) gives
    bit for bit the first tokens and the cache rows of the same prompt
    in the pool's first pages."""
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_pages, page, chunk = 9, 8, 16
    tokens = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 2 * chunk)).astype(np.int32)
    step = jax.jit(lambda cache, table, toks, lengths: paged_prefill_chunk(
        params, cache, table, toks, lengths,
        jnp.full((1,), chunk, jnp.int32),
        jnp.full((1,), chunk - 1, jnp.int32), cfg))

    def prefill(table):
        cache = PagedKVCache.create(cfg, n_pages=n_pages, page_size=page,
                                    kv_dtype=kv_dtype)
        firsts = []
        for i in range(2):          # the second chunk reads the first's
            first, cache = step(
                cache, jnp.asarray([table], jnp.int32),
                jnp.asarray(tokens[:, i * chunk:(i + 1) * chunk]),
                jnp.full((1,), i * chunk, jnp.int32))
            firsts.append(int(first[0]))
        return firsts, [np.asarray(leaf)[:, table] for leaf in cache
                        if leaf is not None]

    low, high = [1, 2, 3, 4], [n_pages - 1, n_pages - 3, n_pages - 2,
                               n_pages - 4]
    firsts_low, rows_low = prefill(low)
    firsts_high, rows_high = prefill(high)
    assert firsts_high == firsts_low
    for a, b in zip(rows_low, rows_high):
        assert a.any()
        np.testing.assert_array_equal(a, b)
