"""int8 KV cache (``kv_cache_dtype``): the decoupled KV storage knob
and its contract — greedy decode with int8 KV emits the plain forward's
choices to int8 rounding (``greedy_oracle``) through every KV write path
(chunked prefill, decode appends, speculative masked commits,
prefix-cache reuse, preemption recompute).
Fast tier: the per-token byte-cost math every capacity surface rides,
the knob resolution, the pool-stats schema, and one smoke; the matrix
rides the slow tier with the other engine suites."""
import jax
import pytest

import greedy_oracle
from skypilot_tpu.inference.engine import (kv_token_bytes,
                                           resolve_kv_cache_dtype)
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, llama


@pytest.fixture(scope='module')
def setup():
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy(cfg, params, prompts, n_new, **kw):
    eng = PagedInferenceEngine(cfg, params, max_batch=4, max_seq=256,
                               attn_impl='xla', **kw)
    return greedy_oracle.greedy(eng, prompts, n_new), eng


def _assert_agree(cfg, params, prompts, outs, n_new, what):
    greedy_oracle.assert_all_agree(cfg, params, prompts, outs, what,
                                   'int8_kv', n_new)


# ---------------------------------------------------------------------------
# Fast tier
# ---------------------------------------------------------------------------
def test_resolve_kv_cache_dtype():
    """None/'auto' follows the weight quantize mode (the historical
    coupling); explicit values decouple in either direction."""
    assert resolve_kv_cache_dtype(None, None) == 'bf16'
    assert resolve_kv_cache_dtype(None, 'int8') == 'int8'
    assert resolve_kv_cache_dtype('auto', 'int8') == 'int8'
    assert resolve_kv_cache_dtype('auto', None) == 'bf16'
    assert resolve_kv_cache_dtype('bf16', 'int8') == 'bf16'
    assert resolve_kv_cache_dtype('int8', None) == 'int8'
    with pytest.raises(ValueError):
        resolve_kv_cache_dtype('fp8', None)


def test_kv_token_bytes_math():
    """The ONE per-token byte cost behind pool sizing, prefill caps,
    preemption pressure and the telemetry gauges: int8 rows are codes
    plus a 4-byte fp32 absmax scale. At serving head_dims (128) the
    bf16/int8 ratio clears the 1.8x pool-capacity acceptance bar."""
    cfg = configs.LLAMA3_8B
    bf16 = kv_token_bytes(cfg, quantized=False)
    i8 = kv_token_bytes(cfg, quantized=True)
    assert bf16 == cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2 * 2
    assert i8 == cfg.n_layers * cfg.n_kv_heads * (cfg.head_dim + 4) * 2
    assert bf16 / i8 >= 1.8
    # Paged pages cost exactly page_size tokens at this rate — the pool
    # auto-size and the capacity gauges can never drift from it.
    assert PagedInferenceEngine._page_bytes(cfg, 128, True) == i8 * 128
    assert PagedInferenceEngine._page_bytes(cfg, 128, False) == bf16 * 128


def test_kv_pool_stats_schema(setup):
    """The token-denominated pool schema the telemetry gauges read:
    page-granular, counting only allocatable pages (page 0 reserved),
    for a quantized pool and for the decoupled spelling (int8 weights
    over bf16 KV)."""
    cfg, params = setup
    keys = {'kv_cache_dtype', 'pool_token_capacity', 'tokens_used',
            'tokens_free', 'preemptions', 'kv_token_bytes',
            'kv_token_bytes_per_shard', 'kv_shards'}
    for kv_dtype, quantize in (('int8', None), ('bf16', 'int8')):
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=64,
                                   page_size=8, attn_impl='xla',
                                   kv_cache_dtype=kv_dtype,
                                   quantize=quantize)
        s = eng.kv_pool_stats()
        assert set(s) == keys
        assert s['kv_cache_dtype'] == kv_dtype
        assert eng.cache.quantized == (kv_dtype == 'int8')
        assert s['pool_token_capacity'] == (eng.alloc.n_pages - 1) * 8
        assert (s['tokens_used'] + s['tokens_free']
                == s['pool_token_capacity'])
        assert s['kv_token_bytes'] == kv_token_bytes(cfg,
                                                     kv_dtype == 'int8')


def test_int8_kv_greedy_smoke(setup):
    """Tier-1 smoke: int8 KV over bf16 weights (prefill scatter + decode
    appends through quantized pages) emits the reference's choices."""
    cfg, params = setup
    prompts = [[3, 1, 4, 1, 5]]
    outs, eng = _greedy(cfg, params, prompts, 8, kv_cache_dtype='int8',
                        page_size=8)
    assert eng.cache.quantized and eng.kv_cache_dtype == 'int8'
    _assert_agree(cfg, params, prompts, outs, 8, 'int8 KV')


# ---------------------------------------------------------------------------
# Slow tier: the int8-vs-bf16 equivalence matrix
# ---------------------------------------------------------------------------
PROMPTS = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1, 8],
           [(i * 7 + 3) % 256 for i in range(60)]]
REPETITIVE = [3, 1, 4, 1, 5, 9, 2, 6] * 4


@pytest.mark.slow
class TestKVInt8Equivalence:

    def test_paged_chunked_prefill(self, setup):
        """Chunked prefill quantizes per chunk inside the layer scan;
        later chunks read the quantized rows back."""
        cfg, params = setup
        i8, eng = _greedy(cfg, params, PROMPTS, 12, kv_cache_dtype='int8',
                          page_size=8, chunk=16)
        _assert_agree(cfg, params, PROMPTS, i8, 12, 'chunked int8 KV')
        assert eng.chunks_prefilled >= 4      # 60-token prompt, chunk 16

    def test_speculative_commits(self, setup):
        """speculate_k>0 with int8 KV: the masked KV commit writes
        quantized rows and decode continues off them. int8 KV rounds at
        different points in the verify forward (in-window rows ride
        full precision) than in vanilla decode, so the two may differ
        on a near-tie: the contract is the reference's choices to int8
        rounding, and nonzero acceptance."""
        cfg, params = setup
        prompts = [REPETITIVE, PROMPTS[2]]
        got, eng = _greedy(cfg, params, prompts, 16, kv_cache_dtype='int8',
                           speculate_k=4, page_size=8)
        _assert_agree(cfg, params, prompts, got, 16, 'spec over int8 KV')
        assert eng.spec_metrics()['spec_accepted'] > 0

    def test_prefix_cache_reuse(self, setup):
        """A prefix-cache HIT reuses already-quantized pages — the
        second request's decode reads them through the fused-dequant
        kernel and still emits the reference's choices."""
        cfg, params = setup
        shared = [(i * 5 + 2) % 256 for i in range(64)]
        p1, p2 = shared + [11, 12], shared + [13, 14, 15]
        eng = PagedInferenceEngine(cfg, params, max_batch=1,
                                   max_seq=256, page_size=8, chunk=16,
                                   attn_impl='xla',
                                   kv_cache_dtype='int8')
        greedy_oracle.greedy(eng, [p1], 4)
        assert eng.alloc.prefix_misses == 1
        got = greedy_oracle.greedy(eng, [p2], 8)
        assert eng.alloc.prefix_hits >= 1
        _assert_agree(cfg, params, [p2], got, 8, 'int8 prefix hit')

    def test_preemption_recompute(self, setup):
        """Pool pressure preempts + recomputes with quantized pages and
        the preemption count surfaces through kv_pool_stats (the
        telemetry counter)."""
        cfg, params = setup
        got, eng = _greedy(cfg, params, PROMPTS, 12, kv_cache_dtype='int8',
                           page_size=8, n_pages=12)
        assert eng.preemptions >= 1
        assert eng.kv_pool_stats()['preemptions'] == eng.preemptions
        _assert_agree(cfg, params, PROMPTS, got, 12,
                      'preempted + recomputed, int8 KV')
