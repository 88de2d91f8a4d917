"""KV round two: cross-layer fused paged attention, int4 KV codes,
and in-scan speculative verify.

Contracts pinned here:

- **Cross-layer batching (op level).** ``paged_decode_attention_all_layers``
  (one pallas_call, layer axis on the grid) is byte-identical to L
  stacked per-layer ``paged_decode_attention`` calls — bf16, int8 AND
  packed-int4 pools; the packed grid kernel's in-VMEM nibble unpack
  exactly equals the unpacked int8-codes reference.
- **Fused merge (op level).** ``paged_decode_attention_fused`` (cache
  pages + ring + current token in ONE kernel) matches the per-layer
  partial + ``merge_partial_with_ring_self`` XLA merge to float ulps.
- **``decode_impl`` (engine level).** ``gather``, ``pallas`` and
  ``cross_layer`` greedy decode each emit the plain forward's choices
  (``greedy_oracle``) across every KV dtype.
- **int4 KV.** Packed uint8 nibble pools (head_dim/2 minor) with
  absmax/7 scales serve the reference's choices to int4 rounding, and
  the matrix (chunked prefill, prefix-cache reuse, speculative
  commits) holds in the slow tier.
- **In-scan speculative verify.** ``speculate_k`` composed with
  ``decode_steps_per_call > 1`` fuses that many propose→verify→commit
  rounds into ONE dispatch; vanilla, single-round and fused decode are
  three programs, each held to the oracle; the device n-gram proposer
  matches the host proposer on the windowed history; pool pressure
  falls back to single-round verify and requests still complete with
  the reference's tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import greedy_oracle
from skypilot_tpu.inference.engine import (kv_token_bytes,
                                           resolve_kv_cache_dtype)
from skypilot_tpu.inference.paged import PagedInferenceEngine, PagedKVCache
from skypilot_tpu.models import configs, llama
from skypilot_tpu.models import quantization as q
from skypilot_tpu.ops.paged_attention import (
    merge_partial_with_ring_self, paged_decode_attention,
    paged_decode_attention_all_layers, paged_decode_attention_fused)

jax.config.update('jax_platforms', 'cpu')

PROMPTS = [[3, 1, 4, 1, 5, 9, 2], [2, 7]]
REPETITIVE = [3, 1, 4, 1, 5, 9, 2, 6] * 4


@pytest.fixture(scope='module')
def setup():
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy(cfg, params, prompts, n_new, max_batch=None, max_seq=64,
            horizon=2, **kw):
    eng = PagedInferenceEngine(cfg, params,
                               max_batch=max_batch or len(prompts),
                               max_seq=max_seq, attn_impl='xla', **kw)
    return greedy_oracle.greedy(eng, prompts, n_new, horizon=horizon), eng


def _assert_agree(cfg, params, prompts, outs, n_new, kv_dtype='bf16',
                  what=''):
    greedy_oracle.assert_all_agree(cfg, params, prompts, outs, what,
                                   greedy_oracle.KV_KIND[kv_dtype], n_new)


# ---------------------------------------------------------------------------
# int4 KV plumbing (fast tier)
# ---------------------------------------------------------------------------
def test_resolve_and_token_bytes_int4():
    """int4 weights pull the KV to int4 under auto; explicit dtypes
    always win; the per-token byte math (packed codes at head_dim/2
    plus a 4-byte fp32 scale per head) clears 3x vs bf16 at serving
    head dims and feeds page sizing exactly."""
    assert resolve_kv_cache_dtype('int4', None) == 'int4'
    assert resolve_kv_cache_dtype(None, 'int4') == 'int4'
    assert resolve_kv_cache_dtype('auto', 'int4') == 'int4'
    assert resolve_kv_cache_dtype('int8', 'int4') == 'int8'
    cfg = configs.LLAMA3_8B
    bf16 = kv_token_bytes(cfg, quantized=False)
    i4 = kv_token_bytes(cfg, 'int4')
    assert i4 == cfg.n_layers * cfg.n_kv_heads * (cfg.head_dim // 2
                                                  + 4) * 2
    assert bf16 / i4 >= 3.0
    assert PagedInferenceEngine._page_bytes(cfg, 128, 'int4') == i4 * 128


def test_packed_pool_layout():
    """Packed pools are uint8 at head_dim/2 with fp32 scales; the
    ``packed`` / ``quant_mode`` detection is dtype-driven on both cache
    kinds; odd head_dim is refused loudly."""
    cfg = configs.TINY
    pc = PagedKVCache.create(cfg, n_pages=4, page_size=8,
                             kv_dtype='int4')
    assert pc.pool_k.dtype == jnp.uint8
    assert pc.pool_k.shape[-1] == cfg.head_dim // 2
    assert pc.k_scale is not None and pc.k_scale.dtype == jnp.float32
    assert pc.packed and pc.quant_mode == 'int4'
    sc = llama.KVCache.create(cfg, 2, 16, kv_dtype='int4')
    assert sc.k.dtype == jnp.uint8
    assert sc.k.shape[-1] == cfg.head_dim // 2
    assert sc.packed and sc.quantized
    import dataclasses
    odd = dataclasses.replace(cfg, head_dim_override=3)
    with pytest.raises(ValueError):
        llama.KVCache.create(odd, 2, 16, kv_dtype='int4')


def test_quantize_kv_rows4_round_trip():
    """absmax/7 row quantization: codes stay in [-7, 7], packed low
    nibble first along head_dim, and unpack x scale reconstructs to
    within half a quantization step."""
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((2, 5, 3, 8))
                       .astype(np.float32))
    codes, scale = llama.quantize_kv_rows4(rows)
    assert codes.dtype == jnp.uint8 and codes.shape[-1] == 4
    unpacked = q.unpack_int4(np.asarray(codes), axis=-1)
    assert unpacked.min() >= -7 and unpacked.max() <= 7
    recon = unpacked.astype(np.float32) * np.asarray(scale)
    err = np.abs(recon - np.asarray(rows))
    assert (err <= 0.5 * np.asarray(scale) + 1e-6).all()


# ---------------------------------------------------------------------------
# Cross-layer / fused kernels (op level, interpret mode)
# ---------------------------------------------------------------------------
def _make_pools(seed, L=2, n_pages=9, hkv=2, page=8, d=8, slots=3,
                P=2, mode='bf16'):
    rng = np.random.default_rng(seed)
    hq = 2 * hkv
    q_all = jnp.asarray(rng.standard_normal((L, slots, hq, d))
                        .astype(np.float32))
    # Distinct pages per slot (page 0 reserved, engine-style).
    ids = rng.permutation(np.arange(1, n_pages))[:slots * P]
    table = jnp.asarray(ids.reshape(slots, P).astype(np.int32))
    lengths = jnp.asarray(
        rng.integers(1, page * P + 1, slots).astype(np.int32))
    shape = (L, n_pages, hkv, page, d)
    if mode == 'bf16':
        pk = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        pv = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        return q_all, pk, pv, None, None, table, lengths
    lo = -7 if mode == 'int4' else -127
    hi = 8 if mode == 'int4' else 128
    ck = rng.integers(lo, hi, shape).astype(np.int8)
    cv = rng.integers(lo, hi, shape).astype(np.int8)
    ks = jnp.asarray(rng.random(shape[:-1]).astype(np.float32) + 0.1)
    vs = jnp.asarray(rng.random(shape[:-1]).astype(np.float32) + 0.1)
    if mode == 'int4':
        return (q_all, jnp.asarray(q.pack_int4(ck, axis=-1)),
                jnp.asarray(q.pack_int4(cv, axis=-1)), ks, vs,
                table, lengths), (jnp.asarray(ck), jnp.asarray(cv))
    return q_all, jnp.asarray(ck), jnp.asarray(cv), ks, vs, table, lengths


@pytest.mark.parametrize('mode', ['bf16', 'int8'])
def test_all_layers_kernel_matches_per_layer(mode):
    """ONE pallas_call over (slots, L, P) == L per-layer calls,
    bit-for-bit (same op sequence per page block)."""
    q_all, pk, pv, ks, vs, table, lengths = _make_pools(1, mode=mode)
    L = q_all.shape[0]
    acc, m, l = paged_decode_attention_all_layers(
        q_all, pk, pv, table, lengths, ks, vs, interpret=True)
    for li in range(L):
        a1, m1, l1 = paged_decode_attention(
            q_all[li], pk, pv, table, lengths, ks, vs, layer=li,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(acc[li]),
                                      np.asarray(a1))
        np.testing.assert_array_equal(np.asarray(m[li]), np.asarray(m1))
        np.testing.assert_array_equal(np.asarray(l[li]), np.asarray(l1))


def test_all_layers_kernel_int4_packed_exact():
    """The packed-int4 grid kernel's in-VMEM nibble unpack is EXACTLY
    the unpacked int8-codes computation (scale-agnostic integer code
    math before the fold)."""
    (q_all, pk4, pv4, ks, vs, table, lengths), (ck, cv) = \
        _make_pools(2, mode='int4')
    got = paged_decode_attention_all_layers(
        q_all, pk4, pv4, table, lengths, ks, vs, interpret=True)
    want = paged_decode_attention_all_layers(
        q_all, ck, cv, table, lengths, ks, vs, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize('mode', ['bf16', 'int8'])
def test_fused_kernel_matches_xla_merge(mode):
    """The fused kernel (pages + ring + current token, one kernel) ==
    per-layer partial then ``merge_partial_with_ring_self`` to float
    ulps (the merge runs elementwise sums where XLA uses dots)."""
    q_all, pk, pv, ks, vs, table, lengths = _make_pools(3, mode=mode)
    rng = np.random.default_rng(4)
    L, slots, hq, d = q_all.shape
    hkv = pk.shape[2]
    H = 4
    k_self = jnp.asarray(rng.standard_normal((slots, hkv, d))
                         .astype(np.float32))
    v_self = jnp.asarray(rng.standard_normal((slots, hkv, d))
                         .astype(np.float32))
    ring_k = jnp.asarray(rng.standard_normal((slots, H, hkv, d))
                         .astype(np.float32))
    ring_v = jnp.asarray(rng.standard_normal((slots, H, hkv, d))
                         .astype(np.float32))
    for ring_len in (0, 2):
        for li in range(L):
            got = paged_decode_attention_fused(
                q_all[li], k_self, v_self, ring_k, ring_v, ring_len,
                pk, pv, table, lengths, ks, vs, layer=li,
                interpret=True)
            partial = paged_decode_attention(
                q_all[li], pk, pv, table, lengths, ks, vs, layer=li,
                interpret=True)
            want = merge_partial_with_ring_self(
                partial, q_all[li][:, None], k_self[:, None],
                v_self[:, None], ring_k, ring_v, ring_len)[:, 0]
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('dtype', ['bf16', 'int8', 'int4'])
def test_cross_layer_engine_identity(setup, dtype):
    """``decode_impl`` 'gather', 'pallas' and 'cross_layer' (the same
    math in three programs) each decode the reference's choices, for
    every KV dtype."""
    cfg, params = setup
    for impl in ('gather', 'pallas', 'cross_layer'):
        outs, _ = _greedy(cfg, params, PROMPTS, 5, page_size=8,
                          kv_cache_dtype=dtype, decode_impl=impl)
        _assert_agree(cfg, params, PROMPTS, outs, 5, dtype,
                      f'{impl}/{dtype}')


def test_int4_greedy_smoke(setup):
    """Tier-1 smoke: greedy decode over int4 KV emits the reference's
    choices to int4 rounding, as bf16 KV does to bf16's (tiny model;
    the matrix rides the slow tier)."""
    cfg, params = setup
    for dtype in ('bf16', 'int4'):
        outs, eng = _greedy(cfg, params, PROMPTS, 8, page_size=8,
                            kv_cache_dtype=dtype)
        _assert_agree(cfg, params, PROMPTS, outs, 8, dtype, dtype)
    assert eng.cache.packed and eng.kv_cache_dtype == 'int4'


# ---------------------------------------------------------------------------
# In-scan speculative verify (fast tier)
# ---------------------------------------------------------------------------
def test_ngram_propose_device_matches_host():
    """The device proposer == the host proposer run on the windowed
    (right-aligned, H-token) history — same match, same continuation,
    same count."""
    from skypilot_tpu.inference.speculative import (ngram_propose,
                                                    ngram_propose_device)
    rng = np.random.RandomState(0)
    H, k = 64, 4
    for _ in range(50):
        n = rng.randint(2, 80)
        vocab = int(rng.choice([3, 5, 50]))
        hist = rng.randint(0, vocab, size=n).tolist()
        row = np.full((1, H), -1, np.int32)
        t = hist[-H:]
        row[0, H - len(t):] = t
        prop, n_prop = ngram_propose_device(jnp.asarray(row), k)
        m = int(n_prop[0])
        want = ngram_propose(hist[-H:], k)
        assert m == len(want)
        assert np.asarray(prop)[0, :m].tolist() == want[:m].tolist()
        # Positions past n_prop are zeroed (fixed-shape contract).
        assert (np.asarray(prop)[0, m:] == 0).all()


SPEC_PROMPTS = [REPETITIVE[:16], [2, 7, 2, 7, 2, 7, 2, 7]]
SPEC_KW = {'page_size': 8, 'decode_impl': 'gather'}


def test_spec_fused_agrees_with_oracle(setup):
    """THE composition contract: vanilla greedy decode, single-round
    speculation and speculate_k x decode_steps_per_call fused rounds all
    commit the reference's choices — the in-scan device proposer and
    budget carry change the dispatch count, never what is accepted."""
    cfg, params = setup
    engines = {}
    for what, kw in (('vanilla', {}), ('single', {'speculate_k': 3}),
                     ('fused', {'speculate_k': 3,
                                'decode_steps_per_call': 3})):
        outs, engines[what] = _greedy(cfg, params, SPEC_PROMPTS, 12,
                                      **SPEC_KW, **kw)
        _assert_agree(cfg, params, SPEC_PROMPTS, outs, 12, what=what)
    # Both paths accept drafts on the repetitive prompts, and the
    # stable metrics schema keeps reporting.
    e1, e2 = engines['single'], engines['fused']
    assert e1.spec_metrics()['spec_accepted'] > 0
    assert e2.spec_metrics()['spec_accepted'] > 0
    assert e2.spec_metrics()['spec_rounds'] >= e2.spec_metrics()[
        'speculate_k']


def test_spec_fused_int4_composes(setup):
    """All three fronts at once: int4 KV + fused spec rounds still
    commit the reference's choices, to int4 rounding."""
    cfg, params = setup
    got, eng = _greedy(cfg, params, SPEC_PROMPTS, 10,
                       kv_cache_dtype='int4', speculate_k=3,
                       decode_steps_per_call=3, **SPEC_KW)
    _assert_agree(cfg, params, SPEC_PROMPTS, got, 10, 'int4',
                  'int4 KV + fused spec')
    assert eng.cache.packed


def test_spec_fused_pool_pressure_fallback(setup):
    """When the pool cannot reserve rounds x (k+1) rows up front, the
    fused step falls back to single-round verify — requests complete,
    with the reference's tokens."""
    cfg, params = setup
    got, _ = _greedy(cfg, params, SPEC_PROMPTS, 10, n_pages=10,
                     speculate_k=3, decode_steps_per_call=4, **SPEC_KW)
    _assert_agree(cfg, params, SPEC_PROMPTS, got, 10,
                  what='fused spec under pool pressure')


def test_spec_fused_budget_respected(setup):
    """The in-scan ``rem`` carry never overshoots ``max_new_tokens``
    even when rounds x (k+1) far exceeds the remaining budget."""
    cfg, params = setup
    got, _ = _greedy(cfg, params, [REPETITIVE[:16]], 3, speculate_k=4,
                     decode_steps_per_call=4)
    _assert_agree(cfg, params, [REPETITIVE[:16]], got, 3,
                  what='fused spec at the budget')


# ---------------------------------------------------------------------------
# Slow tier: the int4-vs-bf16 divergence matrix (mirrors test_kv_int8)
# ---------------------------------------------------------------------------
MATRIX_PROMPTS = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1, 8],
                  [(i * 7 + 3) % 256 for i in range(60)]]


@pytest.mark.slow
class TestKVInt4Equivalence:

    def _greedy4(self, cfg, params, prompts, n_new, **kw):
        return _greedy(cfg, params, prompts, n_new, max_batch=4,
                       max_seq=256, horizon=4, **kw)

    def test_paged_chunked_prefill(self, setup):
        """Chunking under int4: later chunks attend over already
        quantized rows, so chunk widths 16 and 8 are different programs
        with a REAL int4 perturbation between them; each is held to the
        oracle at int4 rounding, and the chunk counter proves the
        60-token prompt actually chunked."""
        cfg, params = setup
        for chunk, n_chunks in ((16, 4), (8, 8)):
            outs, eng = self._greedy4(cfg, params, MATRIX_PROMPTS, 12,
                                      kv_cache_dtype='int4', page_size=8,
                                      chunk=chunk)
            _assert_agree(cfg, params, MATRIX_PROMPTS, outs, 12, 'int4',
                          f'chunk {chunk}')
            assert eng.chunks_prefilled >= n_chunks

    def test_prefix_cache_reuse(self, setup):
        """THE reuse contract: a prefix HIT serving from already-packed
        pages is byte-identical to a COLD run of the same request on
        the same engine config — reuse changes where bytes come from,
        never what they are."""
        cfg, params = setup
        shared = [(i * 5 + 2) % 256 for i in range(64)]
        p1, p2 = shared + [11, 12], shared + [13, 14, 15]
        cold, _ = self._greedy4(cfg, params, [p2], 8,
                                kv_cache_dtype='int4', page_size=8,
                                chunk=16)
        eng = PagedInferenceEngine(cfg, params, max_batch=1,
                                   max_seq=256, page_size=8, chunk=16,
                                   attn_impl='xla',
                                   kv_cache_dtype='int4')
        eng.add_request(p1, max_new_tokens=4)
        eng.run_to_completion(horizon=4)
        assert eng.alloc.prefix_misses == 1
        r2 = eng.add_request(p2, max_new_tokens=8)
        done = eng.run_to_completion(horizon=4)
        assert eng.alloc.prefix_hits >= 1
        assert done[r2].output == cold[0]

    def test_speculative_commits(self, setup):
        """Spec verify with int4 KV (in-window verify rows ride full
        precision where vanilla rows are requantized): the reference's
        choices to int4 rounding, nonzero acceptance."""
        cfg, params = setup
        prompts = [REPETITIVE, MATRIX_PROMPTS[2]]
        got, eng = self._greedy4(cfg, params, prompts, 16,
                                 kv_cache_dtype='int4', page_size=8,
                                 speculate_k=4)
        _assert_agree(cfg, params, prompts, got, 16, 'int4',
                      'spec over int4 KV')
        assert eng.spec_metrics()['spec_accepted'] > 0
