"""int4 fused-dequant weights (``quantize='int4'``).

Contracts pinned here:

- pack/unpack exactness (numpy AND jnp paths, every axis) — the one
  nibble layout (low nibble first, sign-extended, last contracted
  axis) graftcheck GC119 routes everyone to;
- per-channel and ``SKYTPU_INT4_GROUP`` group-wise scale math, and the
  fused ``qeinsum`` contraction matching an explicit
  unpack-dequantize-einsum reference;
- stored-bytes capacity: the quantize-eligible leaves pack to >= 1.8x
  smaller than int8 (0.5x codes + shared scale overhead);
- engine integration: greedy smoke, int4 => int4 KV auto coupling,
  prefix-cache reuse, tp=2 sharded packed codes byte-identical to
  tp=1's, each run's tokens held to the plain forward of the same int4
  tree (``greedy_oracle``);
- THE numerics contract: the int4 engine's greedy tokens are the ones
  the plain forward of the explicitly DEQUANTIZED int4 tree would
  choose — the engine serves exactly the model its codes + scales
  define. (Divergence vs the unquantized bf16 model is the
  quantization error itself — unbounded in principle on random-init
  weights — so agreement is pinned against the quantized model, not
  the parent.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import greedy_oracle
from skypilot_tpu.inference.engine import (prepare_params,
                                           resolve_kv_cache_dtype)
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, llama
from skypilot_tpu.models import quantization as q

PROMPTS = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1, 8],
           [(i * 7 + 3) % 256 for i in range(60)]]


@pytest.fixture(scope='module')
def setup():
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy(cfg, params, prompts, n_new, **kw):
    eng = PagedInferenceEngine(cfg, params, max_batch=4, max_seq=256,
                               attn_impl='xla', **kw)
    return greedy_oracle.greedy(eng, prompts, n_new), eng


def _assert_agree(cfg, ref_params, prompts, outs, kind, what):
    greedy_oracle.assert_all_agree(cfg, ref_params, prompts, outs, what,
                                   kind)


# ---------------------------------------------------------------------------
# Pack / unpack / quantize math
# ---------------------------------------------------------------------------
def test_pack_unpack_exact_numpy_and_jnp():
    rng = np.random.default_rng(0)
    codes = rng.integers(-7, 8, size=(6, 8, 10)).astype(np.int8)
    for ax in (0, 1, 2, -1):
        packed = q.pack_int4(codes, axis=ax)
        assert isinstance(packed, np.ndarray)
        assert packed.dtype == np.uint8
        assert packed.shape[ax] * 2 == codes.shape[ax] \
            or packed.shape[ax] == codes.shape[ax] // 2
        assert np.array_equal(q.unpack_int4(packed, axis=ax), codes)
    pj = q.pack_int4(jnp.asarray(codes), axis=1)
    assert np.array_equal(np.asarray(q.unpack_int4(pj, axis=1)), codes)
    # Full code range incl. -8 (never produced by quantize, but the
    # sign extension must be total).
    edge = np.arange(-8, 8, dtype=np.int8)
    assert np.array_equal(q.unpack_int4(q.pack_int4(edge)), edge)


def test_pack_odd_axis_raises():
    with pytest.raises(ValueError):
        q.pack_int4(np.zeros((3, 4), np.int8), axis=0)


def _dequant4_np(w4: q.QuantizedWeight4, reduce_axes) -> np.ndarray:
    """Explicit unpack + per-group scale reference (test-local)."""
    ax = reduce_axes[-1]
    codes = q.unpack_int4(np.asarray(w4.packed), axis=ax)
    scale = np.asarray(w4.scale, np.float32)
    rep = np.repeat(scale, codes.shape[ax] // scale.shape[ax], axis=ax)
    return codes.astype(np.float32) * rep


def test_quantize_array4_per_channel():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(16, 4, 6)).astype(np.float32))
    w4 = q._quantize_array4(w, (0,))
    assert w4.packed.dtype == jnp.uint8
    assert w4.packed.shape == (8, 4, 6)
    assert w4.scale.shape == (1, 4, 6)
    codes = q.unpack_int4(np.asarray(w4.packed), axis=0)
    assert codes.min() >= -7 and codes.max() <= 7
    err = np.abs(_dequant4_np(w4, (0,)) - np.asarray(w))
    # Bounded by half a quantization step per channel.
    step = np.asarray(w4.scale, np.float32)
    assert (err <= 0.5 * step + 1e-6).all()


def test_group_scale_math(monkeypatch):
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(16, 4, 6)).astype(np.float32))
    g = q._quantize_array4(w, (0,), group=4)
    assert g.scale.shape == (4, 4, 6)         # G = 16/4 groups
    assert g.packed.shape == (8, 4, 6)
    x = jnp.asarray(rng.normal(size=(2, 3, 16)).astype(np.float32))
    y = q.qeinsum('bsd,dhk->bshk', x, g, out_dtype=jnp.float32)
    ref = np.einsum('bsd,dhk->bshk', np.asarray(x),
                    _dequant4_np(g, (0,)))
    assert np.allclose(np.asarray(y), ref, atol=1e-4)
    # Grouped multi-axis contraction (wo-shape: contract heads + hd).
    w2 = jnp.asarray(rng.normal(size=(4, 6, 16)).astype(np.float32))
    g2 = q._quantize_array4(w2, (0, 1), group=2)
    assert g2.scale.shape == (1, 3, 16)
    x2 = jnp.asarray(rng.normal(size=(2, 3, 4, 6)).astype(np.float32))
    y2 = q.qeinsum('bshk,hkd->bsd', x2, g2, out_dtype=jnp.float32)
    ref2 = np.einsum('bshk,hkd->bsd', np.asarray(x2),
                     _dequant4_np(g2, (0, 1)))
    assert np.allclose(np.asarray(y2), ref2, atol=1e-4)
    # Invalid group sizes fail loudly at quantize time.
    with pytest.raises(ValueError):
        q._quantize_array4(w, (0,), group=3)      # odd
    with pytest.raises(ValueError):
        q._quantize_array4(w, (0,), group=5)      # does not divide
    # The env knob feeds quantize_params.
    monkeypatch.setenv('SKYTPU_INT4_GROUP', '8')
    assert q.int4_group_size() == 8
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    p4 = q.quantize_params(params, mode='int4')
    wq = p4['layers']['wq']
    assert wq.scale.shape[1] == cfg.dim // 8      # grouped along d


def test_qeinsum4_matches_dequant_reference():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(16, 4, 6)).astype(np.float32))
    w4 = q._quantize_array4(w, (0,))
    x = jnp.asarray(rng.normal(size=(2, 3, 16)).astype(np.float32))
    y = q.qeinsum('bsd,dhk->bshk', x, w4, out_dtype=jnp.float32)
    ref = np.einsum('bsd,dhk->bshk', np.asarray(x),
                    _dequant4_np(w4, (0,)))
    assert np.allclose(np.asarray(y), ref, atol=1e-4)
    # deq() refuses int4 leaves (packed axis is contraction-specific).
    with pytest.raises(TypeError):
        q.deq(w4)


def test_capacity_ratio_vs_int8(setup):
    """The quantize-eligible leaves (the stream the knob shrinks) pack
    to >= 1.8x smaller than int8 — 0.5x codes + shared scale
    overhead."""
    cfg, params = setup
    p8 = q.quantize_params(params, mode='int8')
    p4 = q.quantize_params(params, mode='int4')

    def quantizable_bytes(tree):
        total = 0
        for key, val in tree['layers'].items():
            if key in q.REDUCE_AXES:
                total += q.quantized_bytes({'x': val})
        if 'unembed' in tree:
            total += q.quantized_bytes({'x': tree['unembed']})
        return total

    ratio = quantizable_bytes(p8) / quantizable_bytes(p4)
    assert ratio >= 1.8, ratio
    # And the whole-tree stored bytes shrink too.
    assert q.quantized_bytes(p4) < q.quantized_bytes(p8)


def test_mode_detection_and_prepare_params(setup):
    cfg, params = setup
    p4 = q.quantize_params(params, mode='int4')
    assert q.quantized_mode(p4) == 'int4'
    assert q.is_quantized(p4)
    assert q.quantized_mode(params) is None
    # prepare_params: on-the-fly int4, and pass-through of a
    # pre-quantized int4 tree (quantize=None resolves to 'int4').
    _, tree, eff = prepare_params(cfg, params, quantize='int4')
    assert eff == 'int4'
    assert isinstance(tree['layers']['wq'], q.QuantizedWeight4)
    _, _, eff2 = prepare_params(cfg, p4, quantize=None)
    assert eff2 == 'int4'
    with pytest.raises(ValueError):
        prepare_params(cfg, params, quantize='int2')
    # int4 weights pull the KV down to int4 under auto (KV round two);
    # an explicit dtype always wins.
    assert resolve_kv_cache_dtype(None, 'int4') == 'int4'
    assert resolve_kv_cache_dtype('bf16', 'int4') == 'bf16'
    assert resolve_kv_cache_dtype('int8', 'int4') == 'int8'


def test_moe_leaves_stay_int8():
    """int4 mode quantizes the dense leaves to packed nibbles; MoE
    expert leaves (deq()-consumed in models/moe.py) stay int8."""
    cfg = configs.TINY_MOE
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    p4 = q.quantize_params(params, mode='int4')
    assert isinstance(p4['layers']['wq'], q.QuantizedWeight4)
    assert isinstance(p4['layers']['moe_gate'], q.QuantizedWeight)
    # And the engine serves it.
    outs, _ = _greedy(cfg, p4, [[1, 2, 3]], 4)
    assert len(outs[0]) == 4


def test_engine_greedy_smoke(setup):
    """Tier-1 smoke: the engine serves int4 weights (auto int4 KV — KV
    round two), and what it emits is the choice of the plain forward of
    the same int4 tree, to int4 KV rounding."""
    cfg, params = setup
    outs, eng = _greedy(cfg, params, PROMPTS, 8, quantize='int4',
                        page_size=8, chunk=16)
    assert eng.kv_cache_dtype == 'int4' and eng.cache.packed
    assert isinstance(eng.params['layers']['w_up'], q.QuantizedWeight4)
    assert all(len(out) == 8 for out in outs)
    _assert_agree(cfg, eng.params, PROMPTS, outs, 'int4_kv', 'int4')


# ---------------------------------------------------------------------------
# Slow tier: equivalence matrix
# ---------------------------------------------------------------------------
def _dequantized_tree(cfg, p4):
    """bf16 tree carrying exactly the int4 model's values."""
    def leaf(key, v):
        if isinstance(v, q.QuantizedWeight4):
            return jnp.asarray(
                _dequant4_np(v, q.REDUCE_AXES[key]).astype(cfg.dtype))
        if isinstance(v, q.QuantizedWeight):
            return jnp.asarray(
                (np.asarray(v.int8, np.float32)
                 * np.asarray(v.scale, np.float32)).astype(cfg.dtype))
        return v

    out = {}
    for k, v in p4.items():
        if k == 'layers':
            out[k] = {kk: leaf(kk, vv) for kk, vv in v.items()}
        else:
            out[k] = leaf(k, v)
    return out


@pytest.mark.slow
class TestInt4Equivalence:

    def test_engine_matches_dequantized_reference(self, setup):
        """THE int4 numerics contract: the tokens of the fused-dequant
        engine are the choices of the plain forward of the explicitly
        dequantized int4 tree — chunked prefill included. The engine
        serves exactly the model its codes + scales define.

        The fused path folds the per-channel scale into the fp32 dot
        OUTPUT while the dequantized tree rounds every weight to bf16
        first — sub-ULP projection differences by construction, so the
        two are different programs and tokens may flip on a near-tie:
        each KV precision is held to its own tolerance."""
        cfg, params = setup
        p4 = q.quantize_params(params, mode='int4')
        ref_tree = _dequantized_tree(cfg, p4)
        for kv_dtype in ('int8', 'int4'):
            got, _ = _greedy(cfg, params, PROMPTS, 16, quantize='int4',
                             kv_cache_dtype=kv_dtype, page_size=8,
                             chunk=16)
            assert all(len(out) == 16 for out in got)
            _assert_agree(cfg, ref_tree, PROMPTS, got,
                          greedy_oracle.KV_KIND[kv_dtype],
                          f'int4 weights, {kv_dtype} KV')

    def test_prefix_cache_reuse(self, setup):
        """A prefix HIT reuses pages written under int4 weights; the
        continuation is still the int4 tree's own choice."""
        cfg, params = setup
        shared = [(i * 5 + 2) % 256 for i in range(64)]
        p1, p2 = shared + [11, 12], shared + [13, 14, 15]
        eng = PagedInferenceEngine(cfg, params, max_batch=1,
                                   max_seq=256, page_size=8, chunk=16,
                                   attn_impl='xla', quantize='int4')
        eng.add_request(p1, max_new_tokens=4)
        eng.run_to_completion(horizon=4)
        assert eng.alloc.prefix_misses == 1
        r2 = eng.add_request(p2, max_new_tokens=8)
        done = eng.run_to_completion(horizon=4)
        assert eng.alloc.prefix_hits >= 1
        _assert_agree(cfg, eng.params, [p2], [done[r2].output],
                      'int4_kv', 'int4 prefix hit')

    def test_tp2_sharded_packed_codes(self, setup, tp_devices):
        """tp=2: packed nibble codes shard like their parents: the
        resident packed bytes are byte-identical to tp=1's, and each
        program's tokens (the sharded one sums partial products in
        another order) are the int4 tree's own choice."""
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.utils.host import host_sync
        cfg, params = setup
        o1, e1 = _greedy(cfg, params, PROMPTS[:2], 8, quantize='int4',
                         prefill_chunk_tokens=16)
        o2, e2 = _greedy(cfg, params, PROMPTS[:2], 8, quantize='int4',
                         prefill_chunk_tokens=16,
                         mesh=mesh_lib.serving_mesh(tp=2))
        for outs, what in ((o1, 'tp=1'), (o2, 'tp=2')):
            _assert_agree(cfg, e1.params, PROMPTS[:2], outs, 'int4_kv',
                          what)
        for key in ('wq', 'w_down'):
            a = np.asarray(host_sync(e1.params['layers'][key].packed))
            b = np.asarray(host_sync(e2.params['layers'][key].packed))
            assert a.dtype == np.uint8
            assert np.array_equal(a, b), key


@pytest.mark.slow
def test_load_checkpoint_int4(tmp_path, setup):
    """Host-side int4 quantization during checkpoint load: packed
    leaves byte-identical to the on-device quantizer's, the
    ``.int4_cache.bin`` round-trips, and the loaded tree serves."""
    from skypilot_tpu.models import weights
    cfg, params = setup
    path = str(tmp_path / 'ckpt')
    weights.save_hf_checkpoint(path, cfg, params)
    # fp32 load: checkpoint values, host scales and the on-device
    # comparison tree all share one dtype, so the host quantizer must
    # match the device quantizer BYTE-FOR-BYTE (same rounded-scale
    # contract, same round-half-even).
    cfg2, loaded = weights.load_checkpoint(path, dtype=jnp.float32,
                                           quantize='int4')
    wq = loaded['layers']['wq']
    assert isinstance(wq, q.QuantizedWeight4)
    fp32 = {k: (v if k != 'layers' else
                {kk: jnp.asarray(np.asarray(vv), jnp.float32)
                 if kk in q.REDUCE_AXES else vv
                 for kk, vv in v.items()})
            for k, v in params.items()}
    dev = q.quantize_params(
        {**fp32, 'layers': {**fp32['layers']}}, mode='int4')
    assert np.array_equal(np.asarray(wq.packed),
                          np.asarray(dev['layers']['wq'].packed))
    assert np.array_equal(np.asarray(wq.scale),
                          np.asarray(dev['layers']['wq'].scale))
    # Cache round-trip: second load reads .int4_cache.bin.
    assert (tmp_path / 'ckpt' / '.int4_cache.bin').exists()
    _, cached = weights.load_checkpoint(path, dtype=jnp.float32,
                                        quantize='int4')
    assert np.array_equal(np.asarray(cached['layers']['wq'].packed),
                          np.asarray(wq.packed))
    outs, _ = _greedy(cfg2, loaded, [[1, 2, 3]], 4)
    assert len(outs[0]) == 4
