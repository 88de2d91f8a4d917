"""Wide prefill pieces: a prompt prefilled alone on a model whose chunk
is bound by the weights it reads takes pieces wider than the chunk
(``paged.wide_piece_width``), so that a routed layer's experts are read
once for more tokens.

The width follows the chip's peaks; off a TPU the engine knows none and
keeps its chunk, so the engines here are told a v5e's
(``paged._chip_generation``). Widths come from the five benchmark
configurations' shapes (``jax.eval_shape``: nothing is materialised);
the pieces themselves run on the tiny presets, at chunk 16, where a
routed model's width is 64.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu import accelerators
from skypilot_tpu.inference import paged
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, kda, llama, quantization
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.telemetry import profiler, registry

V5E = accelerators.TPU_GENERATIONS['v5e']
PAGE, CHUNK = 8, 16
# tests/test_latent_moe.py's float32 tolerance on logits and on the
# rows a program writes: only the order of float32 accumulation differs
# between two programs of one model.
TOL = 2e-4
CONFIGS = os.path.join(os.path.dirname(__file__), '..', 'perfbench',
                       'configs')


@pytest.fixture
def v5e(monkeypatch):
    monkeypatch.setattr(paged, '_chip_generation', lambda: V5E)


def _cell_tree(name):
    """The served tree of a benchmark configuration, as shapes."""
    with open(os.path.join(CONFIGS, f'{name}.json'), encoding='utf-8') as f:
        spec = json.load(f)
    cfg = ModelConfig(**spec['model'])
    quantize = spec.get('deployment', {}).get('quantize')

    def build(key):
        params = llama.init_params(key, cfg)
        if cfg.mixer_pattern:
            params = kda.serving_layout(params)
        if quantize:
            params = quantization.quantize_params(params, mode=quantize)
        return params

    return cfg, jax.eval_shape(build, jax.random.PRNGKey(0))


@pytest.mark.parametrize('name,times', [
    ('qwen2-7b', 1),            # int8 weights: balance ~130 tokens
    ('qwen2.5-1.5b', 1),        # bf16, tied: ~240
    ('ouro-2.6b', 1),           # every pass reads what it computes: ~250
    ('glm-4.7-flash', 4),       # 64 experts, 4 reached: ~2,000
    ('solar-open2-250b', 4),    # 40 held of 320, 1 reached: ~1,200
])
def test_width_from_the_configurations_shapes(name, times):
    cfg, params = _cell_tree(name)
    assert paged.wide_piece_width(params, cfg, 256, V5E) == times * 256
    assert paged.wide_piece_width(params, cfg, 256, None) == 256


def test_width_follows_the_chips_peaks():
    """The balance is a ratio of the chip's rates: a v6e (560 FLOP a
    byte) widens where a v4 (224) does not; the device kinds JAX reports
    name their generation, the CPU none."""
    cfg, params = _cell_tree('qwen2-7b')
    gen = accelerators.generation_of_device
    assert gen('TPU v5 lite') is V5E
    assert gen('TPU v6 lite').name == 'v6e' and gen('cpu') is None
    assert paged.wide_piece_width(params, cfg, 256, gen('TPU v4')) == 256
    assert paged.wide_piece_width(params, cfg, 128, gen('TPU v6 lite')) \
        == 256


def _make(preset, dtype='float32'):
    cfg = dataclasses.replace(preset, dtype=jnp.dtype(dtype))
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, **kw):
    return PagedInferenceEngine(cfg, params=params, max_batch=2,
                                max_seq=192, page_size=PAGE,
                                chunk=kw.pop('chunk', CHUNK), **kw)


def _counters():
    reg = registry.get_registry()
    return {n: reg.get(n).value if reg.get(n) else 0.0
            for n in (profiler.PREFILL_TOKENS_METRIC,
                      profiler.PREFILL_WIDE_PIECES_METRIC,
                      profiler.PREFILL_WIDE_TOKENS_METRIC)}


def _prefill_alone(eng, prompt):
    """Prefill ``prompt`` alone, then decode; its tokens, its first
    token, its slot's rows of every pool and its state (a recurrent
    model's) once prefilled."""
    rid = eng.add_request(prompt, max_new_tokens=4)
    eng._assign_slots()
    (slot,) = eng._prefill_off
    while eng._prefill_off:
        eng._prefill_chunk_batch()
    first = int(eng._pending[-1]['toks'][0])
    pages = np.asarray(eng._pages[slot])
    rows = [np.asarray(pool[:, pages], np.float32)
            for pool in (eng.cache.pool_k, eng.cache.pool_v)]
    state = eng.recurrent_state_of(eng._slots[slot])
    return eng.run_to_completion(horizon=4)[rid].output, first, rows, state


def _rows_of(rows, n):
    """The first ``n`` token rows of a slot's gathered pages."""
    def tokens(a):                      # [L, pages, hkv, page, d]
        a = np.moveaxis(a, 2, 3)        # [L, pages, page, hkv, d]
        return a.reshape(a.shape[0], -1, *a.shape[3:])[:, :n]
    return [tokens(r) for r in rows]


@pytest.mark.parametrize('preset', [configs.TINY_GLM, configs.TINY_SOLAR],
                         ids=['latent', 'recurrent'])
def test_lone_long_prompt_in_wide_pieces_is_the_chunked_one(v5e, preset):
    """A 150-token prompt alone: pieces 64 + 64 + 16 + 6 in place of
    16 x 9 + 6, the same first token, the same rows in the pools and (a
    recurrent model) the same state, within the tolerance; the counters
    move by the two wide pieces and their 128 tokens."""
    cfg, params = _make(preset)
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, 150).tolist()
    wide = _engine(cfg, params)
    assert wide._wide_chunk == 4 * CHUNK
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged, '_chip_generation', lambda: None)
        narrow = _engine(cfg, params)
    assert narrow._wide_chunk == CHUNK
    _, first_n, rows_n, state_n = _prefill_alone(narrow, prompt)
    before = _counters()
    _, first_w, rows_w, state_w = _prefill_alone(wide, prompt)
    moved = {k: v - before[k] for k, v in _counters().items()}
    assert moved == {profiler.PREFILL_TOKENS_METRIC: 150,
                     profiler.PREFILL_WIDE_PIECES_METRIC: 2,
                     profiler.PREFILL_WIDE_TOKENS_METRIC: 128}
    assert (wide.chunks_prefilled, narrow.chunks_prefilled) == (4, 10)
    assert {k[3] for k in wide._prefill_fns} == {CHUNK, 4 * CHUNK}
    assert {k[3] for k in narrow._prefill_fns} == {CHUNK}
    assert first_w == first_n
    for got, want in zip(_rows_of(rows_w, 150), _rows_of(rows_n, 150)):
        np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    if state_n is not None:
        np.testing.assert_allclose(state_w, state_n, rtol=0, atol=TOL)


def test_two_pending_prompts_keep_the_chunk(v5e):
    """Two prompts pending together form one chunk-wide batch: several
    prompts already share the weights' read. No wide piece is counted."""
    cfg, params = _make(configs.TINY_GLM)
    eng = _engine(cfg, params)
    rng = np.random.default_rng(6)
    for n in (150, 140):
        eng.add_request(rng.integers(0, cfg.vocab_size, n).tolist(),
                        max_new_tokens=2)
    eng._assign_slots()
    before = _counters()
    eng._prefill_chunk_batch()
    moved = {k: v - before[k] for k, v in _counters().items()}
    assert list(eng._prefill_fns) == [(2, 2, False, CHUNK)]
    assert moved == {profiler.PREFILL_TOKENS_METRIC: 2 * CHUNK,
                     profiler.PREFILL_WIDE_PIECES_METRIC: 0,
                     profiler.PREFILL_WIDE_TOKENS_METRIC: 0}
    eng.run_to_completion(horizon=4)


def test_the_cold_grid():
    """Resume points a prefix hit may take: multiples of the wide width
    while the cold path's pieces are wide, of the chunk after; every
    multiple of the chunk where the widths are one."""
    eng = type('E', (), {'chunk': 256, '_wide_chunk': 1024})()
    on = PagedInferenceEngine._on_cold_grid
    assert on(eng, 1024, 1600) and on(eng, 1280, 1600)
    assert not on(eng, 768, 1088)         # the cold path's piece is wide
    assert on(eng, 768, 1000) and not on(eng, 640, 1000)
    assert on(eng, 2048, 2112) and not on(eng, 1792, 2112)
    eng._wide_chunk = 256
    assert all(on(eng, at, 1088) for at in range(0, 1088, 256))


def test_prefix_hit_resumes_on_the_cold_grid(v5e):
    """A request whose first 120 tokens are cached: the hit resumes at
    64 (not 112, which the wide cold path never starts a piece at), and
    hit and cold serve byte-identical tokens and rows."""
    cfg, params = _make(configs.TINY_GLM)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 150).tolist()
    prompt = shared[:120] + rng.integers(0, cfg.vocab_size, 30).tolist()
    cold = _engine(cfg, params)
    cold_out, cold_first, cold_rows, _ = _prefill_alone(cold, prompt)
    hit = _engine(cfg, params)
    _prefill_alone(hit, shared)
    hit_id = hit.add_request(prompt, max_new_tokens=4)
    hit._assign_slots()
    (slot,) = hit._prefill_off
    assert hit._slots[slot]._n_matched * PAGE == 64
    while hit._prefill_off:
        hit._prefill_chunk_batch()
    pages = np.asarray(hit._pages[slot])
    hit_rows = [np.asarray(pool[:, pages], np.float32)
                for pool in (hit.cache.pool_k, hit.cache.pool_v)]
    assert int(hit._pending[-1]['toks'][0]) == cold_first
    for got, want in zip(_rows_of(hit_rows, 150), _rows_of(cold_rows, 150)):
        np.testing.assert_array_equal(got, want)
    assert hit.run_to_completion(horizon=4)[hit_id].output == cold_out


def test_dense_engine_keeps_its_programs(v5e):
    """A dense model's chunk is not bound by its weights' read: at chunk
    256 its width is the chunk (bf16 weights: balance ~290 tokens), so a
    600-token prompt alone and two together meet the programs and tokens
    an engine that knows no peaks meets, and no wide piece is counted."""
    cfg, params = _make(configs.TINY, 'bfloat16')
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (600, 300, 280)]

    def serve(eng):
        out = []
        for batch in (prompts[:1], prompts[1:]):
            ids = [eng.add_request(p, max_new_tokens=4) for p in batch]
            done = eng.run_to_completion(horizon=4)
            out += [done[i].output for i in ids]
        return out, sorted(eng._prefill_fns)

    eng = PagedInferenceEngine(cfg, params=params, max_batch=2,
                               max_seq=640, page_size=PAGE)
    assert eng._wide_chunk == eng.chunk == 256
    before = _counters()
    got = serve(eng)
    moved = {k: v - before[k] for k, v in _counters().items()}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged, '_chip_generation', lambda: None)
        want = serve(PagedInferenceEngine(cfg, params=params, max_batch=2,
                                          max_seq=640, page_size=PAGE))
    assert got == want
    assert {k[3] for k in got[1]} == {128, 256}
    assert moved[profiler.PREFILL_WIDE_PIECES_METRIC] == 0
    assert moved[profiler.PREFILL_WIDE_TOKENS_METRIC] == 0
    assert moved[profiler.PREFILL_TOKENS_METRIC] == 600 + 300 + 280
