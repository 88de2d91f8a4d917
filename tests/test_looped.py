"""A looped decoder (``n_loops`` passes over the same layers, sandwich
norms, an exit gate: Ouro's block, ``models/llama.py``) against the plain
reference (``models/reference/ouro.py``) at the ``tiny-ouro`` size: on
logits, with seeded weights whose norms are off one, through every
program that serves it.

Tolerances, each with its reason. The reference computes in float32 at
"highest" precision on the same (possibly bf16-rounded) weights.

- float32 configuration: 2e-4 on the worst logit of the worst position,
  logits of magnitude ~3.5. Only the order of float32 accumulation
  differs (two-block and three-block attention against the whole causal
  form); measured 3e-6 to 5e-6 for the full forward over three seeds, 5e-6
  to 1e-5 through the paged programs.
- bfloat16 configuration: 0.2 on the worst logit of the worst position.
  Activations are rounded to 8 bits of mantissa after every matmul of 3
  passes x 2 layers: measured 0.047-0.100 for the full forward over three
  seeds and 0.063-0.137 through the paged programs. A dense model has no
  near-tie to break (every position moves by rounding only), so the
  worst position can carry the tolerance. Every wrong-mathematics
  variant below moves the worst position by 0.32 or more (int8 weights:
  0.32 in float32, 0.34 in bfloat16; every other variant 3.5 or more),
  so this tolerance refuses each of them.
"""
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import paged
from skypilot_tpu.models import configs, llama
from skypilot_tpu.models.reference import ouro as reference
from test_latent_moe import _int8_round, serve_through_pages

TOL = {'float32': 2e-4, 'bfloat16': 0.2}
PAGE, CHUNK = 8, 16


def error(got, want):
    """The statistic ``TOL`` bounds: the worst logit error of the worst
    position ([n, vocab] logits each side)."""
    return float(np.abs(np.asarray(got, np.float32) - want).max())


def make(dtype, seed=0, cfg=configs.TINY_OURO):
    """Seeded weights; every norm off one and the gate's bias off zero,
    so a norm left out, doubled or swapped shows."""
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(dtype))
    params = llama.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)

    def off_one(path, a):
        name = path[-1].key
        k = jax.random.fold_in(key, sum(map(ord, jax.tree_util.keystr(path))))
        if name.endswith('norm'):
            return a * (1 + 0.3 * jax.random.normal(k, a.shape, a.dtype))
        return a + 0.3 if name == 'b' else a

    return cfg, jax.tree_util.tree_map_with_path(off_one, params)


def reference_logits(params, tokens, cfg, pdf=False):
    logits, exits = reference.forward(params, jnp.asarray(tokens), cfg,
                                      q_block=7, wrap=jax.jit)
    return (np.asarray(logits), np.asarray(exits)) if pdf \
        else np.asarray(logits)


def program_logits(params, tokens, cfg, pdf=False):
    """``llama.forward`` on one sequence."""
    logits, _, exits = jax.jit(lambda p, t: llama.forward(
        p, t, cfg, return_exit=True))(params, jnp.asarray(tokens)[None])
    logits = np.asarray(logits[0], np.float32)
    return (logits, np.asarray(exits[:, 0])) if pdf else logits


def test_preset_differs_where_a_mix_up_would_hide():
    c = configs.TINY_OURO
    assert (c.n_loops, c.n_layers, c.n_cache_layers) == (3, 2, 6)
    assert c.n_heads != c.n_kv_heads and c.n_heads * c.head_dim != c.dim
    assert c.post_norms and c.exit_gate and c.early_exit_threshold == 1
    assert c.kv_spec == configs.KVSpec(2, 24, 24)
    # weights held once, worked n_loops times
    leaves = jax.tree.leaves(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), c)))
    assert c.num_params == sum(x.size for x in leaves)
    once = dataclasses.replace(c, n_loops=1)
    assert once.num_params == c.num_params
    layers = c.num_params - 2 * c.vocab_size * c.dim - c.dim - (c.dim + 1)
    assert c.flops_per_token() - once.flops_per_token() == 2 * 2 * layers
    big = configs.OURO_2_6B
    assert big.num_params == 2_667_974_657 and big.n_cache_layers == 192


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_full_forward_and_exit_pdf_match_reference(dtype):
    cfg, params = make(dtype)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, 40)
    got, got_pdf = program_logits(params, tokens, cfg, pdf=True)
    want, want_pdf = reference_logits(params, tokens, cfg, pdf=True)
    assert error(got, want) < TOL[dtype]
    # a distribution over the 3 passes at every position, none of it
    # trivial; its error is a sigmoid's of the logits' error
    assert got_pdf.shape == (3, 40)
    np.testing.assert_allclose(got_pdf.sum(0), 1.0, atol=1e-5)
    assert 0.01 < want_pdf.min() and want_pdf.max() < 0.99
    assert np.abs(got_pdf - want_pdf).max() < TOL[dtype] / 4


def test_forward_refuses_what_it_cannot_loop():
    cfg, params = make('float32')
    tokens = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match='3 times a token'):
        llama.forward(params, tokens, cfg,
                      cache=llama.KVCache.create(cfg, 1, 16))
    with pytest.raises(ValueError, match='no exit gate'):
        llama.forward(llama.init_params(jax.random.PRNGKey(0), configs.TINY),
                      tokens, configs.TINY, return_exit=True)


# ------------------------------------------------------ the paged programs
def paged_error(cfg, params, first_lens, decode_impl, ref_params=None):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in first_lens]
    n_new = 13 if first_lens[0] > 1 else 25         # crosses pages too
    out, got = serve_through_pages(cfg, params, prompts, n_new, horizon=4,
                                   PAGE=PAGE, decode_impl=decode_impl)
    rows, want = [], []
    for prompt, tokens, logits in zip(prompts, out, got):
        ref = reference_logits(ref_params or params, prompt + tokens, cfg)
        assert sorted(logits) == list(range(len(prompt) - 1,
                                            len(prompt) + n_new - 1))
        rows += list(logits.values())
        want += [ref[pos] for pos in logits]
    return error(np.stack(rows), np.stack(want))


@pytest.mark.parametrize('decode_impl', ['gather', 'pallas'])
@pytest.mark.parametrize('dtype,first_lens', [
    ('float32', (37, 21)),  # chunks 16+16+5 and 16+5: chunk and page
    ('bfloat16', (37, 21)),  # boundaries crossed, unequal lengths
    ('float32', (1, 1)),    # every later position through decode
])
def test_paged_prefill_then_decode_matches_reference(dtype, first_lens,
                                                     decode_impl):
    """Through the cache of 3 x 2 layers: the XLA form over gathered
    pages, and the paged kernel (interpret mode here) with ``layer`` =
    pass * 2 + layer; both against the reference's full forward."""
    cfg, params = make(dtype)
    assert paged_error(cfg, params, first_lens, decode_impl) < TOL[dtype]


def test_engine_serves_it_and_a_token_does_not_depend_on_its_batch():
    """The normal path: ``PagedInferenceEngine`` with chunked prefill,
    fused decode and ring merge. Every served token is the reference's
    best within the tolerance; a request served alone and among three
    others gives the same tokens; pool, gauges and counter count cache
    layers."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.telemetry import profiler, registry
    cfg, params = make('float32')
    reg = registry.get_registry()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (37, 9, 21)]
    eng = PagedInferenceEngine(cfg, params=params, max_batch=4, max_seq=64,
                               page_size=PAGE, chunk=CHUNK)

    def serve(batch):
        ids = [eng.add_request(p, max_new_tokens=8) for p in batch]
        done = eng.run_to_completion(horizon=4)
        return [done[i].output for i in ids]

    tokens_before = reg.get(profiler.PREFILL_TOKENS_METRIC).value
    (alone,) = serve(prompts[2:])
    together = serve(prompts)
    # the second time its first two pages come from the prefix cache
    assert alone == together[2]
    assert eng.cache.pool_k.shape == (6, 4 * 8 + 1, 2, PAGE, 24)
    assert eng.kv_pool_stats()['kv_token_bytes'] == 6 * 2 * (24 + 24) * 4
    assert reg.get(profiler.KV_CACHE_LAYERS_METRIC).value == 6
    assert reg.get(profiler.KV_TOKEN_BYTES_METRIC).value == 2304
    assert (reg.get(profiler.PREFILL_TOKENS_METRIC).value - tokens_before
            == 21 + sum(map(len, prompts)) - 2 * PAGE)
    for prompt, out in zip(prompts, together):
        ref = reference_logits(params, prompt + out, cfg)[
            len(prompt) - 1:len(prompt) + len(out) - 1]
        deficit = ref.max(-1) - ref[np.arange(len(out)), out]
        assert len(out) == 8 and deficit.max() < TOL['float32']


# -------------------------------------------- wrong mathematics must fail
def _previous_pass_cache(real):
    """``llama.run_loops`` handing pass t >= 1 the cache layer of pass
    t - 1: the fault of a cache indexed by the layer alone."""
    def run_loops(body, x, params, cfg, **kw):
        def shifted(carry, layer_idx):
            layer, li = layer_idx
            return body(carry, (layer, jnp.where(li >= cfg.n_layers,
                                                 li - cfg.n_layers, li)))
        return real(shifted, x, params, cfg, **kw)
    return run_loops


@pytest.mark.parametrize('variant', [
    'one_pass_fewer', 'no_post_norms', 'final_norm_after_last_pass_only',
    'previous_pass_cache', 'int8_tree'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_wrong_mathematics_fails_the_tolerance(variant, dtype):
    """Each variant of the mathematics, computed by the program (or, for
    the final norm, by the reference it is held to), is further from the
    right one than the tolerance of its dtype allows; the right
    mathematics is inside it: the tests above."""
    cfg, params = make(dtype)
    tokens = np.random.default_rng(6).integers(0, cfg.vocab_size, 40)
    if variant == 'previous_pass_cache':
        with mock.patch.object(llama, 'run_loops',
                               _previous_pass_cache(llama.run_loops)):
            err = paged_error(cfg, params, (37, 21), 'gather')
    elif variant == 'final_norm_after_last_pass_only':
        def last_only(x, w, eps, t, n_loops):
            return reference.rms_norm(x, w, eps) if t == n_loops - 1 else x
        with mock.patch.object(reference, 'pass_norm', last_only):
            wrong = reference_logits(params, tokens, cfg)
        err = error(program_logits(params, tokens, cfg), wrong)
    else:
        wrong_cfg = {
            'one_pass_fewer': dataclasses.replace(cfg, n_loops=2),
            'no_post_norms': dataclasses.replace(cfg, post_norms=False),
        }.get(variant, cfg)
        wrong_params = _int8_round(params) if variant == 'int8_tree' \
            else params
        err = error(program_logits(wrong_params, tokens, wrong_cfg),
                    reference_logits(params, tokens, cfg))
    assert err > TOL[dtype], (variant, err)


# --------------------------------------------------------------- refusals
@pytest.mark.parametrize('kwargs,reason', [
    ({'speculate_k': 2}, 'speculate_k'),
    ({'adapter_slots': 2}, 'adapter_slots'),
    ({'mesh': 'tp2'}, 'mesh'),
    ({'decode_impl': 'cross_layer'}, 'decode_impl'),
    ({'early_exit_threshold': 0.5}, 'early_exit_threshold'),
    ({'call': 'export'}, 'KV export/ingest'),
    ({'call': 'ingest'}, 'KV export/ingest'),
])
def test_refused_with_its_reason(kwargs, reason):
    """What a looped model cannot yet be combined with raises where it
    is asked for, naming what and why; nothing fails silently."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = make('float32')
    kwargs = dict(kwargs)
    if kwargs.get('mesh'):
        from skypilot_tpu.parallel import mesh as mesh_lib
        kwargs['mesh'] = mesh_lib.serving_mesh(2, 1)
    if 'early_exit_threshold' in kwargs:
        cfg = dataclasses.replace(
            cfg, early_exit_threshold=kwargs.pop('early_exit_threshold'))
    base = dict(params=params, max_batch=2, max_seq=32)
    call = kwargs.pop('call', None)
    if call is None:
        with pytest.raises(ValueError, match=f'n_loops=3.*{reason}'):
            PagedInferenceEngine(cfg, **base, **kwargs)
        return
    eng = PagedInferenceEngine(cfg, **base)
    with pytest.raises(NotImplementedError, match=reason):
        eng._get_export(1) if call == 'export' else eng._get_ingest(8, 1)


# ------------------------------------------- one pass: what it was before
@pytest.mark.parametrize('preset', ['tiny', 'tiny-qwen'])
def test_one_pass_presets_pool_shapes_and_program_keys_unchanged(preset):
    """``n_loops`` 1 and no post-branch norms: the cache-layer count is
    the layer count, so a model of the Llama family has the pool, ring,
    byte accounting, parameter tree and program keys it had, and serves
    the tokens it served."""
    from skypilot_tpu.inference.engine import (_ring_row_bytes,
                                               kv_token_bytes)
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg = configs.get_config(preset)
    L, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    assert (cfg.n_loops, cfg.n_cache_layers) == (1, L)
    assert kv_token_bytes(cfg, 'bf16') == L * hkv * hd * 2 * 2
    assert kv_token_bytes(cfg, 'int8') == L * hkv * (hd + 4) * 2
    assert _ring_row_bytes(cfg, 4) == L * 4 * hkv * hd * 2 * 2
    leaves = set(llama.init_params(jax.random.PRNGKey(0), cfg)['layers'])
    assert leaves == {'attn_norm', 'ffn_norm', 'wq', 'wk', 'wv', 'wo',
                      'w_gate', 'w_up', 'w_down'} | (
        {'bq', 'bk', 'bv'} if cfg.qkv_bias else set())
    eng = PagedInferenceEngine(cfg, max_batch=4, max_seq=64, page_size=8,
                               chunk=16, kv_cache_dtype='int8')
    assert eng.cache.pool_k.shape == (L, 4 * 8 + 1, hkv, 8, hd)
    assert eng.cache.k_scale.shape == (L, 4 * 8 + 1, hkv, 8)
    # no period pattern, no recurrent layer: no state beside the pool,
    # prefixes matched and registered as before
    assert (cfg.mixer_pattern, cfg.n_recurrent_layers) == ((), 0)
    assert eng.rec is None and eng._prefix_reuse
    assert eng.memory_stats()['recurrent_state_bytes'] == 0
    rng = np.random.default_rng(7)
    for n in (21, 5):
        eng.add_request(rng.integers(0, 256, n).tolist(), max_new_tokens=6)
    done = eng.run_to_completion(horizon=4)
    # Recorded on the parent commit (PR 33's tree) with these requests.
    assert sorted(eng._prefill_fns) == [(1, 4, False, 16), (2, 2, False, 16)]
    assert sorted((e['fn'], e['key']) for e in eng._prof.compile_events) == [
        ('decode', '(4, False, 2)'), ('decode', '(4, False, 4)'),
        ('prefill', '(1, 4, False, 16)'), ('prefill', '(2, 2, False, 16)')]
    # (zero biases: tiny-qwen's tokens are tiny's)
    assert [done[k].output for k in sorted(done)] == [
        [191, 26, 40, 191, 26, 191], [98, 225, 142, 78, 142, 78]]


def test_one_pass_program_is_the_parents_program():
    """The jaxpr of ``llama.forward`` for ``tiny`` holds one scan over
    the layers and no scan over passes; a looped model's holds the pass
    scan around it."""
    def scans(cfg):
        params = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        jaxpr = jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(
            params, jnp.zeros((1, 8), jnp.int32))
        outer = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == 'scan']
        inner = [e for o in outer for e in o.params['jaxpr'].jaxpr.eqns
                 if e.primitive.name == 'scan']
        return [o.params['length'] for o in outer], \
            [i.params['length'] for i in inner]
    assert scans(configs.TINY) == ([2], [])
    assert scans(configs.TINY_OURO) == ([3], [2])


def test_audit_preset_no_transfer_no_recompile():
    """The looped engine's steady state, decode through the paged kernel
    as on the chip: no unsanctioned transfer, same-shaped waves compile
    nothing, and only the kernel reads the pool."""
    from skypilot_tpu.analysis import costmodel, jaxpr_audit
    report = jaxpr_audit.run_preset('paged-looped')
    assert 'decode_impl=pallas' in report.name
    assert report.ok(), '\n' + report.format()
    assert not [t for t in report.transfers if not t.sanctioned]
    assert all(a == b for a, b in report.compile_counts.values())
    assert not report.cost_error, report.cost_error
    pool_reads = [e for e in report.dispatch_costs['decode'].eqns
                  if e.read.get(costmodel.KV_POOL)]
    assert pool_reads and {e.prim for e in pool_reads} == {'pallas_call'}
