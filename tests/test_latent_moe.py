"""Latent attention + dropless routed/shared experts (``models/
latent_moe.py``, GLM-4.7-Flash's block) against the plain reference
(``models/reference/glm4_moe_lite.py``) at the ``tiny-glm`` size: on
logits, with seeded weights, through every program that serves it.

Tolerances, each with its reason. The reference computes in float32 at
"highest" precision on the same (possibly bf16-rounded) weights.

- float32 configuration: 2e-4 on logits of magnitude ~4. Only the order
  of float32 accumulation differs (absorbed against expanded attention,
  grouped against looped experts); measured 3e-6 for the full forward,
  under 4e-5 through the paged programs.
- bfloat16 configuration: 0.09, on the MEDIAN position's worst logit.
  Activations are rounded to 8 bits of mantissa after every matmul of 3
  layers: measured 0.04-0.07. The worst position cannot carry a bfloat16
  tolerance: a routing near-tie that bfloat16 and float32 break
  differently moves that position's logits by up to 2, and through its
  cache row every later position's (up to 30 % of 40 positions on one
  seed here). Every wrong-mathematics variant below moves the median
  position by 0.115 or more (int8 weights the least), so the looser
  tolerance still refuses each of them.
"""
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import paged
from skypilot_tpu.models import configs, latent_moe, llama
from skypilot_tpu.models.reference import glm4_moe_lite as reference
from skypilot_tpu.ops import latent_attention

TOL = {'float32': 2e-4, 'bfloat16': 0.09}
PAGE, CHUNK = 8, 16


def error(dtype, got, want):
    """The statistic ``TOL[dtype]`` bounds: over positions ([n, vocab]
    logits each side), the worst logit error of the worst position
    (float32) or of the median position (bfloat16)."""
    per_position = np.abs(np.asarray(got, np.float32) - want).max(-1)
    return float(per_position.max() if dtype == 'float32'
                 else np.median(per_position))


def make(dtype, seed=0):
    cfg = dataclasses.replace(configs.TINY_GLM, dtype=jnp.dtype(dtype))
    return cfg, llama.init_params(jax.random.PRNGKey(seed), cfg)


def reference_logits(params, tokens, cfg):
    return np.asarray(reference.forward(params, jnp.asarray(tokens), cfg,
                                        q_block=7)[0])


def program_logits(params, tokens, cfg):
    """``llama.forward`` (the expanded form) on one sequence."""
    logits, _ = jax.jit(lambda p, t: llama.forward(p, t, cfg))(
        params, jnp.asarray(tokens)[None])
    return np.asarray(logits[0], np.float32)


def test_preset_differs_where_a_mix_up_would_hide():
    c = configs.TINY_GLM
    assert len({c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim}) == 3
    assert c.qk_nope_head_dim + c.qk_rope_head_dim != c.v_head_dim
    assert (c.n_layers, c.n_dense_layers) == (3, 1)
    assert (c.n_routed_experts, c.n_experts_per_token,
            c.n_shared_experts) == (8, 2, 1)
    assert c.routed_scaling_factor == 1.8 and c.ffn_dim != c.moe_ffn_dim
    assert c.kv_spec == configs.KVSpec(1, 32, 8)
    assert float(jnp.abs(make('float32')[1]['layers']['router_bias']
                         ).min()) > 0


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_full_forward_matches_reference(dtype):
    cfg, params = make(dtype)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, 40)
    err = error(dtype, program_logits(params, tokens, cfg),
                reference_logits(params, tokens, cfg))
    assert err < TOL[dtype], err


# ------------------------------------------------------ the paged programs
class LogitTap:
    """Records the logits of every sampling point of the paged programs:
    both hand them to ``llama.mask_nonfinite_tokens``."""

    def __init__(self):
        self.seen = []
        self._real = llama.mask_nonfinite_tokens

    def __call__(self, logits, tokens):
        jax.debug.callback(lambda x: self.seen.append(np.asarray(x)),
                           logits, ordered=True)
        return self._real(logits, tokens)

    def take(self):
        jax.effects_barrier()
        seen, self.seen = self.seen, []
        return seen


def serve_through_pages(cfg, params, prompts, n_new, horizon, PAGE=PAGE,
                        decode_impl='gather'):
    """Chunked paged prefill of ``prompts`` in ONE batch, then ``n_new``
    decode steps in fused horizons with the ring merged in between.
    Returns per prompt (tokens generated, {position: logits}): the logits
    predicting position + 1, at the prompt's last position and at every
    decoded one."""
    n = len(prompts)
    per_row = -(-(max(map(len, prompts)) + n_new) // PAGE)
    P = 1
    while P < per_row:
        P *= 2
    table = np.zeros((n, P), np.int32)
    for i in range(n):
        table[i, :per_row] = 1 + i * per_row + np.arange(per_row)
    cache = paged.PagedKVCache.create(cfg, n_pages=1 + n * per_row,
                                      page_size=PAGE)
    tap = LogitTap()
    got = [dict() for _ in prompts]
    active = jnp.ones(n, bool)
    # Jitted once each: a fresh lambda a call would compile it again.
    prefill = jax.jit(
        lambda c, *a: paged.paged_prefill_chunk(params, c, *a, cfg))
    decode = jax.jit(lambda c, t, l: paged.paged_decode_horizon(
        params, c, jnp.asarray(table), t, l, cfg, horizon=horizon,
        active=active, decode_impl=decode_impl))
    merge = jax.jit(paged.merge_ring_into_pool)
    with mock.patch.object(llama, 'mask_nonfinite_tokens', tap):
        first = np.zeros(n, np.int32)
        for off in range(0, max(map(len, prompts)), CHUNK):
            tokens = np.zeros((n, CHUNK), np.int32)
            lengths, valid = np.zeros(n, np.int32), np.zeros(n, np.int32)
            want = np.full(n, -1, np.int32)
            for i, p in enumerate(prompts):
                piece = p[off:off + CHUNK]
                lengths[i] = min(off, len(p))
                valid[i] = len(piece)
                tokens[i, :len(piece)] = piece
                if piece and off + len(piece) == len(p):
                    want[i] = len(piece) - 1
            tok, cache = prefill(cache, *map(
                jnp.asarray, (table, tokens, lengths, valid, want)))
            (logits,) = tap.take()
            for i, p in enumerate(prompts):
                if want[i] >= 0:
                    got[i][len(p) - 1] = logits[i]
                    first[i] = int(tok[i])
        out = [[int(t)] for t in first]
        cur = jnp.asarray(first)
        lengths = np.array([len(p) for p in prompts], np.int32)
        for _ in range(0, n_new - 1, horizon):
            toks, ring_k, ring_v = decode(cache, cur, jnp.asarray(lengths))
            cache = merge(cache, ring_k, ring_v, jnp.asarray(table),
                          jnp.asarray(lengths), active)
            steps = tap.take()
            toks = np.asarray(toks)
            # + the experts row of a model that routes
            assert toks.shape == (
                n + (cfg.ffn_kind == 'routed_shared'), horizon)
            for i in range(n):
                for h in range(horizon):
                    got[i][int(lengths[i]) + h] = steps[h][i]
                    out[i].append(int(toks[i, h]))
            cur = jnp.asarray(toks[:n, -1])
            lengths = lengths + horizon
    return out, got


@pytest.mark.parametrize('decode_impl', ['gather', 'pallas'])
@pytest.mark.parametrize('dtype,first_lens,page', [
    ('float32', (37, 21), 8),   # chunks 16+16+5 and 16+5: chunk and page
    ('bfloat16', (37, 21), 8),  # boundaries crossed, unequal lengths
    ('float32', (1, 1), 8),     # every later position through decode
    ('float32', (37, 21), 16),  # 16 rope rows of 8 fill the 128 lanes:
    ('float32', (1, 1), 16),    # the lane-packed pool (the chip's form)
])
def test_paged_prefill_then_decode_matches_reference(dtype, first_lens,
                                                     page, decode_impl):
    """``decode_impl``: the absorbed XLA form over gathered pages, and
    the latent paged kernel (interpret mode here) with the ring merged
    in XLA; both against the plain reference."""
    cfg, params = make(dtype)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in first_lens]
    n_new = 13 if first_lens[0] > 1 else 25         # crosses pages too
    if page == 16:
        assert paged.PagedKVCache.create(cfg, n_pages=3, page_size=16
                                         ).pool_v.shape == (3, 3, 1, 1, 128)
    out, got = serve_through_pages(cfg, params, prompts, n_new, horizon=4,
                                   PAGE=page, decode_impl=decode_impl)
    rows, want = [], []
    for prompt, tokens, logits in zip(prompts, out, got):
        ref = reference_logits(params, prompt + tokens, cfg)
        assert sorted(logits) == list(range(len(prompt) - 1,
                                            len(prompt) + n_new - 1))
        rows += list(logits.values())
        want += [ref[pos] for pos in logits]
    err = error(dtype, np.stack(rows), np.stack(want))
    assert err < TOL[dtype], err


def test_engine_serves_it_and_counts():
    """The normal path: ``PagedInferenceEngine`` with chunked prefill,
    fused decode, ring merge and prefix pages; every served token is the
    reference's best within the tolerance, the pool is sized and counted
    by the latent row, and the expert counters move."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.telemetry import profiler, registry
    cfg, params = make('float32')
    reg = registry.get_registry()

    def counters():
        return {name: reg.get(name).value if reg.get(name) else 0.0
                for name in (profiler.MOE_LAYER_STEPS_METRIC,
                             profiler.MOE_DISTINCT_METRIC,
                             profiler.MOE_ASSIGNMENTS_METRIC,
                             profiler.PREFILL_PAIRS_METRIC)}

    eng = PagedInferenceEngine(cfg, params=params, max_batch=4, max_seq=96,
                               page_size=PAGE, chunk=CHUNK)
    before = counters()
    assert eng.decode_impl == 'gather'
    assert eng.resolved_path()['prefill_attn'] == 'xla_absorbed_two_block'
    assert eng.cache.pool_k.shape[2:] == (1, PAGE, cfg.kv_lora_rank)
    assert eng.cache.pool_v.shape[2:] == (1, PAGE, cfg.qk_rope_head_dim)
    # 3 layers x (32 + 8) values x 4 bytes: the latent row, no head axis.
    assert eng.kv_pool_stats()['kv_token_bytes'] == 3 * 40 * 4
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (37, 9, 21)]
    ids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    done = eng.run_to_completion(horizon=4)
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output
        ref = reference_logits(params, prompt + out, cfg)[
            len(prompt) - 1:len(prompt) + len(out) - 1]
        deficit = ref.max(-1) - ref[np.arange(len(out)), out]
        assert len(out) == 12 and deficit.max() < TOL['float32']
    moved = {k: v - before[k] for k, v in counters().items()}
    assert moved[profiler.PREFILL_PAIRS_METRIC] == sum(
        n * (n + 1) // 2 for n in (37, 9, 21))
    steps = moved[profiler.MOE_LAYER_STEPS_METRIC]
    assert steps > 0 and steps % 2 == 0             # 2 expert layers
    per_step = moved[profiler.MOE_DISTINCT_METRIC] / steps
    assert 1 <= per_step <= min(8, 3 * 2)           # <= live rows x top-2
    assert moved[profiler.MOE_ASSIGNMENTS_METRIC] <= steps * 3 * 2


# -------------------------------------------------- absorbed = expanded
@pytest.mark.parametrize('cache_len', [0, 5, 11])
def test_absorbed_attention_is_expanded_attention(cache_len):
    """The same mathematics: the two-block absorbed form over (context,
    chunk) and the three-block decode form over (context, ring, self)
    against the expanded causal form over the whole sequence."""
    b, s, h, dn, dr, dv, r = 2, 12, 4, 24, 8, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    q_nope = jax.random.normal(ks[0], (b, s, h, dn))
    q_rope = jax.random.normal(ks[1], (b, s, h, dr))
    c = jax.random.normal(ks[2], (b, s, r))
    kr = jax.random.normal(ks[3], (b, s, dr))
    w_k = jax.random.normal(ks[4], (r, h, dn)) * r ** -0.5
    w_v = jax.random.normal(ks[5], (r, h, dv)) * r ** -0.5
    scale = (dn + dr) ** -0.5
    with jax.default_matmul_precision('highest'):
        want = latent_attention.expanded_causal_attention(
            q_nope, q_rope, jnp.einsum('bsr,rhk->bshk', c, w_k), kr,
            jnp.einsum('bsr,rhv->bshv', c, w_v), scale=scale)
        q_lat = jnp.einsum('bshk,rhk->bshr', q_nope, w_k)
        n = cache_len
        pad = 3                                     # rows past cache_len
        ctx_c = jnp.pad(c[:, :n], ((0, 0), (0, pad), (0, 0)))
        ctx_kr = jnp.pad(kr[:, :n], ((0, 0), (0, pad), (0, 0)))
        lens = jnp.full((b,), n, jnp.int32)
        chunk = latent_attention.absorbed_cached_attention(
            q_lat[:, n:], q_rope[:, n:], c[:, n:], kr[:, n:], ctx_c,
            ctx_kr, lens, scale=scale)
        chunk = jnp.einsum('bshr,rhv->bshv', chunk, w_v)
        # decode of the last row: context n, ring the rows between
        ring = latent_attention.absorbed_ring_decode_attention(
            q_lat[:, -1:], q_rope[:, -1:], c[:, -1:], kr[:, -1:], ctx_c,
            ctx_kr, lens, jnp.pad(c[:, n:-1], ((0, 0), (0, 2), (0, 0))),
            jnp.pad(kr[:, n:-1], ((0, 0), (0, 2), (0, 0))), s - 1 - n,
            scale=scale)
        ring = jnp.einsum('bshr,rhv->bshv', ring, w_v)
    assert float(jnp.abs(chunk - want[:, n:]).max()) < 2e-5
    assert float(jnp.abs(ring - want[:, -1:]).max()) < 2e-5


# ------------------------------------------------------------- dropless
def test_a_token_does_not_depend_on_its_batch():
    """Dropless: a row's expert output is the same alone, among 30 other
    rows, and beside rows that are not live; rows that are not live get
    nothing and are counted nowhere."""
    cfg, params = make('float32')
    layer = jax.tree.map(lambda a: a[1], params['layers'])
    layer = dict(layer, experts=params['layers']['experts'], expert_layer=1)
    x = jax.random.normal(jax.random.PRNGKey(5), (31, cfg.dim))

    def ffn(rows, live=None):
        y, distinct = latent_moe._moe_ffn(layer, rows[None], cfg,
                                          live if live is None
                                          else live[None])
        return np.asarray(y[0]), int(distinct)

    alone, n_alone = ffn(x[:1])
    crowd, n_crowd = ffn(x)
    assert n_alone == cfg.n_experts_per_token and 6 <= n_crowd <= 8
    np.testing.assert_allclose(crowd[0], alone[0], rtol=0, atol=1e-6)
    live = jnp.arange(31) < 3
    some, n_some = ffn(x, live)
    np.testing.assert_allclose(some[:3], crowd[:3], rtol=0, atol=1e-6)
    assert n_some <= 3 * cfg.n_experts_per_token
    # not live: the routed part is exactly zero, only the shared expert
    shared = np.asarray(llama._ffn(layer['shared'], x[None], cfg)[0])
    np.testing.assert_array_equal(some[3:], shared[3:])
    # ... and the routed experts matter: the rows differ from shared alone
    assert np.abs(some[:3] - shared[:3]).max() > 1e-2


# -------------------------------------------- wrong mathematics must fail
def _int8_round(tree):
    """Every matrix through per-output-channel int8 and back."""
    def rt(a):
        if a.ndim < 2 or a.dtype == jnp.float32 and a.shape[-1] <= 8:
            return a
        af = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(af), axis=-2, keepdims=True) / 127.0
        return (jnp.round(af / scale) * scale).astype(a.dtype)
    return jax.tree.map(rt, tree)


def _route_variant(kind):
    def route(layer, x, cfg):
        logits = jnp.einsum('td,de->te', x.astype(jnp.float32),
                            layer['router'],
                            precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.softmax(logits, -1) if kind == 'softmax'
                  else jax.nn.sigmoid(logits))
        biased = scores + layer['router_bias']
        _, chosen = jax.lax.top_k(biased, cfg.n_experts_per_token)
        w = jnp.take_along_axis(
            biased if kind == 'bias_in_weights' else scores, chosen, -1)
        w = w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scaling_factor
        return chosen.astype(jnp.int32), w
    return route


VARIANTS = ['int8_tree', 'no_shared_expert', 'no_scaling', 'softmax',
            'bias_in_weights']


@pytest.mark.parametrize('variant', VARIANTS)
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_wrong_mathematics_fails_the_tolerance(variant, dtype):
    """Each variant of the mathematics, computed by the program, is
    further from the reference than the tolerance of its dtype allows
    (and the right mathematics is inside it: the tests above)."""
    cfg, params = make(dtype)
    tokens = np.random.default_rng(6).integers(0, cfg.vocab_size, 40)
    ref = reference_logits(params, tokens, cfg)
    wrong_params, wrong_cfg, patched = params, cfg, latent_moe.route
    if variant == 'int8_tree':
        wrong_params = _int8_round(params)
    elif variant == 'no_shared_expert':
        wrong_params = jax.tree.map(lambda a: a, params)
        wrong_params['layers']['shared']['w_down'] = jnp.zeros_like(
            params['layers']['shared']['w_down'])
    elif variant == 'no_scaling':
        wrong_cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    else:
        patched = _route_variant(variant)
    with mock.patch.object(latent_moe, 'route', patched):
        err = error(dtype, program_logits(wrong_params, tokens, wrong_cfg),
                    ref)
    assert err > TOL[dtype], (variant, err)


# --------------------------------------------------------------- refusals
@pytest.mark.parametrize('kwargs,reason', [
    ({'quantize': 'int8'}, 'quantize'),
    ({'quantize': 'int4'}, 'quantize'),
    ({'kv_cache_dtype': 'int8'}, 'kv_cache_dtype'),
    ({'kv_cache_dtype': 'int4'}, 'kv_cache_dtype'),
    ({'speculate_k': 2}, 'speculate_k'),
    ({'adapter_slots': 2}, 'adapter_slots'),
    ({'mesh': 'tp2'}, 'mesh'),
    ({'decode_impl': 'cross_layer'}, 'decode_impl'),
    ({'call': 'export'}, 'KV export/ingest'),
    ({'call': 'ingest'}, 'KV export/ingest'),
])
def test_refused_with_its_reason(kwargs, reason):
    """What the model cannot yet be combined with raises where it is
    asked for, naming what and why; nothing fails silently."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = make('float32')
    kwargs = dict(kwargs)
    if kwargs.get('mesh'):
        from skypilot_tpu.parallel import mesh as mesh_lib
        kwargs['mesh'] = mesh_lib.serving_mesh(2, 1)
    base = dict(params=params, max_batch=2, max_seq=32)
    call = kwargs.pop('call', None)
    if call is None:
        with pytest.raises(ValueError, match=reason):
            PagedInferenceEngine(cfg, **base, **kwargs)
        return
    eng = PagedInferenceEngine(cfg, **base)
    with pytest.raises(NotImplementedError, match=reason):
        eng._get_export(1) if call == 'export' else eng._get_ingest(8, 1)


def test_llama_family_pool_shapes_and_program_keys_unchanged():
    """The cache spec gives a GQA model exactly the pool, ring and byte
    accounting it had: shapes and bytes from (n_kv_heads, head_dim), and
    the same program keys and tokens after the same requests."""
    from skypilot_tpu.inference.engine import (_ring_row_bytes,
                                               kv_token_bytes)
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg = configs.TINY_QWEN
    L, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    assert cfg.kv_spec == configs.KVSpec(hkv, hd, hd)
    assert kv_token_bytes(cfg, 'bf16') == L * hkv * hd * 2 * 2
    assert kv_token_bytes(cfg, 'int8') == L * hkv * (hd + 4) * 2
    assert kv_token_bytes(cfg, 'int4') == L * hkv * (hd // 2 + 4) * 2
    assert _ring_row_bytes(cfg, 4) == L * 4 * hkv * hd * 2 * 2
    for kv_dtype, dtype in (('bf16', jnp.bfloat16), ('int8', jnp.int8)):
        eng = PagedInferenceEngine(cfg, max_batch=4, max_seq=64,
                                   page_size=8, chunk=16,
                                   kv_cache_dtype=kv_dtype)
        n_pages = 4 * 8 + 1
        assert eng.cache.pool_k.shape == (L, n_pages, hkv, 8, hd)
        assert eng.cache.pool_v.shape == (L, n_pages, hkv, 8, hd)
        assert eng.cache.pool_k.dtype == dtype
        if kv_dtype == 'int8':
            assert eng.cache.k_scale.shape == (L, n_pages, hkv, 8)
    # Recorded on the parent commit (PR 28's tree) with these requests:
    # the int8 engine's prefill and decode program keys, and its tokens.
    rng = np.random.default_rng(7)
    for n in (21, 5):
        eng.add_request(rng.integers(0, 256, n).tolist(), max_new_tokens=6)
    done = eng.run_to_completion(horizon=4)
    assert sorted(eng._prefill_fns) == [(1, 4, False, 16), (2, 2, False, 16)]
    assert sorted((e['fn'], e['key']) for e in eng._prof.compile_events) == [
        ('decode', '(4, False, 2)'), ('decode', '(4, False, 4)'),
        ('prefill', '(1, 4, False, 16)'), ('prefill', '(2, 2, False, 16)')]
    assert [done[k].output for k in sorted(done)] == [
        [191, 26, 40, 191, 26, 191], [98, 225, 142, 78, 142, 78]]


def test_audit_preset_no_transfer_no_recompile():
    """The engine's steady state with this model, decode through the
    latent paged kernel as on the chip: the experts-read count rides the
    one sanctioned token readback, same-shaped waves compile nothing,
    and nothing of the decode dispatch but the kernel reads the pool: no
    layer step gathers pages."""
    from skypilot_tpu.analysis import costmodel, jaxpr_audit
    report = jaxpr_audit.run_preset('paged-latent-moe')
    assert 'decode_impl=pallas' in report.name
    assert report.ok(), '\n' + report.format()
    assert not [t for t in report.transfers if not t.sanctioned]
    assert all(a == b for a, b in report.compile_counts.values())
    assert not report.cost_error, report.cost_error
    pool_reads = [e for e in report.dispatch_costs['decode'].eqns
                  if e.read.get(costmodel.KV_POOL)]
    assert pool_reads and {e.prim for e in pool_reads} == {'pallas_call'}
    assert all('per layer' in e.note for e in pool_reads)
