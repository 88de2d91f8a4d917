"""A model of two mixer kinds (``models/kda.py``, Solar Open 2's block:
gated NoPE GQA beside Kimi Delta Attention, every FFN routed + shared
experts of which a range is held) against the plain reference
(``models/reference/solar_open2.py``) at the ``tiny-solar`` size: on
logits, with seeded weights, through every program that serves it.

Tolerances, each with its reason. The reference computes in float32 at
"highest" precision on the same (possibly bf16-rounded) weights.

- float32 configuration: 2e-4 on logits of magnitude ~4. Only the order
  of float32 accumulation differs (the chunked delta rule against the
  token-by-token one, grouped against looped experts, paged against
  whole attention); measured 3e-5 for the full forward and under 6e-5
  through the paged programs.
- bfloat16 configuration: 0.35 on the MEDIAN position's worst logit,
  with EVERY expert chosen (``n_experts_per_token`` 16 of 16, the held 4
  computed). Measured 0.19-0.23 over three seeds: 8 layers, and a KDA
  branch's output is several times a GQA branch's at random weights, so
  the residual stream that bfloat16 rounds is larger (0.05 without the
  KDA branches, the same 0.19 with the whole mixer computed in float32
  from the rounded stream). With top-2 routing the statistic measures
  routing instead: a near-tie that bfloat16 breaks the other way adds
  or drops a whole expert in one of 8 routed layers and moves every
  later position through state and cache (median 0.4-0.8, and 1.4-1.5
  with all 16 held); the float32 cases hold the routed path.
"""
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import paged
from skypilot_tpu.models import configs, kda, latent_moe, llama
from skypilot_tpu.models.reference import solar_open2 as reference
from skypilot_tpu.ops import kda as kda_ops

TOL = {'float32': 2e-4, 'bfloat16': 0.35}
PAGE, CHUNK = 8, 16


def error(dtype, got, want):
    per_position = np.abs(np.asarray(got, np.float32) - want).max(-1)
    return float(per_position.max() if dtype == 'float32'
                 else np.median(per_position))


def make(dtype, seed=0, **changes):
    if dtype == 'bfloat16':         # every expert chosen: see above
        changes.setdefault('n_experts_per_token', 16)
    cfg = dataclasses.replace(configs.TINY_SOLAR, dtype=jnp.dtype(dtype),
                              **changes)
    return cfg, llama.init_params(jax.random.PRNGKey(seed), cfg)


def reference_logits(params, tokens, cfg):
    return np.asarray(reference.forward(params, jnp.asarray(tokens), cfg,
                                        q_block=7)[0])


def program_logits(params, tokens, cfg):
    logits, _ = jax.jit(lambda p, t: llama.forward(p, t, cfg))(
        params, jnp.asarray(tokens)[None])
    return np.asarray(logits[0], np.float32)


def test_preset_differs_where_a_mix_up_would_hide():
    c = configs.TINY_SOLAR
    assert c.layer_kinds == ('gqa', 'kda', 'kda', 'kda') * 2
    assert (c.n_cache_layers, c.n_recurrent_layers) == (2, 6)
    assert len({c.n_heads, c.n_kv_heads, c.kda_heads}) == 3
    assert len({c.head_dim, c.kda_head_dim, c.kda_gate_rank}) == 3
    assert (c.n_routed_experts, c.n_experts_per_token, c.held_experts,
            c.first_held_expert) == (16, 2, 4, 4)
    assert c.kv_spec == configs.KVSpec(2, 24, 24)
    assert c.state_spec == configs.StateSpec(3, 16, 16, 3, 144)
    assert not c.use_rope and c.attn_gate and c.kda_conv == 4


def test_solar_open2_250b_counts():
    """The cut's arithmetic (``perfbench/configs/solar-open2-250b.json``):
    parameters, a cached token, a slot's state; and ``config.json`` of
    the catalog read into the published model."""
    from skypilot_tpu.inference.engine import kv_token_bytes
    from skypilot_tpu.models import weights
    c = configs.get_config('solar-open2-250b')
    assert c.num_params == 3_308_377_920
    assert (c.n_cache_layers, c.n_recurrent_layers) == (1, 3)
    assert kv_token_bytes(c, 'bf16') == 4096
    assert c.state_spec.slot_bytes(2) * 3 == 12_582_912 + 442_368
    plan = kda.leaf_plan(c)
    count = lambda t: sum(
        count(v) for v in t.values()) if isinstance(t, dict) else (
        int(np.prod(kda.vector_shape(t, c))) if isinstance(t, str)
        else t if isinstance(t, int) else int(np.prod(t[0])))
    assert count(plan['kda_layers']['kda']) == 137_740_480
    gqa = {k: plan['layers'][k] for k in ('wq', 'wk', 'wv', 'wo',
                                          'w_attn_gate')}
    assert count(gqa) == 109_051_904
    # 2 FLOPs a parameter a token: everything but the experts, and of a
    # layer's 40 held experts the one a token's 8 of 320 land on.
    expert = 3 * 4096 * 1280
    assert c.flops_per_token() == 2 * (c.num_params - 4 * 39 * expert)
    hf = weights.hf_config_dict(c)
    assert hf['model_type'] == 'solar_open2' and hf['gqa_layers'] == [0]
    assert weights.config_from_hf(hf, name=c.name) == c
    published = weights.config_from_hf(dict(
        hf, num_hidden_layers=48, gqa_layers=list(range(0, 48, 4)),
        vocab_size=196608, n_held_experts=None))
    assert (published.n_cache_layers, published.n_recurrent_layers,
            published.held_experts) == (12, 36, 320)
    with pytest.raises(NotImplementedError, match='tensor names'):
        weights.load_hf_params('/nonexistent', c)


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_full_forward_matches_reference(dtype):
    cfg, params = make(dtype)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, 150)
    err = error(dtype, program_logits(params, tokens, cfg),
                reference_logits(params, tokens, cfg))
    assert err < TOL[dtype], err


# ------------------------------------------- (c) chunked == recurrent KDA
@pytest.mark.parametrize('length', [1, 15, 64, 77, 150])
def test_chunked_delta_rule_is_the_recurrent_one(length):
    """Lengths that are not multiples of the sub-chunk or the block, a
    carried-in state, decays strong enough to underflow (the log of a
    step's decay down to -1800) and none at all."""
    rng = np.random.default_rng(length)
    b, h, dk, dv = 2, 3, 16, 24
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(b, length, h, dk))).astype(np.float32)
    k = unit(rng.normal(size=(b, length, h, dk))).astype(np.float32)
    v = rng.normal(size=(b, length, h, dv)).astype(np.float32)
    g = (-3 * np.exp(rng.normal(size=(b, length, h, dk)) * 2 - 1)
         ).astype(np.float32)
    g[:, ::5] = 0.0
    beta = (2 / (1 + np.exp(-rng.normal(size=(b, length, h))))
            ).astype(np.float32)
    state = rng.normal(size=(b, h, dk, dv)).astype(np.float32)
    with jax.default_matmul_precision('highest'):
        want, S = [], jnp.asarray(state)
        for t in range(length):
            o, S = kda_ops.recurrent_step(S, q[:, t], k[:, t], v[:, t],
                                          g[:, t], beta[:, t])
            want.append(o)
        got, S2 = jax.jit(kda_ops.chunked)(jnp.asarray(state), q, k, v, g,
                                           beta)
    assert np.abs(np.stack(want, 1) - got).max() < 1e-4
    assert np.abs(S - S2).max() < 1e-4
    # the definition itself, in float64, for the first tokens of one head
    S = state[0, 0].astype(np.float64)
    for t in range(min(length, 4)):
        kk = k[0, t, 0].astype(np.float64)
        S = ((np.eye(dk) - beta[0, t, 0] * np.outer(kk, kk))
             @ np.diag(np.exp(g[0, t, 0].astype(np.float64))) @ S
             + beta[0, t, 0] * np.outer(kk, v[0, t, 0]))
        assert np.abs(S.T @ q[0, t, 0] - got[0, t, 0]).max() < 1e-5


def test_padding_moves_neither_state_nor_conv_tail():
    """A run whose last rows carry no token leaves the state and the
    tail where the run without them does."""
    cfg, params = make('float32')
    p = jax.tree.map(lambda a: a[0], params['kda_layers']['kda'])
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 16, cfg.dim)), jnp.float32)
    spec = cfg.state_spec
    rec = (jnp.asarray(rng.normal(size=(2, spec.heads, spec.k_dim,
                                        spec.v_dim)), jnp.float32),
           jnp.asarray(rng.normal(size=(2, spec.conv_rows, spec.conv_dim)),
                       jnp.float32))
    valid = np.array([11, 0])
    live = jnp.arange(16)[None, :] < jnp.asarray(valid)[:, None]
    with jax.default_matmul_precision('highest'):
        y, (S, tail) = kda.mixer(p, h, cfg, rec=rec, live=live)
        y0, (S0, tail0) = kda.mixer(p, h[:1, :11], cfg,
                                    rec=(rec[0][:1], rec[1][:1]))
    assert np.abs(y[0, :11] - y0[0]).max() < 1e-5
    assert np.abs(S[0] - S0[0]).max() < 1e-5
    assert np.array_equal(tail[0], tail0[0])
    assert np.array_equal(S[1], rec[0][1])
    assert np.array_equal(tail[1], rec[1][1])


# ------------------------------------------------------ (b) paged programs
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


def serve_through_pages(cfg, params, prompts, n_new, horizon, slots,
                        decode_impl='gather'):
    """Chunked paged prefill of ``prompts`` in one batch with a padding
    row, each prompt in its slot of ``slots`` (of 4), then ``n_new``
    decode steps in fused horizons over all 4 slots (the others dead),
    the ring merged and the state carried in between. Returns per
    prompt (tokens generated, {position: logits})."""
    n, n_rows, B = len(prompts), len(prompts) + 1, 4
    per_row = -(-(max(map(len, prompts)) + n_new) // PAGE)
    P = 1
    while P < per_row:
        P *= 2
    table = np.zeros((B, P), np.int32)
    for s in slots:
        table[s, :per_row] = 1 + s * per_row + np.arange(per_row)
    cache = paged.PagedKVCache.create(cfg, n_pages=1 + B * per_row,
                                      page_size=PAGE)
    # A state left by an earlier tenant: the first chunk starts from zeros.
    rec = jax.tree.map(lambda a: a + 1, paged.RecurrentState.create(cfg, B))
    tap = LogitTap()
    got = [dict() for _ in prompts]
    active = np.zeros(B, bool)
    active[list(slots)] = True
    slot_ids = np.array(list(slots) + [B], np.int32)
    prefill = jax.jit(lambda c, r, *a: paged.paged_prefill_chunk(
        params, c, *a, cfg, rec=r, slot_ids=jnp.asarray(slot_ids)))
    decode = jax.jit(lambda c, r, t, l: paged.paged_decode_horizon(
        params, c, jnp.asarray(table), t, l, cfg, horizon=horizon,
        active=jnp.asarray(active), decode_impl=decode_impl, rec=r))
    merge = jax.jit(paged.merge_ring_into_pool)
    with mock.patch.object(llama, 'mask_nonfinite_tokens', tap):
        first = np.zeros(B, np.int32)
        for off in range(0, max(map(len, prompts)), CHUNK):
            tokens = np.zeros((n_rows, CHUNK), np.int32)
            lengths = np.zeros(n_rows, np.int32)
            valid = np.zeros(n_rows, np.int32)
            want = np.full(n_rows, -1, np.int32)
            for i, p in enumerate(prompts):
                piece = p[off:off + CHUNK]
                lengths[i] = min(off, len(p))
                valid[i] = len(piece)
                tokens[i, :len(piece)] = piece
                if piece and off + len(piece) == len(p):
                    want[i] = len(piece) - 1
            tok, cache, rec = prefill(cache, rec, *map(
                jnp.asarray, (table[slot_ids.clip(0, B - 1)], tokens,
                              lengths, valid, want)))
            (logits,) = tap.take()
            for i, p in enumerate(prompts):
                if want[i] >= 0:
                    got[i][len(p) - 1] = logits[i]
                    first[slots[i]] = int(tok[i])
        out = [[int(first[s])] for s in slots]
        cur = jnp.asarray(first)
        lengths = np.zeros(B, np.int32)
        lengths[list(slots)] = [len(p) for p in prompts]
        dead = [s for s in range(B) if s not in slots]
        before = jax.tree.map(lambda a: np.asarray(a[:, dead]), rec)
        for _ in range(0, n_new - 1, horizon):
            toks, ring_k, ring_v, rec = decode(cache, rec, cur,
                                               jnp.asarray(lengths))
            cache = merge(cache, ring_k, ring_v, jnp.asarray(table),
                          jnp.asarray(lengths), jnp.asarray(active))
            steps = tap.take()
            toks = np.asarray(toks)
            # + the two expert rows of a model holding a share
            assert toks.shape == (B + 2, horizon)
            for i, s in enumerate(slots):
                for h in range(horizon):
                    got[i][int(lengths[s]) + h] = steps[h][s]
                    out[i].append(int(toks[s, h]))
            cur = jnp.asarray(toks[:B, -1])
            lengths[list(slots)] += horizon
        after = jax.tree.map(lambda a: np.asarray(a[:, dead]), rec)
        assert all(np.array_equal(a, b) for a, b in zip(before, after)), \
            'a dead slot\'s state moved'
    return out, got


@pytest.mark.parametrize('dtype,first_lens,horizon,decode_impl', [
    ('float32', (37, 21), 1, 'gather'),     # chunks 16+16+5 and 16+5
    ('float32', (37, 21), 8, 'gather'),
    ('float32', (37, 21), 8, 'pallas'),     # the GQA layer's paged kernel
    ('bfloat16', (37, 21), 8, 'gather'),
    ('float32', (1, 70), 8, 'gather'),      # more sub-chunks than one
])
def test_paged_prefill_then_decode_matches_reference(dtype, first_lens,
                                                     horizon, decode_impl):
    """Prompts prefilled in chunks that do not divide them, two slots of
    four beside a padding row, a state left in the slots by an earlier
    tenant, then decode through cache and state at horizons 1 and 8;
    every position's logits against the reference's full forward."""
    cfg, params = make(dtype)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in first_lens]
    n_new = 17
    out, got = serve_through_pages(cfg, params, prompts, n_new, horizon,
                                   slots=(2, 0), decode_impl=decode_impl)
    rows, want = [], []
    for prompt, tokens, logits in zip(prompts, out, got):
        ref = reference_logits(params, prompt + tokens, cfg)
        assert sorted(logits) == list(range(len(prompt) - 1,
                                            len(prompt) + n_new - 1))
        rows += list(logits.values())
        want += [ref[pos] for pos in logits]
    err = error(dtype, np.stack(rows), np.stack(want))
    # bfloat16 here is the median of 34 positions: 0.18-0.27 over three
    # seeds alone, 0.52 once beside five other test workers (the CPU's
    # bfloat16 products are summed in another order): twice the room.
    assert err < TOL[dtype] * (1 if dtype == 'float32' else 2), err


def test_decode_through_the_state_kernel_matches_reference():
    """With 128-wide KDA heads ``decode_impl='pallas'`` advances the
    stacked state by ``ops.kda.recurrent_step_in_place`` (interpret mode
    here): the live slots' state alone, where it lies; dead slots'
    untouched (asserted in ``serve_through_pages``)."""
    cfg, params = make('float32', kda_head_dim=128, kda_heads=2)
    assert cfg.state_spec.k_dim % 128 == 0
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (21, 5)]
    seen = []
    real = kda_ops.recurrent_step_in_place

    def spy(*a, **kw):
        seen.append(a[0].shape)
        return real(*a, **kw)

    with mock.patch.object(kda_ops, 'recurrent_step_in_place', spy):
        out, got = serve_through_pages(cfg, params, prompts, 9, 4,
                                       slots=(3, 1), decode_impl='pallas')
    assert seen and set(seen) == {(6, 4, 2, 128, 128)}
    rows, want = [], []
    for prompt, tokens, logits in zip(prompts, out, got):
        ref = reference_logits(params, prompt + tokens, cfg)
        rows += list(logits.values())
        want += [ref[pos] for pos in logits]
    err = error('float32', np.stack(rows), np.stack(want))
    assert err < TOL['float32'], err


@pytest.mark.parametrize('active', [(1, 0, 1, 1, 0), (0, 0, 1, 0, 0),
                                    (1, 1, 1, 1, 1)])
def test_state_kernel_is_the_recurrent_step_on_live_slots(active):
    rng = np.random.default_rng(0)
    L, B, H, dk, dv = 2, 5, 32, 128, 128
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    S = rng.normal(size=(L, B, H, dk, dv)).astype(np.float32)
    q = unit(rng.normal(size=(B, H, dk))).astype(np.float32)
    k = unit(rng.normal(size=(B, H, dk))).astype(np.float32)
    v = rng.normal(size=(B, H, dv)).astype(np.float32)
    g = -np.exp(rng.normal(size=(B, H, dk))).astype(np.float32)
    beta = (2 / (1 + np.exp(-rng.normal(size=(B, H))))).astype(np.float32)
    live = np.array(active, bool)
    new, o = jax.jit(lambda S, l, *a: kda_ops.recurrent_step_in_place(
        S, l, *a, interpret=True))(jnp.asarray(S), jnp.int32(1), q, k, v, g,
                                   beta, jnp.asarray(live))
    o_ref, S_ref = kda_ops.recurrent_step(jnp.asarray(S[1]), q, k, v, g,
                                          beta)
    assert np.abs(new[1] - np.where(live[:, None, None, None], S_ref,
                                    S[1])).max() < 1e-6
    assert np.array_equal(new[0], S[0])         # the other layer
    assert np.array_equal(new[1][~live], S[1][~live])
    assert np.abs(np.where(live[:, None, None], o_ref, 0) - o).max() < 1e-6


def _deficits(params, cfg, prompt, out):
    ref = reference_logits(params, prompt + out, cfg)[
        len(prompt) - 1:len(prompt) + len(out) - 1]
    return ref.max(-1) - ref[np.arange(len(out)), out]


def test_engine_serves_it_and_counts():
    """The normal path: five requests over three slots (slots reused by
    a second request), chunked prefill with padded last chunks, fused
    decode; every served token is the reference's best within the
    tolerance; the pool is sized by the one layer in four that caches
    rows, the state beside it is counted, no prefix is registered, and
    the counters move."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.telemetry import profiler, registry
    cfg, params = make('float32')
    reg = registry.get_registry()
    names = (profiler.MOE_LAYER_STEPS_METRIC, profiler.MOE_ASSIGNMENTS_METRIC,
             profiler.MOE_ASSIGNMENTS_HELD_METRIC,
             profiler.MOE_DISTINCT_METRIC, profiler.STATE_RESETS_METRIC,
             profiler.STATE_RECOMPUTE_METRIC)

    def counters():
        return {n: reg.get(n).value if reg.get(n) else 0.0 for n in names}

    eng = PagedInferenceEngine(cfg, params=params, max_batch=3, max_seq=160,
                               page_size=PAGE, chunk=CHUNK)
    before = counters()
    assert eng.cache.pool_k.shape == (2, 3 * 20 + 1, 2, PAGE, 24)
    assert eng.rec.state.shape == (6, 3, 3, 16, 16)
    assert eng.rec.state.dtype == jnp.float32
    assert eng.rec.conv.shape == (6, 3, 3, 144)
    stats = eng.memory_stats()
    assert stats['recurrent_layers'] == 6
    assert stats['recurrent_state_slot_bytes'] == 6 * (3 * 16 * 16 * 4
                                                       + 3 * 144 * 4)
    assert eng.kv_pool_stats()['kv_token_bytes'] == 2 * 2 * 48 * 4
    assert reg.get(profiler.KV_CACHE_LAYERS_METRIC).value == 2
    assert reg.get(profiler.RECURRENT_LAYERS_METRIC).value == 6
    assert reg.get(profiler.RECURRENT_STATE_BYTES_METRIC).value == \
        stats['recurrent_state_slot_bytes']
    assert reg.get(profiler.MOE_HELD_EXPERTS_METRIC).value == 4
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (37, 9, 21, 70, 5)]
    ids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    done = eng.run_to_completion(horizon=4)
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output
        assert len(out) == 12
        assert _deficits(params, cfg, prompt, out).max() < TOL['float32']
    # the state's shape depends on max_batch alone: the keys are a
    # model's without it
    assert all(len(k) == 4 for k in eng._prefill_fns)
    assert eng.alloc.prefix_hits == 0 and not eng.alloc.retained
    moved = {k: v - before[k] for k, v in counters().items()}
    steps = moved[profiler.MOE_LAYER_STEPS_METRIC]
    assert steps > 0 and steps % 8 == 0             # 8 expert layers
    held = moved[profiler.MOE_ASSIGNMENTS_HELD_METRIC]
    assert 0 < held < moved[profiler.MOE_ASSIGNMENTS_METRIC]
    assert moved[profiler.MOE_DISTINCT_METRIC] <= held
    assert moved[profiler.STATE_RESETS_METRIC] == 5
    assert moved[profiler.STATE_RECOMPUTE_METRIC] == 0



def test_a_slots_state_can_be_read_back():
    """``recurrent_state_of``: a request alone on the engine, prefilled
    in chunks that do not divide it and decoded through whole calls of
    the horizon, leaves its slot the reference's state after every
    token of its context but the last one produced, each recurrent
    layer's (float32: rounding apart); a slot used before starts from
    zeros, and a model with no recurrent layer reads None."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = make('float32')
    eng = PagedInferenceEngine(cfg, params=params, max_batch=2, max_seq=160,
                               page_size=PAGE, chunk=CHUNK)
    rng = np.random.default_rng(5)
    for length, calls in ((37, 2), (70, 3)):        # the same slot twice
        prompt = rng.integers(0, cfg.vocab_size, length).tolist()
        rid = eng.add_request(prompt, max_new_tokens=1 + 8 * calls)
        req = eng.run_to_completion(horizon=8)[rid]
        state = eng.recurrent_state_of(req)
        assert state.shape == (6, 3, 16, 16) and state.dtype == np.float32
        want = []
        reference.forward(params, jnp.asarray(prompt + req.output[:-1]),
                          cfg, states=want)
        want = np.stack(want)
        assert np.abs(state - want).max() < 1e-4 * np.abs(want).max()
    dense = PagedInferenceEngine(configs.TINY, max_batch=2, max_seq=64,
                                 page_size=PAGE, chunk=CHUNK)
    rid = dense.add_request([1, 2, 3], max_new_tokens=2)
    assert dense.recurrent_state_of(
        dense.run_to_completion(horizon=4)[rid]) is None

def test_preempted_request_prefills_again_from_its_first_token():
    """Pool pressure preempts the newest request. Its pages are not
    registered (a state cannot be rebuilt from them): it re-enters with
    prompt + output as its context, prefills all of it again from zeros,
    and every token of both outputs is still the reference's choice."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.telemetry import profiler, registry
    cfg, params = make('float32')
    reg = registry.get_registry()
    value = lambda n: reg.get(n).value if reg.get(n) else 0.0
    before = value(profiler.STATE_RECOMPUTE_METRIC)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 29).tolist()
               for _ in range(2)]
    eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=256,
                               page_size=8, n_pages=12, chunk=CHUNK)
    ids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
    done = eng.run_to_completion(horizon=8)
    assert eng.preemptions >= 1
    assert eng.alloc.prefix_hits == 0 and not eng.alloc.retained
    assert value(profiler.STATE_RECOMPUTE_METRIC) - before > 29
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output
        assert len(out) == 24
        assert _deficits(params, cfg, prompt, out).max() < TOL['float32']


# --------------------------------------------------------- (d) the shares
def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the 4 shares of ``tiny-solar``'s 16 experts
    give, with the shared expert counted once, add up to what the uncut
    reference gives for the whole layer."""
    whole_cfg, whole = make('float32', n_held_experts=None,
                            first_held_expert=0)
    layer = jax.tree.map(lambda a: a[1], whole['kda_layers'])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 9, 64)),
                    jnp.float32)
    fns = {'routing': lambda l, h: reference.routing(l, h, whole_cfg),
           'expert_term': reference.expert_term, 'swiglu': reference.swiglu}
    with jax.default_matmul_precision('highest'):
        want = np.stack([np.asarray(reference.routed_ffn(
            layer, x[b], whole_cfg, fns)[0]) for b in range(2)])
        shared = llama._ffn(layer['shared'], x, whole_cfg)
        total, held_total = np.asarray(shared, np.float32), 0
        for first in (0, 4, 8, 12):
            cfg = dataclasses.replace(whole_cfg, n_held_experts=4,
                                      first_held_expert=first)
            share = dict(layer, expert_layer=0, experts=jax.tree.map(
                lambda a: a[None, first:first + 4], layer['experts']))
            y, counted = latent_moe._moe_ffn(share, x, cfg, None)
            total = total + np.asarray(y - shared)
            held_total += int(counted[1])
    assert held_total == 2 * 9 * 2          # every assignment held once
    assert np.abs(total - want).max() < 1e-5


# ------------------------------------------------------ (f) what it refuses
@pytest.mark.parametrize('kwargs,reason', [
    (dict(quantize='int8'), 'quantize_params knows the dense GQA'),
    (dict(kv_cache_dtype='int8'), 'recurrent state is float32'),
    (dict(speculate_k=2), 'roll the recurrent state back'),
    (dict(adapter_slots=2), 'LoRA bank targets'),
    (dict(mesh='2x1'), 'not yet placed over a mesh'),
    (dict(decode_impl='cross_layer'), 'fused-merge kernel'),
    (dict(call='export'), 'per-slot state that no row holds'),
    (dict(call='ingest'), 'per-slot state that no row holds'),
    (dict(n_loops=2), 'not per \\(pass, layer\\)'),
])
def test_refused_with_its_reason(kwargs, reason):
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.parallel import mesh as mesh_lib
    cfg, params = make('float32')
    kwargs = dict(kwargs)
    if 'mesh' in kwargs:
        kwargs['mesh'] = mesh_lib.serving_mesh(2, 1)
    if 'n_loops' in kwargs:
        cfg = dataclasses.replace(cfg, n_loops=kwargs.pop('n_loops'))
    base = dict(params=params, max_batch=2, max_seq=32)
    call = kwargs.pop('call', None)
    if call is None:
        with pytest.raises(ValueError, match=f'mixer_pattern.*{reason}'):
            PagedInferenceEngine(cfg, **base, **kwargs)
        return
    eng = PagedInferenceEngine(cfg, **base)
    with pytest.raises(NotImplementedError, match=reason):
        eng._get_export(1) if call == 'export' else eng._get_ingest(8, 1)
    with pytest.raises(NotImplementedError, match='one kind of layer'):
        llama.forward(params, jnp.zeros((1, 4), jnp.int32), cfg,
                      cache=llama.KVCache.create(cfg, 1, 8))


# -------------------------------------------- the others: what they were
# Recorded on the parent commit (PR 36's tree): sha1 of the jaxpr text,
# addresses blanked, of llama.forward / paged_decode_horizon (horizon 4,
# 'gather') / paged_prefill_chunk at 2 x 16 tokens, 9 pages of 8.
PARENT_JAXPRS = {
    'tiny': ['dbfc226d7476', 'bd0dc9a4d5f2', '1d5c06a9bb2c'],
    'tiny-qwen': ['40fedcf0ce29', '3bc00419ee2a', '24fb0debda36'],
    'tiny-glm': ['4b9b295c026c', '49d8eaca6edf', '7121ec85bf6a'],
    'tiny-ouro': ['75d682d87b52', 'b3064b0ebbdd', '615c5b9e9c63'],
}


@pytest.mark.parametrize('preset', sorted(PARENT_JAXPRS))
def test_programs_of_the_other_kinds_are_the_parents(preset):
    """With ``mixer_pattern`` empty and every expert held, the three
    programs of a dense, a biased, a latent + routed and a looped model
    trace to the jaxprs they traced to before the period pattern, the
    recurrent state and the held range existed."""
    import hashlib
    import re
    cfg = configs.get_config(preset)
    assert (cfg.mixer_pattern, cfg.n_recurrent_layers, cfg.state_spec,
            cfg.held_experts) == ((), 0, None, cfg.n_routed_experts)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    toks = jnp.zeros((2, 16), jnp.int32)
    cache = jax.eval_shape(
        lambda: paged.PagedKVCache.create(cfg, n_pages=9, page_size=8))
    table, l = jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32)
    jaxprs = [
        jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg)[0])(params,
                                                                 toks),
        jax.make_jaxpr(lambda p, c: paged.paged_decode_horizon(
            p, c, table, l, l, cfg, horizon=4, active=jnp.ones(2, bool),
            decode_impl='gather'))(params, cache),
        jax.make_jaxpr(lambda p, c: paged.paged_prefill_chunk(
            p, c, table, toks, l, l, l, cfg))(params, cache)]
    assert [hashlib.sha1(re.sub(r'0x[0-9a-f]+', '', str(j)).encode()
                         ).hexdigest()[:12] for j in jaxprs] \
        == PARENT_JAXPRS[preset]


def test_period_scan_holds_one_body_a_kind():
    """The jaxpr of ``llama.forward`` for ``tiny-solar`` holds one scan
    over its 2 periods and inside it one over a period's 3 KDA layers
    (2 layer bodies traced, not 8), which holds the 16 rows of the
    triangular inverse's diagonal blocks and the scan over sub-chunks."""
    cfg = configs.TINY_SOLAR
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    jaxpr = jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(
        params, jnp.zeros((1, 70), jnp.int32))
    outer = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == 'scan']
    assert [o.params['length'] for o in outer] == [2]
    inner = [e for e in outer[0].params['jaxpr'].jaxpr.eqns
             if e.primitive.name == 'scan']
    assert [i.params['length'] for i in inner] == [3]
    kda = [e.params['length'] for e in inner[0].params['jaxpr'].jaxpr.eqns
           if e.primitive.name == 'scan']
    assert kda == [16, 2]       # the inverse's block rows; 70 -> 128
