"""A model of Mamba-1 selective state-space layers beside GQA layers
(``models/mamba.py``, Jamba's block: NoPE GQA on one KV head beside
Mamba mixers, a dense SwiGLU FFN in every layer, tied embeddings)
against the plain reference (``models/reference/jamba.py``) at the
``tiny-jamba`` size: on logits, with seeded weights, through every
program that serves it.

Tolerances, each with its reason. The reference computes in float32 at
"highest" precision on the same (possibly bf16-rounded) weights.

- float32 configuration: 2e-4 on logits of magnitude ~4. Only the order
  of float32 accumulation differs (paged against whole attention, the
  fused projections); measured under 2e-5 for the full forward and
  through the paged programs. Each wrong variant below reads 1e-3 or
  more.
- bfloat16 configuration: 0.1 on the MEDIAN position's worst logit:
  the residual stream and every projection rounded to bfloat16 across 8
  layers (measured 0.02-0.04 over three seeds).
"""
import dataclasses
import hashlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import paged
from skypilot_tpu.models import configs, kda, llama, mamba
from skypilot_tpu.models.reference import jamba as reference
from skypilot_tpu.ops import mamba as mamba_ops

TOL = {'float32': 2e-4, 'bfloat16': 0.1}
PAGE, CHUNK = 8, 16


def error(dtype, got, want):
    per_position = np.abs(np.asarray(got, np.float32) - want).max(-1)
    return float(per_position.max() if dtype == 'float32'
                 else np.median(per_position))


def make(dtype, seed=0, **changes):
    cfg = dataclasses.replace(configs.TINY_JAMBA, dtype=jnp.dtype(dtype),
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
    c = configs.TINY_JAMBA
    assert c.layer_kinds == ('mamba', 'mamba', 'gqa', 'mamba') * 2
    assert (c.n_cache_layers, c.n_recurrent_layers) == (2, 6)
    assert c.recurrent_kind == 'mamba' and c.n_kv_heads == 1
    assert len({c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv}) == 3
    assert c.mamba_inner == 2 * c.dim == 128
    assert c.kv_spec == configs.KVSpec(1, 16, 16)
    assert c.state_spec == configs.StateSpec(1, 8, 128, 3, 128, 'mamba')
    assert c.state_spec.token_width == 128
    assert not c.use_rope and c.tie_embeddings and c.ffn_kind == 'dense'
    with pytest.raises(ValueError, match="at most one of 'kda' / 'mamba'"):
        dataclasses.replace(c, mixer_pattern=('gqa', 'kda', 'mamba',
                                              'mamba'))
    with pytest.raises(ValueError, match='dense FFN'):
        dataclasses.replace(c, ffn_kind='routed_shared')


def test_jamba2_3b_counts():
    """The arithmetic of ``perfbench/configs/jamba2-3b.json``:
    parameters, a cached token, a slot's state; and ``config.json`` of
    the catalog read into the published model."""
    from skypilot_tpu.inference.engine import kv_token_bytes
    from skypilot_tpu.models import weights
    c = configs.get_config('jamba2-3b')
    d, I, N, R = 2560, 5120, 16, 160
    mixer = (d * 2 * I + 4 * I + I + I * (R + 2 * N) + R * I + I
             + N * I + I + I * d + R + 2 * N)
    assert mixer == mamba.mixer_params(c) == 41_241_792
    attn = 2 * d * d + 2 * d * 128
    ffn = 3 * d * 8192
    assert attn == 13_762_560 and ffn == 62_914_560
    assert c.num_params == (26 * (mixer + ffn + 2 * d)
                            + 2 * (attn + ffn + 2 * d)
                            + 65536 * d + d) == 3_029_337_472
    assert c.flops_per_token() == 2 * c.num_params
    assert c.layer_kinds.index('gqa') == 7
    assert [i for i, k in enumerate(c.layer_kinds) if k == 'gqa'] == [7, 21]
    assert (c.n_cache_layers, c.n_recurrent_layers) == (2, 26)
    assert kv_token_bytes(c, 'bf16') == 1024
    assert 26 * c.state_spec.slot_bytes(2) == 26 * (327_680 + 30_720) \
        == 9_318_400
    catalog = {
        'architectures': ['JambaForCausalLM'], 'attn_layer_offset': 7,
        'attn_layer_period': 14, 'expert_layer_offset': 1,
        'expert_layer_period': 2, 'hidden_act': 'silu',
        'hidden_size': 2560, 'intermediate_size': 8192,
        'mamba_conv_bias': True, 'mamba_d_conv': 4, 'mamba_d_state': 16,
        'mamba_dt_rank': 160, 'mamba_expand': 2, 'mamba_proj_bias': False,
        'max_position_embeddings': 262144, 'model_type': 'jamba',
        'num_attention_heads': 20, 'num_experts': 1,
        'num_experts_per_tok': 1, 'num_hidden_layers': 28,
        'num_key_value_heads': 1, 'rms_norm_eps': 1e-06,
        'tie_word_embeddings': True, 'vocab_size': 65536}
    assert weights.config_from_hf(catalog, name='jamba2-3b') == c
    hf = weights.hf_config_dict(c)
    assert (hf['model_type'], hf['attn_layer_period'],
            hf['attn_layer_offset']) == ('jamba', 14, 7)
    assert weights.config_from_hf(hf, name=c.name) == c
    with pytest.raises(ValueError, match='routed experts'):
        weights.config_from_hf(dict(catalog, num_experts=16))


# ------------------------------------------------------------ (a) forward
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_full_forward_matches_reference(dtype):
    cfg, params = make(dtype)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, 150)
    err = error(dtype, program_logits(params, tokens, cfg),
                reference_logits(params, tokens, cfg))
    assert err < TOL[dtype], err


# ------------------------------------- (b) the scan's forms are one scan
def _scan_inputs(rng, b, s, N, I):
    dt = np.log1p(np.exp(rng.normal(size=(b, s, I)) - 2)).astype(np.float32)
    dt[:, ::7] = 0.0                            # padding-like tokens
    x = rng.normal(size=(b, s, I)).astype(np.float32)
    B = rng.normal(size=(b, s, N)).astype(np.float32)
    C = rng.normal(size=(b, s, N)).astype(np.float32)
    A = -np.exp(rng.normal(size=(N, I))).astype(np.float32)
    S = rng.normal(size=(b, N, I)).astype(np.float32)
    return S, dt, dt * x, B, C, A


@pytest.mark.parametrize('s', [1, 13, 40])
def test_chunked_scan_is_the_one_token_step(s):
    """``scan_xla`` and the Pallas ``scan_kernel`` (interpret mode; a
    length that is not a multiple of its 8-token iteration) against
    ``step`` token by token from a carried state, and ``step`` against
    the definition in float64."""
    rng = np.random.default_rng(s)
    S0, dt, u, B, C, A = _scan_inputs(rng, 2, s, 8, 256)
    want, S = [], jnp.asarray(S0)
    for t in range(s):
        y, S = mamba_ops.step(S, dt[:, t], u[:, t], B[:, t], C[:, t], A)
        want.append(y)
    want = np.stack(want, 1)
    for form in (mamba_ops.scan_xla, lambda *a: mamba_ops.scan_kernel(
            *a, interpret=True)):
        got, S2 = jax.jit(form)(jnp.asarray(S0), dt, u, B, C, A)
        assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
        assert np.abs(S2 - S).max() < 1e-4 * np.abs(S).max()
    S64 = S0[0].astype(np.float64)
    for t in range(min(s, 3)):
        S64 = (np.exp(dt[0, t][None] * A) * S64
               + np.outer(B[0, t], u[0, t]))
        assert np.abs(S64.T @ C[0, t] - want[0, t]).max() < 1e-4


@pytest.mark.parametrize('active', [
    (1, 0, 1, 1, 0), (0, 0, 1, 0, 0), (1, 1, 1, 1, 1), (0, 0, 0, 0, 0),
    # 24 slots in tail tiles of 8: live slots in the first and the
    # last, none in the middle one
    tuple(int(i in (1, 17, 23)) for i in range(24))])
def test_decode_kernel_is_the_mixer_on_live_slots(active):
    """``mamba.decode_mixer`` (the kernel ``ops.mamba.decode_mix``,
    interpret mode) against ``mixer``'s one-token XLA form on the same
    layer of a stack: the gated output, the new state and the new conv
    tail of every live slot within float32 rounding; every other layer,
    and a dead slot's state and tail, bit for bit; a dead slot's output
    is ``out_proj`` of zeros."""
    cfg, params = make('float32')
    stacks = params['mamba_layers']['mamba']
    n_layers, li = stacks['in_proj'].shape[0], 1
    p = jax.tree.map(lambda a: a[li], stacks)
    spec = cfg.state_spec
    live = np.array(active, bool)
    slots = len(live)
    rng = np.random.default_rng(slots)
    states = rng.normal(size=(n_layers, slots, 1, spec.k_dim, spec.v_dim)
                        ).astype(np.float32)
    tails = rng.normal(size=(n_layers, slots, spec.conv_rows,
                             spec.conv_dim)).astype(np.float32)
    h = jnp.asarray(rng.normal(size=(slots, 1, cfg.dim)), jnp.float32)
    y, new_states, new_tails = jax.jit(
        lambda *a: mamba.decode_mixer(*a, cfg))(
            p, stacks, h, li, jnp.asarray(states), jnp.asarray(tails),
            jnp.asarray(live))
    with jax.default_matmul_precision('highest'):
        y_ref, (S_ref, tail_ref) = mamba.mixer(
            p, h, cfg, rec=(jnp.asarray(states[li]), jnp.asarray(tails[li])),
            live=jnp.asarray(live)[:, None])
    y, new_states, new_tails = map(np.asarray, (y, new_states, new_tails))
    assert np.abs(y[live] - np.asarray(y_ref)[live]).max(initial=0) < 1e-5
    assert np.array_equal(y[~live], np.zeros_like(y[~live]))
    assert np.abs(new_states[li][live]
                  - np.asarray(S_ref)[live]).max(initial=0) < 1e-5
    assert np.array_equal(new_tails[li][live], np.asarray(tail_ref)[live])
    others = np.arange(n_layers) != li
    assert np.array_equal(new_states[others], states[others])
    assert np.array_equal(new_tails[others], tails[others])
    assert np.array_equal(new_states[li][~live], states[li][~live])
    assert np.array_equal(new_tails[li][~live], tails[li][~live])


def test_padding_moves_neither_state_nor_conv_tail():
    """A run whose last rows carry no token leaves the state and the
    tail where the run without them does; a row with none moves
    neither."""
    cfg, params = make('float32')
    p = jax.tree.map(lambda a: a[0], params['mamba_layers']['mamba'])
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 16, cfg.dim)), jnp.float32)
    spec = cfg.state_spec
    rec = (jnp.asarray(rng.normal(size=(2, 1, spec.k_dim, spec.v_dim)),
                       jnp.float32),
           jnp.asarray(rng.normal(size=(2, spec.conv_rows, spec.conv_dim)),
                       jnp.float32))
    valid = np.array([11, 0])
    live = jnp.arange(16)[None, :] < jnp.asarray(valid)[:, None]
    with jax.default_matmul_precision('highest'):
        y, (S, tail) = mamba.mixer(p, h, cfg, rec=rec, live=live)
        y0, (S0, tail0) = mamba.mixer(p, h[:1, :11], cfg,
                                      rec=(rec[0][:1], rec[1][:1]))
    assert np.abs(y[0, :11] - y0[0]).max() < 1e-5
    assert np.abs(S[0] - S0[0]).max() < 1e-5
    assert np.array_equal(tail[0], tail0[0])
    assert np.array_equal(S[1], rec[0][1])
    assert np.array_equal(tail[1], rec[1][1])


# ------------------------------------------------------ (c) paged programs
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
    row, each prompt in its slot of ``slots`` (of 4), a state left in
    every slot by an earlier tenant; then ``n_new`` decode steps in fused
    horizons over all 4 slots (the others dead), the ring merged and the
    state carried in between. Returns per prompt (tokens generated,
    {position: logits}); asserts the dead slots' state never moved."""
    n_rows, B = len(prompts) + 1, 4
    per_row = -(-(max(map(len, prompts)) + n_new) // PAGE)
    P = 1
    while P < per_row:
        P *= 2
    table = np.zeros((B, P), np.int32)
    for s in slots:
        table[s, :per_row] = 1 + s * per_row + np.arange(per_row)
    cache = paged.PagedKVCache.create(cfg, n_pages=1 + B * per_row,
                                      page_size=PAGE)
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
            assert toks.shape == (B, horizon)       # no expert rows
            for i, s in enumerate(slots):
                for h in range(horizon):
                    got[i][int(lengths[s]) + h] = steps[h][s]
                    out[i].append(int(toks[s, h]))
            cur = jnp.asarray(toks[:, -1])
            lengths[list(slots)] += horizon
        after = jax.tree.map(lambda a: np.asarray(a[:, dead]), rec)
        assert all(np.array_equal(a, b) for a, b in zip(before, after)), \
            'a dead slot\'s state moved'
    return out, got


@pytest.mark.parametrize('dtype,lens,horizon,decode_impl', [
    ('float32', (37, 21), 1, 'gather'),     # chunks 16+16+5 and 16+5
    ('float32', (37, 21), 8, 'pallas'),     # paged kernel + mixer kernel
    ('bfloat16', (37, 21), 8, 'gather'),
    ('bfloat16', (37, 21), 8, 'pallas'),    # bf16 tails through the kernel
    ('float32', (1, 70), 4, 'gather'),
])
def test_paged_prefill_then_decode_matches_reference(dtype, lens, horizon,
                                                     decode_impl):
    """Prompts prefilled in chunks that do not divide them, two slots of
    four beside a padding row, a state left in the slots by an earlier
    tenant (the first chunk starts from zeros), then decode through
    cache and state; every position's logits against the reference's
    full forward. 'pallas' takes the paged GQA kernel over a group of 4
    query heads on the one KV head and the Mamba decode kernel, the
    whole mixer between its projections (interpret mode): 128 channels
    tile the lanes."""
    cfg, params = make(dtype)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    n_new = 17
    seen = []
    real = mamba_ops.decode_mix

    def spy(*a, **kw):
        seen.append(a[2].shape)
        return real(*a, **kw)

    with mock.patch.object(mamba_ops, 'decode_mix', spy):
        out, got = serve_through_pages(cfg, params, prompts, n_new, horizon,
                                       slots=(2, 0), decode_impl=decode_impl)
    assert bool(seen) == (decode_impl == 'pallas')
    assert set(seen) <= {(6, 4, 1, 8, 128)}
    rows, want = [], []
    for prompt, tokens, logits in zip(prompts, out, got):
        ref = reference_logits(params, prompt + tokens, cfg)
        assert sorted(logits) == list(range(len(prompt) - 1,
                                            len(prompt) + n_new - 1))
        rows += list(logits.values())
        want += [ref[pos] for pos in logits]
    err = error(dtype, np.stack(rows), np.stack(want))
    assert err < TOL[dtype], err


# ----------------------------------------------------- (d) the engine path
def _deficits(params, cfg, prompt, out):
    ref = reference_logits(params, prompt + out, cfg)[
        len(prompt) - 1:len(prompt) + len(out) - 1]
    return ref.max(-1) - ref[np.arange(len(out)), out]


def test_engine_serves_it_and_counts():
    """The normal path: four requests over two slots (each slot reused
    by a second request, which starts it from zeros), chunked prefill
    with padded last chunks, fused decode; every served token is the
    reference's best within the tolerance; the pool holds the two
    layers that cache rows, the state beside it is counted, its kind is
    said, no prefix is registered, and the scan's counter moves by the
    prompt tokens."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.telemetry import profiler, registry
    cfg, params = make('float32')
    reg = registry.get_registry()
    names = (profiler.STATE_RESETS_METRIC, profiler.STATE_RECOMPUTE_METRIC,
             profiler.PREFILL_SCAN_TOKENS_METRIC)

    def counters():
        return {n: reg.get(n).value if reg.get(n) else 0.0 for n in names}

    eng = PagedInferenceEngine(cfg, params=params, max_batch=2, max_seq=128,
                               page_size=PAGE, chunk=CHUNK)
    before = counters()
    assert eng.cache.pool_k.shape == (2, 2 * 16 + 1, 1, PAGE, 16)
    assert eng.rec.state.shape == (6, 2, 1, 8, 128)
    assert eng.rec.state.dtype == jnp.float32
    assert eng.rec.conv.shape == (6, 2, 3, 128)
    stats = eng.memory_stats()
    assert (stats['recurrent_kind'], stats['recurrent_layers']) == \
        ('mamba', 6)
    assert stats['recurrent_state_slot_bytes'] == 6 * (8 * 128 * 4
                                                       + 3 * 128 * 4)
    assert eng.kv_pool_stats()['kv_token_bytes'] == 2 * 1 * 32 * 4
    assert reg.get(profiler.RECURRENT_LAYERS_METRIC).value == 6
    assert reg.get(profiler.RECURRENT_STATE_BYTES_METRIC).value == \
        stats['recurrent_state_slot_bytes']
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (37, 9, 21, 5)]
    ids = [eng.add_request(p, max_new_tokens=10) for p in prompts]
    done = eng.run_to_completion(horizon=4)
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output
        assert len(out) == 10
        assert _deficits(params, cfg, prompt, out).max() < TOL['float32']
    assert eng.alloc.prefix_hits == 0 and not eng.alloc.retained
    moved = {k: v - before[k] for k, v in counters().items()}
    assert moved[profiler.STATE_RESETS_METRIC] == 4
    assert moved[profiler.STATE_RECOMPUTE_METRIC] == 0
    assert moved[profiler.PREFILL_SCAN_TOKENS_METRIC] == sum(map(len,
                                                                 prompts))


@pytest.mark.parametrize('preset,decode_impl,form', [
    ('tiny-jamba', 'gather', 'xla'), ('tiny-jamba', 'pallas', 'fused'),
    ('tiny', 'pallas', None)])
def test_resolved_path_names_the_mamba_decode_form(preset, decode_impl,
                                                   form):
    """``resolved_path()['mamba_decode']``: which form the decode program
    was built with, the one kernel between a Mamba layer's projections
    or XLA; None for a model without Mamba layers."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg = dataclasses.replace(configs.get_config(preset), dtype=jnp.float32)
    eng = PagedInferenceEngine(cfg, max_batch=2, max_seq=64, page_size=PAGE,
                               chunk=CHUNK, decode_impl=decode_impl)
    assert eng.resolved_path()['mamba_decode'] == form


def test_a_slots_state_can_be_read_back():
    """``recurrent_state_of``: a request alone on the engine, prefilled
    in chunks that do not divide it and decoded through whole calls of
    the horizon, leaves its slot the reference's state after every token
    of its context but the last one produced, each Mamba layer's; the
    slot used again starts from zeros."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = make('float32')
    eng = PagedInferenceEngine(cfg, params=params, max_batch=1, max_seq=128,
                               page_size=PAGE, chunk=CHUNK)
    rng = np.random.default_rng(5)
    for length, calls in ((37, 2), (21, 1)):        # the same slot twice
        prompt = rng.integers(0, cfg.vocab_size, length).tolist()
        rid = eng.add_request(prompt, max_new_tokens=1 + 8 * calls)
        req = eng.run_to_completion(horizon=8)[rid]
        state = eng.recurrent_state_of(req)
        assert state.shape == (6, 1, 8, 128) and state.dtype == np.float32
        want = []
        reference.forward(params, jnp.asarray(prompt + req.output[:-1]),
                          cfg, states=want)
        want = np.stack(want)
        assert np.abs(state - want).max() < 1e-4 * np.abs(want).max()


# ------------------------------------------ (e) wrong mathematics fails
def _int8_rounded(params):
    """Every matrix leaf rounded to int8 a column (per output channel)
    and back: what an int8 tree serves."""
    def rnd(a):
        if a.ndim < 3 or a.dtype != jnp.float32:
            return a
        scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 127
        return jnp.round(a / scale) * scale
    return jax.tree.map(rnd, params)


def _without_conv_bias(params):
    mix = dict(params['mamba_layers']['mamba'])
    del mix['conv_bias']
    return dict(params, mamba_layers=dict(params['mamba_layers'], mamba=mix))


@pytest.mark.parametrize('variant', ['int8_tree', 'bf16_state',
                                     'no_dt_b_c_norms', 'no_conv_bias'])
def test_each_wrong_variant_fails_the_tolerance(variant):
    """The float32 tolerance holds the right program and refuses each of
    four wrong ones: the weights rounded to int8, the scan's state kept
    in bfloat16 (the reference's own, rounded after every token),
    Jamba's three norms of dt, B and C left out, the conv bias left
    out."""
    cfg, params = make('float32')
    tokens = np.random.default_rng(8).integers(0, cfg.vocab_size, 64)
    want = reference_logits(params, tokens, cfg)
    right = error('float32', program_logits(params, tokens, cfg), want)
    assert right < TOL['float32'], right
    if variant == 'int8_tree':
        got = program_logits(_int8_rounded(params), tokens, cfg)
    elif variant == 'bf16_state':
        with mock.patch.object(reference, 'state_dtype',
                               lambda: jnp.bfloat16):
            got = reference_logits(params, tokens, cfg)
    elif variant == 'no_dt_b_c_norms':
        with mock.patch.object(mamba, '_rms', lambda x, w, eps: x):
            got = program_logits(params, tokens, cfg)
    else:
        got = program_logits(_without_conv_bias(params), tokens, cfg)
    wrong = error('float32', got, want)
    assert wrong > 5 * TOL['float32'], (variant, wrong)


# ------------------------------------------------------ (f) what it refuses
@pytest.mark.parametrize('kwargs,reason', [
    (dict(quantize='int8'), "not the Mamba mixer's projections"),
    (dict(kv_cache_dtype='int8'), 'recurrent state is float32'),
    (dict(speculate_k=2), 'roll the recurrent state back'),
    (dict(adapter_slots=2), 'the Mamba mixer has no such projection'),
    (dict(mesh='2x1'), 'per-slot state is not yet placed over a mesh'),
    (dict(decode_impl='cross_layer'), 'fused-merge kernel'),
    (dict(call='export'), 'per-slot state that no row holds'),
    (dict(call='ingest'), 'per-slot state that no row holds'),
])
def test_refused_with_its_reason(kwargs, reason):
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.parallel import mesh as mesh_lib
    cfg, params = make('float32')
    kwargs = dict(kwargs)
    if 'mesh' in kwargs:
        kwargs['mesh'] = mesh_lib.serving_mesh(2, 1)
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
# Recorded on the parent commit (before the 'mamba' kind): sha1 of the
# jaxpr text, addresses blanked, of llama.forward / paged_decode_horizon
# (horizon 4) / paged_prefill_chunk at 2 x 16 tokens, 9 pages of 8, on
# the serving tree; with 128-wide KDA heads 'pallas' takes the state
# kernel. (``test_hybrid_kda.py`` holds the dense, latent and looped
# models' programs to their parents the same way.)
PARENT_JAXPRS = {
    ('tiny-solar', 16, 'gather'): ['533cb6466b85', '4dd8a1e87f03',
                                   'ba61dcd659e7'],
    ('tiny-solar', 128, 'pallas'): ['9255d936c691', 'ef0dee1d0abe',
                                    'beaf8a6b63fe'],
}


@pytest.mark.parametrize('key', sorted(PARENT_JAXPRS))
def test_programs_of_the_other_kinds_are_the_parents(key):
    """The KDA model's three programs trace to the jaxprs they traced to
    before the 'mamba' kind, the per-kind state spec and the period
    scan's optional experts existed."""
    preset, head_dim, impl = key
    cfg = configs.get_config(preset)
    if head_dim != cfg.kda_head_dim:
        cfg = dataclasses.replace(cfg, kda_head_dim=head_dim, kda_heads=2)
    params = jax.eval_shape(lambda: kda.serving_layout(
        llama.init_params(jax.random.PRNGKey(0), cfg)))
    toks = jnp.zeros((2, 16), jnp.int32)
    cache = jax.eval_shape(
        lambda: paged.PagedKVCache.create(cfg, n_pages=9, page_size=8))
    rec = jax.eval_shape(lambda: paged.RecurrentState.create(cfg, 2))
    table, l = jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32)
    ids = jnp.arange(2, dtype=jnp.int32)
    jaxprs = [
        jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg)[0])(params,
                                                                 toks),
        jax.make_jaxpr(lambda p, c, r: paged.paged_decode_horizon(
            p, c, table, l, l, cfg, horizon=4, active=jnp.ones(2, bool),
            decode_impl=impl, rec=r))(params, cache, rec),
        jax.make_jaxpr(lambda p, c, r: paged.paged_prefill_chunk(
            p, c, table, toks, l, l, l, cfg, rec=r, slot_ids=ids))(
                params, cache, rec)]
    assert [hashlib.sha1(re.sub(r'0x[0-9a-f]+', '', str(j)).encode()
                         ).hexdigest()[:12] for j in jaxprs] \
        == PARENT_JAXPRS[key]


def test_period_scan_holds_a_body_a_run():
    """The jaxpr of ``llama.forward`` for ``tiny-jamba`` holds one scan
    over its 2 periods; inside it, in the period's order, a scan over
    the first run of 2 Mamba layers (its body holding the scan over
    tokens), the GQA layer and the single Mamba layer traced in line:
    three layer bodies a period, not eight."""
    cfg = configs.TINY_JAMBA
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    jaxpr = jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(
        params, jnp.zeros((1, 40), jnp.int32))
    outer = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == 'scan']
    assert [o.params['length'] for o in outer] == [2]
    inner = [e.params['length'] for e in outer[0].params['jaxpr'].jaxpr.eqns
             if e.primitive.name == 'scan']
    assert inner == [2, 40]     # the run of two; the lone layer's tokens
