"""Multi-step on-device decode (``decode_steps_per_call``).

The knob pins EXACTLY k fused decode steps (with on-device sampling)
into every jitted decode call, so per-call dispatch, readback lag and
sampling host-syncs amortize k x. Contracts pinned here:

- knob validation + the pin itself: every decode dispatch runs at
  static horizon k even when the caller asks for horizon 1, and a
  lockstep budget-bound round costs exactly one dispatch per k tokens
  (the jaxpr-audit ``multistep`` preset gates the same invariant with
  the transfer/recompile interceptor attached);
- k-matrix: k in {1, 2, 4, 8} are four programs (the ring softmax
  regroups with the horizon), each held to the plain forward's choices
  (``greedy_oracle``);
- early-EOS mid-scan: a request whose eos lands inside a fused call
  ends at its first eos (the substeps past it are discarded at
  readback; co-batched slots keep their tokens);
- sampling determinism: same seed + same k => identical sampled
  output, and the k>1 sampled stream is drawn from the same
  per-request distribution machinery (shared ``sample_tokens``);
- composition: ``speculate_k`` takes precedence for decode (one
  verify round per step — documented), int8/int4-KV engines serve
  under the knob, and the serve layer streams tokens in order through
  the scheduler with ``decode_steps_per_call`` set.
"""
import dataclasses
import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import greedy_oracle
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, llama


@pytest.fixture(scope='module')
def setup():
    cfg = configs.TINY
    # fp32: decisive argmaxes, so the eos a k=1 run picks is where a
    # k=8 run ends too.
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = llama.init_params(jax.random.PRNGKey(0), cfg32)
    return cfg32, params32


def _run(cfg, params, prompts, n_new, *, horizon=1, req_kw=None, **kw):
    eng = PagedInferenceEngine(cfg, params, max_batch=4, max_seq=128,
                               attn_impl='xla', **kw)
    return greedy_oracle.greedy(eng, prompts, n_new, horizon=horizon,
                                **(req_kw or {})), eng


_assert_agree = greedy_oracle.assert_all_agree


PROMPTS = [[1, 2, 3] * 5, [5, 9, 2] * 4]


def test_knob_validation():
    cfg = configs.TINY
    for bad in (0, -3):
        with pytest.raises(ValueError):
            PagedInferenceEngine(cfg, max_batch=2, max_seq=64,
                                 decode_steps_per_call=bad)
    eng = PagedInferenceEngine(cfg, max_batch=2, max_seq=64,
                               decode_steps_per_call=4)
    assert eng.decode_steps_per_call == 4
    assert PagedInferenceEngine(cfg, max_batch=2, max_seq=64
                                ).decode_steps_per_call is None


def test_pin_one_dispatch_per_k_tokens(setup):
    """Every decode dispatch runs at static horizon k (caller asked
    for 1), and a lockstep budget-bound batch costs exactly
    ceil(decode_tokens / k) dispatches — the amortization contract."""
    cfg, params = setup
    k = 4
    eng = PagedInferenceEngine(cfg, params, max_batch=4, max_seq=128,
                               attn_impl='xla', decode_steps_per_call=k)
    calls = []
    inner = eng._decode_fn

    def shim(*args, **kw):
        # horizon is a trailing positional.
        tail = [a for a in args if isinstance(a, (int, bool))]
        calls.append(tail)
        return inner(*args, **kw)

    eng._decode_fn = shim
    # Equal prompts + budget-bound (no eos/stop): all slots lockstep;
    # 2k decode tokens after the prefill-sampled first token.
    for _ in range(4):
        eng.add_request([1, 2, 3, 4, 5, 6], max_new_tokens=2 * k + 1)
    eng.run_to_completion(horizon=1)
    assert calls, 'decode never dispatched'
    horizons = [c[0] for c in calls]
    assert all(h == k for h in horizons), horizons
    # Early slot recycle stops dispatch the moment enqueued calls
    # cover every budget: EXACTLY one dispatch per k tokens.
    assert len(calls) == 2, calls


@pytest.mark.parametrize('k', [1, 2, 4, 8])
def test_greedy_agrees_with_oracle_k_matrix(setup, k):
    cfg, params = setup
    outs, _ = _run(cfg, params, PROMPTS, 20, decode_steps_per_call=k)
    assert all(len(out) == 20 for out in outs)
    _assert_agree(cfg, params, PROMPTS, outs, f'k={k}')


def test_early_eos_mid_scan(setup):
    """EOS landing inside a fused call: the request ends at its first
    eos, the post-eos substeps are discarded, and a co-batched slot
    keeps decoding unaffected; every token of both is still the
    reference's choice."""
    cfg, params = setup
    base, _ = _run(cfg, params, PROMPTS, 20, decode_steps_per_call=1)
    # Pick a FIRST-occurrence token mid-stream, at an output index
    # that keeps the eos inside a fused k=8 call (decode substeps
    # cover output indices 1..8, 9..16 — anything but the call
    # boundaries lands mid-scan).
    row, idx = next((r, i) for r, out in enumerate(base)
                    for i in range(1, 16)
                    if out[i] not in out[:i] and i % 8 != 0)
    eos = int(base[row][idx])
    for k in (1, 8):
        eng = PagedInferenceEngine(cfg, params, max_batch=4, max_seq=128,
                                   attn_impl='xla',
                                   decode_steps_per_call=k)
        rids = [eng.add_request(list(p), max_new_tokens=20,
                                eos_id=eos if r == row else None)
                for r, p in enumerate(PROMPTS)]
        done = eng.run_to_completion(horizon=1)
        outs = [done[r].output for r in rids]
        ended, other = outs[row], outs[1 - row]
        assert ended[-1] == eos and eos not in ended[:-1], (k, ended)
        assert len(ended) == idx + 1, (k, ended)
        assert len(other) == 20
        _assert_agree(cfg, params, PROMPTS, outs, f'k={k}')


def test_sampling_determinism_fixed_seed(setup):
    """Sampled decode under the knob: same seed + same k => identical
    streams (the same program fed the same state); the rng rides
    on-device splits inside the fused scan."""
    cfg, params = setup
    kw = dict(decode_steps_per_call=4, rng_seed=7)
    a, _ = _run(cfg, params, PROMPTS, 16,
                req_kw=dict(temperature=0.9, top_k=8), **dict(kw))
    b, _ = _run(cfg, params, PROMPTS, 16,
                req_kw=dict(temperature=0.9, top_k=8), **dict(kw))
    assert a == b
    assert any(len(set(x)) > 1 for x in a)     # actually sampled


def test_speculative_takes_precedence(setup):
    """speculate_k > 0 drives decode through the verify loop; the
    multi-step knob composes without breaking it (greedy spec output
    is still the reference's choice)."""
    cfg, params = setup
    rep = [3, 1, 4, 1, 5, 9, 2, 6] * 4
    got, eng = _run(cfg, params, [rep], 16, decode_steps_per_call=4,
                    speculate_k=4)
    assert len(got[0]) == 16
    _assert_agree(cfg, params, [rep], got, 'spec under the knob')
    assert eng.spec_metrics()['spec_rounds'] > 0


@pytest.mark.slow
def test_quantized_kv_and_int4_weights(setup):
    """int8 KV and int4 weights both serve under the knob. With a
    quantized cache the k>1 scan attends this horizon's rows from the
    bf16 ring where k=1 reads them back quantized, so each k is held to
    the oracle at its KV precision (int4 weights score through the
    int4 tree itself)."""
    cfg, params = setup
    for k in (1, 4):
        i4, e4 = _run(cfg, params, PROMPTS, 16, decode_steps_per_call=k,
                      quantize='int4', kv_cache_dtype='bf16')
        _assert_agree(cfg, e4.params, PROMPTS, i4, f'int4 weights, k={k}')
        k8, e8 = _run(cfg, params, PROMPTS, 16, decode_steps_per_call=k,
                      kv_cache_dtype='int8')
        assert e8.cache.quantized
        _assert_agree(cfg, params, PROMPTS, k8, f'int8 KV, k={k}',
                      'int8_kv')


@pytest.mark.slow
def test_serve_e2e_streams_in_order():
    """ModelServer with --decode-steps-per-call: tokens stream through
    the scheduler in order, the full output matches the done event,
    and the knob surfaces in both metrics formats."""
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.utils import common_utils
    port = common_utils.find_free_port(19750)
    server = ModelServer('tiny', max_batch=2, max_seq=64, port=port,
                         decode_steps_per_call=4)
    server.start(block=False)
    try:
        assert server._ready.wait(180)
        assert server.engine.decode_steps_per_call == 4
        body = json.dumps({'prompt': [1, 2, 3], 'max_new_tokens': 9,
                           'stream': True}).encode()
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate', body,
            {'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=60) as r:
            events = [json.loads(ln[5:]) for ln in r
                      if ln.startswith(b'data:')]
        tokens = [e['token'] for e in events if 'token' in e]
        assert len(tokens) == 9
        assert events[-1].get('done') is True
        assert events[-1]['tokens'] == tokens
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/metrics?format=json',
                timeout=30) as r:
            payload = json.loads(r.read())
        assert payload['decode_steps_per_call'] == 4
        assert payload['scheduler']['decode_steps_per_call'] == 4
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/metrics', timeout=30) as r:
            prom = r.read().decode()
        assert 'skytpu_decode_steps_per_call 4' in prom
    finally:
        server.stop()
