"""Speculative decoding: n-gram proposer unit tests, device acceptance
math, and the greedy contract — speculative decode at any ``k`` commits
the plain forward's choices, as vanilla greedy decode does (verify and
decode are different programs, so each is held to ``greedy_oracle``,
not to the other; fast smoke in tier-1, the parameterized k/int8 matrix
in the slow tier with the other engine suites). Sampling correctness is
pinned by the top_p->0 collapse (the rejection-sampling verify path
must degenerate to greedy exactly)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import greedy_oracle
from skypilot_tpu.inference import speculative
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, llama


@pytest.fixture(scope='module')
def setup():
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


REPETITIVE = [3, 1, 4, 1, 5, 9, 2, 6] * 4
MIXED = [(i * 7 + 3) % 256 for i in range(40)]


def _engine(cfg, params, **kw):
    kw = dict(dict(max_batch=4, max_seq=256, page_size=8, attn_impl='xla'),
              **kw)
    return PagedInferenceEngine(cfg, params, **kw)


def _run(eng, prompts, n_new, **req_kw):
    return greedy_oracle.greedy(eng, prompts, n_new, horizon=8, **req_kw)


_assert_agree = greedy_oracle.assert_all_agree


# ---------------------------------------------------------------------------
# Fast tier: proposer + acceptance units, one smoke per engine
# ---------------------------------------------------------------------------
class TestNGramProposer:

    def test_repetitive_prompt_proposes_continuation(self):
        hist = [1, 2, 3, 4] * 5          # ...1,2,3,4 | next: 1,2,3,4
        prop = speculative.ngram_propose(hist, k=4)
        assert prop.tolist() == [1, 2, 3, 4]

    def test_most_recent_match_wins(self):
        # "7 8" occurs twice with different continuations; the later
        # occurrence (-> 9) must win over the earlier one (-> 5).
        hist = [7, 8, 5, 0, 7, 8, 9, 1, 7, 8]
        prop = speculative.ngram_propose(hist, k=2)
        assert prop.tolist() == [9, 1]

    def test_longest_ngram_preferred(self):
        # trailing 3-gram "1 2 3" matches at the start (-> 4); the
        # shorter trailing 1-gram "3" also matches elsewhere (-> 7) but
        # the longer match must be tried first.
        hist = [1, 2, 3, 4, 3, 7, 1, 2, 3]
        prop = speculative.ngram_propose(hist, k=1, max_ngram=3)
        assert prop.tolist() == [4]

    def test_no_match_returns_empty(self):
        prop = speculative.ngram_propose([1, 2, 3, 4, 5, 6], k=4)
        assert prop.size == 0
        assert speculative.ngram_propose([5], k=4).size == 0
        assert speculative.ngram_propose([1, 1, 1], k=0).size == 0

    def test_truncated_continuation(self):
        # Match near the end of history: fewer than k tokens follow.
        hist = [4, 5, 6, 9, 4, 5]
        prop = speculative.ngram_propose(hist, k=4)
        assert prop.tolist() == [6, 9, 4, 5][:4]


class TestVerifyTokens:
    """Direct unit test of the device acceptance math with crafted
    logits: position i's argmax is token (i+1)*10."""

    def _logits(self, b, k1, vocab=64):
        logits = np.full((b, k1, vocab), -5.0, np.float32)
        for i in range(k1):
            logits[:, i, (i + 1) * 10] = 5.0
        return jnp.asarray(logits)

    def test_greedy_full_accept_and_bonus(self):
        k = 3
        logits = self._logits(1, k + 1)
        proposals = jnp.asarray([[10, 20, 30]], jnp.int32)
        commit, n = speculative.verify_tokens(
            logits, proposals, jnp.asarray([3], jnp.int32), None,
            None, None, None, sample=False)
        assert int(n[0]) == 4                       # k accepted + bonus
        assert np.asarray(commit)[0, :4].tolist() == [10, 20, 30, 40]

    def test_greedy_first_mismatch_corrects(self):
        k = 3
        logits = self._logits(1, k + 1)
        proposals = jnp.asarray([[10, 99, 30]], jnp.int32)   # d2 wrong
        commit, n = speculative.verify_tokens(
            logits, proposals, jnp.asarray([3], jnp.int32), None,
            None, None, None, sample=False)
        assert int(n[0]) == 2                       # d1 + correction
        assert np.asarray(commit)[0, :2].tolist() == [10, 20]

    def test_padding_proposals_reject(self):
        k = 3
        logits = self._logits(1, k + 1)
        # Drafts all match the argmax chain but only 1 is valid.
        proposals = jnp.asarray([[10, 20, 30]], jnp.int32)
        commit, n = speculative.verify_tokens(
            logits, proposals, jnp.asarray([1], jnp.int32), None,
            None, None, None, sample=False)
        assert int(n[0]) == 2
        assert np.asarray(commit)[0, :2].tolist() == [10, 20]

    def test_sampled_peaked_dist_accepts_like_greedy(self):
        k = 2
        logits = self._logits(2, k + 1)
        proposals = jnp.asarray([[10, 20], [10, 99]], jnp.int32)
        temps = jnp.asarray([1.0, 1.0], jnp.float32)
        topks = jnp.zeros(2, jnp.int32)
        topps = jnp.ones(2, jnp.float32)
        commit, n = speculative.verify_tokens(
            logits, proposals, jnp.full((2,), 2, jnp.int32),
            jax.random.PRNGKey(0), temps, topks, topps, sample=True)
        # Peaked logits (margin 10): p(argmax) ~ 1, so acceptance
        # mirrors greedy and the resample lands on the argmax.
        assert int(n[0]) == 3
        assert np.asarray(commit)[0, :3].tolist() == [10, 20, 30]
        assert int(n[1]) == 2
        assert np.asarray(commit)[1, :2].tolist() == [10, 20]


class TestSpeculativeSmoke:
    """Tier-1 greedy smoke: one prompt mix, k=4, every committed token
    the reference's choice."""

    def test_paged_greedy_equivalence(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, speculate_k=4)
        got = _run(eng, [REPETITIVE, MIXED], 16)
        assert all(len(out) == 16 for out in got)
        _assert_agree(cfg, params, [REPETITIVE, MIXED], got, 'k=4')
        m = eng.spec_metrics()
        assert m['spec_rounds'] > 0
        # The repetitive prompt must actually exercise acceptance —
        # otherwise this smoke proves nothing about commit merging.
        assert m['spec_accepted'] > 0
        assert 0.0 <= m['spec_accept_rate'] <= 1.0
        assert 1.0 <= m['spec_tokens_per_step'] <= 5.0

    def test_spec_off_by_default(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, max_batch=2, max_seq=128)
        assert eng.speculate_k == 0
        m = eng.spec_metrics()                  # stable zero schema
        assert m['spec_accept_rate'] == 0.0
        assert m['spec_tokens_per_step'] == 0.0

    def test_prepare_proposals_outside_lock_contract(self, setup):
        """The serve loop's lock-free prepare: results are consumed by
        the next step; a stale cache entry is recomputed (not used)."""
        cfg, params = setup
        eng = _engine(cfg, params, max_batch=2, speculate_k=4)
        rid = eng.add_request(list(REPETITIVE), max_new_tokens=12)
        while eng.get_finished(rid) is None:
            eng.prepare_proposals()             # what the serve loop does
            eng.step(horizon=4)
        out = eng.get_finished(rid).output
        assert len(out) == 12
        _assert_agree(cfg, params, [REPETITIVE], [out], 'prepared')
        assert eng.spec_metrics()['spec_accepted'] > 0


# ---------------------------------------------------------------------------
# Slow tier: the k matrix + sampling collapse + capacity edges
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestSpeculativeMatrix:

    @pytest.mark.parametrize('k', [1, 2, 4, 8])
    def test_greedy_matrix(self, setup, k):
        cfg, params = setup
        prompts = [REPETITIVE, MIXED, [9],
                   [(i * 11 + 7) % cfg.vocab_size for i in range(40)]]
        got = _run(_engine(cfg, params, speculate_k=k), prompts, 12)
        assert all(len(out) == 12 for out in got)
        _assert_agree(cfg, params, prompts, got, f'k={k}')

    def test_int8_spec(self, setup):
        cfg, params = setup
        prompts = [REPETITIVE, MIXED]
        eng = _engine(cfg, params, max_batch=2, quantize='int8',
                      speculate_k=4)
        got = _run(eng, prompts, 10)
        _assert_agree(cfg, eng.params, prompts, got, 'int8, k=4',
                      'int8_kv')

    def test_sampling_collapse_to_greedy(self, setup):
        """temp>0 with top_p->0 must collapse to greedy THROUGH the
        rejection-sampling verify path (acceptance + residual
        resampling both land on the argmax)."""
        cfg, params = setup
        eng = _engine(cfg, params, max_batch=2, speculate_k=4, rng_seed=7)
        g = eng.add_request(list(REPETITIVE), max_new_tokens=16)
        h = eng.add_request(list(REPETITIVE), max_new_tokens=16,
                            temperature=2.0, top_p=1e-6)
        done = eng.run_to_completion(horizon=8)
        assert done[g].output == done[h].output

    def test_hot_sampling_valid_tokens(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, max_batch=1, speculate_k=4, rng_seed=3)
        rid = eng.add_request(list(REPETITIVE), max_new_tokens=20,
                              temperature=1.5, top_k=50)
        out = eng.run_to_completion(horizon=8)[rid].output
        assert len(out) == 20
        assert all(0 <= t < cfg.vocab_size for t in out)

    def test_eos_and_stop_truncate(self, setup):
        """eos/stop hit mid-commit truncates at the first hit (extra
        committed tokens discarded), and what came before it is the
        reference's choice."""
        cfg, params = setup
        ref = _run(_engine(cfg, params, max_batch=1), [REPETITIVE], 24)[0]
        eos = ref[7]
        out = _run(_engine(cfg, params, max_batch=1, speculate_k=4),
                   [REPETITIVE], 24, eos_id=eos)[0]
        assert out[-1] == eos and eos not in out[:-1]
        _assert_agree(cfg, params, [REPETITIVE], [out], 'eos')
        stop = ref[3:5]
        eng = _engine(cfg, params, max_batch=1, speculate_k=4)
        rid = eng.add_request(list(REPETITIVE), max_new_tokens=24,
                              stop=[stop])
        req = eng.run_to_completion(horizon=8)[rid]
        assert req.stop_hit
        pairs = list(zip(req.output, req.output[1:]))
        assert tuple(stop) not in pairs
        _assert_agree(cfg, params, [REPETITIVE], [req.output + stop],
                      'stop')

    def test_capacity_edge_max_seq(self, setup):
        """Generation that exactly fills max_seq: proposals are capped
        so the committed stream never overruns the cache, and stops at
        capacity as vanilla decode does."""
        cfg, params = setup
        prompt = REPETITIVE[:24]
        budget = 64 - len(prompt)               # exact max_seq fill
        s = _run(_engine(cfg, params, max_batch=1, max_seq=64,
                         speculate_k=4), [prompt], budget)[0]
        assert len(s) == budget
        _assert_agree(cfg, params, [prompt], [s], 'at capacity')

    def test_spec_interleaves_with_chunked_prefill(self, setup):
        """A long prompt admits in chunks while another slot speculates
        — mid-prefill slots are masked out of verify rounds and both
        outputs are the reference's choices."""
        cfg, params = setup
        long_prompt = [(i * 5 + 2) % cfg.vocab_size for i in range(150)]
        eng = _engine(cfg, params, max_batch=2, speculate_k=4,
                      prefill_chunk_tokens=32)
        a = eng.add_request(list(REPETITIVE), max_new_tokens=8)
        eng.step(horizon=1)
        b = eng.add_request(list(long_prompt), max_new_tokens=8)
        done = eng.run_to_completion(horizon=4)
        outs = [done[a].output, done[b].output]
        assert all(len(out) == 8 for out in outs)
        _assert_agree(cfg, params, [REPETITIVE, long_prompt], outs,
                      'spec beside chunked prefill')

    def test_paged_pool_pressure_sheds_then_preempts(self, setup):
        """A pool too small for every slot's k+1 reservation still
        completes every request correctly (proposals shed / newest
        preempted, never a crash or wrong tokens)."""
        cfg, params = setup
        eng = _engine(cfg, params, max_seq=128, n_pages=24, speculate_k=4)
        got = _run(eng, [REPETITIVE] * 4, 16)
        assert all(len(out) == 16 for out in got)
        _assert_agree(cfg, params, [REPETITIVE] * 4, got,
                      'spec under pool pressure')

    def test_cancel_during_speculation(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, max_batch=2, speculate_k=4)
        rid = eng.add_request(list(REPETITIVE), max_new_tokens=200)
        keep = eng.add_request(list(MIXED), max_new_tokens=8)
        for _ in range(3):
            eng.step()
        assert eng.cancel(rid)
        done = eng.run_to_completion(horizon=4)
        assert rid not in done and len(done[keep].output) == 8


# ---------------------------------------------------------------------------
# Serve-layer integration: /metrics schema + the lock-free proposer loop
# ---------------------------------------------------------------------------
SPEC_METRIC_KEYS = ('speculate_k', 'spec_accept_rate',
                    'spec_tokens_per_step', 'spec_proposed',
                    'spec_accepted', 'spec_rounds', 'ttft_ms_median',
                    'ttft_ms_p90')


def _boot_server(port, **kw):
    import time
    import urllib.request

    from skypilot_tpu.serve.server import ModelServer
    server = ModelServer('tiny', max_batch=2, max_seq=64, port=port,
                         **kw)
    server.start(block=False)
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/readiness',
                    timeout=5) as r:
                if r.status == 200:
                    return server
        except Exception:  # pylint: disable=broad-except
            time.sleep(0.3)
    raise RuntimeError('server did not become ready')


@pytest.mark.slow
def test_metrics_schema_stable_spec_on_and_off():
    """/metrics must expose the SAME numeric gauge keys whether
    speculation is on or off (zeros, never omitted keys), and with
    speculation on the accept-rate gauges must move after traffic.
    Also exercises the serve loop's lock-free prepare_proposals path
    end to end."""
    import json
    import urllib.request

    from skypilot_tpu.utils import common_utils

    def gen(port, payload):
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate',
            data=json.dumps(payload).encode(),
            headers={'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def metrics(port):
        # The stable-schema JSON gauge block moved behind ?format=json
        # when /metrics switched to Prometheus exposition by default.
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/metrics?format=json',
                timeout=10) as r:
            return json.loads(r.read())

    prompt = [3, 1, 4, 1, 5, 9] * 4

    def agrees(tokens, what):
        assert len(tokens) == 12
        greedy_oracle.assert_server_agrees(prompt, tokens, what)

    port_off = common_utils.find_free_port(18940)
    srv_off = _boot_server(port_off)
    try:
        m_off = metrics(port_off)
        for key in SPEC_METRIC_KEYS:
            assert key in m_off, key
            assert isinstance(m_off[key], (int, float)), key
        assert m_off['speculate_k'] == 0
        assert m_off['spec_accept_rate'] == 0.0
        assert m_off['scheduler']['speculate_k'] == 0
        agrees(gen(port_off, {'prompt': prompt,
                              'max_new_tokens': 12})['tokens'], 'spec off')
    finally:
        srv_off.stop()

    port_on = common_utils.find_free_port(18960)
    srv_on = _boot_server(port_on, speculate_k=4)
    try:
        agrees(gen(port_on, {'prompt': prompt,
                             'max_new_tokens': 12})['tokens'],
               'spec on')                     # greedy contract e2e
        m_on = metrics(port_on)
        assert set(SPEC_METRIC_KEYS) <= set(m_on)
        assert m_on['speculate_k'] == 4
        assert m_on['spec_rounds'] > 0
        assert m_on['spec_tokens_per_step'] >= 1.0
    finally:
        srv_on.stop()
