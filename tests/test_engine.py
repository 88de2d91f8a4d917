"""Inference engine tests (CPU, tiny model): the request lifecycle,
sampling, quantized weights and the async pipeline, with greedy tokens
held to the plain forward pass (``greedy_oracle``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import greedy_oracle
from skypilot_tpu.inference.engine import _bucket_len
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, llama

# Compile-heavy (jit of full models): slow tier — the fast sweep is
# the orchestration layer (SURVEY §4 offline tier analog).
pytestmark = pytest.mark.slow


@pytest.fixture(scope='module')
def engine_setup():
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestEngine:

    def test_bucketing(self):
        assert _bucket_len(1) == 64
        assert _bucket_len(64) == 64
        assert _bucket_len(65) == 128

    def test_greedy_matches_reference(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                   attn_impl='xla')
        prompt = [3, 1, 4, 1, 5]
        rid = eng.add_request(prompt, max_new_tokens=6)
        done = eng.run_to_completion()
        got = done[rid].output
        assert len(got) == 6
        greedy_oracle.assert_agrees(cfg, params, prompt, got)

    def test_continuous_batching_multiple_requests(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                   attn_impl='xla')
        prompts = [[3, 1, 4], [1, 5, 9, 2], [6, 5], [3, 5, 8, 9, 7]]
        rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        done = eng.run_to_completion()
        assert len(done) == 4
        for rid, p in zip(rids, prompts):
            got = done[rid].output
            assert len(got) == 5
            greedy_oracle.assert_agrees(cfg, params, p, got)

    def test_more_requests_than_slots_drains(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                   attn_impl='xla')
        rids = [eng.add_request([i + 1, i + 2], max_new_tokens=3)
                for i in range(5)]
        done = eng.run_to_completion()
        assert set(done) == set(rids)
        assert all(len(done[r].output) == 3 for r in rids)

    def test_eos_stops_early(self, engine_setup):
        cfg, params = engine_setup
        # find what greedy emits first, use it as eos
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   attn_impl='xla')
        (first,), = greedy_oracle.greedy(eng, [[3, 1, 4]], 1)
        rid = eng.add_request([3, 1, 4], max_new_tokens=10, eos_id=first)
        done = eng.run_to_completion()
        assert done[rid].output == [first]

    def test_capacity_rejected(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=64,
                                   attn_impl='xla')
        with pytest.raises(ValueError):
            eng.add_request(list(range(1, 60)), max_new_tokens=10)
        with pytest.raises(ValueError):
            eng.add_request([], max_new_tokens=1)

    def test_sampling_temperature(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   rng_seed=7, attn_impl='xla')
        rid = eng.add_request([3, 1, 4], max_new_tokens=16,
                              temperature=2.0, top_k=50)
        done = eng.run_to_completion()
        toks = done[rid].output
        assert len(toks) == 16
        assert all(0 <= t < cfg.vocab_size for t in toks)
        # hot sampling at high temperature should not be constant
        assert len(set(toks)) > 1

    def test_top_p_tiny_equals_greedy(self, engine_setup):
        """top_p -> 0 collapses the nucleus to the single top token, so
        even hot sampling reproduces the greedy output."""
        cfg, params = engine_setup
        outs = []
        for top_p in (1e-6, None):      # None = greedy run
            eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                       rng_seed=11, attn_impl='xla')
            if top_p is None:
                rid = eng.add_request([3, 1, 4], max_new_tokens=12)
            else:
                rid = eng.add_request([3, 1, 4], max_new_tokens=12,
                                      temperature=2.0, top_p=top_p)
            outs.append(eng.run_to_completion()[rid].output)
        assert outs[0] == outs[1], outs

    def test_top_p_validated(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   attn_impl='xla')
        with pytest.raises(ValueError, match='top_p'):
            eng.add_request([1, 2], max_new_tokens=2, top_p=0.0)
        with pytest.raises(ValueError, match='top_p'):
            eng.add_request([1, 2], max_new_tokens=2, top_p=1.5)

    def test_stop_sequence_trims_and_finishes(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   attn_impl='xla')
        rid = eng.add_request([3, 1, 4], max_new_tokens=12)
        full = eng.run_to_completion()[rid].output
        stop = full[2:4]                 # 2-token stop inside the output
        eng2 = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                    attn_impl='xla')
        rid = eng2.add_request([3, 1, 4], max_new_tokens=12, stop=[stop])
        req = eng2.run_to_completion()[rid]
        assert req.stop_hit
        assert req.output == full[:2], (req.output, full)

    def test_ttft_recorded(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   attn_impl='xla')
        rid = eng.add_request([1, 2, 3], max_new_tokens=2)
        done = eng.run_to_completion()
        assert done[rid].ttft_ms is not None
        assert done[rid].finish_time >= done[rid].first_token_time


class TestSampleTokens:
    """Unit tests of the shared sampling op (no model)."""

    def test_nucleus_restricts_support(self):
        from skypilot_tpu.inference.engine import sample_tokens
        # Row distribution: probs ~ [0.5, 0.25, 0.125, ...]; top_p=0.6
        # keeps {0, 1} (mass before token 1 is 0.5 < 0.6; before token
        # 2 it is 0.75 >= 0.6).
        logits = jnp.log(jnp.array([[0.5, 0.25, 0.125, 0.0625, 0.0625]],
                                   jnp.float32))
        temps = jnp.ones((1,), jnp.float32)
        topks = jnp.zeros((1,), jnp.int32)
        topps = jnp.full((1,), 0.6, jnp.float32)
        seen = set()
        for i in range(50):
            tok = sample_tokens(logits, jax.random.PRNGKey(i), temps,
                                topks, topps)
            seen.add(int(tok[0]))
        assert seen == {0, 1}, seen

    def test_top_p_one_keeps_full_support(self):
        from skypilot_tpu.inference.engine import sample_tokens
        logits = jnp.zeros((1, 4), jnp.float32)      # uniform
        temps = jnp.ones((1,), jnp.float32)
        topks = jnp.zeros((1,), jnp.int32)
        topps = jnp.ones((1,), jnp.float32)
        seen = {int(sample_tokens(logits, jax.random.PRNGKey(i), temps,
                                  topks, topps)[0]) for i in range(80)}
        assert seen == {0, 1, 2, 3}, seen

    def test_composes_with_top_k(self):
        from skypilot_tpu.inference.engine import sample_tokens
        # top_k=3 cuts tokens 3-4; top_p=0.75 over the renormalized
        # top-3 ([0.4, 0.33, 0.27]) keeps all three (mass before token
        # 2 is 0.73 < 0.75). Distinct logits: ties at the k-th value
        # would all pass the threshold.
        logits = jnp.log(jnp.array(
            [[0.3, 0.25, 0.2, 0.15, 0.1]], jnp.float32))
        temps = jnp.ones((1,), jnp.float32)
        topks = jnp.full((1,), 3, jnp.int32)
        topps = jnp.full((1,), 0.75, jnp.float32)
        seen = {int(sample_tokens(logits, jax.random.PRNGKey(i), temps,
                                  topks, topps)[0]) for i in range(60)}
        assert seen <= {0, 1, 2}, seen


class TestInt8Quantization:
    """Weight-only int8 serving: halved weight stream, bounded logits
    error, engine path end to end."""

    def test_quantized_forward_close(self):
        import numpy as np
        from skypilot_tpu.models import configs, llama, quantization
        cfg = configs.TINY
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        qparams = quantization.quantize_params(params)
        toks = jnp.arange(32).reshape(1, 32) % cfg.vocab_size
        ref, _ = llama.forward(params, toks, cfg)
        got, _ = llama.forward(qparams, toks, cfg)
        ref = np.asarray(ref, np.float32)
        got = np.asarray(got, np.float32)
        # int8 per-channel: logits track closely but not exactly.
        assert np.abs(ref - got).max() < 0.35, np.abs(ref - got).max()
        # argmax (greedy decode) largely agrees
        agree = (ref.argmax(-1) == got.argmax(-1)).mean()
        assert agree > 0.9, agree

    def test_quantized_bytes_halved(self):
        from skypilot_tpu.models import configs, llama, quantization
        cfg = configs.TINY
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        full = quantization.quantized_bytes(params)
        q = quantization.quantized_bytes(
            quantization.quantize_params(params))
        assert q < 0.7 * full, (q, full)

    def test_engine_generates_int8(self):
        from skypilot_tpu.inference.paged import PagedInferenceEngine
        from skypilot_tpu.models import configs
        eng = PagedInferenceEngine(configs.TINY, max_batch=2, max_seq=64,
                                   quantize='int8')
        rid = eng.add_request([1, 2, 3], max_new_tokens=8)
        done = eng.run_to_completion(horizon=8)
        assert len(done[rid].output) == 8

    def test_int8_kv_cache_outputs_close_to_bf16(self):
        """Same prompts, bf16 vs int8(weights+KV): outputs stay close
        (greedy tokens mostly agree on a random tiny model)."""
        from skypilot_tpu.inference.paged import PagedInferenceEngine
        from skypilot_tpu.models import configs
        outs = {}
        for mode in (None, 'int8'):
            eng = PagedInferenceEngine(configs.TINY, max_batch=2, max_seq=64,
                                       quantize=mode)
            assert eng.cache.quantized == (mode == 'int8')
            rid = eng.add_request(list(range(1, 12)), max_new_tokens=6)
            done = eng.run_to_completion(horizon=4)
            outs[mode] = done[rid].output
        assert len(outs['int8']) == 6
        agree = sum(a == b for a, b in zip(outs[None], outs['int8']))
        assert agree >= 3, outs


class TestShardedInt8:
    """int8 quantization combined with a device mesh — the production
    serving shape (7B-class, tp-sharded, quantized; VERDICT r3 task 2;
    ref anchor: vLLM --tensor-parallel-size recipes,
    llm/llama-3/llama3.yaml:109)."""

    def _mesh(self, tp):
        from skypilot_tpu.parallel import mesh as mesh_lib
        spec = mesh_lib.MeshSpec(dp=1, fsdp=1, sp=1, tp=tp)
        return mesh_lib.make_mesh(
            spec, devices=jax.devices()[:spec.num_devices])

    def test_int8_tp2_and_single_device_int8(self, engine_setup):
        """Sharded and unsharded int8 serving each emit the choices of
        the plain forward of the int8 tree."""
        from skypilot_tpu.models import quantization
        cfg, params = engine_setup
        qparams = quantization.quantize_params(params)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        for mesh in (None, self._mesh(2)):
            eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                       mesh=mesh, quantize='int8',
                                       attn_impl='xla')
            rid = eng.add_request(prompt, max_new_tokens=8)
            out = eng.run_to_completion(horizon=4)[rid].output
            assert len(out) == 8
            greedy_oracle.assert_agrees(
                cfg, qparams, prompt, out, 'int8_kv',
                'single' if mesh is None else 'tp2')

    def test_int8_scales_shard_with_parents(self, engine_setup):
        """Quantized leaves + scales get mesh shardings; scale unit dims
        replicate while output-channel dims follow the parent."""
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=64,
                                   mesh=self._mesh(2), quantize='int8',
                                   attn_impl='xla')
        wq = eng.params['layers']['wq']
        # int8 codes: heads dim (axis 2) sharded over tp=2
        spec = wq.int8.sharding.spec
        assert 'tp' in str(spec), spec
        # scale has the contracted dim as size 1 and still lands on the
        # mesh without error
        assert wq.scale.shape[1] == 1
        # int8 KV pool sharded too: kv_heads dim rides tp
        assert eng.cache.quantized
        assert 'tp' in str(eng.cache.pool_k.sharding.spec), \
            eng.cache.pool_k.sharding.spec

    def test_quantize_logical_axes_structure(self):
        """Axes tree after quantization matches the quantized params
        tree structure exactly (tree_map compatibility)."""
        from skypilot_tpu.models import configs, llama, quantization
        cfg = configs.TINY
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        qparams = quantization.quantize_params(params)
        qaxes = quantization.quantize_logical_axes(
            llama.param_logical_axes(cfg))
        is_leaf = lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)
        # Must not raise: structures line up leaf-for-leaf.
        jax.tree.map(lambda a, p: None, qaxes, qparams, is_leaf=is_leaf)


class TestCancel:
    """Engine-side request cancellation (dropped streaming clients must
    release their decode slot — ADVICE r3 serve/server.py finding)."""

    def test_cancel_queued(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   attn_impl='xla')
        r1 = eng.add_request([1, 2, 3], max_new_tokens=4)
        r2 = eng.add_request([4, 5, 6], max_new_tokens=4)
        assert eng.cancel(r2)
        done = eng.run_to_completion()
        assert r1 in done and r2 not in done

    def test_cancel_active_frees_slot(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   attn_impl='xla')
        rid = eng.add_request([1, 2, 3], max_new_tokens=64)
        eng.step(horizon=2)          # admit + some decode
        assert eng.num_active == 1
        assert eng.cancel(rid)
        assert eng.num_active == 0
        assert not eng.has_work()
        assert eng.get_finished(rid) is None   # aborted, not served
        # engine still serves new work afterwards
        r2 = eng.add_request([7, 8], max_new_tokens=3)
        done = eng.run_to_completion()
        assert len(done[r2].output) == 3

    def test_cancel_finished_noop(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=128,
                                   attn_impl='xla')
        rid = eng.add_request([1, 2], max_new_tokens=2)
        eng.run_to_completion()
        assert not eng.cancel(rid)
        assert eng.get_finished(rid) is not None


class TestAsyncPipeline:
    """The async dispatch pipeline (engine._pending): decode calls are
    enqueued with device-resident tokens/cache and their results read
    back up to _PIPELINE_DEPTH calls later. These tests pin the
    invariants the lag must preserve."""

    def test_results_lag_but_complete(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=64)
        rid = eng.add_request([1, 2, 3], max_new_tokens=6)
        all_events = []
        for _ in range(30):
            all_events.extend(eng.step(horizon=2))
            if eng.get_finished(rid):
                break
        assert eng.get_finished(rid) is not None
        toks = [t for r, t, _ in all_events if r == rid]
        assert toks == eng.get_finished(rid).output

    def test_lagged_equals_reference(self, engine_setup):
        """Tokens produced through the pipeline are the no-cache
        reference's choices — the device token chaining (call N+1 fed
        call N's last column without a host trip) must not skew the
        sequence."""
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=64)
        prompt = [5, 9, 2, 14]
        rid = eng.add_request(prompt, max_new_tokens=8)
        done = eng.run_to_completion(horizon=4)
        assert len(done[rid].output) == 8
        greedy_oracle.assert_agrees(cfg, params, prompt, done[rid].output)

    def test_inflight_bookkeeping_drains(self, engine_setup):
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=64)
        for _ in range(4):
            eng.add_request([1, 2, 3], max_new_tokens=5)
        eng.run_to_completion(horizon=4)
        assert not any(eng._slot_inflight)
        assert not eng._pending
        assert eng.num_active == 0

    def test_cancel_mid_flight_discards_tokens(self, engine_setup):
        """Cancel between enqueue and processing: the in-flight call's
        tokens for that request must be dropped, and the slot reusable."""
        cfg, params = engine_setup
        eng = PagedInferenceEngine(cfg, params, max_batch=1, max_seq=64)
        rid = eng.add_request([1, 2, 3], max_new_tokens=30)
        eng.step(horizon=2)          # admit (prefill enqueued)
        eng.step(horizon=2)          # decode enqueued
        assert eng.cancel(rid)
        n_before = len(eng.get_finished(rid).output) \
            if eng.get_finished(rid) else 0
        assert n_before == 0         # cancelled, not finished
        rid2 = eng.add_request([4, 5], max_new_tokens=3)
        done = eng.run_to_completion(horizon=4)
        assert rid2 in done
        assert len(done[rid2].output) == 3
        assert rid not in done


class TestW8A8Prefill:
    """Opt-in int8-activation prefill (quantization.w8a8_region):
    int8 x int8 MXU dots on the compute-bound prefill, decode W8A16."""

    def test_qeinsum_w8a8_close_to_exact(self):
        from skypilot_tpu.models import quantization as q
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (4, 8, 64), jnp.bfloat16)
        w = q._quantize_array(
            jax.random.normal(jax.random.PRNGKey(1), (64, 96),
                              jnp.bfloat16), (0,))
        exact = q.qeinsum('bsd,df->bsf', x, w, out_dtype=jnp.float32)
        with q.w8a8_region():
            approx = q.qeinsum('bsd,df->bsf', x, w,
                               out_dtype=jnp.float32)
        # per-row int8 activations: ~0.5-1% relative error on a
        # 64-deep dot of unit-scale gaussians
        err = jnp.abs(approx - exact)
        rel = float(jnp.max(err) / (jnp.max(jnp.abs(exact)) + 1e-6))
        assert rel < 0.05, rel

    def test_engine_generates_with_w8a8_prefill(self, engine_setup):
        cfg, params = engine_setup
        from skypilot_tpu.models import quantization
        qparams = quantization.quantize_params(params)
        eng = PagedInferenceEngine(cfg, qparams, max_batch=2, max_seq=64,
                                   prefill_w8a8=True)
        rid = eng.add_request([3, 1, 4, 1, 5], max_new_tokens=6)
        done = eng.run_to_completion(horizon=4)
        assert len(done[rid].output) == 6
        # Decode is untouched: a second engine without w8a8 but the
        # same prefilled first token should continue identically given
        # the same cache content modulo prefill activation noise — we
        # only assert generation is well-formed (ids in vocab).
        assert all(0 <= t < cfg.vocab_size for t in done[rid].output)

    def test_region_is_trace_time_scoped(self):
        from skypilot_tpu.models import quantization as q
        assert not getattr(q._a8_region, 'active', False)
        with q.w8a8_region():
            assert q._a8_region.active
        assert not q._a8_region.active
