"""Multi-chip tensor-parallel serving (the production (tp, dp) path).

Runs on the virtual CPU mesh tests/conftest.py forces (8 devices); the
``tp_devices`` fixture skips LOUDLY if that override was defeated.
Covers the round-8 contract:

- tp=2 (and tp=2 x dp=2, and int8 KV) greedy decode emits the plain
  forward's choices, as tp=1 does (a sharded program sums partial
  products in another order than the unsharded one, so each is held to
  ``greedy_oracle``, not to the other),
- sharded prefix-cache hit reuse,
- pool-pressure preemption/resume under tp,
- per-shard pool/byte accounting + the placement policy,
- scheduler work-token scaling with mesh shape,
- an e2e model-server boot with --tp 2 serving a streamed completion
  with the mesh reported through /metrics.
"""
import json
import urllib.request

import jax
import pytest

import greedy_oracle
from skypilot_tpu.inference.engine import kv_shard_degree, kv_token_bytes
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs
from skypilot_tpu.models import llama
from skypilot_tpu.parallel import mesh as mesh_lib

PROMPTS = ([1, 2, 3] * 9, [4, 5] * 10, [7] * 21)


@pytest.fixture(scope='module')
def setup():
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run(cfg, params, *, gen=8, prompts=PROMPTS, **kw):
    eng = PagedInferenceEngine(cfg, params, max_batch=4, max_seq=128,
                               prefill_chunk_tokens=16, attn_impl='xla',
                               **kw)
    return greedy_oracle.greedy(eng, prompts, gen, horizon=8), eng


def _assert_agree(cfg, params, outs, what, kind='bf16', gen=8,
                  prompts=PROMPTS):
    greedy_oracle.assert_all_agree(cfg, params, prompts, outs, what, kind,
                                   gen)


# ---------------------------------------------------------- mesh helpers
def test_serving_mesh_shapes(tp_devices):
    assert mesh_lib.serving_mesh(1, 1) is None     # meshless fast path
    m = mesh_lib.serving_mesh(tp=2)
    assert mesh_lib.mesh_axis_sizes(m)['tp'] == 2
    assert mesh_lib.mesh_axis_sizes(None) == {
        a: 1 for a in mesh_lib.MESH_AXES}
    with pytest.raises(ValueError):
        mesh_lib.serving_mesh(tp=1024)


def test_sharded_cache_is_born_sharded(tp_devices, setup, monkeypatch):
    """The tp=4 server's first run on four real chips died here: the
    pool is sized per device, and it was built whole on the first device
    before being resharded. Its zeros must be made inside a program with
    out_shardings (traced), never eagerly — a virtual CPU device has no
    memory limit to say so."""
    from skypilot_tpu.inference.paged import PagedKVCache as cls
    traced = []
    create = cls.create.__func__

    def watched(klass, *args, **kwargs):
        cache = create(klass, *args, **kwargs)
        traced.append(isinstance(cache[0], jax.core.Tracer))
        return cache

    monkeypatch.setattr(cls, 'create', classmethod(watched))
    cfg, params = setup
    eng = PagedInferenceEngine(cfg, params, max_batch=4, max_seq=128,
                               mesh=mesh_lib.serving_mesh(tp=2))
    assert traced and all(traced), traced
    assert len(eng.cache[0].sharding.device_set) == 2


def test_serving_spec_from_env(monkeypatch):
    monkeypatch.setenv('SKYTPU_TP', '2')
    monkeypatch.setenv('SKYTPU_DP', '3')
    spec = mesh_lib.serving_spec_from_env()
    assert (spec.tp, spec.dp) == (2, 3)
    # Explicit args beat the env (the --tp/--dp contract).
    spec = mesh_lib.serving_spec_from_env(tp=4, dp=1)
    assert (spec.tp, spec.dp) == (4, 1)


def test_axis_shard_degree_divisibility(tp_devices):
    m = mesh_lib.serving_mesh(tp=2)
    assert mesh_lib.axis_shard_degree(m, 'tp', 4) == 2
    # MQA-style: tp does not divide the dim -> replicated, degree 1.
    assert mesh_lib.axis_shard_degree(m, 'tp', 3) == 1
    assert mesh_lib.axis_shard_degree(None, 'tp', 4) == 1


# ------------------------------------------- sharded decode vs the oracle
def test_tp2_greedy_agrees_with_oracle(setup, tp_devices):
    """The acceptance bar: tp=2 greedy decode emits the reference's
    choices, exactly as tp=1 is held to."""
    cfg, params = setup
    for what, kw in (('tp=1', {}),
                     ('tp=2', {'mesh': mesh_lib.serving_mesh(tp=2)})):
        outs, _ = _run(cfg, params, **kw)
        _assert_agree(cfg, params, outs, what)


def test_tp2_dp2_paged_agrees_with_oracle(setup, tp_devices):
    cfg, params = setup
    if jax.device_count() < 4:
        pytest.skip('needs 4 devices for (tp=2, dp=2)')
    out, _ = _run(cfg, params, mesh=mesh_lib.serving_mesh(tp=2, dp=2))
    _assert_agree(cfg, params, out, 'tp=2 x dp=2')


def test_tp2_int8_kv_agrees_with_oracle(setup, tp_devices):
    cfg, params = setup
    out, eng = _run(cfg, params, kv_cache_dtype='int8',
                    mesh=mesh_lib.serving_mesh(tp=2))
    assert eng.cache.quantized
    _assert_agree(cfg, params, out, 'tp=2, int8 KV', 'int8_kv')


# ------------------------------------------------------- prefix caching
def test_sharded_prefix_cache_hit_reuse(setup, tp_devices):
    """A second request sharing full pages must hit the prefix index
    under tp — no recompute of the shared pages, tail-only prefill —
    and still decode correctly on the head-sharded pool."""
    cfg, params = setup
    mesh = mesh_lib.serving_mesh(tp=2)
    eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=96,
                               chunk=16, attn_impl='xla', mesh=mesh)
    shared = list(range(1, 3 * eng.page + 1))      # 3 full pages
    r1 = eng.add_request(shared + [40], max_new_tokens=4)
    eng.run_to_completion(horizon=4)
    chunks_before = eng.chunks_prefilled
    r2 = eng.add_request(shared + [41], max_new_tokens=4)
    done = eng.run_to_completion(horizon=4)
    assert eng.alloc.prefix_hits >= 1
    assert eng.chunks_prefilled - chunks_before <= 1
    assert len(done[r2].output) == 4
    del r1


# ---------------------------------------------------- preemption under tp
def test_preemption_resume_under_tp(setup, tp_devices):
    """Pool pressure on the SHARDED pool: the newest request preempts,
    re-registers its written pages, and resumes; what both requests
    emit is still the reference's choice (the recompute is a prefill
    where an uninterrupted run decoded: another program)."""
    cfg, params = setup
    mesh = mesh_lib.serving_mesh(tp=2)
    prompt = list(range(1, 30))
    eng = PagedInferenceEngine(cfg, params, max_batch=2, max_seq=256,
                               page_size=8, n_pages=12,
                               attn_impl='xla', mesh=mesh)
    outs = greedy_oracle.greedy(eng, [prompt, prompt], 24)
    assert eng.preemptions >= 1
    _assert_agree(cfg, params, outs, 'preempted under tp=2', gen=24,
                  prompts=[prompt, prompt])


# ------------------------------------------------- per-shard accounting
def test_kv_token_bytes_per_shard(setup, tp_devices):
    cfg, _ = setup
    mesh = mesh_lib.serving_mesh(tp=2)
    assert kv_shard_degree(cfg, mesh) == 2         # TINY: 4 kv heads
    assert kv_token_bytes(cfg, False, mesh=mesh) == \
        kv_token_bytes(cfg, False) // 2
    # dp replicates: no per-shard credit beyond tp.
    if jax.device_count() >= 4:
        mesh_dp = mesh_lib.serving_mesh(tp=2, dp=2)
        assert kv_token_bytes(cfg, False, mesh=mesh_dp) == \
            kv_token_bytes(cfg, False) // 2


def test_pool_stats_per_shard_under_tp(setup, tp_devices):
    """Token capacities stay GLOBAL (a token is a token at any mesh
    shape); byte views halve per shard under tp=2."""
    cfg, params = setup
    mesh = mesh_lib.serving_mesh(tp=2)
    _, single = _run(cfg, params, gen=2, prompts=([1, 2, 3],))
    _, sharded = _run(cfg, params, gen=2, prompts=([1, 2, 3],), mesh=mesh)
    s1, s2 = single.kv_pool_stats(), sharded.kv_pool_stats()
    assert s2['pool_token_capacity'] == s1['pool_token_capacity']
    assert s2['kv_token_bytes'] == s1['kv_token_bytes']
    assert s2['kv_token_bytes_per_shard'] == s1['kv_token_bytes'] // 2
    assert s2['kv_shards'] == 2
    assert single.mesh_axes()['tp'] == 1
    assert sharded.mesh_axes()['tp'] == 2


# ----------------------------------------------------- placement policy
def test_adaptive_tp_placement_policy():
    from skypilot_tpu.serve import placement
    gb = int(1e9)
    # Fits one chip: latency tier still maxes tp for TPOT; throughput
    # tier spends the chips on dp replicas instead.
    lat = placement.choose_parallelism(7 * gb, 4, slo_tier='latency')
    assert (lat.tp, lat.dp) == (4, 1)
    thr = placement.choose_parallelism(7 * gb, 4,
                                       slo_tier='throughput')
    assert (thr.tp, thr.dp) == (1, 4)
    # 26 GB of weights (13B bf16) on 16 GB chips: min tp=4 even for
    # the throughput tier; the rest goes dp.
    big = placement.choose_parallelism(26 * gb, 8,
                                       slo_tier='throughput')
    assert (big.tp, big.dp) == (4, 2)
    with pytest.raises(ValueError):
        placement.choose_parallelism(26 * gb, 1)
    plan = placement.plan_for_model('llama3-8b', 4,
                                    slo_tier='throughput')
    assert plan.tp * plan.dp == 4
    assert plan.as_env() == {'SKYTPU_TP': str(plan.tp),
                             'SKYTPU_DP': str(plan.dp)}


def test_plan_for_spec_modes():
    from skypilot_tpu.serve import placement
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    fixed = SkyServiceSpec(readiness_path='/readiness',
                           parallelism_policy='fixed', tp=2, dp=3)
    p = placement.plan_for_spec(fixed)
    assert (p.tp, p.dp) == (2, 3)
    bare = SkyServiceSpec(readiness_path='/readiness')
    assert placement.plan_for_spec(bare).chips == 1
    adaptive = SkyServiceSpec(readiness_path='/readiness',
                              chips_per_replica=4,
                              parallelism_model='llama3-1b',
                              slo_tier='latency')
    p = placement.plan_for_spec(adaptive)
    assert (p.tp, p.dp) == (4, 1)


def test_service_spec_parallelism_yaml():
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec.from_yaml_config({
        'readiness_probe': '/readiness',
        'parallelism': {'policy': 'adaptive', 'chips_per_replica': 2,
                        'slo_tier': 'throughput',
                        'model': 'llama3-1b'},
    })
    assert spec.chips_per_replica == 2
    assert spec.slo_tier == 'throughput'
    assert spec.parallelism_model == 'llama3-1b'


# ------------------------------------------------- scheduler mesh scaling
def test_scheduler_work_token_scaling(setup, tp_devices):
    """The cold-meter Retry-After fallback scales with the mesh's
    tp x dp: a sharded replica chews the same work tokens faster, so
    the quoted backoff must shrink accordingly."""
    import threading

    from skypilot_tpu.serve import scheduler as scheduler_lib

    class FakeEngine:
        max_batch = 8

        def __init__(self, axes):
            self._axes = axes

        def mesh_axes(self):
            return self._axes

        def kv_pool_stats(self):
            return {'pool_token_capacity': 1024}

        def remaining_work_tokens(self):
            return 0

    def retry_for(axes):
        sched = scheduler_lib.RequestScheduler(threading.Lock())
        sched.bind_engine(FakeEngine(axes))
        # Small enough to stay inside the [1, 120] s clamp at tp=1:
        # 4000 tokens / (8 tok/s x 8 slots) = 62.5 s.
        return sched.retry_after_s('latency', work=4000)

    single = retry_for({'tp': 1, 'dp': 1})
    tp2 = retry_for({'tp': 2, 'dp': 1})
    tp2dp2 = retry_for({'tp': 2, 'dp': 2})
    assert tp2 < single
    assert tp2dp2 < tp2
    assert tp2 <= single // 2 + 1
    # The factor is surfaced for operators.
    sched = scheduler_lib.RequestScheduler(threading.Lock())
    sched.bind_engine(FakeEngine({'tp': 2, 'dp': 2}))
    assert sched.mesh_speedup == 4
    assert sched.json_stats()['mesh_speedup'] == 4


# ------------------------------------------------------------ e2e server
def test_e2e_server_tp2_streamed_completion(tp_devices):
    """Boot the model server with --tp 2 (the ModelServer tp knob),
    stream a completion, and read the mesh shape back through BOTH
    /metrics formats — the whole multi-chip serving path end to end."""
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.utils import common_utils
    port = common_utils.find_free_port(19500)
    server = ModelServer('tiny', max_batch=2, max_seq=64, port=port,
                         tp=2)
    server.start(block=False)
    try:
        assert server._ready.wait(180)
        assert server.engine.mesh is not None
        assert server.engine.mesh_axes()['tp'] == 2
        body = json.dumps({'prompt': [1, 2, 3], 'max_new_tokens': 6,
                           'stream': True}).encode()
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate', body,
            {'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert 'text/event-stream' in r.headers.get(
                'Content-Type', '')
            events = [json.loads(ln[5:]) for ln in r
                      if ln.startswith(b'data:')]
        tokens = [e['token'] for e in events if 'token' in e]
        assert len(tokens) == 6
        assert events[-1].get('done') is True
        # The reference's choices through the server too.
        greedy_oracle.assert_server_agrees([1, 2, 3], tokens,
                                           'tp=2 server')
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/metrics?format=json',
                timeout=10) as r:
            payload = json.loads(r.read())
        assert payload['mesh']['tp'] == 2
        assert payload['mesh']['devices'] == 2
        assert payload['sched']['mesh_speedup'] == 2
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/metrics', timeout=10) as r:
            prom = r.read().decode()
        assert 'skytpu_mesh_shape{axis="tp"} 2' in prom
    finally:
        server.stop()
