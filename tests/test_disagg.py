"""Disaggregated prefill/decode serving (round 9): KV wire codec,
engine export/ingest, phase-aware routing, handoff e2e.

The contracts under test:

- **Byte identity.** A request prefilled on one engine/server and
  handed off to another continues greedy decode BYTE-IDENTICALLY to a
  colocated run — the KV rows land at the exact original bytes
  (int8 codes + scales never dequantize on the wire).
- **Loud rejection.** Malformed, truncated, or mismatched handoffs are
  refused with ``ValueError``/HTTP 400 (and counted) before anything
  touches the pool; capacity refusals are retryable (503).
- **Zero lost requests.** A decode worker dying mid-continuation
  surfaces a retryable error with the generated prefix; the LB's
  in-flight recovery resubmits prompt+prefix and the client still sees
  one complete, byte-identical stream (extends the round-7 chaos
  harness).
"""
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import greedy_oracle
from skypilot_tpu import telemetry
from skypilot_tpu.inference import kv_transfer
from skypilot_tpu.serve import disagg as disagg_lib
from skypilot_tpu.serve import faults as faults_lib
from skypilot_tpu.utils import common_utils

jax.config.update('jax_platforms', 'cpu')


# ---------------------------------------------------------------- helpers
def _make_engine(kv_cache_dtype, max_batch=2, max_seq=128):
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    return PagedInferenceEngine(configs.get_config('tiny'),
                                max_batch=max_batch, max_seq=max_seq,
                                kv_cache_dtype=kv_cache_dtype)


def _run_to_first_token(engine, rid):
    """Step until ``rid``'s first token event surfaces; returns it."""
    deadline = time.time() + 120
    while time.time() < deadline:
        for r, tok, _fin in engine.step(horizon=2):
            if r == rid:
                return tok
    raise TimeoutError('no first token')


def _fake_snapshot(kv_cache_dtype='int8', n_layers=2, n_kv=2, d=4,
                   prompt=(1, 2, 3, 4, 5), output=(7,), **over):
    """A structurally valid snapshot with deterministic contents."""
    n_rows = len(prompt) + len(output) - 1
    rng = np.random.default_rng(0)
    snap = {
        'kv_cache_dtype': kv_cache_dtype,
        'n_rows': n_rows,
        'model': {'n_layers': n_layers, 'n_kv_heads': n_kv,
                  'head_dim': d},
        'prompt': list(prompt), 'output': list(output),
        'max_new_tokens': 16, 'temperature': 0.0, 'top_k': 0,
        'top_p': 1.0, 'eos_id': None, 'stop': None, 'priority': 0,
    }
    shape = (n_layers, n_rows, n_kv, d)
    if kv_cache_dtype == 'int4':
        cshape = shape[:-1] + (d // 2,)
        snap['k'] = rng.integers(0, 256, cshape).astype(np.uint8)
        snap['v'] = rng.integers(0, 256, cshape).astype(np.uint8)
        snap['k_scale'] = rng.random(shape[:3]).astype(np.float32)
        snap['v_scale'] = rng.random(shape[:3]).astype(np.float32)
    elif kv_cache_dtype == 'int8':
        snap['k'] = rng.integers(-127, 128, shape).astype(np.int8)
        snap['v'] = rng.integers(-127, 128, shape).astype(np.int8)
        snap['k_scale'] = rng.random(shape[:3]).astype(np.float32)
        snap['v_scale'] = rng.random(shape[:3]).astype(np.float32)
    else:
        import ml_dtypes
        snap['k'] = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)
        snap['v'] = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)
        snap['k_scale'] = snap['v_scale'] = None
    snap.update(over)
    return snap


# ------------------------------------------------------------ wire codec
@pytest.mark.parametrize('dtype', ['int8', 'bf16', 'int4'])
def test_wire_roundtrip_exact(dtype):
    snap = _fake_snapshot(dtype)
    blob = kv_transfer.encode_handoff(snap)
    out = kv_transfer.decode_handoff(blob)
    assert out['kv_cache_dtype'] == dtype
    assert out['prompt'] == snap['prompt']
    assert out['output'] == snap['output']
    assert out['n_rows'] == snap['n_rows']
    # Codes/rows and scales round-trip EXACTLY (bit-for-bit) in their
    # stored dtype — no widening, no requantization, no unpacking
    # (int4 nibble rows stay packed uint8 at head_dim/2 on the wire).
    assert out['k'].dtype == snap['k'].dtype
    assert out['k'].tobytes() == snap['k'].tobytes()
    assert out['v'].tobytes() == snap['v'].tobytes()
    if dtype in ('int8', 'int4'):
        assert out['k'].dtype == (np.uint8 if dtype == 'int4'
                                  else np.int8)
        assert out['k_scale'].dtype == np.float32
        assert out['k_scale'].tobytes() == snap['k_scale'].tobytes()
        assert out['v_scale'].tobytes() == snap['v_scale'].tobytes()
    else:
        assert out['k'].dtype.name == 'bfloat16'


def test_wire_int8_half_the_bytes_of_bf16():
    """The economics of the handoff: int8 codes are half the bf16
    rows; even with fp32 scales the int8 blob must be well under the
    bf16 one at realistic head dims."""
    int8 = len(kv_transfer.encode_handoff(_fake_snapshot(
        'int8', d=128, prompt=tuple(range(1, 40)))))
    bf16 = len(kv_transfer.encode_handoff(_fake_snapshot(
        'bf16', d=128, prompt=tuple(range(1, 40)))))
    assert int8 < 0.6 * bf16, (int8, bf16)


def test_wire_malformed_rejected():
    snap = _fake_snapshot('int8')
    blob = kv_transfer.encode_handoff(snap)
    with pytest.raises(ValueError, match='bad magic'):
        kv_transfer.decode_handoff(b'XXXX' + blob[4:])
    with pytest.raises(ValueError, match='truncated'):
        kv_transfer.decode_handoff(blob[:len(blob) // 2])
    with pytest.raises(ValueError, match='trailing'):
        kv_transfer.decode_handoff(blob + b'junk')
    with pytest.raises(ValueError, match='short blob'):
        kv_transfer.decode_handoff(b'SK')
    # Header lies about n_rows vs the actual token counts.
    bad = _fake_snapshot('int8')
    bad['n_rows'] = 3
    with pytest.raises(ValueError, match='n_rows'):
        kv_transfer.encode_decode = None  # noqa: avoid accidental reuse
        kv_transfer.decode_handoff(kv_transfer.encode_handoff(bad))
    # No generated token at all.
    with pytest.raises(ValueError, match='at least the first'):
        kv_transfer.decode_handoff(kv_transfer.encode_handoff(
            _fake_snapshot('int8', output=())))


# --------------------------------------------- allocator prefix guard
def test_register_prefix_validates_page_count():
    from skypilot_tpu.inference.paged import PageAllocator
    alloc = PageAllocator(n_pages=8, page_size=4)
    pages = [alloc.alloc() for _ in range(2)]
    ctx = list(range(13))          # 3 full pages of 4 — needs 3 pages
    with pytest.raises(ValueError, match='cannot cover'):
        alloc.register_prefix(ctx, pages, 0)
    # Nothing was content-addressed by the failed call.
    assert not alloc.by_hash and not alloc.page_hash
    # A covering page list registers fine.
    pages.append(alloc.alloc())
    alloc.register_prefix(ctx, pages, 0)
    assert len(alloc.by_hash) == 3


# ------------------------------------------------ engine export/ingest
@pytest.mark.parametrize('dtype', ['int8', 'bf16', 'int4'])
def test_handoff_byte_identical_to_colocated(dtype):
    """THE disaggregation contract: export after the first token, wire
    round-trip, ingest into a second engine — the greedy continuation
    is byte-identical to an uninterrupted colocated run."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6] * 4        # > 1 page, uneven tail
    ref_eng = _make_engine(dtype)
    rid = ref_eng.add_request(list(prompt), max_new_tokens=20)
    reference = ref_eng.run_to_completion(horizon=4)[rid].output

    src = _make_engine(dtype)
    rid = src.add_request(list(prompt), max_new_tokens=20, hold=True)
    first = _run_to_first_token(src, rid)
    snap, _events = src.export_kv_snapshot(rid)
    assert snap is not None
    # Held request: exactly the prefill-sampled first token, no local
    # decode-ahead racing the handoff.
    assert snap['output'] == [first] == reference[:1]
    assert src.cancel(rid)
    snap = kv_transfer.decode_handoff(kv_transfer.encode_handoff(snap))

    dst = _make_engine(dtype)
    rid2 = dst.ingest_kv_snapshot(snap)
    out = dst.run_to_completion(horizon=4)[rid2].output
    assert out == reference, dtype


def test_ingest_no_free_slot_is_retryable():
    eng = _make_engine('int8', max_batch=1)
    eng.add_request([1, 2, 3, 4], max_new_tokens=30)
    for _ in range(2):
        eng.step(horizon=1)                     # occupy the only slot
    with pytest.raises(kv_transfer.HandoffCapacityError):
        eng.ingest_kv_snapshot(_fake_snapshot(
            'int8', n_layers=eng.cfg.n_layers,
            n_kv=eng.cfg.n_kv_heads, d=eng.cfg.head_dim))


def test_ingest_rejects_mismatches():
    eng = _make_engine('int8')
    good = dict(_fake_snapshot('int8', n_layers=eng.cfg.n_layers,
                               n_kv=eng.cfg.n_kv_heads,
                               d=eng.cfg.head_dim))
    # Wrong KV dtype: int8 pools never transcode bf16 handoffs.
    bad = dict(good, kv_cache_dtype='bf16')
    with pytest.raises(ValueError, match='kv_cache_dtype'):
        eng.ingest_kv_snapshot(bad)
    # Wrong model shape.
    bad = dict(good, model=dict(good['model'], n_layers=99))
    with pytest.raises(ValueError, match='n_layers'):
        eng.ingest_kv_snapshot(bad)
    # Truncated row batch: n_rows consistent with prompt/output but
    # the arrays are short.
    bad = dict(good, k=good['k'][:, :2])
    with pytest.raises(ValueError, match='rows shape'):
        eng.ingest_kv_snapshot(bad)
    # Already-complete request.
    bad = dict(good, max_new_tokens=1)
    with pytest.raises(ValueError, match='complete'):
        eng.ingest_kv_snapshot(bad)
    # A clean snapshot still lands after all the rejections.
    assert isinstance(eng.ingest_kv_snapshot(good), int)


def test_hold_blocks_decode_until_released():
    eng = _make_engine('bf16')
    rid = eng.add_request([5, 6, 7, 8] * 3, max_new_tokens=12,
                          hold=True)
    first = _run_to_first_token(eng, rid)
    # Held: stepping decodes nothing further.
    for _ in range(6):
        events = eng.step(horizon=4)
        assert [e for e in events if e[0] == rid] == []
    assert not eng.has_runnable_work()
    req = next(r for r in eng._slots if r is not None)
    assert req.output == [first]
    assert eng.release_hold(rid)
    out = eng.run_to_completion(horizon=4)[rid].output
    assert len(out) == 12


def test_scheduler_adopt_routes_and_skips_ttft():
    import threading as th
    from skypilot_tpu.serve import scheduler as scheduler_lib
    lock = th.Lock()
    sched = scheduler_lib.RequestScheduler(lock)

    class _Eng:
        max_batch = 4

        def pop_finished(self, rid):
            return None
    sched._engine = _Eng()
    sr = sched.adopt(7, tier='latency', prompt=[1, 2], output=[3],
                     max_new_tokens=8)
    assert sr.handoff and sr.request_id == 7
    assert sched.inflight == 1
    sched.on_events(_Eng(), [(7, 11, False)])
    assert sr.outbox.get(timeout=5) == (11, False)
    # TTFT quantiles skip handoff continuations.
    before = sched._h_ttft['latency'].count
    sr.result = type('R', (), {'ttft_ms': 0.5,
                               'first_token_time': 1.0,
                               'finish_time': 2.0,
                               'output': [3, 11]})()
    sched._record_finished(sr)
    assert sched._h_ttft['latency'].count == before


# --------------------------------------------------- phase-aware policy
class _FakeReplica:
    """A /metrics?format=json stub with settable role/load/headroom."""

    def __init__(self, role, queue_tokens=0, kv_free=1000):
        import http.server as hs
        outer = self
        self.role, self.queue_tokens, self.kv_free = \
            role, queue_tokens, kv_free

        class H(hs.BaseHTTPRequestHandler):
            timeout = 10

            def log_message(self, *a):
                del a

            def do_GET(self):  # noqa: N802
                body = json.dumps({
                    'queue_tokens_total': outer.queue_tokens,
                    'kv_pool_tokens_free': outer.kv_free,
                    'mesh': {'tp': 1, 'dp': 1},
                    'disagg': {'role': outer.role},
                }).encode()
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.port = common_utils.find_free_port(19200)
        self.httpd = hs.ThreadingHTTPServer(('127.0.0.1', self.port), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return f'http://127.0.0.1:{self.port}'

    def stop(self):
        self.httpd.shutdown()


def test_phase_aware_policy_routing_and_handoff_target():
    from skypilot_tpu.serve import load_balancing_policies as lb_policies
    replicas = [_FakeReplica('prefill', queue_tokens=500),
                _FakeReplica('prefill', queue_tokens=100),
                _FakeReplica('decode', kv_free=50),
                _FakeReplica('decode', kv_free=5000),
                _FakeReplica('colocated', queue_tokens=0)]
    try:
        policy = lb_policies.make_policy('phase_aware')
        policy.set_ready_replicas([r.url for r in replicas])
        # New requests go to the prefill pool, least queued tokens
        # first — NOT to the idle colocated or decode replicas.
        assert policy.select_replica() == replicas[1].url
        # Handoff target: the decode worker with the most free KV.
        assert policy.handoff_target() == replicas[3].url
        # Excluding it falls to the next decode worker.
        assert policy.handoff_target(
            exclude={replicas[3].url}) == replicas[2].url
        # Prefill pool exhausted -> colocated fallback.
        assert policy.select_replica(
            exclude={replicas[0].url, replicas[1].url}) \
            == replicas[4].url
        # Everything else gone -> decode workers still answer.
        assert policy.select_replica(
            exclude={r.url for r in replicas[:2]} | {replicas[4].url}) \
            in (replicas[2].url, replicas[3].url)
    finally:
        for r in replicas:
            r.stop()


def test_phase_aware_planned_roles_fallback():
    """Cold probes (dead endpoints): the controller-planned roles
    still steer routing."""
    from skypilot_tpu.serve import load_balancing_policies as lb_policies
    policy = lb_policies.make_policy('phase_aware')
    urls = ['http://127.0.0.1:1', 'http://127.0.0.1:2',
            'http://127.0.0.1:3']
    policy.set_ready_replicas(urls)
    policy.set_replica_roles({urls[0]: 'decode', urls[1]: 'prefill',
                              urls[2]: 'colocated'})
    assert policy.select_replica() == urls[1]
    assert policy.handoff_target() == urls[0]


def test_role_resolution_and_spec():
    assert disagg_lib.resolve_role(None) == 'colocated'
    assert disagg_lib.resolve_role('prefill') == 'prefill'
    with pytest.raises(ValueError, match='unknown replica role'):
        disagg_lib.resolve_role('oracle')
    import os
    os.environ[disagg_lib.ROLE_ENV] = 'decode'
    try:
        assert disagg_lib.resolve_role(None) == 'decode'
    finally:
        del os.environ[disagg_lib.ROLE_ENV]

    from skypilot_tpu.serve import placement
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec.from_yaml_config({
        'readiness_probe': '/readiness',
        'replicas': 4,
        'load_balancing_policy': 'phase_aware',
        'disaggregation': {'prefill_replicas': 1,
                           'decode_replicas': 2},
    })
    assert spec.disagg_enabled
    assert spec.to_yaml_config()['disaggregation'] == {
        'prefill_replicas': 1, 'decode_replicas': 2}
    roles = []
    for _ in range(4):
        roles.append(placement.role_for_new_replica(spec, roles))
    assert roles == ['prefill', 'decode', 'decode', 'colocated']
    # A dead prefill worker's replacement re-fills the prefill pool.
    assert placement.role_for_new_replica(
        spec, ['decode', 'decode', 'colocated']) == 'prefill'
    # No block = everything colocated.
    plain = SkyServiceSpec.from_yaml_config(
        {'readiness_probe': '/readiness'})
    assert placement.role_for_new_replica(plain, []) == 'colocated'
    # One-sided pools are refused loudly.
    from skypilot_tpu import exceptions
    with pytest.raises(exceptions.InvalidServiceSpecError,
                       match='BOTH'):
        SkyServiceSpec.from_yaml_config({
            'readiness_probe': '/readiness',
            'disaggregation': {'prefill_replicas': 2}})


def test_handoff_fault_site_registered():
    assert 'handoff' in faults_lib.FAULT_SITES
    inj = faults_lib.FaultInjector({'rules': [
        {'kind': 'partial_response', 'site': 'handoff', 'at': 1}]})
    assert inj.fire('handoff').kind == 'partial_response'
    assert inj.fire('handoff') is None


# ----------------------------------------------------- jaxpr audit gate
def test_disagg_audit_preset():
    from skypilot_tpu.analysis import jaxpr_audit
    assert 'disagg' in jaxpr_audit.PRESETS
    assert 'disagg' in jaxpr_audit.DEFAULT_PRESETS
    report = jaxpr_audit.PRESETS['disagg']()
    assert report.ok(), report.format()
    # Phase isolation: the decode worker compiled ZERO prefill
    # programs across the whole audited run.
    key = 'decode-worker prefill programs (must stay 0)'
    assert report.compile_counts[key] == (0, 0)


# ------------------------------------------------------- server-level e2e
def _start_server(port, **kw):
    from skypilot_tpu.serve.server import ModelServer
    kw.setdefault('max_batch', 2)
    kw.setdefault('max_seq', 128)
    srv = ModelServer('tiny', port=port, **kw)
    srv.start(block=False)
    return srv


def _generate(base, payload, timeout=120, headers=None):
    h = {'Content-Type': 'application/json'}
    h.update(headers or {})
    req = urllib.request.Request(base + '/generate',
                                 json.dumps(payload).encode(), h)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _stream(base, payload, timeout=120, headers=None):
    h = {'Content-Type': 'application/json'}
    h.update(headers or {})
    req = urllib.request.Request(base + '/generate',
                                 json.dumps(payload).encode(), h)
    tokens, done, error = [], None, None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            if not raw.startswith(b'data:'):
                continue
            ev = json.loads(raw[5:].strip())
            if 'token' in ev:
                tokens.append(int(ev['token']))
            if ev.get('done'):
                done = ev
            if 'error' in ev:
                error = ev
    return tokens, done, error


def test_server_handoff_e2e_byte_identical():
    """prefill proc → decode proc over HTTP: streaming and
    non-streaming handoffs both land int8 KV on the wire and continue
    byte-identically to a colocated run; telemetry moves."""
    pd = common_utils.find_free_port(19300)
    pp = common_utils.find_free_port(pd + 1)
    dec = _start_server(pd, role='decode', kv_cache_dtype='int8')
    pre = _start_server(pp, role='prefill', kv_cache_dtype='int8',
                        handoff_targets=[f'http://127.0.0.1:{pd}'])
    try:
        assert dec._ready.wait(180) and pre._ready.wait(180)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6] * 3
        reference = _generate(f'http://127.0.0.1:{pd}',
                              {'prompt': prompt,
                               'max_new_tokens': 16})['tokens']
        reg = telemetry.get_registry()
        sent0 = reg.get('skytpu_disagg_handoff_total',
                        outcome='sent').value
        bytes0 = reg.get('skytpu_kv_transfer_bytes_total',
                         direction='export').value
        h_transfer = reg.histogram('skytpu_kv_transfer_seconds')
        t_count0 = h_transfer.count

        # Non-streaming: picked up via the static target list.
        res = _generate(f'http://127.0.0.1:{pp}',
                        {'prompt': prompt, 'max_new_tokens': 16})
        assert res['tokens'] == reference
        assert res['handoff'] is True

        # Streaming, explicit router header.
        tokens, done, error = _stream(
            f'http://127.0.0.1:{pp}',
            {'prompt': prompt, 'max_new_tokens': 16, 'stream': True},
            headers={'X-Handoff-Target': f'http://127.0.0.1:{pd}'})
        assert error is None
        assert tokens == reference
        assert done['tokens'] == reference
        assert done['finish_reason'] == 'length'

        assert reg.get('skytpu_disagg_handoff_total',
                       outcome='sent').value == sent0 + 2
        assert reg.get('skytpu_disagg_handoff_total',
                       outcome='completed').value >= 2
        moved = reg.get('skytpu_kv_transfer_bytes_total',
                        direction='export').value - bytes0
        assert moved > 0
        assert h_transfer.count >= t_count0 + 2
        # Prefill worker served only the first token locally per
        # request; the decode worker decoded the rest.
        with urllib.request.urlopen(
                f'http://127.0.0.1:{pp}/metrics?format=json',
                timeout=10) as r:
            m = json.loads(r.read())
        assert m['disagg']['role'] == 'prefill'
        assert m['disagg']['kv_transfer_bytes']['export'] > 0
    finally:
        dec.stop()
        pre.stop()


def test_server_handoff_fallback_local():
    """No decode worker (dead target / injected handoff failure): the
    prefill replica decodes locally — same tokens, nothing lost."""
    pp = common_utils.find_free_port(19350)
    pre = _start_server(
        pp, role='prefill',
        handoff_targets=['http://127.0.0.1:9'],     # nothing listening
        fault_spec=None)
    try:
        assert pre._ready.wait(180)
        prompt = [2, 7, 1, 8] * 4
        # Dead static target is never picked (headroom probe fails) →
        # no handoff attempted, local serving.
        res = _generate(f'http://127.0.0.1:{pp}',
                        {'prompt': prompt, 'max_new_tokens': 10})
        assert len(res['tokens']) == 10
        assert 'handoff' not in res
        # Explicit header to a dead target: handoff POST fails →
        # colocated fallback, same output.
        res2 = _generate(
            f'http://127.0.0.1:{pp}',
            {'prompt': prompt, 'max_new_tokens': 10},
            headers={'X-Handoff-Target': 'http://127.0.0.1:9'})
        assert res2['tokens'] == res['tokens']
        reg = telemetry.get_registry()
        assert reg.get('skytpu_disagg_handoff_total',
                       outcome='failed').value >= 1
        # Streaming with an injected handoff fault: falls back too.
        pre._faults = faults_lib.FaultInjector({'rules': [
            {'kind': 'partial_response', 'site': 'handoff', 'at': 1}]})
        tokens, done, error = _stream(
            f'http://127.0.0.1:{pp}',
            {'prompt': prompt, 'max_new_tokens': 10, 'stream': True},
            headers={'X-Handoff-Target': f'http://127.0.0.1:{pp}'})
        assert error is None and done is not None
        assert tokens == res['tokens']
    finally:
        pre.stop()


class _FakeController:
    """Answers the LB's sync POST with replica URLs + planned roles
    (the round-7 chaos harness's controller stub, extended with the
    disaggregation role payload)."""

    def __init__(self, replica_urls, roles=None, retry_after_s=5):
        import http.server as hs
        self.replica_urls = list(replica_urls)
        self.roles = dict(roles or {})
        outer = self

        class H(hs.BaseHTTPRequestHandler):
            timeout = 30

            def log_message(self, *a):
                del a

            def do_POST(self):  # noqa: N802
                body = json.dumps({
                    'ready_replica_urls': outer.replica_urls,
                    'retry_after_s': retry_after_s,
                    'replica_roles': outer.roles,
                }).encode()
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.port = common_utils.find_free_port(19500)
        self.httpd = hs.ThreadingHTTPServer(('127.0.0.1', self.port), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return f'http://127.0.0.1:{self.port}'

    def stop(self):
        self.httpd.shutdown()


def _start_lb(controller_url, monkeypatch, policy='phase_aware',
              max_attempts=4):
    from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
    monkeypatch.setenv('SKYTPU_LB_SYNC', '3600')
    port = common_utils.find_free_port(19600)
    lb = SkyServeLoadBalancer(controller_url=controller_url, port=port,
                              policy_name=policy,
                              max_attempts=max_attempts)
    lb.start()
    lb._sync_once()
    return lb, port


def test_decode_worker_death_midstream_zero_lost(monkeypatch):
    """Extends the round-7 chaos contract to disaggregated fleets: the
    decode worker crash-injects mid-continuation; the prefill relay
    surfaces a retryable error with the generated prefix; the LB's
    in-flight recovery resubmits prompt+prefix through the phase-aware
    policy (prefill worker → surviving decode pool, here the colocated
    fallback) — the client sees ONE whole stream, every token the
    reference's choice (the recovery recomputes prompt + prefix by
    prefill: another program than uninterrupted decode). Zero lost
    requests."""
    pd = common_utils.find_free_port(19700)
    pp = common_utils.find_free_port(pd + 1)
    # The decode worker dies early in the continuation (its engine
    # loop only ever runs for ingested work, so iteration 2 is
    # mid-continuation with most of the budget still owed).
    dec = _start_server(pd, role='decode',
                        fault_spec={'seed': 0, 'rules': [
                            {'kind': 'replica_crash',
                             'site': 'engine_step', 'at': 2}]})
    pre = _start_server(pp, role='prefill')
    urls = {pp: 'prefill', pd: 'decode'}
    try:
        assert dec._ready.wait(180) and pre._ready.wait(180)
        prompt, gen = [3, 1, 4, 1, 5] * 3, 40
        ctrl = _FakeController(
            [f'http://127.0.0.1:{p}' for p in (pp, pd)],
            roles={f'http://127.0.0.1:{p}': r for p, r in urls.items()})
        lb, lport = _start_lb(ctrl.url, monkeypatch)
        try:
            tokens, done, error = _stream(
                f'http://127.0.0.1:{lport}',
                {'prompt': prompt, 'max_new_tokens': gen,
                 'stream': True}, timeout=180)
            assert error is None, error
            assert done is not None
            assert len(tokens) == gen and done['tokens'] == tokens
            greedy_oracle.assert_server_agrees(prompt, tokens,
                                               'recovered stream')
            # The crash really happened and was survived.
            reg = telemetry.get_registry()
            crash = reg.get('skytpu_faults_injected_total',
                            kind='replica_crash')
            assert crash is not None and crash.value >= 1
            assert dec._error is not None
            assert reg.get('skytpu_requests_migrated_total',
                           outcome='completed').value >= 1
        finally:
            lb.stop()
            ctrl.stop()
    finally:
        dec.stop()
        pre.stop()


def test_kv_ingest_malformed_and_capacity():
    port = common_utils.find_free_port(19400)
    srv = _start_server(port, role='decode')
    base = f'http://127.0.0.1:{port}'
    try:
        assert srv._ready.wait(180)
        reg = telemetry.get_registry()
        rej0 = reg.get('skytpu_disagg_handoff_total',
                       outcome='rejected').value
        # Garbage blob → 400, counted.
        req = urllib.request.Request(
            base + '/kv/ingest', data=b'not a handoff',
            headers={'Content-Type': 'application/octet-stream'})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
        err = json.loads(ei.value.read())['error']
        assert err['type'] == 'invalid_handoff'
        # Mismatched model shape → 400 too (valid wire, wrong engine).
        blob = kv_transfer.encode_handoff(_fake_snapshot(
            'bf16', n_layers=99))
        req = urllib.request.Request(
            base + '/kv/ingest', data=blob,
            headers={'Content-Type': 'application/octet-stream'})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
        assert reg.get('skytpu_disagg_handoff_total',
                       outcome='rejected').value >= rej0 + 2
    finally:
        srv.stop()
