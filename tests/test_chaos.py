"""Fault-tolerant serving (round 7): deterministic fault injection,
graceful drain, and in-flight request recovery.

The contract under test is **zero lost requests**: under any injected
fault (replica crash mid-stream, probe timeouts, preemption signals,
broken proxy streams), every accepted request either completes — with
byte-identical greedy output to an uninterrupted run — or receives a
clean retryable error carrying ``Retry-After``.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

import greedy_oracle
from skypilot_tpu import telemetry
from skypilot_tpu.serve import faults as faults_lib
from skypilot_tpu.utils import common_utils

jax.config.update('jax_platforms', 'cpu')


# ---------------------------------------------------------------- helpers
class _FakeController:
    """Answers the LB's sync POST with a settable replica list + hint."""

    def __init__(self, replica_urls, retry_after_s=7):
        import http.server
        self.replica_urls = list(replica_urls)
        self.retry_after_s = retry_after_s
        outer = self

        class H(http.server.BaseHTTPRequestHandler):
            timeout = 30

            def log_message(self, *a):
                del a

            def do_POST(self):  # noqa: N802
                body = json.dumps({
                    'ready_replica_urls': outer.replica_urls,
                    'retry_after_s': outer.retry_after_s,
                }).encode()
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        import http.server as hs
        self.port = common_utils.find_free_port(19500)
        self.httpd = hs.ThreadingHTTPServer(('127.0.0.1', self.port), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return f'http://127.0.0.1:{self.port}'

    def stop(self):
        self.httpd.shutdown()


def _start_server(port, fault_spec=None, **kw):
    from skypilot_tpu.serve.server import ModelServer
    kw.setdefault('max_batch', 2)
    kw.setdefault('max_seq', 128)
    srv = ModelServer('tiny', port=port, fault_spec=fault_spec, **kw)
    srv.start(block=False)
    return srv


def _start_lb(controller_url, monkeypatch, max_attempts=3):
    from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
    monkeypatch.setenv('SKYTPU_LB_SYNC', '3600')   # no background churn
    port = common_utils.find_free_port(19600)
    lb = SkyServeLoadBalancer(controller_url=controller_url, port=port,
                              max_attempts=max_attempts)
    lb.start()
    lb._sync_once()
    return lb, port


def _generate(base, payload, timeout=120, headers=None):
    h = {'Content-Type': 'application/json'}
    h.update(headers or {})
    req = urllib.request.Request(base + '/generate',
                                 json.dumps(payload).encode(), h)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _stream(base, payload, timeout=120):
    """Collect a /generate SSE stream: (token list, done event|None,
    error event|None)."""
    req = urllib.request.Request(
        base + '/generate', json.dumps(payload).encode(),
        {'Content-Type': 'application/json'})
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


# ------------------------------------------------------- injector units
def test_fault_injector_deterministic_counters():
    inj = faults_lib.FaultInjector({'seed': 7, 'rules': [
        {'kind': 'engine_stall', 'site': 'engine_step', 'at': 2},
        {'kind': 'probe_timeout', 'site': 'probe', 'every': 3,
         'count': 2},
    ]})
    hits = [inj.fire('engine_step') for _ in range(4)]
    assert [h.kind if h else None for h in hits] == \
        [None, 'engine_stall', None, None]
    probe_hits = [inj.fire('probe') for _ in range(9)]
    # every=3 capped at count=2: invocations 3 and 6 fire, 9 does not.
    assert [i + 1 for i, h in enumerate(probe_hits) if h] == [3, 6]
    assert inj.site_count('probe') == 9


def test_fault_injector_seeded_prob_reproducible():
    spec = {'seed': 123, 'rules': [
        {'kind': 'slow_response', 'site': 'proxy', 'prob': 0.5}]}
    a = [bool(faults_lib.FaultInjector(spec).fire('proxy'))
         for _ in range(1)]
    seq1 = [bool(r) for r in
            (lambda i: [i.fire('proxy') for _ in range(20)])(
                faults_lib.FaultInjector(spec))]
    seq2 = [bool(r) for r in
            (lambda i: [i.fire('proxy') for _ in range(20)])(
                faults_lib.FaultInjector(spec))]
    assert seq1 == seq2 and any(seq1) and not all(seq1)
    del a


def test_fault_spec_env_and_validation(monkeypatch, tmp_path):
    assert faults_lib.make_injector(None) is None or \
        os.environ.get(faults_lib.FAULT_SPEC_ENV)
    monkeypatch.setenv(faults_lib.FAULT_SPEC_ENV, json.dumps(
        {'rules': [{'kind': 'replica_crash', 'site': 'engine_step',
                    'at': 1}]}))
    inj = faults_lib.get_injector()
    assert inj is not None and inj.fire('engine_step').kind == \
        'replica_crash'
    spec_file = tmp_path / 'spec.json'
    spec_file.write_text(json.dumps({'rules': []}))
    assert faults_lib.make_injector(f'@{spec_file}') is not None
    with pytest.raises(ValueError, match='unknown fault kind'):
        faults_lib.make_injector(
            {'rules': [{'kind': 'meteor', 'site': 'probe'}]})
    with pytest.raises(ValueError, match='unknown fault site'):
        faults_lib.make_injector(
            {'rules': [{'kind': 'replica_crash', 'site': 'moon'}]})


def test_inference_layer_never_imports_faults():
    """Injection disabled ⇒ zero overhead on the hot path: the compute
    layer must not even reference the faults module (the jaxpr-audit
    presets therefore see byte-identical programs either way)."""
    import skypilot_tpu
    root = os.path.join(os.path.dirname(skypilot_tpu.__file__),
                        'inference')
    for fname in os.listdir(root):
        if not fname.endswith('.py'):
            continue
        with open(os.path.join(root, fname), encoding='utf-8') as f:
            assert 'faults' not in f.read(), fname


# ------------------------------------------------------ backoff jitter
def _make_manager(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    from skypilot_tpu.serve.replica_managers import ReplicaManager
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec.from_yaml_config(
        {'readiness_probe': '/readiness'})
    return ReplicaManager('chaos-test', spec, {})


def test_bump_backoff_jitter_and_cap(tmp_path, monkeypatch):
    import random as random_mod
    from skypilot_tpu.serve import replica_managers as rm
    monkeypatch.setenv('SKYTPU_SERVE_LAUNCH_BACKOFF', '4')
    mgr = _make_manager(tmp_path, monkeypatch)
    mgr._rng = random_mod.Random(0)
    assert not mgr.in_launch_backoff()
    assert mgr.backoff_remaining() == 0.0
    delays = []
    for _ in range(12):
        t0 = time.time()
        mgr._bump_backoff()
        delays.append(mgr._backoff_until - t0)
        assert mgr.in_launch_backoff()
    # Jittered exponential: each delay lands in
    # [frac, 1.0] x min(base 2^(n-1), cap); the cap is a hard ceiling.
    base, cap = 4.0, rm._LAUNCH_BACKOFF_CAP
    for n, d in enumerate(delays, start=1):
        target = min(base * 2 ** (n - 1), cap)
        assert rm._BACKOFF_JITTER_FRAC * target - 0.05 <= d <= \
            target + 0.05, (n, d, target)
    assert all(d <= cap + 0.05 for d in delays)
    # Jitter actually varies (not a constant multiplier).
    late = [d for n, d in enumerate(delays, start=1)
            if base * 2 ** (n - 1) >= cap]
    assert len(set(round(d, 3) for d in late)) > 1, late
    # A successful probe resets it (probe_all does this inline; the
    # fields are the contract).
    with mgr._lock:
        mgr._launch_failures = 0
        mgr._backoff_until = 0.0
    assert not mgr.in_launch_backoff()


def test_retry_after_hint_tracks_backoff(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_SERVE_LAUNCH_BACKOFF', '40')
    mgr = _make_manager(tmp_path, monkeypatch)
    assert mgr.retry_after_hint() == 15          # no replicas at all
    mgr._bump_backoff()
    hint = mgr.retry_after_hint()
    assert 40 * 0.5 - 1 <= hint <= 41            # backoff remainder


# --------------------------------------------------- probe/preempt faults
def test_probe_timeout_injection(tmp_path, monkeypatch):
    """An injected probe_timeout makes a live, answering replica look
    probe-dead — the consecutive-failure escalation is exercisable."""
    import http.server

    class H(http.server.BaseHTTPRequestHandler):
        timeout = 10

        def log_message(self, *a):
            del a

        def do_GET(self):  # noqa: N802
            self.send_response(200)
            self.send_header('Content-Length', '2')
            self.end_headers()
            self.wfile.write(b'ok')

    port = common_utils.find_free_port(19700)
    httpd = http.server.ThreadingHTTPServer(('127.0.0.1', port), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        mgr = _make_manager(tmp_path, monkeypatch)
        from skypilot_tpu.serve.replica_managers import ReplicaInfo
        info = ReplicaInfo(1, 'c', 1, False, port)
        info.url = f'http://127.0.0.1:{port}'
        assert mgr._probe_one(info) is True         # genuinely alive
        mgr._faults = faults_lib.FaultInjector({'rules': [
            {'kind': 'probe_timeout', 'site': 'probe', 'at': 2,
             'delay_s': 0.01}]})
        assert mgr._probe_one(info) is True         # invocation 1
        assert mgr._probe_one(info) is False        # injected timeout
        assert mgr._probe_one(info) is True         # back to honest
    finally:
        httpd.shutdown()


def test_replica_manager_drain_flow(tmp_path, monkeypatch):
    """drain(): READY -> DRAINING (out of ready_urls immediately), the
    replica's /drain contract is honored, and the cluster tears down
    once the replica reports drained."""
    import http.server
    from skypilot_tpu.serve import serve_state

    state = {'drained': False}

    class H(http.server.BaseHTTPRequestHandler):
        timeout = 10

        def log_message(self, *a):
            del a

        def _send(self, payload):
            body = json.dumps(payload).encode()
            self.send_response(200)
            self.send_header('Content-Length', str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):  # noqa: N802
            self._send({'draining': True, 'inflight': 1})

        def do_GET(self):  # noqa: N802
            self._send({'draining': True,
                        'drained': state['drained'], 'inflight': 0})

    port = common_utils.find_free_port(19750)
    httpd = http.server.ThreadingHTTPServer(('127.0.0.1', port), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    mgr = _make_manager(tmp_path, monkeypatch)
    try:
        from skypilot_tpu.serve.replica_managers import ReplicaInfo
        info = ReplicaInfo(1, 'chaos-drain-c', 1, False, port)
        info.url = f'http://127.0.0.1:{port}'
        info.status = serve_state.ReplicaStatus.READY
        with mgr._lock:
            mgr._replicas[1] = info
        assert mgr.ready_urls() == [info.url]
        assert mgr.drain(1, deadline_s=15) is True
        assert info.status == serve_state.ReplicaStatus.DRAINING
        assert mgr.ready_urls() == []            # out of rotation NOW
        assert mgr.drain(1) is False             # idempotent
        time.sleep(0.8)                          # mid-drain: still up
        assert info.status == serve_state.ReplicaStatus.DRAINING
        state['drained'] = True
        deadline = time.time() + 20
        while time.time() < deadline and 1 in mgr._replicas:
            time.sleep(0.1)
        assert 1 not in mgr._replicas            # torn down after drain
    finally:
        httpd.shutdown()


def test_preemption_warning_routes_through_drain(tmp_path, monkeypatch):
    from skypilot_tpu.serve import serve_state
    mgr = _make_manager(tmp_path, monkeypatch)
    from skypilot_tpu.serve.replica_managers import ReplicaInfo
    info = ReplicaInfo(2, 'chaos-warn-c', 1, True, 12345)
    info.url = 'http://127.0.0.1:1'              # nothing listening
    info.status = serve_state.ReplicaStatus.READY
    with mgr._lock:
        mgr._replicas[2] = info
    assert mgr.handle_preemption_warning(2, deadline_s=5) is True
    # DRAINING first; the unreachable drain endpoint then degrades to
    # plain teardown on the drain thread (may already have happened).
    assert info.status in (serve_state.ReplicaStatus.DRAINING,
                           serve_state.ReplicaStatus.SHUTTING_DOWN)
    deadline = time.time() + 20
    while time.time() < deadline and 2 in mgr._replicas:
        time.sleep(0.1)
    assert 2 not in mgr._replicas


# ------------------------------------------------------- engine export
def test_export_inflight():
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64)
    eng.add_request([1, 2, 3], max_new_tokens=8)
    eng.add_request([4, 5], max_new_tokens=4, temperature=0.7,
                    top_k=5, priority=1)
    eng.add_request([6, 7, 8, 9], max_new_tokens=4)   # queued (2 slots)
    for _ in range(3):
        eng.step(horizon=2)
    exported = eng.export_inflight()
    by_prompt = {tuple(e['prompt']): e for e in exported}
    assert (1, 2, 3) in by_prompt and (4, 5) in by_prompt
    first = by_prompt[(1, 2, 3)]
    assert first['remaining_new_tokens'] == \
        first['max_new_tokens'] - len(first['output'])
    sampled = by_prompt[(4, 5)]
    assert sampled['temperature'] == 0.7 and sampled['top_k'] == 5
    assert sampled['priority'] == 1
    # Finished requests drop out of the export.
    eng.run_to_completion(horizon=8)
    assert eng.export_inflight() == []


# ------------------------------------------------------------ drain e2e
def test_drain_endpoint_completes_within_deadline():
    port = common_utils.find_free_port(19800)
    srv = _start_server(port)
    base = f'http://127.0.0.1:{port}'
    try:
        assert srv._ready.wait(180)
        reg = telemetry.get_registry()
        h_drain = reg.histogram('skytpu_replica_drain_seconds')
        drain_count0 = h_drain.count
        streams = [srv.submit_stream([3 + i, 5, 7], max_new_tokens=24,
                                     temperature=0.0, top_k=0,
                                     eos_id=None) for i in range(2)]
        status = json.loads(urllib.request.urlopen(
            urllib.request.Request(
                base + '/drain',
                data=json.dumps({'deadline_s': 60}).encode(),
                headers={'Content-Type': 'application/json'}),
            timeout=10).read())
        assert status['draining'] is True
        # New work is refused with a retryable 503 + Retry-After.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _generate(base, {'prompt': [1, 2], 'max_new_tokens': 2},
                      timeout=30)
        assert ei.value.code == 503
        assert int(ei.value.headers['Retry-After']) >= 1
        err = json.loads(ei.value.read())['error']
        assert err['reason'] == 'draining'
        # In-flight requests run to completion (not cancelled).
        for sr in streams:
            tokens = []
            while True:
                token, finished = sr.outbox.get(timeout=60)
                assert token is not None, sr.outbox.error
                tokens.append(token)
                if finished:
                    break
            assert len(tokens) == 24
            srv.finish_stream(sr)
        # Drain completes well within the deadline and is measured.
        deadline = time.time() + 30
        while time.time() < deadline:
            st = json.loads(urllib.request.urlopen(
                base + '/drain', timeout=10).read())
            if st['drained']:
                break
            time.sleep(0.1)
        assert st['drained'] is True and st['inflight'] == 0
        assert h_drain.count == drain_count0 + 1
        # Readiness reports draining (the probe pulls it from rotation).
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + '/readiness', timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())['status'] == 'draining'
        # Shed counter rode the stable 'draining' reason.
        shed = reg.get('skytpu_sched_shed_total', tier='latency',
                       reason='draining')
        assert shed is not None and shed.value >= 1
    finally:
        srv.stop()


# ----------------------------------------------------------- LB contract
def test_lb_503_no_replicas_json_and_retry_after(monkeypatch):
    ctrl = _FakeController([], retry_after_s=11)
    lb, port = _start_lb(ctrl.url, monkeypatch)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f'http://127.0.0.1:{port}/x',
                                   timeout=10)
        err = ei.value
        assert err.code == 503
        assert err.headers['Retry-After'] == '11'
        payload = json.loads(err.read())
        assert payload['retryable'] is True
        assert payload['retry_after_s'] == 11
        assert 'No ready replicas' in payload['error']
    finally:
        lb.stop()
        ctrl.stop()


def test_scheduler_429_retry_after_passes_through_lb(monkeypatch):
    port = common_utils.find_free_port(19850)
    srv = _start_server(port)
    try:
        assert srv._ready.wait(180)
        srv.sched._max_queue_tokens = 4        # everything real sheds
        ctrl = _FakeController([f'http://127.0.0.1:{port}'])
        lb, lport = _start_lb(ctrl.url, monkeypatch)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _generate(f'http://127.0.0.1:{lport}',
                          {'prompt': [1, 2, 3, 4],
                           'max_new_tokens': 16}, timeout=30)
            err = ei.value
            assert err.code == 429
            payload = json.loads(err.read())['error']
            # Retry-After passed through the LB unmodified.
            assert int(err.headers['Retry-After']) == \
                payload['retry_after_s']
        finally:
            lb.stop()
            ctrl.stop()
    finally:
        srv.stop()


def test_request_key_idempotent_replay():
    port = common_utils.find_free_port(19860)
    srv = _start_server(port)
    base = f'http://127.0.0.1:{port}'
    try:
        assert srv._ready.wait(180)
        payload = {'prompt': [2, 4, 6], 'max_new_tokens': 6,
                   'request_key': 'idem-1'}
        first = _generate(base, payload)
        again = _generate(base, payload)
        assert again['deduped'] is True
        assert again['tokens'] == first['tokens']
        # The header spelling (what the LB mints) dedupes too.
        third = _generate(base, {'prompt': [2, 4, 6],
                                 'max_new_tokens': 6},
                          headers={'X-Request-ID': 'idem-1'})
        assert third['deduped'] is True
        assert third['tokens'] == first['tokens']
    finally:
        srv.stop()


# ------------------------------------------------------- chaos e2e (LB)
def test_mid_stream_migration_byte_identical(monkeypatch):
    """Deterministic mid-stream break (injected partial_response after
    5 token events): the LB migrates the stream to the other replica
    with the generated prefix; the client sees one whole stream, every
    token of it the reference's choice (the target recomputes prompt +
    prefix by prefill where an uninterrupted run decoded: another
    program, so held to ``greedy_oracle``)."""
    pa = common_utils.find_free_port(19900)
    pb = common_utils.find_free_port(pa + 1)
    sa = _start_server(pa)
    sb = _start_server(pb)
    try:
        assert sa._ready.wait(180) and sb._ready.wait(180)
        prompt, gen = [3, 1, 4, 1, 5], 16
        ctrl = _FakeController([f'http://127.0.0.1:{pa}',
                                f'http://127.0.0.1:{pb}'])
        lb, lport = _start_lb(ctrl.url, monkeypatch)
        lb._faults = faults_lib.FaultInjector({'rules': [
            {'kind': 'partial_response', 'site': 'proxy_stream',
             'at': 1, 'after_events': 5}]})
        reg = telemetry.get_registry()
        migrated0 = reg.get('skytpu_requests_migrated_total',
                            outcome='completed').value
        h_rec = reg.histogram('skytpu_replica_recovery_seconds')
        rec0 = h_rec.count
        try:
            tokens, done, error = _stream(
                f'http://127.0.0.1:{lport}',
                {'prompt': prompt, 'max_new_tokens': gen,
                 'stream': True})
            assert error is None
            assert done is not None
            assert len(tokens) == gen and done['tokens'] == tokens
            greedy_oracle.assert_server_agrees(prompt, tokens,
                                               'migrated stream')
            assert reg.get('skytpu_requests_migrated_total',
                           outcome='completed').value == migrated0 + 1
            assert h_rec.count == rec0 + 1
            fault_c = reg.get('skytpu_faults_injected_total',
                              kind='partial_response')
            assert fault_c is not None and fault_c.value >= 1
        finally:
            lb.stop()
            ctrl.stop()
    finally:
        sa.stop()
        sb.stop()


def test_chaos_kill_replica_mid_stream_zero_lost(monkeypatch):
    """THE chaos contract (deterministic seed): one of two replicas is
    crash-injected mid-stream under concurrent load — zero lost
    requests (every accepted stream completes), and every completed
    stream's greedy output is whole and the reference's choice."""
    pa = common_utils.find_free_port(19950)
    pb = common_utils.find_free_port(pa + 1)
    # Replica A dies on its 4th engine-loop iteration — mid-stream for
    # whatever it is serving at that point (deterministic given the
    # fault spec; which requests land on A is load-dependent, and the
    # contract must hold either way).
    sa = _start_server(pa, fault_spec={'seed': 0, 'rules': [
        {'kind': 'replica_crash', 'site': 'engine_step', 'at': 4}]})
    sb = _start_server(pb)
    try:
        assert sa._ready.wait(180) and sb._ready.wait(180)
        prompts = [[11 + i, 3, 5, 7 + i] for i in range(6)]
        gen = 24
        ctrl = _FakeController([f'http://127.0.0.1:{pa}',
                                f'http://127.0.0.1:{pb}'])
        lb, lport = _start_lb(ctrl.url, monkeypatch, max_attempts=4)
        results = {}
        errors = {}

        def one(p):
            try:
                results[tuple(p)] = _stream(
                    f'http://127.0.0.1:{lport}',
                    {'prompt': p, 'max_new_tokens': gen,
                     'stream': True})
            except Exception as e:  # noqa: BLE001 - recorded and asserted
                errors[tuple(p)] = f'{type(e).__name__}: {e}'

        try:
            threads = [threading.Thread(target=one, args=(p,))
                       for p in prompts]
            for t in threads:
                t.start()
                time.sleep(0.05)
            for t in threads:
                t.join(timeout=180)
            assert not errors, errors
            lost = []
            for p in prompts:
                tokens, done, error = results[tuple(p)]
                if error is not None or done is None:
                    lost.append((p, error))
                    continue
                assert len(tokens) == gen, (p, tokens)
                greedy_oracle.assert_server_agrees(p, tokens,
                                                   'stream under chaos')
            # ZERO lost requests: every accepted stream completed whole
            # (a retryable error event would have
            # been acceptable per the contract only if no replica
            # survived — here B is alive, so everything completes).
            assert lost == [], lost
            # The injected crash actually happened and was survived.
            reg = telemetry.get_registry()
            crash = reg.get('skytpu_faults_injected_total',
                            kind='replica_crash')
            assert crash is not None and crash.value >= 1
            assert sa._error is not None          # A really died
        finally:
            lb.stop()
            ctrl.stop()
    finally:
        sa.stop()
        sb.stop()
