"""SLO-aware serving core (`serve/scheduler.py`): fast unit tests for
the admission policy (priority ordering, shortest-remaining-work
tie-break, tier budget split, shed threshold, Retry-After math), e2e
smoke through the real model server (both engines: incremental
streaming off the engine loop, cancel mid-stream releases the slot,
HTTP 429 + Retry-After), the queue-depth LB policy, and a slow
saturation test asserting the latency tier's TTFT stays bounded while
the throughput tier absorbs the overload.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from skypilot_tpu.serve import scheduler as sched_lib
from skypilot_tpu.telemetry import registry as registry_lib


@pytest.fixture()
def fresh_registry():
    """Scheduler unit tests read absolute counter values — give each
    one a clean process registry (servers/engines built later re-create
    their handles get-or-create, so this is safe to swap mid-session)."""
    yield registry_lib.reset_registry()
    registry_lib.reset_registry()


class FakeEngine:
    """The slice of the engine surface the scheduler drives: slot
    accounting, priority-carrying add_request, remaining-work."""

    def __init__(self, max_batch=4, capacity=1024):
        self.max_batch = max_batch
        self.num_active = 0
        self.queue_depth = 0
        self.capacity = capacity
        self.added = []     # (rid, prompt, max_new_tokens, priority)
        self._next_id = 0
        self.cancelled = []
        self.inflight_tokens = 0

    def kv_pool_stats(self):
        return {'pool_token_capacity': self.capacity, 'tokens_used': 0,
                'tokens_free': self.capacity, 'preemptions': 0,
                'kv_cache_dtype': 'bf16', 'kv_token_bytes': 0}

    def add_request(self, prompt, max_new_tokens=128, priority=0,
                    **sampling):
        del sampling
        rid = self._next_id
        self._next_id += 1
        self.added.append((rid, list(prompt), max_new_tokens, priority))
        self.num_active += 1
        return rid

    def remaining_work_tokens(self):
        return self.inflight_tokens

    def pop_finished(self, rid):
        del rid
        return None

    def cancel(self, rid):
        self.cancelled.append(rid)
        self.num_active = max(0, self.num_active - 1)
        return True


def make_sched(engine=None, **kw):
    kw.setdefault('default_tier', 'latency')
    sched = sched_lib.RequestScheduler(threading.Lock(), **kw)
    if engine is not None:
        sched.bind_engine(engine)
    return sched


# ---------------------------------------------------------------- units
def test_resolve_tier_default_and_validation(fresh_registry):
    sched = make_sched(default_tier='throughput')
    assert sched.resolve_tier(None) == 'throughput'
    assert sched.resolve_tier('') == 'throughput'
    assert sched.resolve_tier('latency') == 'latency'
    with pytest.raises(ValueError, match='unknown SLO tier'):
        sched.resolve_tier('realtime')
    with pytest.raises(ValueError, match='unknown SLO tier'):
        make_sched(default_tier='bogus')
    with pytest.raises(ValueError, match='latency_admit_frac'):
        make_sched(latency_admit_frac=1.0)


def test_tier_priority_hint_reaches_engine(fresh_registry):
    """Tier index IS the engine priority hint: latency=0 beats
    throughput=1 inside engine-internal requeues too."""
    eng = FakeEngine(max_batch=2)
    sched = make_sched(eng)
    sched.submit([1] * 8, max_new_tokens=8, tier='throughput')
    sched.submit([1] * 8, max_new_tokens=8, tier='latency')
    sched.fill_engine(eng)
    prios = {p for (_, _, _, p) in eng.added}
    assert prios == {0, 1}
    # Deficit split starts at the latency tier: it is admitted first.
    assert eng.added[0][3] == sched_lib.TIERS.index('latency')


def test_engine_queue_pop_orders_by_priority_fifo_within():
    """The engine-side half of the contract: queued requests pop most
    urgent (lowest priority) first, FIFO within a class — a paged
    preemption requeue cannot park a latency request behind newly
    queued throughput work."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64)
    ids = [eng.add_request([1, 2, 3], max_new_tokens=2, priority=p)
           for p in (1, 0, 1, 0)]
    popped = [eng._queue_pop().request_id for _ in range(4)]
    assert popped == [ids[1], ids[3], ids[0], ids[2]]


def test_srw_pop_shortest_work_first_fifo_ties(fresh_registry):
    eng = FakeEngine(max_batch=8)
    sched = make_sched(eng)
    a = sched.submit([1] * 40, max_new_tokens=10, tier='latency')
    b = sched.submit([1] * 5, max_new_tokens=5, tier='latency')
    c = sched.submit([1] * 5, max_new_tokens=5, tier='latency')
    sched.fill_engine(eng)
    order = [rid for rid, *_ in eng.added]
    assert order == [b.request_id, c.request_id, a.request_id]
    assert b.request_id is not None and b.seq < c.seq  # FIFO tie-break


def test_bank_full_defers_only_blocked_request(fresh_registry):
    """AdapterBankFullError defers exactly the blocked request for the
    cycle and keeps admitting everything else — a bank-full adapter
    must not head-of-line-block base-model admission. The deferred
    request goes back to its queue for the next cycle."""
    from skypilot_tpu.inference.adapters import AdapterBankFullError

    class BankFullEngine(FakeEngine):
        def add_request(self, prompt, max_new_tokens=128, priority=0,
                        **sampling):
            if sampling.get('adapter') == 'full':
                raise AdapterBankFullError('all slots pinned')
            return super().add_request(
                prompt, max_new_tokens=max_new_tokens,
                priority=priority, **sampling)

    eng = BankFullEngine(max_batch=4)
    sched = make_sched(eng)
    # Shortest work: SRW picks the blocked request FIRST every cycle.
    blocked = sched.submit([1] * 2, max_new_tokens=2, tier='latency',
                           adapter='full')
    base_a = sched.submit([1] * 8, max_new_tokens=8, tier='latency')
    base_b = sched.submit([1] * 8, max_new_tokens=8,
                          tier='throughput')
    sched.fill_engine(eng)
    admitted = {rid for rid, *_ in eng.added}
    assert admitted == {base_a.request_id, base_b.request_id}
    assert blocked.request_id is None
    assert sched.backlog == 1          # requeued for the next cycle
    # Pins released: the deferred request admits next cycle.
    sched.fill_engine(eng)
    assert blocked.request_id is None  # still full this fake cycle
    BankFullEngine.add_request = FakeEngine.add_request
    sched.fill_engine(eng)
    assert blocked.request_id is not None
    assert sched.backlog == 0


def test_budget_split_deficit_weighted(fresh_registry):
    """With both tiers backlogged and equal request sizes, admitted
    work tracks latency_admit_frac (7/10 at 0.7)."""
    eng = FakeEngine(max_batch=10)
    sched = make_sched(eng, latency_admit_frac=0.7,
                       max_queue_tokens=100_000)
    for _ in range(10):
        sched.submit([1] * 10, max_new_tokens=10, tier='latency')
    for _ in range(10):
        sched.submit([1] * 10, max_new_tokens=10, tier='throughput')
    sched.fill_engine(eng)     # 10 free slots
    lat = sum(1 for (_, _, _, p) in eng.added if p == 0)
    assert len(eng.added) == 10
    assert lat == 7
    # An idle tier's share flows to the busy one: drain latency, refill
    # throughput only — all free slots go to throughput.
    eng2 = FakeEngine(max_batch=4)
    sched2 = make_sched(eng2, latency_admit_frac=0.7)
    for _ in range(4):
        sched2.submit([1] * 10, max_new_tokens=10, tier='throughput')
    sched2.fill_engine(eng2)
    assert all(p == 1 for (_, _, _, p) in eng2.added)


def test_shed_threshold_per_tier_and_counter(fresh_registry):
    eng = FakeEngine(max_batch=0)        # nothing admits; queues grow
    sched = make_sched(eng, max_queue_tokens=100)
    sched.submit([1] * 50, max_new_tokens=10, tier='latency')   # 60 ok
    with pytest.raises(sched_lib.ShedError) as ei:
        sched.submit([1] * 40, max_new_tokens=10, tier='latency')
    assert ei.value.reason == 'queue_full'
    assert ei.value.tier == 'latency'
    assert ei.value.retry_after_s >= 1
    # The bound is per tier: the other tier still admits.
    sched.submit([1] * 40, max_new_tokens=10, tier='throughput')
    reg = registry_lib.get_registry()
    shed = reg.get('skytpu_sched_shed_total', tier='latency',
                   reason='queue_full')
    assert shed is not None and shed.value == 1
    # Queue state unchanged by the shed.
    assert sched.json_stats()['tiers']['latency']['queue_tokens'] == 60


def test_token_rate_meter_windowed():
    m = sched_lib._TokenRateMeter(window_s=10.0)
    assert m.rate(now=100.0) == 0.0
    m.add(100, now=100.0)
    m.add(200, now=105.0)
    assert m.rate(now=105.0) == pytest.approx(300 / 5.0)
    # Events age out of the window.
    m.add(50, now=112.0)
    assert m.rate(now=112.0) == pytest.approx((200 + 50) / 7.0)


def test_retry_after_math(fresh_registry):
    from skypilot_tpu.telemetry import clock
    eng = FakeEngine(max_batch=4)
    sched = make_sched(eng, max_queue_tokens=100_000)
    # Cold meter: conservative 8 tok/s/slot floor over max_batch slots.
    assert sched.retry_after_s('latency', 64) == 2   # ceil(64 / 32)
    # Warm meter: measured throughput is the denominator. Timestamps
    # ride the real monotonic clock (retry_after_s reads it); pick
    # quotients far from integer boundaries so clock drift between
    # the add and the assert cannot flip the ceil.
    now = clock.monotonic()
    sched._rate.add(300, now=now - 10.0)
    sched._rate.add(300, now=now)                    # ~60 tok/s
    assert sched.retry_after_s('latency', 85) == 2   # ceil(85/60)
    # Work ahead counts: engine in-flight + queued tokens at or above
    # the tier.
    eng.inflight_tokens = 60
    sched.submit([1] * 20, max_new_tokens=10, tier='latency')   # 30 q
    assert sched.retry_after_s('latency', 85) == 3   # (60+30+85)/60
    # A latency arrival does not wait behind throughput backlog...
    sched.submit([1] * 290, max_new_tokens=10, tier='throughput')
    assert sched.retry_after_s('latency', 85) == 3
    # ...but a throughput arrival waits behind both tiers (+300).
    assert sched.retry_after_s('throughput', 85) == 8
    # Clamps: [1, 120].
    assert sched.retry_after_s('latency', 0) >= 1
    eng.inflight_tokens = 10_000_000
    assert sched.retry_after_s('latency', 85) == 120


def test_cancel_queued_releases_tokens(fresh_registry):
    eng = FakeEngine(max_batch=0)
    sched = make_sched(eng, max_queue_tokens=100)
    sr = sched.submit([1] * 50, max_new_tokens=10, tier='latency')
    assert sched.cancel(sr) is True
    token, finished = sr.outbox.get(timeout=1)
    assert (token, finished) == (None, True)
    assert sr.outbox.error == 'cancelled'
    # Tokens released: the bound admits a new request again.
    sched.submit([1] * 80, max_new_tokens=10, tier='latency')


def test_fail_all_wakes_every_waiter(fresh_registry):
    eng = FakeEngine(max_batch=1)
    sched = make_sched(eng, max_queue_tokens=10_000)
    admitted = sched.submit([1] * 4, max_new_tokens=4)
    sched.fill_engine(eng)
    assert admitted.request_id is not None
    queued = sched.submit([1] * 4, max_new_tokens=4)
    sched.fail_all('engine exploded')
    for sr in (admitted, queued):
        token, finished = sr.outbox.get(timeout=1)
        assert (token, finished) == (None, True)
        assert 'engine exploded' in sr.outbox.error
    with pytest.raises(RuntimeError, match='engine failed'):
        sched.submit([1] * 4, max_new_tokens=4)
    reg = registry_lib.get_registry()
    shed = reg.get('skytpu_sched_shed_total', tier='latency',
                   reason='engine_error')
    assert shed is not None and shed.value == 1   # queued one only


def test_outbox_order_fail_idempotent_and_aget():
    ob = sched_lib.Outbox()
    ob.put(7, False)
    ob.put(8, True)
    assert ob.get(timeout=1) == (7, False)
    assert ob.get(timeout=1) == (8, True)
    ob.fail('first')
    ob.fail('second')
    assert ob.error == 'first'
    assert ob.get(timeout=1) == (None, True)

    import asyncio
    ob2 = sched_lib.Outbox()
    ob2.put(42, True)
    assert asyncio.run(ob2.aget()) == (42, True)


# ---------------------------------------------------- queue-depth LB policy
class _MetricsReplica:
    """Fake replica serving only /metrics?format=json."""

    def __init__(self, port, queue_tokens):
        import http.server
        self.queue_tokens = queue_tokens
        outer = self

        class H(http.server.BaseHTTPRequestHandler):
            timeout = 10

            def log_message(self, *a):
                del a

            def do_GET(self):
                body = json.dumps(
                    {'queue_tokens_total': outer.queue_tokens}).encode()
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        import http.server as hs
        self.httpd = hs.ThreadingHTTPServer(('127.0.0.1', port), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()


def test_queue_depth_policy_prefers_least_loaded():
    from skypilot_tpu.serve import load_balancing_policies as lb
    from skypilot_tpu.utils import common_utils
    p1 = common_utils.find_free_port(19100)
    r1 = _MetricsReplica(p1, queue_tokens=5000)
    p2 = common_utils.find_free_port(p1 + 1)
    r2 = _MetricsReplica(p2, queue_tokens=10)
    try:
        policy = lb.make_policy('queue_depth')
        u1, u2 = f'http://127.0.0.1:{p1}', f'http://127.0.0.1:{p2}'
        policy.set_ready_replicas([u1, u2])
        assert policy.select_replica() == u2
        # In-flight dispatches advance the loaded score between probes
        # so a burst within one TTL window still spreads.
        for _ in range(1 + 5000 // policy.EST_TOKENS_PER_REQUEST):
            policy.pre_execute(u2)
        assert policy.select_replica() == u1
        # exclude (the LB's transparent retry) is honored.
        assert policy.select_replica(exclude={u1}) == u2
    finally:
        r1.stop()
        r2.stop()


def test_queue_depth_policy_degrades_on_probe_failure():
    from skypilot_tpu.serve import load_balancing_policies as lb
    from skypilot_tpu.utils import common_utils
    dead = f'http://127.0.0.1:{common_utils.find_free_port(19200)}'
    policy = lb.make_policy('queue_depth')
    policy.set_ready_replicas([dead])
    # Probe fails; the policy still returns the replica (least-load
    # fallback) rather than blackholing.
    assert policy.select_replica() == dead


# ------------------------------------------------------------- e2e smoke
def _post(port, payload, timeout=60, headers=None):
    body = json.dumps(payload).encode()
    h = {'Content-Type': 'application/json'}
    h.update(headers or {})
    req = urllib.request.Request(
        f'http://127.0.0.1:{port}/generate', body, h)
    return urllib.request.urlopen(req, timeout=timeout)


@pytest.fixture()
def tiny_server():
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.utils import common_utils
    port = common_utils.find_free_port(19300)
    server = ModelServer('tiny', max_batch=2, max_seq=64, port=port)
    server.start(block=False)
    assert server._ready.wait(180)
    yield server
    server.stop()


def test_e2e_stream_incremental_and_cancel(tiny_server):
    """Tokens arrive through the outbox BEFORE the request finishes
    (true incremental streaming off the engine loop), and finishing a
    stream early cancels engine-side, releasing the slot."""
    server = tiny_server
    sr = server.submit_stream([1, 2, 3, 4], max_new_tokens=48,
                              temperature=0.0, top_k=0, eos_id=None)
    token, finished = sr.outbox.get(timeout=60)
    # First token is live while the engine still owns the request —
    # the incremental contract (48 tokens take several fused steps).
    assert token is not None and not finished
    assert sr.result is None
    aborted_before = server._m_aborted.value
    server.finish_stream(sr)               # client walks away
    assert server._m_aborted.value == aborted_before + 1
    # The slot is released: a fresh request completes promptly.
    with _post(server.port, {'prompt': [5, 6], 'max_new_tokens': 3,
                             'slo_tier': 'latency'}) as r:
        out = json.loads(r.read())
    assert len(out['tokens']) == 3
    deadline = time.time() + 30
    while server.engine.num_active and time.time() < deadline:
        time.sleep(0.05)
    assert server.engine.num_active == 0


def test_e2e_sse_streams_all_tokens(tiny_server):
    server = tiny_server
    with _post(server.port, {'prompt': [1, 2, 3], 'max_new_tokens': 6,
                             'stream': True}) as r:
        assert 'text/event-stream' in r.headers.get('Content-Type', '')
        events = [json.loads(ln[5:]) for ln in r
                  if ln.startswith(b'data:')]
    tokens = [e['token'] for e in events if 'token' in e]
    assert len(tokens) == 6
    assert events[-1].get('done') is True
    assert events[-1]['tokens'] == tokens


def test_e2e_shed_429_with_retry_after(tiny_server):
    server = tiny_server
    server.sched._max_queue_tokens = 4     # work=prompt+gen > 4 sheds
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, {'prompt': [1, 2, 3, 4],
                                'max_new_tokens': 8}, timeout=30)
        err = ei.value
        assert err.code == 429
        retry_after = int(err.headers['Retry-After'])
        assert retry_after >= 1
        payload = json.loads(err.read())['error']
        assert payload['reason'] == 'queue_full'
        assert payload['retry_after_s'] == retry_after
        # X-SLO-Tier header routes the shed to the declared tier.
        with pytest.raises(urllib.error.HTTPError) as ei2:
            _post(server.port, {'prompt': [1, 2, 3, 4],
                                'max_new_tokens': 8}, timeout=30,
                  headers={'X-SLO-Tier': 'throughput'})
        assert json.loads(ei2.value.read())['error']['tier'] == \
            'throughput'
    finally:
        server.sched._max_queue_tokens = 10_000
    # Shed counters visible at /metrics?format=json.
    with urllib.request.urlopen(
            f'http://127.0.0.1:{server.port}/metrics?format=json',
            timeout=10) as r:
        m = json.loads(r.read())
    assert m['sched']['tiers']['latency']['shed_total'] >= 1
    assert m['sched']['tiers']['throughput']['shed_total'] >= 1


def test_e2e_unknown_tier_is_400(tiny_server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(tiny_server.port, {'prompt': [1, 2], 'max_new_tokens': 2,
                                 'slo_tier': 'platinum'}, timeout=30)
    assert ei.value.code == 400


# --------------------------------------------------------------- slow e2e
@pytest.mark.slow
def test_latency_tier_ttft_bounded_under_overload():
    """Saturation: a wall of throughput-tier work floods the engine;
    interactive latency-tier requests submitted into the overload must
    keep a bounded TTFT (they jump the backlog via tier priority +
    SRW) — the r05 failure mode this subsystem exists to fix."""
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.utils import common_utils
    port = common_utils.find_free_port(19400)
    server = ModelServer('tiny', max_batch=2, max_seq=128, port=port,
                         max_queue_tokens=100_000)
    server.start(block=False)
    try:
        assert server._ready.wait(180)
        # Overload: 10 long throughput requests against 2 slots.
        flood = [server.submit_stream(
            [1 + i] * 16, max_new_tokens=96, temperature=0.0, top_k=0,
            eos_id=None, tier='throughput') for i in range(10)]
        lat_ttfts = []
        for i in range(4):
            time.sleep(0.3)
            t0 = time.time()
            sr = server.submit_stream([7, 8, 9], max_new_tokens=4,
                                      temperature=0.0, top_k=0,
                                      eos_id=None, tier='latency')
            token, _ = sr.outbox.get(timeout=120)
            assert token is not None
            lat_ttfts.append(time.time() - t0)
            server.finish_stream(sr)
        for sr in flood:
            server.finish_stream(sr)
        stats = server.sched.json_stats()
        lat_med = sorted(lat_ttfts)[len(lat_ttfts) // 2]
        # Bounded: an interactive request never waits behind the whole
        # 10-deep flood (which is ~10x96 decode tokens of work).
        assert lat_med < 20.0
        # And the scheduler admitted every latency request ahead of the
        # remaining throughput backlog.
        assert stats['tiers']['latency']['admitted'] == 4
        # The backlog was real while the latency requests cut it.
        assert stats['tiers']['throughput']['admitted'] < 10 or \
            stats['tiers']['latency']['queue_wait_ms_p90'] < 20_000
    finally:
        server.stop()
