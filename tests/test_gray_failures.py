"""Gray-failure defense (round 13): wedge watchdog, NaN blast-radius
isolation, checksummed KV wires, byzantine-replica quarantine.

The contract under test: a replica that keeps answering HTTP while
serving wrong bytes (bit-flipped KV, corrupted weights, byzantine
responses) or nothing at all (wedged step) must be DETECTED and
CONTAINED — per-request eviction for NaN bursts, checksum refusal for
corrupt wires, degraded readiness + failover for wedges, quarantine
for byzantine replicas — with zero lost requests and byte-identical
surviving streams end to end.
"""
import json
import struct
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from skypilot_tpu.serve import faults as faults_lib
from skypilot_tpu.utils import common_utils

jax.config.update('jax_platforms', 'cpu')


# ---------------------------------------------------------------------------
# Checksummed wire formats (SKKV / SKPF / SKCK v2)
# ---------------------------------------------------------------------------
def _bf16_snapshot(n_rows=5):
    import ml_dtypes
    L, hkv, d = 2, 2, 4
    return {
        'kv_cache_dtype': 'bf16', 'n_rows': n_rows,
        'model': {'n_layers': L, 'n_kv_heads': hkv, 'head_dim': d},
        'prompt': [1, 2, 3], 'output': [4, 5, 6],
        'max_new_tokens': 10, 'temperature': 0.0, 'top_k': 0,
        'top_p': 1.0, 'eos_id': None, 'stop': None, 'priority': 0,
        'k': np.arange(L * n_rows * hkv * d, dtype=np.float32
                       ).reshape(L, n_rows, hkv, d
                                 ).astype(ml_dtypes.bfloat16),
        'v': np.ones((L, n_rows, hkv, d), ml_dtypes.bfloat16),
        'k_scale': None, 'v_scale': None,
    }


def _int8_snapshot(n_rows=5):
    L, hkv, d = 2, 2, 4
    return {
        'kv_cache_dtype': 'int8', 'n_rows': n_rows,
        'model': {'n_layers': L, 'n_kv_heads': hkv, 'head_dim': d},
        'prompt': [1, 2, 3], 'output': [4, 5, 6],
        'max_new_tokens': 10, 'temperature': 0.0, 'top_k': 0,
        'top_p': 1.0, 'eos_id': None, 'stop': None, 'priority': 0,
        'k': (np.arange(L * n_rows * hkv * d) % 127).astype(np.int8
              ).reshape(L, n_rows, hkv, d),
        'v': np.ones((L, n_rows, hkv, d), np.int8),
        'k_scale': np.full((L, n_rows, hkv), 0.5, np.float32),
        'v_scale': np.full((L, n_rows, hkv), 0.25, np.float32),
    }


@pytest.mark.parametrize('dtype', ['bf16', 'int8'])
def test_wire_fuzz_handoff_every_byte(dtype):
    """Flip EVERY byte of a v2 SKKV container, one at a time — magic,
    header, every buffer, every checksum — and assert the decoder
    refuses each mutation with ValueError. Zero silent mis-decodes:
    the property that makes a bit-flipped handoff a retryable refusal
    instead of a byte-wrong continuation."""
    from skypilot_tpu.inference import kv_transfer as kt
    snap = _bf16_snapshot() if dtype == 'bf16' else _int8_snapshot()
    blob = kt.encode_handoff(snap)
    ref = kt.decode_handoff(blob)            # pristine decodes fine
    assert ref['n_rows'] == snap['n_rows']
    for i in range(len(blob)):
        mutated = bytearray(blob)
        mutated[i] ^= 0xff
        with pytest.raises(ValueError):
            kt.decode_handoff(bytes(mutated))


def test_wire_fuzz_prefix_and_checkpoint_every_byte():
    from skypilot_tpu.inference import kv_transfer as kt
    snap = _int8_snapshot()
    pe = kt.as_prefix_entry(snap)
    pblob = kt.encode_prefix_chain(pe)
    assert kt.decode_prefix_chain(pblob)['tokens'] == pe['tokens']
    for i in range(len(pblob)):
        mutated = bytearray(pblob)
        mutated[i] ^= 0xff
        with pytest.raises(ValueError):
            kt.decode_prefix_chain(bytes(mutated))
    cblob = kt.encode_checkpoint([snap, pe])
    kinds = [e['entry_kind'] for e in kt.decode_checkpoint(cblob)]
    assert kinds == ['request', 'prefix']
    for i in range(len(cblob)):
        mutated = bytearray(cblob)
        mutated[i] ^= 0xff
        with pytest.raises(ValueError):
            kt.decode_checkpoint(bytes(mutated))


def _downgrade_handoff_to_v1(blob, magic):
    """Re-pack a v2 container as the version-1 (pre-checksum) layout:
    version=1 header, no crc32 manifest entries, no trailing header
    CRC — what an old replica's checkpoint file looks like."""
    off = len(magic)
    (hlen,) = struct.unpack_from('>I', blob, off)
    header = json.loads(blob[off + 4:off + 4 + hlen])
    header['version'] = 1
    for meta in header['buffers']:
        meta.pop('crc32', None)
    hj = json.dumps(header).encode()
    body = blob[off + 4 + hlen:len(blob) - 4]     # strip header CRC
    return magic + struct.pack('>I', len(hj)) + hj + body


def test_wire_v1_containers_still_decode():
    """Old (version-1, pre-checksum) containers stay readable — a
    checkpoint written before the CRC rollout must still warm a new
    replica."""
    from skypilot_tpu.inference import kv_transfer as kt
    snap = _int8_snapshot()
    v1 = _downgrade_handoff_to_v1(kt.encode_handoff(snap), kt.MAGIC)
    out = kt.decode_handoff(v1)
    assert out['n_rows'] == snap['n_rows']
    np.testing.assert_array_equal(out['k'], snap['k'])
    pe = kt.as_prefix_entry(snap)
    v1p = _downgrade_handoff_to_v1(kt.encode_prefix_chain(pe),
                                   kt.PREFIX_MAGIC)
    assert kt.decode_prefix_chain(v1p)['tokens'] == pe['tokens']
    # v1 SKCK: version word 1, 8-byte (crc-less) entry prefixes.
    out_blobs = [kt.encode_handoff(snap)]
    v1c = (kt.CKPT_MAGIC + struct.pack('>I', 1)
           + struct.pack('>I', len(out_blobs))
           + b''.join(struct.pack('>Q', len(b)) + b
                      for b in out_blobs))
    entries = kt.decode_checkpoint(v1c)
    assert [e['entry_kind'] for e in entries] == ['request']


def test_corrupt_container_lands_nothing(tmp_path):
    """All-or-nothing warmup: a corrupt checkpoint body raises BEFORE
    any pool/slot mutation — the pool's page accounting is untouched
    (a truncated-or-corrupt body can never partially land rows)."""
    from skypilot_tpu.inference import kv_transfer as kt
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'),
                               max_batch=2, max_seq=64)
    rid = eng.add_request(list(range(1, 20)), max_new_tokens=4)
    eng.run_to_completion()
    entries, _ = eng.export_prefix_snapshots()
    assert entries, 'expected a cached prefix chain to export'
    blob = kt.encode_checkpoint(entries)
    free0 = len(eng.alloc.free)
    retained0 = len(eng.alloc.retained)
    corrupt = bytearray(blob)
    corrupt[len(blob) // 2] ^= 0xff               # mid-buffer flip
    with pytest.raises(ValueError):
        kt.decode_checkpoint(bytes(corrupt))
    assert len(eng.alloc.free) == free0
    assert len(eng.alloc.retained) == retained0
    del rid


# ---------------------------------------------------------------------------
# NaN blast-radius isolation
# ---------------------------------------------------------------------------
def test_mask_nonfinite_tokens_unit():
    import jax.numpy as jnp
    from skypilot_tpu.models import llama
    logits = jnp.array([[1.0, 2.0, 3.0],
                        [1.0, jnp.nan, 3.0],
                        [jnp.inf, 2.0, 3.0],
                        [0.0, 0.0, 0.0]])
    toks = jnp.array([2, 1, 0, 0], jnp.int32)
    out = np.asarray(llama.mask_nonfinite_tokens(logits, toks))
    assert out.tolist() == [2, llama.NONFINITE_TOKEN,
                            llama.NONFINITE_TOKEN, 0]


def test_nan_poisoned_params_evict_all():
    """Poisoned weights (every logits row NaN): every live request is
    evicted with ``nan_evicted`` — never streamed as argmax-of-NaN
    (which is token 0, silently plausible)."""
    import jax.numpy as jnp
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64)
    rid0 = eng.add_request([1, 2, 3, 4], max_new_tokens=4)
    fin = eng.run_to_completion()
    assert len(fin[rid0].output) == 4            # healthy baseline
    eng.params['final_norm'] = jnp.full_like(eng.params['final_norm'],
                                             jnp.nan)
    rid = eng.add_request([5, 6, 7, 8], max_new_tokens=4)
    evicted = []
    for _ in range(50):
        if not (eng.has_work() or eng._pending):
            break
        for r, tok, done in eng.step(horizon=2):
            if r == rid and tok < 0 and done:
                evicted.append(r)
    assert evicted == [rid]
    assert eng.nan_evictions >= 1
    assert eng.num_active == 0
    assert eng.pop_finished(rid) is None          # never "finished"


def test_nan_blast_radius_is_one_request():
    """Co-batched isolation: when ONE slot's readback carries the
    sentinel, exactly that request is evicted; its neighbor's tokens
    land and the neighbor runs to completion untouched."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64)
    # On the CPU every result is ready at once: keep the pipeline's lag,
    # which this test reaches into.
    eng._eager_drain = False
    ra = eng.add_request([1, 2, 3, 4], max_new_tokens=6)
    rb = eng.add_request([9, 8, 7, 6], max_new_tokens=6)
    # Drive until both are decoding with a pending decode call.
    for _ in range(20):
        eng.step(horizon=1)
        if (eng.num_active == 2 and eng._pending
                and eng._pending[0]['kind'] == 'decode'):
            break
    assert eng._pending and eng._pending[0]['kind'] == 'decode'
    entry = eng._pending[0]
    slot_a = next(s for s, r in enumerate(entry['snapshot'])
                  if r is not None and r.request_id == ra)
    toks = np.array(jax.device_get(entry['toks']))
    toks[slot_a, :] = -1                          # poison ONE slot
    entry['toks'] = toks                          # host array: readback
    events = eng._process_one()
    assert (ra, -1, True) in events
    assert all(tok >= 0 for r, tok, _ in events if r == rb)
    req_a = next(r for r in [entry['snapshot'][slot_a]])
    assert req_a.nan_evicted
    # The neighbor finishes normally.
    fin = eng.run_to_completion()
    assert rb in fin and len(fin[rb].output) == 6
    assert ra not in fin


def test_scheduler_turns_sentinel_into_retryable_error():
    """The scheduler fails exactly the poisoned request's outbox with
    a retryable NaN message and ticks the gray-failure counter; other
    events in the same batch route normally."""
    from skypilot_tpu import telemetry
    from skypilot_tpu.serve import scheduler as sched_lib

    class FakeEngine:
        max_batch = 4
        num_active = 0
        queue_depth = 0
        _next = 100

        def add_request(self, prompt, **kw):
            FakeEngine._next += 1
            return FakeEngine._next

        def pop_finished(self, rid):
            return None

        def remaining_work_tokens(self):
            return 0

    lock = threading.Lock()
    sched = sched_lib.RequestScheduler(lock)
    eng = FakeEngine()
    sched.bind_engine(eng)
    sra = sched.submit([1, 2], max_new_tokens=4)
    srb = sched.submit([3, 4], max_new_tokens=4)
    sched.fill_engine(eng)
    assert sra.request_id is not None and srb.request_id is not None
    c = telemetry.get_registry().counter(
        'skytpu_gray_failures_total',
        'Gray failures detected by the data-plane defense layer',
        kind='nan_logits')
    before = c.value
    sched.on_events(eng, [(sra.request_id, -1, True),
                          (srb.request_id, 7, False)])
    assert c.value == before + 1
    tok, done = sra.outbox.get(timeout=5)
    assert tok is None and done
    assert 'non-finite' in sra.outbox.error
    tok, done = srb.outbox.get(timeout=5)
    assert tok == 7 and not done                 # neighbor untouched


# ---------------------------------------------------------------------------
# Wedge watchdog
# ---------------------------------------------------------------------------
def _make_server(**kw):
    from skypilot_tpu.serve.server import ModelServer
    kw.setdefault('max_batch', 2)
    kw.setdefault('max_seq', 128)
    kw.setdefault('port', common_utils.find_free_port(19900))
    return ModelServer('tiny', **kw)


def test_watchdog_virtual_clock_unit():
    """Clock-injected watchdog: arming a step and advancing the
    virtual clock past the deadline flips the replica to degraded,
    fails the scheduler over, and ticks the gray counter — without
    ever loading an engine or starting HTTP."""
    from skypilot_tpu import telemetry
    clock = {'t': 100.0}
    srv = _make_server(step_watchdog_s=5.0,
                       watchdog_clock=lambda: clock['t'])
    assert srv.watchdog_age_s() == 0.0
    assert srv.watchdog_check() is False          # nothing armed
    srv._wd_arm()
    clock['t'] += 4.0
    assert srv.watchdog_check() is False          # under deadline
    assert 3.9 < srv.watchdog_age_s() < 4.1
    clock['t'] += 2.0
    c = telemetry.get_registry().counter(
        'skytpu_gray_failures_total',
        'Gray failures detected by the data-plane defense layer',
        kind='wedged_step')
    before = c.value
    assert srv.watchdog_check() is True           # fired
    assert c.value == before + 1
    assert srv._degraded is not None and 'wedged_step' in srv._degraded
    assert not srv._ready.is_set()
    with pytest.raises(RuntimeError):
        srv.sched.submit([1, 2], max_new_tokens=2)
    assert srv.watchdog_check() is False          # fires exactly once
    # A cleared stamp reports age 0 (the scrape-time gauge value).
    srv._wd_clear()
    assert srv.watchdog_age_s() == 0.0


def test_watchdog_disabled_never_fires():
    clock = {'t': 0.0}
    srv = _make_server(step_watchdog_s=0,
                       watchdog_clock=lambda: clock['t'])
    srv._wd_arm()
    clock['t'] += 1e6
    assert srv.watchdog_check() is False
    assert srv._degraded is None


def test_nan_alarm_escalates_to_degraded():
    """Repeated NaN evictions cross the replica-level alarm threshold:
    the server degrades (sick replica — bad HBM / corrupt weights),
    instead of evicting single requests forever."""
    srv = _make_server(nan_alarm_threshold=3, step_watchdog_s=0)
    assert srv.nan_alarm_threshold == 3
    # The escalation predicate the engine loop applies:
    srv._nan_seen = 3
    srv._gray_degrade('nan_logits', 'replica-level NaN storm',
                      count=False)
    assert srv._degraded is not None and 'nan_logits' in srv._degraded
    assert not srv._ready.is_set()


@pytest.mark.slow
def test_injected_wedge_detected_and_contained():
    """e2e: an injected wedged_step hangs the engine loop mid-run; the
    watchdog (tiny deadline) flips /readiness to a degraded 503, the
    in-flight stream gets a RETRYABLE error, and new submits get a
    retryable 503 — the exact surface the manager and LB act on."""
    port = common_utils.find_free_port(19920)
    srv = _make_server(
        port=port, step_watchdog_s=0.5,
        fault_spec={'seed': 0, 'rules': [
            {'kind': 'wedged_step', 'site': 'engine_step', 'at': 2}]})
    srv.start(block=False)
    try:
        deadline = time.time() + 120
        while time.time() < deadline and not srv._ready.is_set():
            time.sleep(0.2)
        assert srv._ready.is_set()
        body = json.dumps({'prompt': [3, 1, 4, 1, 5], 'stream': True,
                           'max_new_tokens': 64}).encode()
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate', body,
            {'Content-Type': 'application/json'})
        error_ev = None
        with urllib.request.urlopen(req, timeout=120) as r:
            for line in r:
                if not line.startswith(b'data:'):
                    continue
                ev = json.loads(line[5:].strip())
                if 'error' in ev:
                    error_ev = ev
                    break
                if ev.get('done'):
                    break
        assert error_ev is not None, 'wedge never surfaced'
        assert error_ev.get('retryable') is True
        # Readiness reports the degraded state (the manager's probe
        # escalation replaces the replica).
        try:
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/readiness',
                    timeout=10) as r:
                payload = json.loads(r.read())
        except urllib.error.HTTPError as e:
            assert e.code == 503
            payload = json.loads(e.read())
        assert payload.get('status') == 'degraded'
        assert 'wedged_step' in payload.get('cause', '')
        # New submits: retryable 503 (the LB retries elsewhere).
        body2 = json.dumps({'prompt': [1, 2],
                            'max_new_tokens': 2}).encode()
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(urllib.request.Request(
                f'http://127.0.0.1:{port}/generate', body2,
                {'Content-Type': 'application/json'}), timeout=10)
        assert exc.value.code == 503
        assert 'Retry-After' in exc.value.headers
    finally:
        srv.stop()


def _sse_stream(base, prompt, n, timeout=180):
    body = json.dumps({'prompt': prompt, 'stream': True,
                       'max_new_tokens': n}).encode()
    req = urllib.request.Request(
        base + '/generate', body, {'Content-Type': 'application/json'})
    toks, done, err = [], None, None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for line in r:
            if not line.startswith(b'data:'):
                continue
            ev = json.loads(line[5:].strip())
            if 'token' in ev:
                toks.append(int(ev['token']))
            if 'error' in ev:
                err = ev
                break
            if ev.get('done'):
                done = ev
                break
    return toks, done, err


@pytest.mark.slow
def test_injected_nan_evicts_one_stream_direct():
    """e2e (single replica, no LB): an injected nan_logits evicts the
    live stream with a RETRYABLE error (the event the LB's recovery
    resubmits on), a single hit never trips the replica alarm, and the
    server keeps serving afterwards."""
    port = common_utils.find_free_port(19960)
    srv = _make_server(
        port=port, step_watchdog_s=0, nan_alarm_threshold=100,
        fault_spec={'seed': 0, 'rules': [
            {'kind': 'nan_logits', 'site': 'engine_step', 'at': 2}]})
    srv.start(block=False)
    try:
        deadline = time.time() + 120
        while time.time() < deadline and not srv._ready.is_set():
            time.sleep(0.2)
        toks, done, err = _sse_stream(f'http://127.0.0.1:{port}',
                                      [3, 1, 4, 1, 5], 96)
        assert err is not None and done is None
        assert err.get('retryable') is True
        assert 'non-finite' in str(err.get('error'))
        assert srv.engine.nan_evictions == 1
        assert srv._degraded is None          # one hit: no alarm
        # The replica keeps serving (blast radius was one request).
        toks2, done2, err2 = _sse_stream(f'http://127.0.0.1:{port}',
                                         [9, 8, 7], 8)
        assert err2 is None and len(toks2) == 8
    finally:
        srv.stop()


@pytest.mark.slow
def test_nan_evicted_stream_migrates_byte_identical_through_lb(
        monkeypatch):
    """The acceptance contract: a NaN-evicted stream through the live
    LB migrates to the surviving replica and the client sees ONE
    complete stream whose tokens are byte-identical to an
    uninterrupted run — zero lost requests."""
    import sys
    sys.path.insert(0, 'tests')
    from test_chaos import _FakeController, _start_lb
    from skypilot_tpu import telemetry
    pa = common_utils.find_free_port(20200)
    pb = common_utils.find_free_port(pa + 1)
    # Replica A evicts its first live request (latched nan_logits);
    # replica B is healthy — and the byte-identity reference.
    sa = _make_server(port=pa, step_watchdog_s=0,
                      nan_alarm_threshold=100,
                      fault_spec={'seed': 0, 'rules': [
                          {'kind': 'nan_logits', 'site': 'engine_step',
                           'at': 2}]})
    sb = _make_server(port=pb, step_watchdog_s=0)
    sa.start(block=False)
    sb.start(block=False)
    ctrl = lb = None
    try:
        deadline = time.time() + 180
        while time.time() < deadline and not (
                sa._ready.is_set() and sb._ready.is_set()):
            time.sleep(0.2)
        prompt = [3, 1, 4, 1, 5]
        ref, ref_done, ref_err = _sse_stream(
            f'http://127.0.0.1:{pb}', prompt, 96)
        assert ref_err is None and len(ref) == 96
        # Round-robin selects candidates[0] == replica A for the first
        # request — it lands on the nan-injected replica.
        ctrl = _FakeController([f'http://127.0.0.1:{pa}',
                                f'http://127.0.0.1:{pb}'])
        lb, lb_port = _start_lb(ctrl.url, monkeypatch)
        reg = telemetry.get_registry()
        mig0 = reg.counter('skytpu_requests_migrated_total',
                           'In-flight requests migrated off a failed '
                           'replica', outcome='completed').value
        toks, done, err = _sse_stream(f'http://127.0.0.1:{lb_port}',
                                      prompt, 96)
        assert err is None, err               # zero lost
        assert done is not None
        assert sa.engine.nan_evictions == 1   # A really evicted it
        assert len(toks) == 96
        assert toks == ref                    # byte-identical
        assert done['tokens'] == ref
        # The migrated counter ticks right AFTER the done event flushes
        # — poll briefly instead of racing the LB thread.
        deadline = time.time() + 10
        mc = reg.counter(
            'skytpu_requests_migrated_total',
            'In-flight requests migrated off a failed replica',
            outcome='completed')
        while time.time() < deadline and mc.value < mig0 + 1:
            time.sleep(0.05)
        assert mc.value == mig0 + 1
    finally:
        if lb is not None:
            lb.stop()
        if ctrl is not None:
            ctrl.stop()
        sa.stop()
        sb.stop()


# ---------------------------------------------------------------------------
# Byzantine canary + quarantine (manager-level, fake env)
# ---------------------------------------------------------------------------
class _CanaryEnv:
    """ControlPlaneEnv double: virtual clock + canned canary answers +
    recorded drain/teardown calls."""

    def __init__(self, answers):
        # url -> token list answered to /generate canaries.
        self.answers = dict(answers)
        self.t = 1000.0
        self.drained = []
        self.downed = []
        import random as random_mod
        self._rng = random_mod.Random(0)

    # time
    def time(self):
        return self.t

    def monotonic(self):
        return self.t

    def sleep(self, s):
        self.t += s

    # concurrency: run spawned tasks INLINE (deterministic tests)
    def spawn(self, fn, *args):
        fn(*args)

    def run_parallel(self, fns):
        for fn in fns:
            fn()

    def rng(self):
        return self._rng

    # HTTP
    def http_json(self, url, payload=None, timeout=10.0):
        base, _, path = url.partition('//')[2].partition('/')
        path = '/' + path
        if path == '/generate':
            return {'tokens': list(self.answers[f'http://{base}'])}
        if path == '/drain':
            self.drained.append(f'http://{base}')
            return {'draining': True, 'drained': True, 'inflight': 0}
        raise RuntimeError(f'unexpected {url}')

    def http_post_bytes(self, url, data, content_type='', timeout=30.0):
        raise RuntimeError('unused')

    def probe_http(self, url, post_data, timeout):
        return True

    # clusters
    def launch_cluster(self, task, cluster_name):
        pass

    def cluster_head_ip(self, cluster_name):
        return '127.0.0.1'

    def down_cluster(self, cluster_name):
        self.downed.append(cluster_name)

    def cluster_gone(self, cluster_name):
        return False

    # persistence / faults
    def persist_replica(self, *a, **kw):
        pass

    def remove_replica(self, *a, **kw):
        pass

    def fault_injector(self):
        return None


def _canary_manager(tmp_path, monkeypatch, env):
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    from skypilot_tpu.serve.replica_managers import ReplicaManager
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec.from_yaml_config(
        {'readiness_probe': '/readiness'})
    return ReplicaManager('gray-test', spec, {}, env=env)


def _seed_ready(mgr, replica_id, url):
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import ReplicaInfo
    info = ReplicaInfo(replica_id, f'gray-c{replica_id}', 1, False,
                       8000 + replica_id)
    info.url = url
    info.status = serve_state.ReplicaStatus.READY
    with mgr._lock:
        mgr._replicas[replica_id] = info
    return info


def test_canary_digest_stable():
    from skypilot_tpu.serve.replica_managers import canary_digest
    assert canary_digest([1, 2, 3]) == canary_digest((1, 2, 3))
    assert canary_digest([1, 2, 3]) != canary_digest([1, 2, 4])
    assert len(canary_digest([])) == 16


def test_byzantine_replica_quarantined_before_second_response(
        tmp_path, monkeypatch):
    """Two replicas: the first answers the canary honestly (reference
    digest learned), the second answers WRONG — it is quarantined on
    that very first wrong canary: out of ready_urls immediately,
    drained, torn down, counted."""
    from skypilot_tpu import telemetry
    from skypilot_tpu.serve import serve_state
    env = _CanaryEnv({'http://10.0.0.1:8001': [5, 6, 7],
                      'http://10.0.0.2:8002': [5, 6, 99]})
    mgr = _canary_manager(tmp_path, monkeypatch, env)
    mgr.configure_canary(interval_s=30.0, prompt=[11, 13],
                         max_new_tokens=3)
    good = _seed_ready(mgr, 1, 'http://10.0.0.1:8001')
    bad = _seed_ready(mgr, 2, 'http://10.0.0.2:8002')
    reg = telemetry.get_registry()
    q0 = reg.counter(
        'skytpu_replicas_quarantined_total',
        'Replicas quarantined after a byzantine (wrong-digest) '
        'canary response').value
    g0 = reg.counter(
        'skytpu_gray_failures_total',
        'Gray failures detected by the data-plane defense layer',
        kind='byzantine_response').value
    mgr.probe_all()
    # Replica 1 learned the reference; replica 2 mismatched -> gone.
    assert mgr._canary_learned is not None
    assert bad.status in (serve_state.ReplicaStatus.QUARANTINED,
                          serve_state.ReplicaStatus.SHUTTING_DOWN)
    assert good.status == serve_state.ReplicaStatus.READY
    assert mgr.ready_urls() == ['http://10.0.0.1:8001']
    assert mgr.quarantined_count == 1
    assert reg.counter(
        'skytpu_replicas_quarantined_total',
        'Replicas quarantined after a byzantine (wrong-digest) '
        'canary response').value == q0 + 1
    assert reg.counter(
        'skytpu_gray_failures_total',
        'Gray failures detected by the data-plane defense layer',
        kind='byzantine_response').value == g0 + 1
    # The quarantined replica was drained then torn down (the inline
    # env runs the spawned drain->down chain synchronously). Its
    # cluster is in the downed list; the healthy one is untouched.
    assert any('gray-c2' in c for c in env.downed)
    assert not any('gray-c1' in c for c in env.downed)
    # A second canary round against the survivor changes nothing.
    env.t += 60.0
    mgr.probe_all()
    assert mgr.quarantined_count == 1
    assert good.status == serve_state.ReplicaStatus.READY


def test_canary_expected_digest_catches_first_answerer(
        tmp_path, monkeypatch):
    """With a configured expected digest the first answerer gets no
    learn-the-reference grace — a byzantine FIRST replica is caught
    too (closing the quorum-of-one window)."""
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import canary_digest
    env = _CanaryEnv({'http://10.0.0.9:8009': [1, 2, 3]})
    mgr = _canary_manager(tmp_path, monkeypatch, env)
    mgr.configure_canary(interval_s=10.0, prompt=[11],
                         max_new_tokens=3,
                         expected_digest=canary_digest([7, 7, 7]))
    bad = _seed_ready(mgr, 3, 'http://10.0.0.9:8009')
    mgr.probe_all()
    assert bad.status in (serve_state.ReplicaStatus.QUARANTINED,
                          serve_state.ReplicaStatus.SHUTTING_DOWN)
    assert mgr.quarantined_count == 1


def test_canary_interval_and_transport_failures(tmp_path, monkeypatch):
    """Canary cadence rides the env clock; transport failures are NOT
    byzantine (liveness belongs to the readiness probes)."""
    env = _CanaryEnv({'http://10.0.0.1:8001': [5, 6, 7]})
    mgr = _canary_manager(tmp_path, monkeypatch, env)
    mgr.configure_canary(interval_s=100.0, prompt=[11],
                         max_new_tokens=3)
    info = _seed_ready(mgr, 1, 'http://10.0.0.1:8001')
    mgr.probe_all()
    t_first = info.last_canary_t
    assert t_first > 0
    env.t += 10.0
    mgr.probe_all()                      # within cadence: no canary
    assert info.last_canary_t == t_first
    # Transport failure: replica vanishes from the answer table.
    env.t += 200.0
    env.answers.pop('http://10.0.0.1:8001')
    env.answers['http://10.0.0.1:8001'] = None  # -> TypeError inside

    def boom(url, payload=None, timeout=10.0):
        raise ConnectionRefusedError('canary transport down')

    env.http_json = boom
    mgr.probe_all()
    assert mgr.quarantined_count == 0    # not quarantined
    from skypilot_tpu.serve import serve_state
    assert info.status == serve_state.ReplicaStatus.READY


def test_injected_byzantine_fault_site(tmp_path, monkeypatch):
    """The 'canary' fault site (kind byzantine_response) forces the
    quarantine path deterministically — no corrupt replica needed."""
    from skypilot_tpu.serve import serve_state
    env = _CanaryEnv({'http://10.0.0.1:8001': [5, 6, 7],
                      'http://10.0.0.2:8002': [5, 6, 7]})
    mgr = _canary_manager(tmp_path, monkeypatch, env)
    mgr.configure_canary(interval_s=5.0, prompt=[11], max_new_tokens=3)
    mgr._faults = faults_lib.FaultInjector({'rules': [
        {'kind': 'byzantine_response', 'site': 'canary', 'at': 2}]})
    a = _seed_ready(mgr, 1, 'http://10.0.0.1:8001')
    b = _seed_ready(mgr, 2, 'http://10.0.0.2:8002')
    mgr.probe_all()
    quarantined = [i for i in (a, b)
                   if i.status in (
                       serve_state.ReplicaStatus.QUARANTINED,
                       serve_state.ReplicaStatus.SHUTTING_DOWN)]
    assert len(quarantined) == 1         # exactly the 2nd canary
    assert mgr.quarantined_count == 1


@pytest.mark.slow
def test_live_canary_quarantine_through_lb(tmp_path, monkeypatch):
    """e2e: the manager canaries two LIVE model servers over real HTTP
    (greedy /generate, digest learned from the first), an injected
    byzantine_response quarantines the second on its FIRST wrong
    canary, and an LB policy synced from ready_urls immediately stops
    selecting it — while the healthy replica keeps serving."""
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    from skypilot_tpu.serve import load_balancing_policies as lbp
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import ReplicaManager
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    pa = common_utils.find_free_port(20700)
    pb = common_utils.find_free_port(pa + 1)
    sa = _make_server(port=pa, step_watchdog_s=0)
    sb = _make_server(port=pb, step_watchdog_s=0)
    sa.start(block=False)
    sb.start(block=False)
    try:
        deadline = time.time() + 180
        while time.time() < deadline and not (
                sa._ready.is_set() and sb._ready.is_set()):
            time.sleep(0.2)
        spec = SkyServiceSpec.from_yaml_config(
            {'readiness_probe': '/readiness'})
        mgr = ReplicaManager('gray-live', spec, {})
        mgr.configure_canary(interval_s=0.01, prompt=[11, 13, 17],
                             max_new_tokens=6)
        # The injected byzantine hits the SECOND canaried replica.
        mgr._faults = faults_lib.FaultInjector({'rules': [
            {'kind': 'byzantine_response', 'site': 'canary',
             'at': 2}]})
        infos = []
        for rid, port in ((1, pa), (2, pb)):
            info = _seed_ready(mgr, rid, f'http://127.0.0.1:{port}')
            infos.append(info)
        # Canary both (replica ids iterate in insertion order): the
        # first answers honestly over live HTTP and sets the learned
        # digest; the second is forced byzantine.
        assert mgr._canary_check(infos[0]) is False
        assert mgr._canary_learned is not None
        assert mgr._canary_check(infos[1]) is True
        assert infos[1].status in (
            serve_state.ReplicaStatus.QUARANTINED,
            serve_state.ReplicaStatus.SHUTTING_DOWN)
        assert mgr.quarantined_count == 1
        # ready_urls -> LB policy: the quarantined replica is excluded
        # from selection IMMEDIATELY (before it can serve a second
        # wrong response to routed traffic).
        urls = mgr.ready_urls()
        assert urls == [f'http://127.0.0.1:{pa}']
        pol = lbp.make_policy('round_robin')
        pol.set_ready_replicas(urls)
        for _ in range(4):
            assert pol.select_replica() == f'http://127.0.0.1:{pa}'
        # The healthy replica still serves.
        toks, done, err = _sse_stream(f'http://127.0.0.1:{pa}',
                                      [1, 2, 3], 6)
        assert err is None and len(toks) == 6
    finally:
        sa.stop()
        sb.stop()


def test_quarantined_is_terminal_and_excluded():
    from skypilot_tpu.serve import serve_state
    st = serve_state.ReplicaStatus.QUARANTINED
    assert st.is_terminal()
    # LB-policy exclusion: quarantined replicas never reach
    # set_ready_replicas (ready_urls filters on READY), so a policy
    # fed the post-quarantine list cannot select them.
    from skypilot_tpu.serve import load_balancing_policies as lbp
    pol = lbp.make_policy('round_robin')
    pol.set_ready_replicas(['http://a', 'http://b'])
    pol.set_ready_replicas(['http://a'])     # b quarantined
    for _ in range(4):
        assert pol.select_replica() == 'http://a'


# ---------------------------------------------------------------------------
# Corrupted wire -> fallback-local (server-level)
# ---------------------------------------------------------------------------
def test_corrupt_warmup_rejected_with_gray_tick(tmp_path):
    """A corrupted checkpoint container posted to warm_from_checkpoint
    raises (ValueError — the HTTP surface turns it into a 400) and the
    server-side gray counter path recognizes the checksum signature."""
    from skypilot_tpu.inference import kv_transfer as kt
    snap = _int8_snapshot()
    blob = kt.encode_checkpoint([snap])
    corrupt = bytearray(blob)
    corrupt[len(blob) - 20] ^= 0xff
    with pytest.raises(ValueError) as exc:
        kt.decode_checkpoint(bytes(corrupt))
    # The 400 paths key the kv_corruption gray tick on this signature.
    assert ('checksum mismatch' in str(exc.value)
            or 'malformed' in str(exc.value))


def test_corrupt_blob_deterministic():
    rule = faults_lib.FaultRule(kind='kv_corruption', site='kv_wire',
                                at=1, n=5)
    blob = bytes(range(10))
    out = faults_lib.corrupt_blob(blob, rule)
    assert out != blob and len(out) == len(blob)
    assert out == faults_lib.corrupt_blob(blob, rule)   # deterministic
    assert out[5] == blob[5] ^ 0xff
    assert faults_lib.corrupt_blob(b'', rule) == b''


def test_new_fault_kinds_and_sites_validate():
    """The four gray kinds/sites parse strictly (reusing the round-12
    loud-unknown-field machinery): valid rules parse, typo'd sites and
    trigger-less rules are loud ValueErrors."""
    inj = faults_lib.FaultInjector({'seed': 1, 'rules': [
        {'kind': 'wedged_step', 'site': 'engine_step', 'at': 2},
        {'kind': 'nan_logits', 'site': 'engine_step', 'every': 3},
        {'kind': 'kv_corruption', 'site': 'kv_wire', 'at': 1, 'n': 9},
        {'kind': 'byzantine_response', 'site': 'canary', 'at': 1},
        {'kind': 'nan_logits', 'site': 'sim_gray', 'at': 1, 'n': 4},
    ]})
    assert inj.fire('kv_wire') is not None
    with pytest.raises(ValueError, match='unknown fault site'):
        faults_lib.FaultInjector({'rules': [
            {'kind': 'wedged_step', 'site': 'engine_stepp', 'at': 1}]})
    with pytest.raises(ValueError, match='unknown fault kind'):
        faults_lib.FaultInjector({'rules': [
            {'kind': 'wedgedstep', 'site': 'engine_step', 'at': 1}]})
    with pytest.raises(ValueError, match='no.*trigger|trigger'):
        faults_lib.FaultInjector({'rules': [
            {'kind': 'byzantine_response', 'site': 'canary'}]})
    with pytest.raises(ValueError, match='unknown fault-rule field'):
        faults_lib.FaultInjector({'rules': [
            {'kind': 'kv_corruption', 'site': 'kv_wire', 'att': 1}]})


# ---------------------------------------------------------------------------
# Fleet-scale gray storm (simulator)
# ---------------------------------------------------------------------------
def test_sim_gray_failure_storm_zero_lost():
    """The fleet-scale drill: one wedged replica, a NaN burst, a
    byzantine replica, and a bit-flipped checkpoint — the REAL control
    plane (manager probes, canary quarantine, drain, autoscaler
    replacement) contains all four with zero lost requests, and the
    byzantine replica is quarantined on its first wrong canary."""
    from skypilot_tpu.serve.sim import scenarios
    rep = scenarios.run_scenario('gray_failure_storm', seed=5)
    assert rep['requests']['lost'] == 0
    assert rep['replicas']['quarantined'] == 1
    fired = rep['faults_fired']
    assert fired.get('sim_gray:wedged_step') == 1
    assert fired.get('sim_gray:nan_logits') == 1
    assert fired.get('sim_gray:byzantine_response') == 1
    assert fired.get('kv_wire:kv_corruption') == 1
    assert rep['requests']['migrated'] > 0       # NaN evictions et al.
    # Determinism: same seed, byte-identical event log.
    rep2 = scenarios.run_scenario('gray_failure_storm', seed=5)
    assert rep['event_log_sha256'] == rep2['event_log_sha256']


def test_sim_wedged_replica_is_gray():
    """A wedged SimReplica accepts work (HTTP alive) but its readiness
    degrades — the exact gray contract the live watchdog produces."""
    from skypilot_tpu.serve.sim import replica as sim_replica
    curve = sim_replica.ServiceCurve.from_bench()
    rep = sim_replica.SimReplica('c', 'http://10.0.0.1:1', curve,
                                 lambda: 0.0)
    rep.wedged = True
    job = rep.enqueue(0.0, 2, 100.0, 50.0, 'latency')
    assert job is not None                        # still ACCEPTS work
    assert job.finish_t > 1e9                     # ... that never ends
    with pytest.raises(sim_replica.SimHTTPError):
        rep.handle('/readiness', None, None)
    # Canary surface: healthy vs byzantine answers differ, healthy
    # answers are fleet-identical.
    healthy = rep.handle('/generate', {'prompt': [11, 13],
                                       'max_new_tokens': 4}, None)
    rep2 = sim_replica.SimReplica('c2', 'http://10.0.0.2:1', curve,
                                  lambda: 0.0)
    assert rep2.handle('/generate', {'prompt': [11, 13],
                                     'max_new_tokens': 4},
                       None) == healthy
    rep2.byzantine = True
    assert rep2.handle('/generate', {'prompt': [11, 13],
                                     'max_new_tokens': 4},
                       None) != healthy
