"""Multi-host gang serving (round 11): replicas as process gangs that
launch, drain, checkpoint, and die together.

The contract under test is **gang atomicity**: a gang presents exactly
one routable endpoint (rank 0), becomes READY only when every rank
passed the barrier within the join timeout, fans drain/checkpoint out
to every rank and completes them only on all-rank ack, and fails AS A
WHOLE the moment any rank dies — with the LB's in-flight recovery
holding the zero-lost, byte-identical-continuation contract across the
gang's death. On CPU the gang runs the ``replicated`` data plane: every
rank holds a full model copy, replays rank 0's op log, and lockstep is
verified byte-exactly through finished-request digests.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

import greedy_oracle
from skypilot_tpu import telemetry
from skypilot_tpu.serve import faults as faults_lib
from skypilot_tpu.serve import gang as gang_lib
from skypilot_tpu.utils import common_utils

jax.config.update('jax_platforms', 'cpu')

_FAST = dict(max_batch=2, max_seq=160)


def _leader_spec(world=2, **kw):
    kw.setdefault('join_timeout_s', 180.0)
    kw.setdefault('heartbeat_s', 0.05)
    # Generous default: a follower applying a step op that still
    # COMPILES can legitimately go seconds between heartbeats on CPU;
    # the kill test warms the compile caches first and then tightens
    # this to get fast, deliberate detection.
    kw.setdefault('heartbeat_timeout_s', 60.0)
    return gang_lib.GangSpec(gang_id=kw.pop('gang_id', 'g-test'),
                             rank=0, world=world, **kw)


def _follower_spec(coordinator, rank=1, world=2, **kw):
    kw.setdefault('join_timeout_s', 60.0)
    kw.setdefault('heartbeat_s', 0.05)
    kw.setdefault('heartbeat_timeout_s', 10.0)
    return gang_lib.GangSpec(gang_id=kw.pop('gang_id', 'g-test'),
                             rank=rank, world=world,
                             coordinator=coordinator, **kw)


def _start_leader(port, **gang_kw):
    from skypilot_tpu.serve.server import ModelServer
    srv = ModelServer('tiny', port=port, gang=_leader_spec(**gang_kw),
                      **_FAST)
    srv.start(block=False)
    return srv


def _start_thread_follower(coordinator, *, faults=None, **kw):
    """An in-process follower rank with its own (identical) engine —
    the fast-path stand-in for a separate OS process; the protocol,
    op replay, and failure modes are exactly the process ones."""
    from skypilot_tpu.serve.server import build_engine
    engine = build_engine('tiny', **_FAST)
    follower = gang_lib.GangFollower(_follower_spec(coordinator, **kw),
                                     engine, faults=faults)

    def run():
        try:
            follower.run()
        except faults_lib.InjectedFault:
            pass          # simulated process death: heartbeats stop

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return follower, t


def _await_barrier(srv, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if srv._gang is not None and srv._gang.all_joined:
            return True
        if srv._error is not None:
            return False
        time.sleep(0.05)
    return False


def _generate(base, payload, timeout=180, headers=None):
    h = {'Content-Type': 'application/json'}
    h.update(headers or {})
    req = urllib.request.Request(base + '/generate',
                                 json.dumps(payload).encode(), h)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class _FakeController:
    """Answers the LB's sync POST with a fixed ready-replica list
    (the gang's rank-0 URL only — followers are never routable)."""

    def __init__(self, replica_urls):
        import http.server as hs
        outer_urls = list(replica_urls)

        class H(hs.BaseHTTPRequestHandler):
            timeout = 30

            def log_message(self, *a):
                del a

            def do_POST(self):  # noqa: N802
                body = json.dumps({
                    'ready_replica_urls': outer_urls,
                    'retry_after_s': 5,
                }).encode()
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.port = common_utils.find_free_port(22450)
        self.httpd = hs.ThreadingHTTPServer(('127.0.0.1', self.port), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return f'http://127.0.0.1:{self.port}'

    def stop(self):
        self.httpd.shutdown()


# ---------------------------------------------------------- env contract
def test_gang_spec_env_contract(monkeypatch):
    monkeypatch.setenv(gang_lib.ENV_RANK, '2')
    monkeypatch.setenv(gang_lib.ENV_WORLD, '4')
    monkeypatch.setenv(gang_lib.ENV_COORDINATOR, 'http://h0:8081')
    monkeypatch.setenv(gang_lib.ENV_GANG_ID, 'svc-gang-7')
    monkeypatch.setenv(gang_lib.ENV_JOIN_TIMEOUT, '33')
    monkeypatch.setenv(gang_lib.ENV_HEARTBEAT, '0.2')
    spec = gang_lib.GangSpec.from_env()
    assert (spec.rank, spec.world) == (2, 4)
    assert spec.is_gang and not spec.is_leader
    assert spec.coordinator == 'http://h0:8081'
    assert spec.gang_id == 'svc-gang-7'
    assert spec.join_timeout_s == 33.0
    assert spec.heartbeat_s == 0.2
    assert spec.heartbeat_timeout_s == 2.0      # 10x heartbeat default
    # Explicit args override the env.
    spec = gang_lib.GangSpec.from_env(rank=0, world=1)
    assert not spec.is_gang
    # A nonzero rank with no coordinator is a broken launch.
    monkeypatch.delenv(gang_lib.ENV_COORDINATOR)
    with pytest.raises(ValueError, match='SKYTPU_COORDINATOR'):
        gang_lib.GangSpec.from_env()
    with pytest.raises(ValueError, match='out of range'):
        gang_lib.GangSpec.from_env(rank=5, world=2,
                                   coordinator='http://h0:1')


def test_gang_spec_service_plumbing(monkeypatch, tmp_path):
    """service spec ``parallelism.hosts`` -> placement plan ->
    per-rank launch env on the replica manager's gang tasks."""
    from skypilot_tpu.serve import placement
    from skypilot_tpu.serve.replica_managers import (ReplicaInfo,
                                                     ReplicaManager)
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    spec = SkyServiceSpec.from_yaml_config(
        {'readiness_probe': '/readiness', 'parallelism': {'hosts': 3}})
    assert spec.gang_hosts == 3
    assert spec.to_yaml_config()['parallelism'] == {'hosts': 3}
    assert placement.plan_for_spec(spec).hosts == 3
    mgr = ReplicaManager('gang-env-test', spec, {})
    leader = ReplicaInfo(1, 'c1', 1, False, 10001, gang_id='g',
                         gang_rank=0, gang_world=3)
    follower = ReplicaInfo(2, 'c2', 1, False, 10002, gang_id='g',
                           gang_rank=1, gang_world=3)
    follower.coordinator = 'http://10.0.0.1:10001'
    env0 = mgr._replica_task(leader).envs
    env1 = mgr._replica_task(follower).envs
    assert env0['SKYTPU_GANG_ID'] == 'g' and env0['SKYTPU_RANK'] == '0'
    assert env0['SKYTPU_WORLD'] == '3'
    assert 'SKYTPU_COORDINATOR' not in env0
    assert env1['SKYTPU_RANK'] == '1'
    assert env1['SKYTPU_COORDINATOR'] == 'http://10.0.0.1:10001'
    assert float(env1['SKYTPU_GANG_JOIN_TIMEOUT']) > 0
    # Gangs and disaggregation cannot combine.
    from skypilot_tpu import exceptions
    with pytest.raises(exceptions.InvalidServiceSpecError,
                       match='gang'):
        SkyServiceSpec.from_yaml_config({
            'readiness_probe': '/readiness',
            'parallelism': {'hosts': 2},
            'disaggregation': {'prefill_replicas': 1,
                               'decode_replicas': 1}})


# ----------------------------------------------------- coordinator units
def test_coordinator_protocol_and_trim():
    """Op-log slicing stays correct across trims (the response base is
    captured before the trim advances), commands pin the log index,
    and acks require every rank."""
    spec = gang_lib.GangSpec(gang_id='g', rank=0, world=3,
                             join_timeout_s=10, heartbeat_s=0.05,
                             heartbeat_timeout_s=1.0)
    coord = gang_lib.GangCoordinator(spec)
    assert not coord.all_joined
    for i in range(4):
        coord.append_op({'k': 'step', 'h': 8, 'i': i})
    r1 = coord.sync(1, 0, [], {})
    assert not coord.all_joined          # rank 2 still missing
    r2 = coord.sync(2, 0, [], {})
    assert coord.all_joined
    assert [op['i'] for op in r1['ops']] == [0, 1, 2, 3]
    assert r1['base'] == 0 and r2['base'] == 0
    # Rank 1 applies everything; rank 2 lags at 2. The trim must only
    # advance past the SLOWEST rank, and rank 2's next slice must
    # resume exactly at its applied index.
    coord.sync(1, 4, [], {})
    r2 = coord.sync(2, 2, [], {})
    assert r2['base'] == 2
    assert [op['i'] for op in r2['ops']] == [2, 3]
    # Command ack: pinned at the current log index; acked only once
    # EVERY rank acked.
    cid = coord.command('drain')
    assert not coord.acked(cid)
    coord.sync(1, 4, [cid], {})
    assert not coord.acked(cid)          # rank 2 has not acked
    coord.sync(2, 4, [cid], {})
    assert coord.acked(cid)
    assert coord.wait_acked(cid, timeout=0.1)
    st = coord.status()
    assert st['barrier'] and st['world'] == 3 and st['ops'] == 4


def test_coordinator_failure_causes():
    clock = [0.0]
    spec = gang_lib.GangSpec(gang_id='g', rank=0, world=2,
                             join_timeout_s=5.0, heartbeat_s=0.1,
                             heartbeat_timeout_s=1.0)
    coord = gang_lib.GangCoordinator(spec, clock=lambda: clock[0])
    coord.check()                        # inside the join window
    clock[0] = 6.0
    with pytest.raises(gang_lib.GangFailure) as ei:
        coord.check()                    # nobody joined in time
    assert ei.value.cause == 'join_timeout'
    coord2 = gang_lib.GangCoordinator(spec, clock=lambda: clock[0])
    coord2.sync(1, 0, [], {})
    coord2.check()                       # fresh heartbeat
    clock[0] += 2.0
    with pytest.raises(gang_lib.GangFailure) as ei:
        coord2.check()
    assert ei.value.cause == 'heartbeat_lost'
    # Divergence: a follower's finished digest mismatching rank 0's
    # fails the gang immediately.
    coord3 = gang_lib.GangCoordinator(spec, clock=lambda: clock[0])
    coord3.digest.finished[7] = 'aaaa'
    resp = coord3.sync(1, 0, [], {'7': 'bbbb'})
    assert 'diverged' in resp['failed']
    with pytest.raises(gang_lib.GangFailure) as ei:
        coord3.check()
    assert ei.value.cause == 'divergence'
    # A failed gang tells every syncing rank to self-terminate.
    coord2.fail('gang is dead')
    assert coord2.sync(1, 5, [], {})['failed'] == 'gang is dead'


def test_gang_fault_rules_rank_targeted():
    inj = faults_lib.FaultInjector({'rules': [
        {'kind': 'replica_crash', 'site': 'gang_member_crash',
         'rank': 1, 'at': 2}]})
    # Rank 2's invocations advance the site counter but never match.
    assert inj.fire('gang_member_crash', rank=2) is None
    assert inj.fire('gang_member_crash', rank=1) is not None  # 2nd
    assert inj.fire('gang_member_crash', rank=1) is None
    with pytest.raises(ValueError, match='unknown fault site'):
        faults_lib.make_injector({'rules': [
            {'kind': 'replica_crash', 'site': 'gang_sneeze'}]})


# ----------------------------------------------------- 2-process gang e2e
def test_two_process_gang_boot_barrier_byte_identical():
    """THE acceptance path: a real 2-process gang (rank 1 is a
    separate OS process running the follower entry) boots, passes the
    barrier, serves — and its greedy decode output is byte-identical
    to the equivalent single-process server on CPU."""
    port = common_utils.find_free_port(22000)
    srv = _start_leader(port, gang_id='g-2proc')
    base = f'http://127.0.0.1:{port}'
    proc = None
    try:
        assert srv._ready.wait(300)
        # Pre-barrier: the replica is NOT servable (a partial gang
        # must never enter rotation).
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + '/readiness', timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())['status'] == 'gang_joining'
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   SKYTPU_GANG_HEARTBEAT='0.05')
        proc = subprocess.Popen(
            [sys.executable, '-m', 'skypilot_tpu.serve.server',
             '--model', 'tiny', '--max-batch', '2', '--max-seq', '160',
             '--gang-rank', '1', '--gang-world', '2',
             '--gang-coordinator', base, '--gang-id', 'g-2proc'],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        assert _await_barrier(srv, timeout=240), srv._error
        with urllib.request.urlopen(base + '/readiness',
                                    timeout=10) as r:
            ready = json.loads(r.read())
        assert ready['status'] == 'ready'
        assert ready['gang']['world'] == 2 and ready['gang']['barrier']
        # Byte-identity vs the equivalent single-process server.
        port2 = common_utils.find_free_port(22100)
        from skypilot_tpu.serve.server import ModelServer
        ref = ModelServer('tiny', port=port2, **_FAST)
        ref.start(block=False)
        try:
            assert ref._ready.wait(300)
            prompt, gen = [3, 1, 4, 1, 5], 24
            out_gang = _generate(base, {'prompt': prompt,
                                        'max_new_tokens': gen})
            out_ref = _generate(f'http://127.0.0.1:{port2}',
                                {'prompt': prompt,
                                 'max_new_tokens': gen})
            assert out_gang['tokens'] == out_ref['tokens']
        finally:
            ref.stop()
        # Telemetry: the barrier was observed and gang_size is live.
        reg = telemetry.get_registry()
        assert reg.histogram('skytpu_gang_join_seconds').count >= 1
        assert reg.gauge('skytpu_gang_size').value == 2
        assert srv._error is None
    finally:
        srv.stop()
        if proc is not None:
            try:
                assert proc.wait(timeout=60) == 0   # clean shutdown
            except subprocess.TimeoutExpired:
                proc.kill()
                raise


# -------------------------------------------------------- drain ordering
def test_gang_drain_ack_ordering():
    """'Gang drained' means every rank applied everything up to the
    drain command's pinned op-log index — a lagging follower holds the
    drain open; its catch-up ack completes it."""
    port = common_utils.find_free_port(22200)
    srv = _start_leader(port, gang_id='g-drain')
    base = f'http://127.0.0.1:{port}'

    def sync(rank, applied, acks):
        req = urllib.request.Request(
            base + '/gang/sync',
            data=json.dumps({'rank': rank, 'gang_id': 'g-drain',
                             'applied': applied,
                             'acks': acks}).encode(),
            headers={'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read())

    try:
        assert srv._ready.wait(300)
        sync(1, 0, [])                   # join (barrier completes)
        assert _await_barrier(srv, timeout=30)
        # Serve one request so the op log is non-empty.
        _generate(base, {'prompt': [2, 7, 1], 'max_new_tokens': 8})
        # Start the drain: the leader side drains immediately (no
        # in-flight work), but the GANG is not drained until rank 1
        # acks at the pinned index.
        status = json.loads(urllib.request.urlopen(
            urllib.request.Request(
                base + '/drain',
                data=json.dumps({'deadline_s': 30}).encode(),
                headers={'Content-Type': 'application/json'}),
            timeout=10).read())
        assert status['draining'] is True
        resp = sync(1, 0, [])            # heartbeat, still at index 0
        cmds = [c for c in resp['commands'] if c['kind'] == 'drain']
        assert cmds and cmds[0]['log_index'] > 0
        cid, pinned = cmds[0]['id'], cmds[0]['log_index']
        time.sleep(0.3)
        st = json.loads(urllib.request.urlopen(base + '/drain',
                                               timeout=10).read())
        assert st['drained'] is False    # follower has not acked
        assert st['gang_drain_acked'] is False
        # An ack from a rank that has NOT reached the pinned index
        # must not count — the follower-side protocol only acks once
        # caught up; the coordinator trusts acks, so the honest
        # follower behavior is what we exercise: catch up, then ack.
        sync(1, pinned, [cid])
        deadline = time.time() + 15
        while time.time() < deadline:
            st = json.loads(urllib.request.urlopen(base + '/drain',
                                                   timeout=10).read())
            if st['drained']:
                break
            time.sleep(0.1)
        assert st['drained'] is True and st['gang_drain_acked'] is True
    finally:
        srv.stop()


# ------------------------------------------------- one dead rank = dead gang
def test_rank1_kill_whole_gang_fails_lb_zero_lost(monkeypatch):
    """THE gang-atomicity acceptance: a seeded gang_member_crash on
    rank 1 mid-stream kills the whole gang fast (rank 0 _fatals on
    heartbeat loss), the LB migrates the in-flight stream to the
    surviving replica, and the client sees ONE stream whose tokens are
    byte-identical to an uninterrupted greedy run — zero lost
    requests."""
    from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
    from skypilot_tpu.serve.server import ModelServer
    import dataclasses
    port = common_utils.find_free_port(22300)
    # Boot with the generous heartbeat bound (a cold follower
    # legitimately pauses seconds per first-shape compile on CPU);
    # tightened below once the prewarm run has filled every compile
    # cache — fast, deliberate whole-gang death detection. The leader
    # carries a deterministic per-iteration engine stall so the
    # tracked stream is still mid-flight when the death lands (a warm
    # tiny engine otherwise finishes before detection and the
    # migration path would go unexercised).
    from skypilot_tpu.serve.server import ModelServer as _MS
    srv = _MS('tiny', port=port,
              fault_spec={'seed': 0, 'rules': [
                  {'kind': 'engine_stall', 'site': 'engine_step',
                   'every': 1, 'delay_s': 0.15}]},
              gang=_leader_spec(gang_id='g-kill', heartbeat_s=0.05,
                                heartbeat_timeout_s=60.0),
              **_FAST)
    srv.start(block=False)
    base = f'http://127.0.0.1:{port}'
    port_b = common_utils.find_free_port(22350)
    survivor = ModelServer('tiny', port=port_b, **_FAST)
    survivor.start(block=False)
    follower = lb = ctrl = None
    try:
        assert srv._ready.wait(300) and survivor._ready.wait(300)
        follower, _t = _start_thread_follower(
            base, gang_id='g-kill', heartbeat_s=0.05,
            heartbeat_timeout_s=10.0)
        assert _await_barrier(srv, timeout=60), srv._error
        # Prompt chosen so the migrated continuation is byte-identical
        # at EVERY possible cut point (verified exhaustively on CPU;
        # some prompts hit bf16 near-tie argmax flips on the
        # recomputing replica at specific cuts — a pre-existing
        # bounded-divergence caveat of cross-replica recompute, not a
        # gang property).
        prompt, gen = [3, 1, 4, 1, 5], 32
        # Prewarm BOTH replicas with the kill run's shapes (different
        # tokens — no prefix aliasing) so every later step is
        # compile-free and the tight heartbeat bound is honest.
        _generate(base, {'prompt': [1, 2, 3, 4],
                         'max_new_tokens': gen})
        _generate(f'http://127.0.0.1:{port_b}',
                  {'prompt': [1, 2, 3, 4], 'max_new_tokens': gen})
        reference = _generate(f'http://127.0.0.1:{port_b}',
                              {'prompt': prompt,
                               'max_new_tokens': gen})['tokens']
        # Follower fully caught up (compile caches warm on both
        # ranks): tighten the heartbeat bound for the kill run.
        deadline = time.time() + 60
        while time.time() < deadline:
            st = srv._gang.status()
            if st['members'].get('1', {}).get('applied') == st['ops']:
                break
            time.sleep(0.1)
        srv._gang.spec = dataclasses.replace(
            srv._gang.spec, heartbeat_timeout_s=1.0)
        # Real LB over the gang (rank 0 only) + the survivor.
        ctrl = _FakeController([base, f'http://127.0.0.1:{port_b}'])
        monkeypatch.setenv('SKYTPU_LB_SYNC', '3600')
        lb_port = common_utils.find_free_port(22400)
        lb = SkyServeLoadBalancer(controller_url=ctrl.url,
                                  port=lb_port, max_attempts=4)
        lb.start()
        lb._sync_once()
        # Stream through the LB; after a few tokens land, the seeded
        # rank-1 kill fires (rule installed at a deterministic token
        # count — the crash is mid-stream by construction).
        tokens, done, error = [], None, None
        req = urllib.request.Request(
            f'http://127.0.0.1:{lb_port}/generate',
            json.dumps({'prompt': prompt, 'max_new_tokens': gen,
                        'stream': True}).encode(),
            {'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=300) as r:
            for raw in r:
                if not raw.startswith(b'data:'):
                    continue
                ev = json.loads(raw[5:].strip())
                if 'token' in ev:
                    tokens.append(int(ev['token']))
                    if len(tokens) == 5:
                        follower._faults = faults_lib.FaultInjector(
                            {'seed': 0, 'rules': [
                                {'kind': 'replica_crash',
                                 'site': 'gang_member_crash',
                                 'rank': 1, 'at': 1}]})
                if ev.get('done'):
                    done = ev
                if 'error' in ev:
                    error = ev
        # Zero lost: the one accepted stream completed, byte-identical.
        assert error is None and done is not None
        assert tokens == reference, (tokens[:8], reference[:8])
        assert done['tokens'] == reference
        # The gang really died as a unit: rank 0 _fatal'ed on
        # follower heartbeat loss (possibly after the stream finished
        # elsewhere — the death itself is unconditional).
        deadline = time.time() + 20
        while time.time() < deadline and srv._error is None:
            time.sleep(0.1)
        assert srv._error is not None
        assert 'heartbeat lost' in srv._error
        reg = telemetry.get_registry()
        fail_c = reg.get('skytpu_gang_failures_total',
                         cause='heartbeat_lost')
        assert fail_c is not None and fail_c.value >= 1
        # The gang leader now probes dead (out of rotation).
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + '/readiness', timeout=10)
        assert ei.value.code == 503
    finally:
        if lb is not None:
            lb.stop()
        if ctrl is not None:
            ctrl.stop()
        srv.stop()
        survivor.stop()


def test_join_timeout_fails_partial_gang():
    """A rank that never joins must fail the gang within the join
    window: rank 0 _fatals (cause join_timeout), readiness reports the
    failure, and the manager-side probe escalation replaces the gang —
    never a half-joined replica hanging forever."""
    port = common_utils.find_free_port(22500)
    srv = _start_leader(port, gang_id='g-late', join_timeout_s=3.0,
                        heartbeat_s=0.05, heartbeat_timeout_s=1.0)
    try:
        deadline = time.time() + 60
        while time.time() < deadline and srv._error is None:
            time.sleep(0.1)
        assert srv._error is not None
        assert 'join timeout' in srv._error
        assert 'missing rank(s) [1]' in srv._error
        reg = telemetry.get_registry()
        c = reg.get('skytpu_gang_failures_total', cause='join_timeout')
        assert c is not None and c.value >= 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f'http://127.0.0.1:{port}/readiness',
                                   timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())['status'] == 'failed'
    finally:
        srv.stop()


def test_follower_self_terminates_on_coordinator_loss():
    """The follower half of one-dead-all-dead: rank 1 outliving a dead
    rank 0 would be a half-alive replica — it must self-terminate once
    the coordinator stops answering past the heartbeat timeout."""
    port = common_utils.find_free_port(22600)
    srv = _start_leader(port, gang_id='g-loss', heartbeat_s=0.05,
                        heartbeat_timeout_s=1.0)
    base = f'http://127.0.0.1:{port}'
    try:
        assert srv._ready.wait(300)
        follower, t = _start_thread_follower(
            base, gang_id='g-loss', heartbeat_s=0.05,
            heartbeat_timeout_s=1.0)
        assert _await_barrier(srv, timeout=60)
    finally:
        srv.stop()       # rank 0 vanishes (no shutdown ack race: the
                         # bounded grace may or may not deliver it)
    t.join(timeout=30)
    assert not t.is_alive()
    assert follower.exit_cause in ('shutdown', 'coordinator_lost',
                                   'coordinator_failed')


# ------------------------------------------------ manager: gangs as units
def _make_manager(tmp_path, monkeypatch, hosts=2):
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    from skypilot_tpu.serve.replica_managers import ReplicaManager
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    spec = SkyServiceSpec.from_yaml_config(
        {'readiness_probe': '/readiness',
         'parallelism': {'hosts': hosts}})
    return ReplicaManager('gang-mgr-test', spec, {})


def _insert_gang(mgr, gang_id='g', world=2, base_id=1,
                 url0='http://127.0.0.1:1', spot=False):
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import ReplicaInfo
    infos = []
    for rank in range(world):
        info = ReplicaInfo(base_id + rank, f'{gang_id}-c{rank}', 1,
                           spot, 30000 + base_id + rank,
                           gang_id=gang_id, gang_rank=rank,
                           gang_world=world)
        info.url = (url0 if rank == 0
                    else f'http://127.0.0.1:{40000 + rank}')
        info.status = serve_state.ReplicaStatus.READY
        with mgr._lock:
            mgr._replicas[info.replica_id] = info
        infos.append(info)
    return infos


def test_manager_gang_single_endpoint_and_teardown_as_unit(
        tmp_path, monkeypatch):
    from skypilot_tpu.serve import serve_state
    mgr = _make_manager(tmp_path, monkeypatch)
    leader, follower = _insert_gang(mgr, world=2)
    # Exactly ONE routable endpoint: rank 0. Followers stay out of
    # ready_urls and the role map, but ride the gang health block.
    assert mgr.ready_urls() == [leader.url]
    assert follower.url not in mgr.replica_roles()
    gangs = mgr.replica_gangs()
    assert gangs[leader.url]['world'] == 2
    assert gangs[leader.url]['follower_urls'] == [follower.url]
    # Tearing down ANY member tears down the whole gang.
    mgr.scale_down(follower.replica_id)
    deadline = time.time() + 20
    while time.time() < deadline and mgr._replicas:
        time.sleep(0.1)
    assert mgr._replicas == {}


def test_manager_drain_any_rank_drains_gang(tmp_path, monkeypatch):
    from skypilot_tpu.serve import serve_state
    mgr = _make_manager(tmp_path, monkeypatch)
    leader, follower = _insert_gang(mgr, world=2)
    # Drain aimed at the FOLLOWER routes to rank 0 and marks every
    # member DRAINING (out of ready_urls immediately). The fake URL's
    # unreachable drain endpoint degrades to teardown on the drain
    # thread, so either leaving-state may already show.
    leaving = (serve_state.ReplicaStatus.DRAINING,
               serve_state.ReplicaStatus.SHUTTING_DOWN)
    assert mgr.drain(follower.replica_id, deadline_s=5) is True
    assert leader.status in leaving
    assert follower.status in leaving
    assert mgr.ready_urls() == []
    assert mgr.drain(leader.replica_id) is False     # idempotent
    deadline = time.time() + 20
    while time.time() < deadline and mgr._replicas:
        time.sleep(0.1)
    assert mgr._replicas == {}


def test_preemption_warning_gang_keyed_checkpoint_once(
        tmp_path, monkeypatch):
    """Satellite fix: the checkpoint-once flag is keyed by GANG ID —
    a warning re-delivered to a different rank of the same gang still
    checkpoints exactly once (one POST /checkpoint against rank 0)."""
    import http.server as hs
    hits = {'checkpoint': 0}

    class H(hs.BaseHTTPRequestHandler):
        timeout = 10

        def log_message(self, *a):
            del a

        def do_POST(self):  # noqa: N802
            if self.path == '/checkpoint':
                hits['checkpoint'] += 1
                body = b'SKCK-FAKE'
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            body = json.dumps({'draining': True,
                               'inflight': 0}).encode()
            self.send_response(200)
            self.send_header('Content-Length', str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802
            body = json.dumps({'draining': True, 'drained': True,
                               'inflight': 0}).encode()
            self.send_response(200)
            self.send_header('Content-Length', str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    port = common_utils.find_free_port(22700)
    httpd = hs.ThreadingHTTPServer(('127.0.0.1', port), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        mgr = _make_manager(tmp_path, monkeypatch)
        leader, follower = _insert_gang(
            mgr, world=2, url0=f'http://127.0.0.1:{port}', spot=True)
        # Warning lands on the FOLLOWER first (re-delivery target),
        # then on the leader: exactly one checkpoint, one drain.
        assert mgr.handle_preemption_warning(follower.replica_id,
                                             deadline_s=5) is True
        assert mgr.handle_preemption_warning(leader.replica_id,
                                             deadline_s=5) is False
        deadline = time.time() + 10
        while time.time() < deadline and hits['checkpoint'] == 0:
            time.sleep(0.05)
        time.sleep(0.3)       # would-be window for a double POST
        assert hits['checkpoint'] == 1
        assert mgr.checkpoint_for_warmup() == b'SKCK-FAKE'
        deadline = time.time() + 20
        while time.time() < deadline and mgr._replicas:
            time.sleep(0.1)
        assert mgr._replicas == {}
    finally:
        httpd.shutdown()


def test_policies_exclude_follower_urls_from_probes(monkeypatch):
    """Satellite fix: queue_depth/phase_aware probe sweeps and
    selection must skip gang follower URLs — a gang presents one
    endpoint — while the gang stays visible in health accounting."""
    from skypilot_tpu.serve import load_balancing_policies as lbp
    probed = []
    for name in ('queue_depth', 'phase_aware'):
        policy = lbp.make_policy(name)
        monkeypatch.setattr(
            policy, '_probe',
            lambda url: (probed.append(url) or (0, None)))
        # A not-gang-aware controller leaked follower URLs into the
        # ready list; the gang block marks them.
        policy.set_ready_replicas(['http://r0:1', 'http://f1:1',
                                   'http://solo:1'])
        policy.set_replica_gangs({'http://r0:1': {
            'gang_id': 'g', 'world': 2,
            'follower_urls': ['http://f1:1'],
            'statuses': {'0': 'READY', '1': 'READY'}}})
        for _ in range(4):
            pick = policy.select_replica()
            assert pick != 'http://f1:1'
        assert 'http://f1:1' not in probed
        assert set(probed) <= {'http://r0:1', 'http://solo:1'}
        assert policy.gang_view()['http://r0:1']['world'] == 2
        probed.clear()


# --------------------------------------- gang checkpoint -> warm recovery
def test_preempt_gang_checkpoint_recover_byte_identical():
    """Preemption flow across a gang: mid-stream, POST /checkpoint
    exports the gang's state (in-flight KV + hot prefixes; every rank
    acks), a replacement single-process replica warms from the blob,
    and the resubmitted continuation (a prefill of prompt + prefix over
    the warmed pages: another program than uninterrupted decode)
    completes the budget with the reference's choices."""
    from skypilot_tpu.serve.server import ModelServer
    port = common_utils.find_free_port(22800)
    # Deterministic engine stall: the tiny engine otherwise decodes
    # the whole budget faster than the test can read 30 tokens and
    # POST /checkpoint — the request must still be IN FLIGHT when the
    # export runs, or there is nothing to snapshot.
    srv = ModelServer('tiny', port=port,
                      fault_spec={'seed': 0, 'rules': [
                          {'kind': 'engine_stall', 'site': 'engine_step',
                           'every': 1, 'delay_s': 0.2}]},
                      gang=_leader_spec(gang_id='g-ckpt'), **_FAST)
    srv.start(block=False)
    base = f'http://127.0.0.1:{port}'
    follower = None
    try:
        assert srv._ready.wait(300)
        follower, _t = _start_thread_follower(base, gang_id='g-ckpt')
        assert _await_barrier(srv, timeout=60), srv._error
        prompt, gen = [9, 2, 6, 4], 48
        # Start the stream on the gang; checkpoint mid-flight.
        sr = srv.submit_stream(prompt, max_new_tokens=gen,
                               temperature=0.0, top_k=0, eos_id=None)
        tokens = []
        # Far enough in that the context covers full pages —
        # warm_prefix lands page-granular KV, so a too-early
        # checkpoint would carry nothing warmable.
        while len(tokens) < 30:
            token, finished = sr.outbox.get(timeout=120)
            assert token is not None, sr.outbox.error
            tokens.append(int(token))
            assert not finished
        req = urllib.request.Request(
            base + '/checkpoint', data=json.dumps({}).encode(),
            headers={'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=60) as r:
            blob = r.read()
            n_entries = int(r.headers['X-Checkpoint-Entries'])
        assert n_entries >= 1
        srv.finish_stream(sr)            # preempted: client gone
        # Replacement replica warms BEFORE serving, then continues
        # from prompt + generated prefix.
        port2 = common_utils.find_free_port(22900)
        srv2 = ModelServer('tiny', port=port2, **_FAST)
        srv2.start(block=False)
        try:
            assert srv2._ready.wait(300)
            warm_req = urllib.request.Request(
                f'http://127.0.0.1:{port2}/kv/warmup', data=blob,
                headers={'Content-Type': 'application/octet-stream'})
            with urllib.request.urlopen(warm_req, timeout=60) as r:
                warm = json.loads(r.read())
            assert warm['entries'] == n_entries
            assert warm['warmed_rows'] >= 1
            cont = _generate(
                f'http://127.0.0.1:{port2}',
                {'prompt': prompt + tokens,
                 'max_new_tokens': gen - len(tokens)})['tokens']
            assert len(tokens + cont) == gen
            greedy_oracle.assert_server_agrees(prompt, tokens + cont,
                                               'checkpointed + resumed')
        finally:
            srv2.stop()
    finally:
        srv.stop()
