"""Replica manager: launches/terminates/probes replica clusters.

Role of reference ``SkyPilotReplicaManager``
(``sky/serve/replica_managers.py:608``): every replica is an ordinary
cluster launched through the full stack (``sky/serve/replica_managers.py:
58-170`` does ``sky.launch`` in a subprocess; here a thread —
``execution.launch`` is already process-safe via per-cluster locks).
Readiness probing (``:1026``) is an HTTP GET/POST against
``http://<head_ip>:<replica_port><readiness_path>``; preemption handling
(``:782``) maps cluster-gone to PREEMPTED so the autoscaler replaces it.

TPU-first: a replica is a whole slice; its head IP is the slice's worker-0
and the in-tree model server (multi-controller JAX) listens there. On the
local provider each replica gets its own port (many replicas share one
host) — injected as ``SKYTPU_REPLICA_PORT`` either way.

Environment seam (``serve/control_env.py``): every outside-world touch
— wall clock, sleeps, background tasks, replica HTTP, cluster
launch/teardown/status, row persistence, fault-injector resolution —
routes through the injected :class:`ControlPlaneEnv`. The default
:class:`LiveControlPlaneEnv` reproduces the pre-refactor behavior
verbatim; ``serve/sim/`` swaps in a virtual-clock environment so the
SAME launch/probe/drain/checkpoint/warmup/backfill state machines run
against 1000 simulated replicas at millions of requests per wall-second
(ROADMAP item 5's fleet-scale simulator).
"""
from __future__ import annotations

import hashlib
import json
import threading
import time
import typing
from typing import Any, Dict, List, Optional, Sequence

from skypilot_tpu import exceptions
from skypilot_tpu import telemetry
from skypilot_tpu import tpu_logging
from skypilot_tpu.serve import control_env
from skypilot_tpu.serve import faults as faults_lib
from skypilot_tpu.serve import serve_state
from skypilot_tpu.task import Task
from skypilot_tpu.utils import common_utils

if typing.TYPE_CHECKING:
    from skypilot_tpu.serve.service_spec import SkyServiceSpec

logger = tpu_logging.init_logger(__name__)

# Stable outcome label set of skytpu_replicas_adopted_total{outcome}:
# what restart reconciliation did with each persisted replica row /
# pending journal op it found (docs/robustness.md, controller failure
# domain).
ADOPT_OUTCOMES = ('adopted', 'probe_pending', 'drain_resumed',
                  'teardown_replayed', 'zombie_killed', 'preempted')

_PROBE_FAILURE_GRACE = 3          # consecutive probe failures → NOT_READY
_PROBE_FAILURE_TERMINATE = 9      # consecutive failures → replace replica
_MAX_RETAINED_FAILED = 3          # FAILED rows kept for debugging
_LAUNCH_BACKOFF_CAP = 300.0
# Launch-backoff jitter band: the delay is drawn uniformly from
# [JITTER_FRAC, 1.0] x the exponential target, so replicas that failed
# together don't relaunch together (a synchronized retry storm against
# the same exhausted zone/quota).
_BACKOFF_JITTER_FRAC = 0.5


def _launch_backoff_base() -> float:
    import os
    return float(os.environ.get('SKYTPU_SERVE_LAUNCH_BACKOFF', '5'))


def _drain_deadline_default() -> float:
    """Graceful-drain deadline before a draining replica is torn down
    regardless (in-flight requests past it fail over via the LB)."""
    import os
    return float(os.environ.get('SKYTPU_SERVE_DRAIN_S', '30'))


def _warmup_timeout() -> float:
    """Bound on the prefix-cache warmup POST against a freshly READY
    replica (a wedged warmup must not keep capacity out of rotation —
    past it the replica enters rotation cold)."""
    import os
    return float(os.environ.get('SKYTPU_SERVE_WARMUP_TIMEOUT', '30'))


def _gang_join_timeout() -> float:
    """Barrier bound shipped to every gang rank: unless all ranks join
    rank 0 within this window, the gang fails and is replaced as one
    unit."""
    import os
    return float(os.environ.get('SKYTPU_GANG_JOIN_TIMEOUT', '120'))


def _ckpt_ttl() -> float:
    """Checkpoint staleness bound: prefix KV older than this is not
    worth shipping to a recovered replica (the traffic that made those
    prefixes hot has moved on)."""
    import os
    return float(os.environ.get('SKYTPU_SERVE_CKPT_TTL', '3600'))


def _canary_interval() -> float:
    """Byzantine-detection canary cadence per replica (seconds on the
    env clock); 0 (the default) disables canary probing."""
    import os
    return float(os.environ.get('SKYTPU_CANARY_INTERVAL_S', '0'))


def _canary_prompt() -> List[int]:
    """The canary's greedy prompt (comma-separated token ids). Fixed
    and known, so every healthy replica of one model version answers
    with the SAME token sequence — the digest the manager compares."""
    import os
    raw = os.environ.get('SKYTPU_CANARY_PROMPT', '11,13,17,19')
    return [int(t) for t in raw.split(',') if t.strip()]


def _canary_max_tokens() -> int:
    import os
    return int(os.environ.get('SKYTPU_CANARY_TOKENS', '8'))


def canary_digest(tokens: Sequence[int]) -> str:
    """The canonical digest of a canary response's token list — what
    the manager compares across replicas (and what tests and the
    simulator compute on the other side)."""
    return hashlib.sha256(
        json.dumps([int(t) for t in tokens]).encode()).hexdigest()[:16]


def _probe_counter(outcome: str) -> 'telemetry.Counter':
    """Probe-outcome counters in the shared process registry (the
    controller's /metrics surface via the dashboard)."""
    return telemetry.get_registry().counter(
        'skytpu_replica_probe_total',
        'Replica readiness-probe outcomes', outcome=outcome)


def _transition_counter(to_status: str) -> 'telemetry.Counter':
    return telemetry.get_registry().counter(
        'skytpu_replica_transitions_total',
        'Replica status transitions observed by the probe loop',
        to=to_status)


class ReplicaInfo:
    """In-memory mirror of one replica row + probe bookkeeping."""

    def __init__(self, replica_id: int, cluster_name: str, version: int,
                 is_spot: bool, port: int, role: str = 'colocated',
                 gang_id: Optional[str] = None, gang_rank: int = 0,
                 gang_world: int = 1,
                 created_time: Optional[float] = None):
        self.replica_id = replica_id
        self.cluster_name = cluster_name
        self.version = version
        self.is_spot = is_spot
        self.port = port
        # Disaggregation phase role (prefill/decode/colocated) — the
        # pool this replica was launched to fill; rides the launch env
        # as SKYTPU_ROLE.
        self.role = role
        # Multi-host gang membership (serve/gang.py): members share a
        # gang_id and come up / drain / checkpoint / die TOGETHER.
        # Rank 0 owns the replica's one routable endpoint (probed,
        # routed, drained over HTTP); followers are tracked for health
        # accounting and cluster lifecycle only — never probed, never
        # in ready_urls. ``coordinator`` is rank 0's URL, set before a
        # follower launches (its SKYTPU_COORDINATOR env).
        self.gang_id = gang_id
        self.gang_rank = gang_rank
        self.gang_world = gang_world
        self.coordinator: Optional[str] = None
        self.status = serve_state.ReplicaStatus.PENDING
        self.url: Optional[str] = None
        self.consecutive_failures = 0
        self.first_probe_time: Optional[float] = None
        # Spot resilience bookkeeping: when the scale-up was issued
        # (provision-latency observation — the forecast autoscaler's
        # pre-scaling lead time learns from these; the manager stamps
        # its env clock so simulated fleets observe virtual latencies),
        # whether this replica's prefix cache was already checkpointed
        # on a preemption warning (idempotence under a racing drain),
        # and whether its replacement warmup already ran (once per
        # replica, BEFORE it first enters ready_urls).
        self.created_time = (created_time if created_time is not None
                             else time.time())
        self.checkpointed = False
        self.warmed = False
        # Byzantine-detection canary bookkeeping: when this replica
        # was last canaried (env clock; 0 = never).
        self.last_canary_t = 0.0
        # Lifecycle-journal bookkeeping (round 15): the pending
        # journal op ids this replica's in-flight launch / drain carry
        # (finished when the op acks), and a teardown-started latch so
        # a replica's cluster is never torn down twice — not by racing
        # scale_down calls, and not by a restarted controller
        # replaying an op the dying one already ran.
        self.launch_op: Optional[int] = None
        self.drain_op: Optional[int] = None
        self.teardown_started = False


class ReplicaManager:

    def __init__(self, service_name: str, spec: 'SkyServiceSpec',
                 task_config: dict, version: int = 1,
                 reserved_ports: Optional[set] = None,
                 env: Optional[control_env.ControlPlaneEnv] = None):
        self.service_name = service_name
        self.spec = spec
        self.task_config = task_config
        self.version = version
        self._reserved_ports = set(reserved_ports or ())
        # The simulator-or-live effect seam: every clock read, sleep,
        # background task, replica HTTP round-trip, cluster op and row
        # write below goes through this (control_env.py).
        self._env = control_env.resolve(env)
        self._replicas: Dict[int, ReplicaInfo] = {}
        self._next_replica_id = 1
        # RLock: _persist checks membership under the lock and is called
        # both with and without it held.
        self._lock = threading.RLock()
        # DB-serialization lock (graftcheck GC102): sqlite row writes/
        # deletes happen under THIS lock only, so probe sweeps and
        # scale decisions contending on the hot ``_lock`` never stall
        # behind disk I/O. Ordering: ``_db_lock`` is taken FIRST, then
        # ``_lock`` briefly for the membership check — the row write
        # then runs with only ``_db_lock`` held. A racing removal needs
        # ``_db_lock`` too, so check+write stay atomic with respect to
        # pop+delete and no phantom row can survive a removal.
        self._db_lock = threading.Lock()
        self._shutdown = False
        self._launch_failures = 0
        self._backoff_until = 0.0
        # Backoff jitter source (tests seed it for determinism; the
        # sim env hands out a scenario-seeded RNG).
        self._rng = self._env.rng()
        # Deterministic fault injection (serve/faults.py): resolved
        # once from the env (SKYTPU_FAULT_SPEC live; the scenario's
        # injector in sim); None = hooks are one attribute check.
        # Sites here: 'probe' (probe_timeout), 'preempt'
        # (preempt_signal — hard kill), 'preempt_warning'
        # (preempt_signal with advance notice — routes through drain),
        # 'spot_preemption' (counted per swept SPOT replica only —
        # seeded spot-kill schedules for the chaos tests).
        self._faults = self._env.fault_injector()
        # Spot resilience: the latest prefix-cache checkpoint exported
        # by a preemption-warned replica (bytes + export wall time;
        # latest wins, TTL-bounded), landed into replacement replicas
        # via /kv/warmup BEFORE they enter ready_urls. _ckpt_lock
        # serializes the store against concurrent warnings; the HTTP
        # fetch itself runs outside every lock.
        self._ckpt_lock = threading.Lock()
        self._ckpt_bytes: Optional[bytes] = None
        self._ckpt_time: float = 0.0
        # Checkpoint-once dedupe, keyed by GANG (falling back to the
        # replica id for singles): a preemption warning re-delivered
        # to a *different rank* of the same gang must still checkpoint
        # exactly once — the per-ReplicaInfo flag alone can't see that
        # the gang already checkpointed through another member. Guarded
        # by the manager lock like the per-replica flag it generalizes.
        # BOUNDED: entries are evicted in ``_untrack`` when the replica
        # (or the last member of the gang) is torn down, so a
        # long-lived manager churning thousands of spot replicas holds
        # only live keys.
        self._ckpt_done: Dict[str, bool] = {}
        # Provision-latency observations (scale-up issued -> READY)
        # not yet consumed by the controller; the forecast autoscaler
        # learns its pre-scaling lead time from them.
        self._provision_obs: List[float] = []
        # Fleet-telemetry scrape hook: the controller installs its
        # FleetAggregator's ``ingest`` here; after each successful
        # readiness probe the manager pulls the replica's
        # ``/telemetry/summary`` (resuming from a per-replica trace
        # cursor) and feeds it through. Best-effort — a scrape failure
        # never fails the probe.
        self._telemetry_sink: Optional[Any] = None
        self._telemetry_cursors: Dict[str, int] = {}
        reg = telemetry.get_registry()
        self._m_spot_preempt = reg.counter(
            'skytpu_spot_preemptions_total',
            'Spot replica preemptions observed (advance warnings and '
            'hard cluster losses)')
        self._h_warmup = reg.histogram(
            'skytpu_prefix_warmup_seconds',
            'Prefix-cache warmup of a recovered replica: checkpoint '
            'POST to landed (s)',
            buckets=telemetry.registry.DEFAULT_SECONDS_BUCKETS)
        self._h_provision = reg.histogram(
            'skytpu_replica_provision_seconds',
            'Replica provision latency: scale-up issued to first '
            'READY (s)',
            buckets=telemetry.registry.DEFAULT_SECONDS_BUCKETS)
        # Byzantine-replica quarantine (round 13): the manager
        # periodically (env-clock-driven) probes each READY replica
        # with a canary greedy prompt whose answer digest is known; a
        # mismatch — silently corrupted replica, the SDC failure mode
        # clean-failure machinery can't see — moves the replica to
        # QUARANTINED: out of ready_urls immediately, drained, torn
        # down, replaced. The reference digest is either configured
        # (``expected_digest``) or learned from the first healthy
        # answer per spec version (blue-green rollovers reset it — a
        # new model version legitimately answers differently).
        self._canary_interval = _canary_interval()
        self._canary_prompt = _canary_prompt()
        self._canary_max_new = _canary_max_tokens()
        self._canary_expected: Optional[str] = None
        self._canary_learned: Optional[str] = None
        self.quarantined_count = 0
        self._m_quarantined = reg.counter(
            'skytpu_replicas_quarantined_total',
            'Replicas quarantined after a byzantine (wrong-digest) '
            'canary response')
        # Restart reconciliation (round 15): what the journal replay
        # did with each persisted row — registered at construction so
        # the series render as zeros from the first scrape.
        self._m_adopted = {
            outcome: reg.counter(
                'skytpu_replicas_adopted_total',
                'Persisted replicas handled by restart reconciliation '
                '(adopted = healthy and re-owned without relaunch)',
                outcome=outcome)
            for outcome in ADOPT_OUTCOMES}
        faults_lib.register_metrics()

    def configure_canary(self, interval_s: float,
                         prompt: Optional[List[int]] = None,
                         max_new_tokens: Optional[int] = None,
                         expected_digest: Optional[str] = None) -> None:
        """Enable/override byzantine canary probing (tests and the
        fleet simulator; live deployments use the SKYTPU_CANARY_*
        env)."""
        self._canary_interval = float(interval_s)
        if prompt is not None:
            self._canary_prompt = [int(t) for t in prompt]
        if max_new_tokens is not None:
            self._canary_max_new = int(max_new_tokens)
        self._canary_expected = expected_digest

    # ------------------------------------------------------------- update
    def update_version(self, spec: 'SkyServiceSpec', task_config: dict,
                       version: int) -> None:
        """Blue-green-lite (reference ``:1172``): new replicas launch with
        the new task; old-version replicas are drained by the controller
        once enough new-version replicas are ready."""
        old_version = self.version
        self.spec = spec
        self.task_config = task_config
        self.version = version
        # A new version may legitimately answer the canary differently
        # (new weights): relearn the reference digest from the first
        # healthy new-version replica. The persisted digest is keyed
        # by version, so the stale key is dropped and a restart mid-
        # rollover relearns exactly like the live path.
        self._canary_learned = None
        if version != old_version:
            self._del_note(f'canary_digest:v{old_version}')

    # ------------------------------------------------------------- launch
    def _replica_cluster_name(self, replica_id: int) -> str:
        return f'{self.service_name}-replica-{replica_id}'

    def _replica_task(self, info: ReplicaInfo) -> Task:
        task = Task.from_yaml_config(dict(self.task_config))
        envs = dict(task.envs or {})
        envs['SKYTPU_REPLICA_PORT'] = str(info.port)
        envs['SKYTPU_SERVE_REPLICA_ID'] = str(info.replica_id)
        envs['SKYTPU_SERVE_SERVICE'] = self.service_name
        # Adaptive-TP placement (serve/placement.py): the replica's
        # (tp, dp) mesh shape rides the launch env — the model server
        # reads SKYTPU_TP/SKYTPU_DP via serving_spec_from_env unless
        # overridden with explicit --tp/--dp.
        envs.update(self.parallelism_plan().as_env())
        # Disaggregation role (prefill/decode/colocated): same env
        # contract — the model server reads SKYTPU_ROLE unless started
        # with an explicit --role.
        envs['SKYTPU_ROLE'] = info.role
        # Multi-tenant LoRA (``adapters:`` spec block): bank size /
        # checkpoint dir / rank ride the same launch-env contract —
        # the model server reads SKYTPU_ADAPTER_* unless started with
        # explicit --adapter-* flags.
        if self.spec.adapter_slots > 0:
            envs['SKYTPU_ADAPTER_SLOTS'] = str(self.spec.adapter_slots)
            envs['SKYTPU_ADAPTER_RANK'] = str(self.spec.adapter_rank)
            if self.spec.adapter_dir:
                envs['SKYTPU_ADAPTER_DIR'] = self.spec.adapter_dir
        # Gang launch env (serve/gang.py): every rank gets the shared
        # gang identity; nonzero ranks additionally get rank 0's URL
        # as the coordinator (set by _launch_replica once rank 0's
        # address resolves).
        if info.gang_world > 1:
            envs['SKYTPU_GANG_ID'] = info.gang_id or ''
            envs['SKYTPU_RANK'] = str(info.gang_rank)
            envs['SKYTPU_WORLD'] = str(info.gang_world)
            envs['SKYTPU_GANG_JOIN_TIMEOUT'] = str(_gang_join_timeout())
            if info.gang_rank > 0 and info.coordinator:
                envs['SKYTPU_COORDINATOR'] = info.coordinator
        task.update_envs(envs)
        if info.is_spot:
            task.set_resources([r.copy(use_spot=True)
                                for r in task.resources])
        return task

    def parallelism_plan(self):
        """The (tp, dp) plan every replica of the current spec version
        launches with (serve/placement.py)."""
        from skypilot_tpu.serve import placement
        return placement.plan_for_spec(self.spec)

    def scale_up(self, use_spot: bool = False) -> Optional[int]:
        """Start one replica launch in the background; returns its id
        (None once the manager is shutting down). With
        ``parallelism: hosts: N`` in the spec, "one replica" is a
        GANG of N processes sharing a gang ID: rank 0 plus N-1
        followers, launched together and replaced together."""
        from skypilot_tpu.serve import placement
        world = max(1, int(self.parallelism_plan().hosts))
        with self._lock:
            if self._shutdown:
                return None
            replica_id = self._next_replica_id
            self._next_replica_id += 1
            port = self._pick_port(replica_id)
            # Disaggregation pool fill: count only replicas that are
            # not already leaving — a draining/failed prefill worker's
            # replacement must re-fill the prefill pool.
            live_roles = [r.role for r in self._replicas.values()
                          if r.gang_rank == 0
                          and not r.status.is_terminal()
                          and r.status not in (
                              serve_state.ReplicaStatus.SHUTTING_DOWN,
                              serve_state.ReplicaStatus.DRAINING)]
            role = placement.role_for_new_replica(self.spec, live_roles)
            gang_id = (f'{self.service_name}-gang-{replica_id}'
                       f'-v{self.version}' if world > 1 else None)
            info = ReplicaInfo(replica_id,
                               self._replica_cluster_name(replica_id),
                               self.version, use_spot, port, role=role,
                               gang_id=gang_id, gang_rank=0,
                               gang_world=world,
                               created_time=self._env.time())
            info.status = serve_state.ReplicaStatus.PROVISIONING
            self._replicas[replica_id] = info
            followers: List[ReplicaInfo] = []
            for rank in range(1, world):
                fid = self._next_replica_id
                self._next_replica_id += 1
                fport = self._pick_port(fid)
                finfo = ReplicaInfo(
                    fid, self._replica_cluster_name(fid),
                    self.version, use_spot, fport, role=role,
                    gang_id=gang_id, gang_rank=rank, gang_world=world,
                    created_time=self._env.time())
                finfo.status = serve_state.ReplicaStatus.PROVISIONING
                self._replicas[fid] = finfo
                followers.append(finfo)
        # Journal BEFORE persisting rows or spawning the launch: a
        # crash at any later point leaves a pending 'launch' op whose
        # payload carries the full descriptor (role/gang/port), so the
        # restarted controller can kill the zombie cluster — or adopt
        # the replica with its role and gang membership intact.
        for member in [info] + followers:
            member.launch_op = self._journal_start(
                'launch', member, payload=self._descriptor(member))
        self._persist(info)
        for finfo in followers:
            self._persist(finfo)
        # Rank 0 launches first: followers need its resolved address
        # as their SKYTPU_COORDINATOR (_launch_replica fans them out
        # once rank 0 reaches STARTING).
        self._env.spawn(self._launch_replica, info)
        return replica_id

    @staticmethod
    def _descriptor(info: ReplicaInfo) -> Dict[str, object]:
        """The journal payload that lets a restarted controller
        rebuild this replica's ReplicaInfo without guessing (live
        probes refine role/gang where the replica still answers)."""
        return {
            'cluster_name': info.cluster_name,
            'port': info.port,
            'is_spot': info.is_spot,
            'role': info.role,
            'gang_id': info.gang_id,
            'gang_rank': info.gang_rank,
            'gang_world': info.gang_world,
            'version': info.version,
        }

    def shutdown(self) -> None:
        """Refuse further scale_up; in-flight launches will self-clean."""
        with self._lock:
            self._shutdown = True

    def in_launch_backoff(self) -> bool:
        """True while recent launch failures put new launches on hold
        (exponential backoff so a persistent failure — quota, bad image —
        doesn't spin up a doomed launch every controller tick)."""
        with self._lock:
            return self._env.time() < self._backoff_until

    def backoff_remaining(self) -> float:
        """Seconds until launches resume (0 when not backing off) —
        the controller ships this to the LB as the Retry-After hint on
        the no-ready-replicas 503."""
        with self._lock:
            return max(0.0, self._backoff_until - self._env.time())

    def retry_after_hint(self) -> int:
        """Whole-second Retry-After for clients hitting the service
        while no replica is READY, from live replica state: the launch
        backoff remainder when backing off, a short probe-propagation
        interval while a replica is already starting/draining, and a
        provisioning-scale guess otherwise."""
        backoff = self.backoff_remaining()
        if backoff > 0:
            return max(1, int(backoff))
        with self._lock:
            statuses = {r.status for r in self._replicas.values()}
        if (serve_state.ReplicaStatus.STARTING in statuses
                or serve_state.ReplicaStatus.READY in statuses
                or serve_state.ReplicaStatus.DRAINING in statuses):
            # A replica exists and is (nearly) servable: the LB learns
            # about it at its next controller sync.
            return 5
        if serve_state.ReplicaStatus.PROVISIONING in statuses:
            return max(5, int(self.spec.initial_delay_seconds / 4))
        return 15

    def _pick_port(self, replica_id: int) -> int:
        """Fixed spec port on real clouds (distinct head IPs); a free local
        port per replica on the local provider (shared host). Ports
        recorded by OTHER services (allocated but possibly unbound) are
        excluded via the shared serve-state table."""
        cloud = (self.task_config.get('resources') or {}).get('cloud')
        if cloud != 'local':
            return self.spec.replica_port
        taken = self._reserved_ports | {
            r.port for r in self._replicas.values()}
        taken |= serve_state.allocated_ports()
        start = 10000
        while True:
            port = common_utils.find_free_port(start)
            if port not in taken:
                return port
            start = port + 1

    def _launch_replica(self, info: ReplicaInfo) -> None:
        task = self._replica_task(info)
        try:
            self._env.launch_cluster(task, info.cluster_name)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Replica {info.replica_id} launch failed: '
                           f'{type(e).__name__}: {e}')
            self._record_launch_result(info, failed=True)
            return
        # A concurrent scale_down/shutdown may have removed this replica
        # while the launch was in flight; the fresh cluster is then
        # orphaned — tear it down instead of resurrecting the DB row.
        with self._lock:
            abandoned = (self._shutdown
                         or self._replicas.get(info.replica_id) is not info
                         or info.status !=
                         serve_state.ReplicaStatus.PROVISIONING)
        if abandoned:
            logger.info(f'Replica {info.replica_id} was removed during '
                        'launch; tearing its cluster down.')
            try:
                self._env.down_cluster(info.cluster_name)
            except Exception as e:  # pylint: disable=broad-except
                logger.warning(
                    f'Teardown of abandoned replica cluster '
                    f'{info.cluster_name} failed (it may leak): '
                    f'{type(e).__name__}: {e}')
            self._untrack(info.replica_id)
            self._journal_finish(info.launch_op)
            info.launch_op = None
            return
        head_ip = self._env.cluster_head_ip(info.cluster_name)
        if head_ip is None:
            self._record_launch_result(info, failed=True)
            return
        with self._lock:
            # Re-check under the lock: a scale_down between the abandoned
            # check above and here must not have its SHUTTING_DOWN status
            # clobbered back to STARTING.
            if info.status != serve_state.ReplicaStatus.PROVISIONING:
                return
            info.url = f'http://{head_ip}:{info.port}'
            info.status = serve_state.ReplicaStatus.STARTING
            info.first_probe_time = self._env.time()
            followers = ([r for r in self._replicas.values()
                          if info.gang_id is not None
                          and r.gang_id == info.gang_id
                          and r.gang_rank > 0
                          and r.status ==
                          serve_state.ReplicaStatus.PROVISIONING]
                         if info.gang_rank == 0 else [])
            for f in followers:
                # Rank 0's address is the gang bus every follower
                # syncs against; set before their tasks render env.
                f.coordinator = info.url
        self._persist(info)
        # Gang fan-out: rank 0 is up, launch the follower ranks (each
        # its own cluster, same gang ID). Readiness still waits on the
        # barrier — rank 0's /readiness stays 503 until every rank
        # joins within SKYTPU_GANG_JOIN_TIMEOUT.
        for f in followers:
            self._env.spawn(self._launch_replica, f)
        self._record_launch_result(info, failed=False)

    def _record_launch_result(self, info: ReplicaInfo, failed: bool) -> None:
        if not failed:
            # NOTE: launch success only clears the backoff once the
            # replica actually turns READY (probe_all) — a cluster that
            # provisions fine but whose app never answers must still
            # back off, or it churns whole slices forever.
            return
        info.status = serve_state.ReplicaStatus.FAILED
        self._persist(info)
        # The launch op is terminal either way: a FAILED row is kept
        # for debugging (pruned by _bump_backoff), not replayed.
        self._journal_finish(info.launch_op)
        info.launch_op = None
        try:      # a launch can fail after partially creating the cluster
            self._env.down_cluster(info.cluster_name)
        except exceptions.ClusterDoesNotExist:
            pass
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Cleanup of failed replica '
                           f'{info.cluster_name} failed: {e}')
        self._bump_backoff()
        # Gang atomicity at launch: ONE rank failing to provision
        # fails the whole gang (a partial gang can never pass the
        # barrier anyway — tear it down now instead of burning the
        # join timeout).
        self.scale_down_gang(info.gang_id,
                             serve_state.ReplicaStatus.FAILED,
                             except_id=info.replica_id)

    def _bump_backoff(self) -> None:
        """One more replica died before ever serving: extend the
        exponential launch backoff (jittered — concurrent failures
        must not produce synchronized retry storms against the same
        exhausted zone/quota) and prune old FAILED rows."""
        with self._lock:
            self._launch_failures += 1
            delay = min(
                _launch_backoff_base() * (2 ** (self._launch_failures - 1)),
                _LAUNCH_BACKOFF_CAP)
            # Uniform over [_BACKOFF_JITTER_FRAC, 1.0] x delay: decorrelates
            # concurrent managers while keeping the exponential shape
            # (and the cap as a hard ceiling).
            delay *= (_BACKOFF_JITTER_FRAC
                      + (1.0 - _BACKOFF_JITTER_FRAC) * self._rng.random())
            self._backoff_until = self._env.time() + delay
            # Keep only the newest few FAILED rows (status/debugging);
            # older ones would otherwise accumulate one per retry forever.
            failed_ids = sorted(
                rid for rid, r in self._replicas.items()
                if r.status == serve_state.ReplicaStatus.FAILED)
            prune = failed_ids[:-_MAX_RETAINED_FAILED]
        for rid in prune:      # outside _lock: _untrack takes _db_lock
            self._untrack(rid)

    # --------------------------------------------------------------- gang
    def _gang_members_locked(self, gang_id: Optional[str]
                             ) -> List[ReplicaInfo]:
        """Every tracked member of ``gang_id`` (callers hold _lock)."""
        if gang_id is None:
            return []
        return [r for r in self._replicas.values()
                if r.gang_id == gang_id]

    def _gang_leader_locked(self, info: ReplicaInfo) -> ReplicaInfo:
        """The rank-0 member of ``info``'s gang (``info`` itself for
        singles/rank 0) — the one routable endpoint every HTTP-side
        lifecycle action (probe, drain, checkpoint) targets."""
        if info.gang_id is None or info.gang_rank == 0:
            return info
        for r in self._replicas.values():
            if r.gang_id == info.gang_id and r.gang_rank == 0:
                return r
        return info

    def _ckpt_key(self, info: ReplicaInfo) -> str:
        return info.gang_id or f'replica-{info.replica_id}'

    def scale_down_gang(self, gang_id: Optional[str],
                        status: Optional[serve_state.ReplicaStatus]
                        = None, *,
                        except_id: Optional[int] = None) -> None:
        """Tear down every member of a gang: one dead rank means the
        whole gang is dead — the controller then replaces the gang as
        one unit (its next tick sees all members terminal). No-op for
        ``gang_id=None`` (singles route through ``scale_down``)."""
        if gang_id is None:
            return
        with self._lock:
            member_ids = [r.replica_id for r in
                          self._gang_members_locked(gang_id)
                          if r.replica_id != except_id
                          and not r.status.is_terminal()
                          and r.status !=
                          serve_state.ReplicaStatus.SHUTTING_DOWN]
        for rid in member_ids:
            self._scale_down_one(rid, status)

    def replica_gangs(self) -> Dict[str, Dict[str, object]]:
        """rank0 url -> gang health block, for the LB sync payload:
        the policies use it to keep follower addresses out of probe
        sweeps while still accounting every rank's existence."""
        with self._lock:
            out: Dict[str, Dict[str, object]] = {}
            for r in self._replicas.values():
                if r.gang_id is None or r.gang_rank != 0 or not r.url:
                    continue
                members = self._gang_members_locked(r.gang_id)
                out[r.url] = {
                    'gang_id': r.gang_id,
                    'world': r.gang_world,
                    'follower_urls': [m.url for m in members
                                      if m.gang_rank > 0
                                      and m.url is not None],
                    'statuses': {str(m.gang_rank): m.status.value
                                 for m in members},
                }
            return out

    # -------------------------------------------------------------- drain
    def drain(self, replica_id: int,
              deadline_s: Optional[float] = None) -> bool:
        """Graceful scale-down: mark the replica DRAINING (it drops out
        of ``ready_urls`` — the LB removes it from rotation at its next
        sync), ask its model server to stop admitting and finish its
        in-flight requests, then tear the cluster down once drained or
        at the deadline. Idempotent; returns True when a drain was
        started (False: unknown replica or already leaving)."""
        with self._lock:
            info = self._replicas.get(replica_id)
            if info is not None:
                # Gang atomicity: a drain aimed at ANY member drains
                # the gang — through rank 0, its one HTTP endpoint
                # (rank 0's /drain fans out on the gang bus and
                # reports drained only once every rank acked).
                info = self._gang_leader_locked(info)
            if info is None or info.status in (
                    serve_state.ReplicaStatus.DRAINING,
                    serve_state.ReplicaStatus.SHUTTING_DOWN) or \
                    info.status.is_terminal():
                return False
            # A replica that never served (no URL yet) has nothing to
            # drain — plain scale_down below.
            drainable = (info.url is not None and info.status in (
                serve_state.ReplicaStatus.READY,
                serve_state.ReplicaStatus.NOT_READY))
            if drainable:
                info.status = serve_state.ReplicaStatus.DRAINING
                members = self._gang_members_locked(info.gang_id)
                for m in members:
                    if m.gang_rank > 0 and not m.status.is_terminal():
                        # Followers leave rotation bookkeeping with
                        # their leader (they were never routable, but
                        # health accounting must show the gang
                        # leaving as one unit).
                        m.status = serve_state.ReplicaStatus.DRAINING
        if not drainable:
            self.scale_down(replica_id)
            return False
        _transition_counter('DRAINING').inc()
        deadline_s = (float(deadline_s) if deadline_s is not None
                      else _drain_deadline_default())
        # Journal the drain with its ABSOLUTE deadline before the
        # first effect (the /drain POST): a controller that dies
        # mid-drain restarts and resumes the wait at the REMAINING
        # budget — in-flight requests get exactly the window they were
        # promised, not a fresh full deadline and not an instant kill.
        info.drain_op = self._journal_start(
            'drain', info, payload={'deadline_s': deadline_s},
            deadline_at=self._env.time() + deadline_s)
        self._persist(info)
        logger.info(f'Draining replica {info.replica_id}'
                    + (f' (gang {info.gang_id})' if info.gang_id
                       else '')
                    + f' (deadline {deadline_s:.0f}s).')
        self._env.spawn(self._drain_then_down, info, deadline_s)
        return True

    def _drain_then_down(self, info: ReplicaInfo,
                         deadline_s: float) -> None:
        try:
            self._await_replica_drain(info, deadline_s)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Drain of replica {info.replica_id} failed '
                           f'({type(e).__name__}: {e}); tearing down '
                           'anyway')
        self.scale_down(info.replica_id)
        self._journal_finish(info.drain_op)
        info.drain_op = None

    def _await_replica_drain(self, info: ReplicaInfo,
                             deadline_s: float) -> None:
        """POST /drain to the replica's model server, then poll its
        drain status until drained or the deadline. A replica whose
        server doesn't implement the drain contract (no ``draining``
        key in the response) tears down immediately — there is nothing
        to wait for. Deadline stragglers (a replica that never reports
        ``drained``) are torn down at exactly the deadline; their
        in-flight requests fail over through the LB's recovery path."""
        assert info.url is not None
        deadline = self._env.monotonic() + deadline_s
        try:
            payload = self._env.http_json(
                info.url + '/drain', {'deadline_s': deadline_s},
                timeout=10)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Drain request to replica '
                           f'{info.replica_id} failed '
                           f'({type(e).__name__}: {e}); skipping wait')
            return
        if not isinstance(payload, dict) or 'draining' not in payload:
            logger.info(f'Replica {info.replica_id} has no drain '
                        'support; tearing down immediately.')
            return
        while self._env.monotonic() < deadline:
            try:
                status = self._env.http_json(info.url + '/drain',
                                             timeout=10)
                if status.get('drained'):
                    logger.info(
                        f'Replica {info.replica_id} drained cleanly.')
                    return
            except Exception as e:  # pylint: disable=broad-except
                logger.warning(f'Drain poll of replica '
                               f'{info.replica_id} failed '
                               f'({type(e).__name__}: {e}); assuming '
                               'gone')
                return
            # Jittered poll (graftcheck GC112: no fixed-sleep loops),
            # bounded by the remaining deadline so the teardown lands
            # AT the deadline, not one poll interval past it.
            remaining = deadline - self._env.monotonic()
            if remaining <= 0:
                break
            self._env.sleep(min(remaining,
                                0.25 * (0.5 + self._rng.random())))
        logger.warning(f'Replica {info.replica_id} drain deadline '
                       f'({deadline_s:.0f}s) exceeded; tearing down '
                       '(stragglers fail over through the LB).')

    def handle_preemption_warning(
            self, replica_id: int,
            deadline_s: Optional[float] = None) -> bool:
        """Advance preemption notice (cloud spot warning / injected
        ``preempt_signal`` at the ``preempt_warning`` /
        ``spot_preemption`` sites): checkpoint the replica's hot
        prefix-cache chains FIRST (the KV is gone once the capacity
        is), then route through graceful drain so in-flight work
        finishes (or migrates) before the capacity disappears.

        Race-free with an in-flight drain AND re-delivery to another
        rank: the checkpoint step is guarded by a flag keyed by GANG
        ID (replica id for singles) under the manager lock, so a
        warning that lands while a drain is already running — or a
        warning re-delivered to a *different rank of the same gang* —
        still checkpoints exactly once and never double-drains."""
        logger.info(f'Preemption warning for replica {replica_id}; '
                    'checkpointing and draining ahead of it.')
        with self._lock:
            info = self._replicas.get(replica_id)
            if info is not None:
                # Gang-atomic: warnings to any rank checkpoint/drain
                # the gang through its rank-0 endpoint.
                info = self._gang_leader_locked(info)
            if info is not None and info.is_spot:
                self._m_spot_preempt.inc()
        if info is not None:
            self._checkpoint_replica(info)
            return self.drain(info.replica_id, deadline_s)
        return self.drain(replica_id, deadline_s)

    def _checkpoint_replica(self, info: ReplicaInfo) -> None:
        """Fetch the replica's prefix-cache checkpoint (``POST
        /checkpoint`` against the gang leader — the response body is
        the SKCK container; a gang leader's export completes only when
        every rank acked) and store it for replacement warmup. At most
        once per gang (flag keyed by gang ID under the lock);
        best-effort — a failure clears the flag so a later warning may
        retry, and the drain proceeds either way."""
        key = self._ckpt_key(info)
        with self._lock:
            if self._ckpt_done.get(key) or info.url is None:
                return
            self._ckpt_done[key] = True
            info.checkpointed = True
        # Persist the dedupe key: a controller that dies between the
        # checkpoint and the preemption must never double-checkpoint
        # the same gang after restart (re-delivered warnings included).
        self._put_note(f'ckpt_done:{key}', True)
        try:
            blob = self._env.http_post_bytes(
                info.url + '/checkpoint', b'{}',
                content_type='application/json', timeout=30)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Checkpoint of replica {info.replica_id} '
                           f'failed ({type(e).__name__}: {e}); its '
                           'replacement will boot cold')
            with self._lock:
                self._ckpt_done[key] = False
                info.checkpointed = False
            self._del_note(f'ckpt_done:{key}')
            return
        if self._faults is not None:
            # Deterministic checkpoint corruption (site 'kv_wire', kind
            # kv_corruption): one byte of the fetched container flips —
            # the replacement's CRC-checked warmup must refuse it and
            # boot cold, never byte-wrong warm.
            rule = self._faults.fire('kv_wire')
            if rule is not None and rule.kind == 'kv_corruption':
                blob = faults_lib.corrupt_blob(blob, rule)
                logger.warning('injected kv_corruption on the stored '
                               'checkpoint (1 byte flipped)')
        with self._ckpt_lock:
            self._ckpt_bytes = blob
            self._ckpt_time = self._env.time()
        logger.info(f'Checkpointed replica {info.replica_id}: '
                    f'{len(blob)} byte(s) of prefix-cache state.')

    def checkpoint_for_warmup(self) -> Optional[bytes]:
        """The freshest stored checkpoint, or None (none taken yet, or
        stale past the TTL — cold traffic has moved on)."""
        with self._ckpt_lock:
            if self._ckpt_bytes is None:
                return None
            if self._env.time() - self._ckpt_time > _ckpt_ttl():
                return None
            return self._ckpt_bytes

    def _warm_replica(self, info: ReplicaInfo) -> None:
        """Land the stored checkpoint into a replica that just passed
        its first probe — BEFORE it is marked READY, so by the time
        the LB routes to it the prefix cache already holds the
        preempted replica's hot chains (near-warm recovery TTFT). At
        most once per replica; best-effort with a bounded timeout —
        a failed warmup costs only cold-cache latency."""
        if info.warmed:
            return
        info.warmed = True
        blob = self.checkpoint_for_warmup()
        if blob is None or info.url is None:
            return
        t0 = self._env.monotonic()
        try:
            import json as _json
            body = self._env.http_post_bytes(
                info.url + '/kv/warmup', blob,
                content_type='application/octet-stream',
                timeout=_warmup_timeout())
            payload = _json.loads(body)
        except Exception as e:  # pylint: disable=broad-except
            if '400' in str(e) or 'invalid_checkpoint' in str(e):
                # The warmup target REFUSED the container (malformed /
                # checksum mismatch): a corrupted checkpoint became a
                # cold boot instead of byte-wrong warmth.
                faults_lib.gray_failure_counter('kv_corruption').inc()
            logger.warning(f'Prefix warmup of replica '
                           f'{info.replica_id} failed '
                           f'({type(e).__name__}: {e}); entering '
                           'rotation cold')
            return
        dur = self._env.monotonic() - t0
        self._h_warmup.observe(dur)
        logger.info(
            f'Replica {info.replica_id} prefix-warmed in {dur:.2f}s: '
            f'{payload.get("warmed_rows", 0)} row(s) across '
            f'{payload.get("entries", 0)} entr(ies).')

    def pop_provision_observations(self) -> List[float]:
        """Drain the unconsumed provision-latency observations (the
        controller feeds them to the forecast autoscaler's lead-time
        EWMA each tick)."""
        with self._lock:
            obs, self._provision_obs = self._provision_obs, []
        return obs

    # ------------------------------------------------------------ teardown
    def scale_down(self, replica_id: int, status: Optional[
            serve_state.ReplicaStatus] = None) -> None:
        """Terminate a replica (async; cluster teardown is slow). A
        gang member's teardown tears the WHOLE gang down — one dead
        rank, dead gang, replaced as one unit."""
        with self._lock:
            info = self._replicas.get(replica_id)
            gang_id = info.gang_id if info is not None else None
        self._scale_down_one(replica_id, status)
        self.scale_down_gang(gang_id, status, except_id=replica_id)

    def _scale_down_one(self, replica_id: int, status: Optional[
            serve_state.ReplicaStatus] = None) -> None:
        with self._lock:
            info = self._replicas.get(replica_id)
            if info is None:
                return
            if info.teardown_started:
                # Exactly-once teardown: racing scale_down calls (a
                # drain deadline racing a probe escalation, re-issued
                # autoscaler decisions, journal replay after restart)
                # must never run a second down_cluster for the same
                # replica.
                return
            info.teardown_started = True
            info.status = status or serve_state.ReplicaStatus.SHUTTING_DOWN
        self._persist(info)
        op_id = self._journal_start('teardown', info)

        def _down():
            try:
                self._env.down_cluster(info.cluster_name)
            except exceptions.ClusterDoesNotExist:
                pass
            except Exception as e:  # pylint: disable=broad-except
                logger.warning(f'Teardown of {info.cluster_name} failed: '
                               f'{type(e).__name__}: {e}')
            self._untrack(replica_id)  # atomic vs _persist (see _db_lock)
            self._journal_finish(op_id)

        self._env.spawn(_down)

    def terminate_all(self) -> None:
        with self._lock:
            ids = list(self._replicas)
        fns = []
        for rid in ids:
            info = self._replicas.get(rid)
            if info is None:
                continue
            fns.append(lambda i=info: self._sync_down(i))
        self._env.run_parallel(fns)

    def _sync_down(self, info: ReplicaInfo) -> None:
        try:
            self._env.down_cluster(info.cluster_name)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Teardown of {info.cluster_name} during '
                           f'terminate_all failed (it may leak): '
                           f'{type(e).__name__}: {e}')
        self._untrack(info.replica_id)

    # ------------------------------------------------------------- probing
    def _probe_one(self, info: ReplicaInfo) -> bool:
        assert info.url is not None
        if self._faults is not None:
            rule = self._faults.fire('probe')
            if rule is not None and rule.kind == 'probe_timeout':
                # Injected probe timeout: burn (a bounded slice of) the
                # timeout, then report failure — the consecutive-
                # failure escalation runs exactly as for a real one.
                self._env.sleep(min(rule.delay_s,
                                    self.spec.readiness_timeout_seconds))
                logger.warning(f'Probe of replica {info.replica_id} '
                               'failed (injected probe_timeout)')
                return False
        url = info.url + self.spec.readiness_path
        try:
            return self._env.probe_http(
                url, self.spec.post_data,
                self.spec.readiness_timeout_seconds)
        except Exception as e:  # pylint: disable=broad-except
            # Routine while a replica boots; the consecutive-failure
            # counters escalate, but the reason must stay observable.
            logger.debug(f'Probe of replica {info.replica_id} ({url}) '
                         f'failed: {type(e).__name__}: {e}')
            return False

    def _check_preempted(self, info: ReplicaInfo) -> bool:
        """Cluster-gone (or not UP) while we thought it was running =
        preemption (reference ``_handle_preemption`` ``:782``)."""
        if self._faults is not None:
            rule = self._faults.fire('preempt')
            if rule is not None and rule.kind == 'preempt_signal':
                logger.warning(f'Replica {info.replica_id} preempted '
                               '(injected preempt_signal)')
                return True
        return self._env.cluster_gone(info.cluster_name)

    def set_telemetry_sink(self, sink: Any) -> None:
        """Install the controller's fleet-telemetry ingest callable:
        ``sink(source, payload)`` receives each scraped
        ``/telemetry/summary`` body keyed by the replica's URL."""
        self._telemetry_sink = sink

    def _scrape_telemetry(self, info: ReplicaInfo) -> None:
        """Pull one replica's telemetry summary right after a
        successful readiness probe and hand it to the sink. The
        per-replica cursor makes completed traces ship at most once;
        any failure is logged at debug and otherwise ignored — the
        fleet plane must never destabilize the health plane."""
        if self._telemetry_sink is None or not info.url:
            return
        source = info.url.rstrip('/')
        since = self._telemetry_cursors.get(source, 0)
        try:
            payload = self._env.http_json(
                f'{source}/telemetry/summary?since={since}',
                timeout=self.spec.readiness_timeout_seconds)
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'telemetry scrape of replica '
                         f'{info.replica_id} failed: '
                         f'{type(e).__name__}: {e}')
            return
        if not isinstance(payload, dict):
            return
        cursor = payload.get('cursor')
        if isinstance(cursor, int):
            self._telemetry_cursors[source] = cursor
        try:
            self._telemetry_sink(source, payload)
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'telemetry ingest for replica '
                         f'{info.replica_id} failed: '
                         f'{type(e).__name__}: {e}')

    def probe_all(self) -> None:
        """One probe sweep (reference ``_probe_all_replicas`` ``:1026``)."""
        with self._lock:
            infos = list(self._replicas.values())
        for info in infos:
            if info.status not in (serve_state.ReplicaStatus.STARTING,
                                   serve_state.ReplicaStatus.READY,
                                   serve_state.ReplicaStatus.NOT_READY):
                continue
            if info.gang_rank > 0:
                # Follower ranks have no probe endpoint (rank 0 is the
                # gang's one routable URL; its readiness already
                # embeds the barrier and the gang bus covers process
                # health). Cluster existence is their only direct
                # signal — and a follower cluster gone means the WHOLE
                # gang is gone (scale_down is gang-atomic).
                if self._check_preempted(info):
                    logger.info(
                        f'Gang {info.gang_id}: follower rank '
                        f'{info.gang_rank} (replica '
                        f'{info.replica_id}) preempted; failing the '
                        'whole gang.')
                    if info.is_spot:
                        self._m_spot_preempt.inc()
                    _transition_counter('PREEMPTED').inc()
                    self.scale_down(info.replica_id,
                                    serve_state.ReplicaStatus.PREEMPTED)
                continue
            # Advance preemption warning (injected; cloud spot notices
            # would land here too): drain instead of hard-killing.
            if (self._faults is not None
                    and info.status == serve_state.ReplicaStatus.READY):
                rule = self._faults.fire('preempt_warning')
                if rule is not None and rule.kind == 'preempt_signal':
                    self.handle_preemption_warning(info.replica_id)
                    continue
                # Spot-targeted kill schedule: the site counter only
                # advances for SPOT replicas, so an `at`/`every` rule
                # deterministically names the Nth spot sweep — the
                # chaos/bench seeded spot-preemption path (checkpoint
                # + drain + teardown + backfill).
                if info.is_spot:
                    rule = self._faults.fire('spot_preemption')
                    if rule is not None and \
                            rule.kind == 'preempt_signal':
                        self.handle_preemption_warning(info.replica_id)
                        continue
            # Cluster existence is ground truth, checked BEFORE the HTTP
            # probe: a terminated replica's address can keep answering (IP
            # reuse on clouds; surviving process on the local provider).
            if self._check_preempted(info):
                logger.info(f'Replica {info.replica_id} preempted.')
                if info.is_spot:
                    # Hard loss (no advance warning): counted the same
                    # as a warned preemption; nothing to checkpoint —
                    # the capacity is already gone.
                    self._m_spot_preempt.inc()
                info.status = serve_state.ReplicaStatus.PREEMPTED
                _transition_counter('PREEMPTED').inc()
                self._persist(info)
                self.scale_down(info.replica_id,
                                serve_state.ReplicaStatus.PREEMPTED)
                continue
            if self._probe_one(info):
                _probe_counter('success').inc()
                info.consecutive_failures = 0
                if info.status != serve_state.ReplicaStatus.READY:
                    # First successful probe: prefix-warm from the
                    # latest preemption checkpoint BEFORE the replica
                    # is marked READY — it must never enter ready_urls
                    # (and thus LB rotation) cold when warm state
                    # exists.
                    self._warm_replica(info)
                    logger.info(f'Replica {info.replica_id} is READY at '
                                f'{info.url}.')
                    _transition_counter('READY').inc()
                    # The journaled launch op is acked: the replica
                    # served a probe — it is no longer a potential
                    # zombie for restart reconciliation to reap.
                    self._journal_finish(info.launch_op)
                    info.launch_op = None
                    self._h_provision.observe(
                        max(0.0, self._env.time() - info.created_time))
                    with self._lock:     # a replica serves: reset backoff
                        self._launch_failures = 0
                        self._backoff_until = 0.0
                        self._provision_obs.append(
                            max(0.0,
                                self._env.time() - info.created_time))
                info.status = serve_state.ReplicaStatus.READY
                self._persist(info)
                self._mirror_gang_ready(info)
                # Byzantine canary (env-clock cadence): a READY
                # replica that answers the known-digest greedy canary
                # WRONG is quarantined before it can serve a second
                # wrong response.
                self._canary_check(info)
                # Fleet-telemetry scrape rides the probe it just
                # passed (best-effort: never fails the sweep).
                self._scrape_telemetry(info)
                continue
            # Probe failed on a live cluster.
            _probe_counter('failure').inc()
            if info.status == serve_state.ReplicaStatus.STARTING:
                elapsed = self._env.time() - (info.first_probe_time or 0)
                if elapsed > self.spec.initial_delay_seconds:
                    logger.warning(
                        f'Replica {info.replica_id} failed to become ready '
                        f'within {self.spec.initial_delay_seconds}s.')
                    info.status = serve_state.ReplicaStatus.FAILED_PROBE
                    _transition_counter('FAILED_PROBE').inc()
                    self._persist(info)
                    self.scale_down(info.replica_id,
                                    serve_state.ReplicaStatus.FAILED_PROBE)
                    # The cluster came up but the app never served — the
                    # relaunch loop must back off, not churn slices.
                    self._bump_backoff()
                continue
            info.consecutive_failures += 1
            if info.consecutive_failures >= _PROBE_FAILURE_TERMINATE:
                # The app on a still-UP cluster is persistently dead
                # (crashed server, wedged process). NOT_READY is neither
                # ready nor terminal, so without this the autoscaler
                # counts it alive forever and never replaces it.
                logger.warning(
                    f'Replica {info.replica_id} failed '
                    f'{info.consecutive_failures} consecutive probes; '
                    'terminating it for replacement.')
                info.status = serve_state.ReplicaStatus.FAILED_PROBE
                _transition_counter('FAILED_PROBE').inc()
                self._persist(info)
                self.scale_down(info.replica_id,
                                serve_state.ReplicaStatus.FAILED_PROBE)
                self._bump_backoff()
            elif info.consecutive_failures >= _PROBE_FAILURE_GRACE:
                if info.status != serve_state.ReplicaStatus.NOT_READY:
                    _transition_counter('NOT_READY').inc()
                info.status = serve_state.ReplicaStatus.NOT_READY
                self._persist(info)

    # --------------------------------------------------------- quarantine
    def _canary_check(self, info: ReplicaInfo) -> bool:
        """One canary evaluation for a READY replica (no-op unless the
        cadence elapsed on the env clock). Greedy canary prompt ->
        digest of the returned tokens -> compare against the
        configured/learned reference. A mismatch quarantines; a
        transport failure is IGNORED here (liveness belongs to the
        readiness-probe escalation — the canary only judges replicas
        that answer). Returns True when the replica was quarantined."""
        if (self._canary_interval <= 0 or info.gang_rank > 0
                or info.url is None):
            return False
        now = self._env.time()
        if now - info.last_canary_t < self._canary_interval:
            return False
        info.last_canary_t = now
        forced = False
        if self._faults is not None:
            # Deterministic byzantine injection (site 'canary', kind
            # byzantine_response): this replica's answer is treated as
            # wrong-digest — the quarantine path runs exactly as for a
            # really-corrupted replica.
            rule = self._faults.fire('canary')
            if rule is not None and rule.kind == 'byzantine_response':
                forced = True
        if not forced:
            try:
                resp = self._env.http_json(
                    info.url + '/generate',
                    {'prompt': list(self._canary_prompt),
                     'max_new_tokens': self._canary_max_new,
                     'temperature': 0.0},
                    timeout=30)
                tokens = (resp or {}).get('tokens')
                if not isinstance(tokens, list):
                    return False
                digest = canary_digest(tokens)
            except Exception as e:  # pylint: disable=broad-except
                logger.debug(
                    f'Canary probe of replica {info.replica_id} '
                    f'failed ({type(e).__name__}: {e}); the readiness '
                    'probe escalation owns liveness')
                return False
            expected = self._canary_expected or self._canary_learned
            if expected is None:
                # Quorum-of-first: the reference digest is learned
                # from the first replica that answers (configure an
                # expected_digest to close the first-answerer-is-
                # byzantine window). Persisted keyed by version: a
                # restarted controller keeps judging canaries against
                # the SAME reference instead of relearning from a
                # possibly-byzantine first answerer.
                self._canary_learned = digest
                self._put_note(f'canary_digest:v{self.version}', digest)
                logger.info(
                    f'Canary reference digest learned from replica '
                    f'{info.replica_id}: {digest}')
                return False
            if digest == expected:
                return False
            logger.warning(
                f'Replica {info.replica_id} answered the canary with '
                f'digest {digest} != expected {expected} (byzantine '
                'response — silent data corruption).')
        else:
            logger.warning(
                f'Replica {info.replica_id} canary forced byzantine '
                '(injected byzantine_response).')
        return self.quarantine_replica(info.replica_id)

    def quarantine_replica(self, replica_id: int) -> bool:
        """Byzantine containment: move the replica (the WHOLE gang for
        gang members) to QUARANTINED — out of ``ready_urls``
        immediately, excluded by every LB policy at its next sync,
        then drained and torn down; the autoscaler replaces it
        (QUARANTINED is terminal). Idempotent; returns True when a
        quarantine was started."""
        with self._lock:
            info = self._replicas.get(replica_id)
            if info is not None:
                info = self._gang_leader_locked(info)
            if info is None or info.status.is_terminal() or \
                    info.status in (
                        serve_state.ReplicaStatus.SHUTTING_DOWN,):
                return False
            info.status = serve_state.ReplicaStatus.QUARANTINED
            for m in self._gang_members_locked(info.gang_id):
                if m.gang_rank > 0 and not m.status.is_terminal():
                    m.status = serve_state.ReplicaStatus.QUARANTINED
            self.quarantined_count += 1
        _transition_counter('QUARANTINED').inc()
        self._m_quarantined.inc()
        faults_lib.gray_failure_counter('byzantine_response').inc()
        self._persist(info)
        logger.warning(
            f'Replica {info.replica_id}'
            + (f' (gang {info.gang_id})' if info.gang_id else '')
            + ' QUARANTINED: out of rotation now, draining, then '
              'tearing down for replacement.')
        self._env.spawn(self._drain_then_down, info,
                        _drain_deadline_default())
        return True

    def _mirror_gang_ready(self, leader: ReplicaInfo) -> None:
        """Health accounting for follower ranks: rank 0 READY means
        the barrier completed, which means every rank is up — mirror
        the status onto the follower rows (they are never probed and
        never routable, but operators and the autoscaler must see the
        gang's full health picture)."""
        if leader.gang_id is None:
            return
        with self._lock:
            members = [m for m in
                       self._gang_members_locked(leader.gang_id)
                       if m.gang_rank > 0 and m.status in (
                           serve_state.ReplicaStatus.STARTING,
                           serve_state.ReplicaStatus.NOT_READY)]
            for m in members:
                m.status = serve_state.ReplicaStatus.READY
        for m in members:
            self._persist(m)

    # ------------------------------------------------------ reconciliation
    def reconcile(self) -> Dict[str, int]:
        """Rebuild the manager after a controller restart from the
        persisted rows + pending journal ops + controller notes, with
        live probes as ground truth (docs/robustness.md, controller
        failure domain). Per discovered replica, exactly one of:

        - **adopted** — healthy (cluster up, probe passes): re-owned
          in place, role/gang recovered from the journal descriptor
          and refined by live ``/metrics?format=json`` +
          ``/gang/status`` probes; never relaunched, never re-warmed.
        - **probe_pending** — cluster up but the app not answering:
          re-enters STARTING with a fresh grace window.
        - **drain_resumed** — an interrupted drain continues at its
          *remaining* deadline (the journal stored the absolute one).
        - **teardown_replayed** — an unacked teardown (or a terminal/
          SHUTTING_DOWN row) runs exactly once.
        - **zombie_killed** — a crash mid-launch leaked a cluster with
          no live owner: torn down, row cleared, the autoscaler
          relaunches fresh.
        - **preempted** — the cluster vanished during the outage:
          marked PREEMPTED and cleaned up like any hard loss.

        Also restores the checkpoint-dedupe keys (a preemption warning
        re-delivered after restart still checkpoints exactly once) and
        the learned canary digest for the current spec version, and
        seeds ``_next_replica_id`` / the reserved-port set from the
        persisted history so an adopted fleet never collides with new
        launches. Idempotent: an empty DB reconciles to a no-op."""
        rows = self._env.load_replica_rows(self.service_name)
        ops = self._env.pending_ops(self.service_name)
        notes = self._env.get_notes(self.service_name)
        stats = {outcome: 0 for outcome in ADOPT_OUTCOMES}
        now = self._env.time()
        # Durable facts first: dedupe keys + the canary reference.
        with self._lock:
            for key, val in notes.items():
                if key.startswith('ckpt_done:') and val:
                    self._ckpt_done[key[len('ckpt_done:'):]] = True
        digest = notes.get(f'canary_digest:v{self.version}')
        if isinstance(digest, str) and self._canary_learned is None:
            self._canary_learned = digest
        launch_ops = {op['replica_id']: op for op in ops
                      if op['kind'] == 'launch'}
        drain_ops = {op['replica_id']: op for op in ops
                     if op['kind'] == 'drain'}
        teardown_ops = {op['replica_id']: op for op in ops
                        if op['kind'] == 'teardown'}
        # Id/port seeding: the counter must clear every id the service
        # EVER persisted (rows and in-flight ops both), or an adopted
        # fleet gets a duplicate replica id on the first scale-up.
        max_id = max(
            [r['replica_id'] for r in rows]
            + [op['replica_id'] or 0 for op in ops] + [0])
        with self._lock:
            self._next_replica_id = max(self._next_replica_id,
                                        max_id + 1)
            self._reserved_ports |= {r['port'] for r in rows
                                     if r.get('port')}
        for row in sorted(rows, key=lambda r: r['replica_id']):
            rid = row['replica_id']
            self._reconcile_row(
                row, launch_ops.pop(rid, None),
                drain_ops.pop(rid, None), teardown_ops.pop(rid, None),
                stats, now)
        # Launch ops with no row: the controller died between the
        # journal write and the row write — the cluster (if the launch
        # thread got that far) is a zombie with no owner.
        for rid in sorted(launch_ops):
            op = launch_ops[rid]
            cluster = ((op.get('payload') or {}).get('cluster_name')
                       or self._replica_cluster_name(rid))
            logger.warning(f'Reconcile: journaled launch of replica '
                           f'{rid} has no row; reaping zombie cluster '
                           f'{cluster}.')
            self._env.spawn(self._reap_zombie, cluster, op['op_id'],
                            None)
            stats['zombie_killed'] += 1
        # Stray drain/teardown ops with no row: the op's teardown
        # completed but the finish ack was lost in the crash — done.
        for op in (list(drain_ops.values())
                   + list(teardown_ops.values())):
            self._journal_finish(op['op_id'])
        for outcome, n in stats.items():
            if n:
                self._m_adopted[outcome].inc(n)
        if any(stats.values()):
            logger.info(
                'Reconciled persisted state: '
                + ', '.join(f'{k}={v}' for k, v in sorted(stats.items())
                            if v))
        return stats

    def _reconcile_row(self, row: Dict[str, object],
                       launch_op: Optional[Dict[str, object]],
                       drain_op: Optional[Dict[str, object]],
                       teardown_op: Optional[Dict[str, object]],
                       stats: Dict[str, int], now: float) -> None:
        rid = int(row['replica_id'])
        payload = dict((launch_op or {}).get('payload') or {})
        info = ReplicaInfo(
            rid, str(row['cluster_name']), int(row['version']),
            bool(row['is_spot']),
            int(row.get('port') or self.spec.replica_port),
            role=str(payload.get('role') or 'colocated'),
            gang_id=payload.get('gang_id'),
            gang_rank=int(payload.get('gang_rank') or 0),
            gang_world=int(payload.get('gang_world') or 1),
            created_time=now)
        info.url = row.get('url')
        # Adopted replicas are already serving traffic: re-warming
        # them would clobber a hot prefix cache with a stale blob.
        info.warmed = True
        status = row['status']
        if (teardown_op is not None
                or status == serve_state.ReplicaStatus.SHUTTING_DOWN
                or status.is_terminal()):
            # Replay the unacked teardown exactly once (the row alone
            # is evidence enough: a terminal status only persists on
            # the way into scale_down).
            info.status = (status if status.is_terminal()
                           else serve_state.ReplicaStatus.SHUTTING_DOWN)
            info.teardown_started = True
            with self._lock:
                self._replicas[rid] = info
            op_id = (teardown_op['op_id'] if teardown_op
                     else self._journal_start('teardown', info))
            for op in (drain_op, launch_op):
                if op:
                    self._journal_finish(op['op_id'])
            self._env.spawn(self._reap_zombie, info.cluster_name,
                            op_id, rid)
            stats['teardown_replayed'] += 1
            return
        if status in (serve_state.ReplicaStatus.PENDING,
                      serve_state.ReplicaStatus.PROVISIONING):
            # Crash mid-launch: the launch thread died with the old
            # controller. Whatever the cloud built is a zombie — tear
            # it down and let the autoscaler relaunch fresh.
            info.status = serve_state.ReplicaStatus.SHUTTING_DOWN
            info.teardown_started = True
            with self._lock:
                self._replicas[rid] = info
            op_id = (launch_op['op_id'] if launch_op
                     else self._journal_start('teardown', info))
            self._env.spawn(self._reap_zombie, info.cluster_name,
                            op_id, rid)
            stats['zombie_killed'] += 1
            return
        # STARTING / READY / NOT_READY / DRAINING: the replica claims
        # to exist — cluster existence is ground truth, then the probe.
        if self._env.cluster_gone(info.cluster_name):
            logger.info(f'Reconcile: replica {rid} lost while the '
                        'controller was down (preempted).')
            if info.is_spot:
                self._m_spot_preempt.inc()
            info.status = serve_state.ReplicaStatus.PREEMPTED
            info.teardown_started = True
            with self._lock:
                self._replicas[rid] = info
            self._persist(info)
            op_id = self._journal_start('teardown', info)
            for op in (drain_op, launch_op):
                if op:
                    self._journal_finish(op['op_id'])
            self._env.spawn(self._reap_zombie, info.cluster_name,
                            op_id, rid)
            stats['preempted'] += 1
            return
        if status == serve_state.ReplicaStatus.DRAINING or \
                drain_op is not None:
            # Resume the interrupted drain at its REMAINING deadline.
            deadline_at = (drain_op or {}).get('deadline_at')
            remaining = max(0.0, float(deadline_at) - now) \
                if deadline_at is not None else 0.0
            info.status = serve_state.ReplicaStatus.DRAINING
            info.drain_op = (drain_op['op_id'] if drain_op
                             else self._journal_start(
                                 'drain', info, deadline_at=now))
            with self._lock:
                self._replicas[rid] = info
            self._persist(info)
            if launch_op:
                self._journal_finish(launch_op['op_id'])
            logger.info(f'Reconcile: resuming drain of replica {rid} '
                        f'with {remaining:.1f}s of its deadline left.')
            self._env.spawn(self._drain_then_down, info, remaining)
            stats['drain_resumed'] += 1
            return
        if info.gang_rank > 0:
            # Follower ranks serve no HTTP: their health is the
            # leader's barrier + cluster existence (checked above).
            info.status = status
            with self._lock:
                self._replicas[rid] = info
            stats['adopted' if status ==
                  serve_state.ReplicaStatus.READY else
                  'probe_pending'] += 1
            return
        healthy = info.url is not None and self._probe_one(info)
        if healthy:
            # ORPHAN ADOPTION: the replica is alive and serving — own
            # it again without relaunching (relaunching a healthy
            # fleet is the scale-to-zero failure mode this exists to
            # prevent). Role/mesh/gang re-read from the live replica.
            self._adopt_probe(info)
            info.status = serve_state.ReplicaStatus.READY
            info.consecutive_failures = 0
            with self._lock:
                self._replicas[rid] = info
            self._persist(info)
            if launch_op:
                self._journal_finish(launch_op['op_id'])
            logger.info(f'Reconcile: adopted healthy replica {rid} at '
                        f'{info.url} (role={info.role}'
                        + (f', gang={info.gang_id}' if info.gang_id
                           else '') + ').')
            stats['adopted'] += 1
            return
        # Cluster up, app not answering (booting, or it died with the
        # controller): STARTING with a fresh grace window — the normal
        # probe escalation replaces it if it never comes back.
        info.status = serve_state.ReplicaStatus.STARTING
        info.first_probe_time = now
        info.launch_op = launch_op['op_id'] if launch_op else None
        with self._lock:
            self._replicas[rid] = info
        self._persist(info)
        stats['probe_pending'] += 1

    def _adopt_probe(self, info: ReplicaInfo) -> None:
        """Refine an adopted replica's descriptor from the replica
        itself: disaggregation role from ``/metrics?format=json``,
        gang identity from ``/gang/status``. Best-effort — the journal
        descriptor already seeded both."""
        assert info.url is not None
        try:
            payload = self._env.http_json(
                info.url + '/metrics?format=json', timeout=10)
            role = (payload.get('disagg') or {}).get('role') \
                if isinstance(payload, dict) else None
            if role:
                info.role = str(role)
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'Adopt probe (/metrics) of replica '
                         f'{info.replica_id} failed: '
                         f'{type(e).__name__}: {e}')
        try:
            payload = self._env.http_json(info.url + '/gang/status',
                                          timeout=10)
            if isinstance(payload, dict) and payload.get('gang_id'):
                info.gang_id = str(payload['gang_id'])
                info.gang_world = int(payload.get('world',
                                                  info.gang_world))
                info.gang_rank = int(payload.get('rank', 0))
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'Adopt probe (/gang/status) of replica '
                         f'{info.replica_id} failed: '
                         f'{type(e).__name__}: {e}')

    def _reap_zombie(self, cluster_name: str, op_id: Optional[int],
                     replica_id: Optional[int]) -> None:
        """Tear down a cluster the crashed controller left behind
        (zombie launch, unacked teardown) and clear its row + op."""
        try:
            self._env.down_cluster(cluster_name)
        except exceptions.ClusterDoesNotExist:
            pass
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Reconcile teardown of {cluster_name} '
                           f'failed (it may leak): '
                           f'{type(e).__name__}: {e}')
        if replica_id is not None:
            self._untrack(replica_id)
        self._journal_finish(op_id)

    # ------------------------------------------------------------- queries
    def replicas(self) -> List[ReplicaInfo]:
        with self._lock:
            return list(self._replicas.values())

    def ready_urls(self) -> List[str]:
        """The routable endpoints: READY replicas' URLs — rank 0 only
        for gangs. A gang presents exactly ONE endpoint; follower
        URLs must never reach LB rotation or policy probe sweeps."""
        with self._lock:
            return [r.url for r in self._replicas.values()
                    if r.status == serve_state.ReplicaStatus.READY
                    and r.url is not None and r.gang_rank == 0]

    def replica_roles(self) -> Dict[str, str]:
        """url -> disaggregation role for every ROUTABLE replica with
        an address (gang followers excluded — they are not endpoints)
        — the LB sync payload (the phase-aware policy's cold-probe
        fallback)."""
        with self._lock:
            return {r.url: r.role for r in self._replicas.values()
                    if r.url is not None and r.gang_rank == 0}

    # ------------------------------------------- journaled persistence
    # THE sanctioned lifecycle-state writers (graftcheck GC120): every
    # replica-row write, journal op and controller note in this file
    # and controller.py goes through _persist/_untrack/_journal_start/
    # _journal_finish/_put_note/_del_note — nothing else may touch the
    # serve DB, so the journal can never drift from what the state
    # machines actually did.
    def _journal_start(self, kind: str, info: ReplicaInfo,
                       payload: Optional[Dict[str, object]] = None,
                       deadline_at: Optional[float] = None
                       ) -> Optional[int]:
        """Journal a multi-step lifecycle op BEFORE its first effect
        runs; returns the op id (None when the journal write failed —
        the op still runs, it just won't be resumable)."""
        body = dict(payload or {})
        body.setdefault('cluster_name', info.cluster_name)
        try:
            return self._env.journal_op_start(
                self.service_name, kind, info.replica_id,
                info.gang_id, body, deadline_at=deadline_at)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(
                f'journal write for {kind} of replica '
                f'{info.replica_id} failed ({type(e).__name__}: {e}); '
                'the op will not survive a controller restart')
            return None

    def _journal_finish(self, op_id: Optional[int]) -> None:
        if op_id is None:
            return
        try:
            self._env.journal_op_finish(self.service_name, op_id)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'journal finish of op {op_id} failed '
                           f'({type(e).__name__}: {e}); a restart may '
                           'replay it (replay is idempotent)')

    def _put_note(self, key: str, value: object) -> None:
        try:
            self._env.put_note(self.service_name, key, value)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'controller note {key!r} write failed '
                           f'({type(e).__name__}: {e})')

    def _del_note(self, key: str) -> None:
        try:
            self._env.del_note(self.service_name, key)
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'controller note {key!r} delete failed '
                         f'({type(e).__name__}: {e})')

    def _persist(self, info: ReplicaInfo) -> None:
        """Write the replica row — only while the replica is still
        tracked. ``_db_lock`` serializes this check+write against
        ``_untrack``'s pop+delete, so a concurrent scale_down can't
        leave a phantom row for an untracked replica; the hot ``_lock``
        is held only for the in-memory membership check, never across
        the sqlite write."""
        with self._db_lock:
            with self._lock:
                if self._replicas.get(info.replica_id) is not info:
                    return
            self._env.persist_replica(
                self.service_name, info.replica_id, info.cluster_name,
                info.status, info.url, info.version, info.is_spot,
                port=info.port)

    def _untrack(self, replica_id: int) -> None:
        """Atomically drop a replica from the in-memory table AND its
        DB row (the removal half of the ``_persist`` protocol). Also
        evicts the checkpoint-dedupe key once the replica — or the
        LAST member of its gang — is gone, so ``_ckpt_done`` stays
        bounded by the number of LIVE replicas/gangs no matter how
        many thousands churn through a long-lived manager."""
        dead_key: Optional[str] = None
        with self._db_lock:
            with self._lock:
                info = self._replicas.pop(replica_id, None)
                if info is not None:
                    if info.gang_id is None:
                        key = f'replica-{replica_id}'
                        if self._ckpt_done.pop(key, None) is not None:
                            dead_key = key
                    elif not any(r.gang_id == info.gang_id
                                 for r in self._replicas.values()):
                        if self._ckpt_done.pop(info.gang_id,
                                               None) is not None:
                            dead_key = info.gang_id
            self._env.remove_replica(self.service_name, replica_id)
        if dead_key is not None:
            # The persisted dedupe mirror is bounded the same way the
            # in-memory dict is: evicted with the (last member of the)
            # replica/gang it keyed.
            self._del_note(f'ckpt_done:{dead_key}')
