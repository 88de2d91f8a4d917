"""Per-service controller: autoscaler loop + replica manager + a small
HTTP API the load balancer syncs against.

Role of reference ``sky/serve/controller.py`` (``SkyServeController``
``:36``, ``_run_autoscaler`` ``:64``): periodically evaluate the
autoscaler against current replica states and apply the scaling
decisions; expose ``/controller/load_balancer_sync`` so the LB can push
request timestamps and pull ready replica URLs (reference uses FastAPI;
stdlib http.server here — no extra deps on the controller cluster).
"""
from __future__ import annotations

import http.server
import json
import os
import threading
from typing import Any, Dict, List, Optional
import urllib.parse

from skypilot_tpu import tpu_logging
from skypilot_tpu.serve import autoscalers
from skypilot_tpu.serve import control_env
from skypilot_tpu.serve import replica_managers
from skypilot_tpu.serve import serve_state
from skypilot_tpu.serve.service_spec import SkyServiceSpec
from skypilot_tpu.telemetry import fleet as fleet_lib

logger = tpu_logging.init_logger(__name__)


def _tick() -> float:
    return float(os.environ.get('SKYTPU_SERVE_TICK', '10'))


class ServeController:

    def __init__(self, service_name: str, spec: SkyServiceSpec,
                 task_config: Dict[str, Any], port: int,
                 reserved_ports: Optional[set] = None,
                 env: Optional[control_env.ControlPlaneEnv] = None,
                 recover: bool = False):
        self.service_name = service_name
        self.spec = spec
        self.port = port
        # The simulator-or-live seam (control_env.py): the manager's
        # state machines and the autoscaler/forecaster clocks all draw
        # from one environment, so a simulated controller tick is the
        # SAME code on a virtual time axis.
        self._env = control_env.resolve(env)
        self.replica_manager = replica_managers.ReplicaManager(
            service_name, spec, task_config,
            reserved_ports=(reserved_ports or set()) | {port},
            env=self._env)
        self.autoscaler = autoscalers.Autoscaler.from_spec(
            spec, clock=self._env.time)
        # Fleet telemetry plane: merged per-replica metrics, assembled
        # cross-process traces, and SLO burn-rate accounting — fed on
        # the probe path (replica scrapes) and the LB sync body, and
        # clocked through the env seam so the simulator drives the
        # identical aggregation code on its virtual clock.
        self.fleet = fleet_lib.FleetAggregator(
            clock=self._env.time,
            slos=fleet_lib.slos_from_config(
                getattr(spec, 'slos', None)))
        self.replica_manager.set_telemetry_sink(self.fleet.ingest)
        self._stop = threading.Event()      # stops the autoscaler loop
        self._done = threading.Event()      # teardown fully finished
        self._httpd: Optional[http.server.ThreadingHTTPServer] = None
        self._threads: List[threading.Thread] = []
        # Crash-safety telemetry + the controller's own fault hook
        # (site 'controller_tick', kind controller_crash — the loop
        # dies WITHOUT teardown, exactly like a real process crash).
        from skypilot_tpu import telemetry
        reg = telemetry.get_registry()
        self._m_restarts = reg.counter(
            'skytpu_controller_restarts_total',
            'Controller boots that found persisted lifecycle state to '
            'reconcile (restarts; a first boot over an empty journal '
            'does not count)')
        self._h_reconcile = reg.histogram(
            'skytpu_reconcile_seconds',
            'Restart reconciliation wall time: journal replay + '
            'adoption probes to manager rebuilt',
            buckets=telemetry.registry.DEFAULT_SECONDS_BUCKETS)
        self._faults = self._env.fault_injector()
        # What the last recovery boot did per persisted replica
        # (outcome -> count); empty on a fresh boot.
        self.last_reconcile: Dict[str, int] = {}
        # Horizontal LB tier membership: every LB registers its
        # (lb_id, url) on each sync; the pruned live set ships back as
        # ``lb_peers`` so all LBs agree on the consistent-hash ring.
        # Deliberately EPHEMERAL (never journaled): membership is
        # liveness — a restarted controller relearns it within one
        # sync period, exactly like the replica probe state.
        self._lb_lock = threading.Lock()
        self._lb_registry: Dict[str, Any] = {}
        if recover:
            self._recover()

    # ----------------------------------------------------- LB tier feed
    def note_lb_sync(self, lb_id: Optional[str],
                     lb_url: Optional[str]) -> Dict[str, str]:
        """Register the syncing LB (if it identified itself) and
        return the live peer map (lb_id -> url). Peers that missed
        ``SKYTPU_LB_PEER_TTL`` (default 15 s) of syncs age out — a
        crashed LB leaves the ring within one TTL and session-key
        ownership converges on the survivors."""
        now = self._env.monotonic()
        ttl = float(os.environ.get('SKYTPU_LB_PEER_TTL', '15'))
        with self._lb_lock:
            registry = dict(self._lb_registry)
            if lb_id:
                registry[str(lb_id)] = (str(lb_url or ''), now)
            self._lb_registry = {
                k: v for k, v in registry.items()
                if now - v[1] < ttl}
            return {k: v[0] for k, v in self._lb_registry.items()}

    # ----------------------------------------------------------- recovery
    def _recover(self) -> None:
        """Recovery boot: restore the autoscaler/forecaster snapshot,
        then rebuild the replica manager from the journal + live
        probes (``ReplicaManager.reconcile``). Idempotent over an
        empty DB — ``serve/service.py`` always boots with
        ``recover=True`` and a first boot reconciles to a no-op."""
        t0 = self._env.monotonic()
        restored = self._restore_autoscaler_state()
        stats = self.replica_manager.reconcile()
        self.last_reconcile = stats
        if restored or any(stats.values()):
            self._m_restarts.inc()
            self._h_reconcile.observe(
                max(0.0, self._env.monotonic() - t0))
            logger.info(
                f'Controller for {self.service_name} restarted: '
                f'reconciled in {self._env.monotonic() - t0:.3f}s '
                f'({stats}).')

    def _persist_autoscaler_state(self) -> None:
        """Journaled persist helper (graftcheck GC120): snapshot the
        autoscaler target + forecaster rings + learned provision lead
        each tick, so a restart never scales the fleet toward
        min_replicas while live traffic needs it."""
        try:
            self._env.put_note(self.service_name, 'autoscaler_state',
                               self.autoscaler.export_state())
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'autoscaler snapshot persist failed: '
                         f'{type(e).__name__}: {e}')

    def _restore_autoscaler_state(self) -> bool:
        try:
            notes = self._env.get_notes(self.service_name)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'autoscaler snapshot restore failed: '
                           f'{type(e).__name__}: {e}')
            return False
        state = notes.get('autoscaler_state')
        if not isinstance(state, dict):
            return False
        self.autoscaler.restore_state(state)
        return True

    # ---------------------------------------------------------- scaling
    def _replica_views(self) -> List[autoscalers.ReplicaView]:
        views = []
        for info in self.replica_manager.replicas():
            if info.gang_rank > 0:
                # A gang is ONE unit of serving capacity: rank 0
                # represents it to the autoscaler (counting followers
                # would make a 4-host gang look like 4 replicas and
                # freeze scale-up at 1/4 the intended fleet).
                continue
            views.append(autoscalers.ReplicaView(
                replica_id=info.replica_id,
                is_ready=(info.status == serve_state.ReplicaStatus.READY),
                is_spot=info.is_spot,
                is_terminal=info.status.is_terminal(),
                is_draining=(info.status ==
                             serve_state.ReplicaStatus.DRAINING),
                version=info.version))
        return views

    def _autoscaler_step(self) -> None:
        # Observed provision latencies (scale-up issued -> READY) feed
        # the forecast autoscaler's pre-scaling lead time; the base
        # autoscalers ignore them.
        for obs in self.replica_manager.pop_provision_observations():
            self.autoscaler.note_provision_seconds(obs)
        decisions = self.autoscaler.evaluate_scaling(self._replica_views())
        for d in decisions:
            if d.operator == autoscalers.DecisionOperator.SCALE_UP:
                if self.replica_manager.in_launch_backoff():
                    continue      # recent launch failure; retry later
                self.replica_manager.scale_up(
                    use_spot=bool(d.target.get('use_spot')))
            else:
                # Scale-down routes through graceful drain: the replica
                # leaves LB rotation, finishes its in-flight requests
                # under the drain deadline, THEN tears down — no work
                # is killed mid-decode. drain() is idempotent across
                # controller ticks and falls back to a direct teardown
                # for replicas that never served.
                self.replica_manager.drain(d.target['replica_id'])
        self._drain_old_versions()

    def _drain_old_versions(self) -> None:
        """Blue-green completion (reference ``replica_managers.py:1172``):
        once enough latest-version replicas are READY, old-version
        replicas are terminated."""
        latest = self.replica_manager.version
        infos = self.replica_manager.replicas()
        ready_new = sum(
            1 for i in infos if i.version == latest
            and i.status == serve_state.ReplicaStatus.READY)
        if ready_new < self.autoscaler.target_num_replicas:
            return
        for info in infos:
            if info.gang_rank > 0:
                continue      # gangs drain through their rank 0
            if info.version < latest and not info.status.is_terminal() \
                    and info.status not in (
                        serve_state.ReplicaStatus.SHUTTING_DOWN,
                        serve_state.ReplicaStatus.DRAINING):
                logger.info(f'Draining replica {info.replica_id} '
                            f'(v{info.version} < v{latest}).')
                self.replica_manager.drain(info.replica_id)

    def apply_update(self) -> None:
        """Reload spec/task from serve state after an `update` RPC bumped
        the version; new replicas launch with the new task."""
        record = serve_state.get_service(self.service_name)
        if record is None:
            return
        version = record['version']
        if version == self.replica_manager.version:
            return
        spec = SkyServiceSpec.from_yaml_config(
            record['task_config']['service'])
        self.spec = spec
        self.replica_manager.update_version(spec, record['task_config'],
                                            version)
        self.autoscaler.update_spec(spec, version)
        self.fleet.set_slos(fleet_lib.slos_from_config(
            getattr(spec, 'slos', None)))
        logger.info(f'Service {self.service_name} updated to v{version}.')

    def _update_service_status(self) -> None:
        record = serve_state.get_service(self.service_name)
        if record is None or record['status'] in (
                serve_state.ServiceStatus.SHUTTING_DOWN,):
            return
        infos = self.replica_manager.replicas()
        n_ready = sum(1 for i in infos
                      if i.status == serve_state.ReplicaStatus.READY)
        if n_ready > 0:
            status = serve_state.ServiceStatus.READY
        elif infos:
            status = serve_state.ServiceStatus.REPLICA_INIT
        else:
            status = serve_state.ServiceStatus.NO_REPLICA
        if status != record['status']:
            serve_state.set_service_status(self.service_name, status)

    def tick(self, *, sync_state: bool = True) -> None:
        """One controller evaluation: reconcile version, probe every
        replica, evaluate + apply scaling, refresh the service row.
        The live loop calls this on a wall-clock cadence; the fleet
        simulator calls it on the virtual clock (``sync_state=False``
        skips the sqlite-backed version/status reconciliation — a
        simulated service has no DB row and must never touch the
        operator's serve state)."""
        if sync_state:
            # Version reconciliation every tick: the update RPC's
            # POST is only a nudge — if it was missed, the DB version
            # must not stay permanently ahead of the running service.
            self.apply_update()
        self.replica_manager.probe_all()
        self._autoscaler_step()
        # Snapshot the scaling brain through the env seam (a no-op DB
        # in sim is still the same code path): restarts restore it.
        self._persist_autoscaler_state()
        if sync_state:
            self._update_service_status()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                if self._faults is not None:
                    rule = self._faults.fire('controller_tick')
                    if rule is not None and \
                            rule.kind == 'controller_crash':
                        logger.error(
                            'injected controller_crash: the control '
                            'plane dies NOW without teardown '
                            '(replicas keep serving; the journal '
                            'stays for the next boot to reconcile)')
                        self.crash()
                        return
                self.tick()
            except Exception:  # pylint: disable=broad-except
                logger.exception('controller loop error')
            self._stop.wait(_tick())

    # ------------------------------------------------------------- HTTP
    def _make_handler(controller):  # noqa: N805
        class Handler(http.server.BaseHTTPRequestHandler):
            # Socket-op timeout (graftcheck GC107): a stalled LB/CLI
            # peer must not pin a controller thread forever.
            timeout = 60

            def log_message(self, *args):  # quiet
                del args

            def _json(self, code: int, payload: Dict[str, Any]) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header('Content-Type', 'application/json')
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                parsed = urllib.parse.urlparse(self.path)
                query = urllib.parse.parse_qs(parsed.query)
                if parsed.path == '/controller/ready':
                    self._json(200, {'ready': True})
                elif parsed.path == '/controller/status':
                    self._json(200, controller.status_payload())
                elif parsed.path == '/fleet/metrics':
                    if query.get('format', [''])[0] == 'json':
                        self._json(200, controller.fleet.render_json())
                        return
                    body = (controller.fleet.render_prometheus()
                            .encode())
                    self.send_response(200)
                    self.send_header(
                        'Content-Type',
                        'text/plain; version=0.0.4; charset=utf-8')
                    self.send_header('Content-Length', str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif parsed.path == '/fleet/traces':
                    self._json(200,
                               {'traces': controller.fleet.trace_ids()})
                elif parsed.path.startswith('/fleet/trace/'):
                    tid = parsed.path[len('/fleet/trace/'):]
                    if query.get('format', [''])[0] == 'chrome':
                        events = controller.fleet.chrome_events(tid)
                        if events is None:
                            self._json(404, {'error':
                                             f'trace {tid!r} unknown'})
                            return
                        self._json(200, {'traceEvents': events,
                                         'displayTimeUnit': 'ms'})
                        return
                    assembled = controller.fleet.assemble_trace(tid)
                    if assembled is None:
                        self._json(404,
                                   {'error': f'trace {tid!r} unknown'})
                        return
                    self._json(200, assembled)
                else:
                    self._json(404, {'error': f'no route {self.path}'})

            def do_POST(self):  # noqa: N802
                length = int(self.headers.get('Content-Length', 0))
                try:
                    payload = json.loads(self.rfile.read(length) or b'{}')
                except json.JSONDecodeError:
                    self._json(400, {'error': 'bad json'})
                    return
                if self.path == '/controller/load_balancer_sync':
                    ts = payload.get('request_timestamps', [])
                    # Optional parallel SLO-tier tags (the LB reads
                    # X-SLO-Tier): the forecaster keeps per-tier
                    # arrival series next to the 'all' series.
                    controller.autoscaler.collect_request_information(
                        ts, payload.get('request_tiers'))
                    # The LB piggybacks its completed trace legs (and
                    # its clock, for skew accounting) on the sync it
                    # already makes.
                    tel = payload.get('telemetry')
                    if isinstance(tel, dict):
                        controller.fleet.ingest(
                            str(payload.get('lb_id') or 'lb'), tel)
                    self._json(200, {
                        # Per-tier SLO burn/attainment: LBs surface it
                        # next to their own health gauges.
                        'slo': controller.fleet.slo_status(),
                        'ready_replica_urls':
                            controller.replica_manager.ready_urls(),
                        # Retry-After hint for the LB's own 503 while
                        # no replica is READY, from live probe/launch
                        # backoff state.
                        'retry_after_s':
                            controller.replica_manager.retry_after_hint(),
                        # The (tp, dp) plan replicas of the current
                        # spec version run with — the LB's replica
                        # view carries it alongside the live
                        # per-replica mesh probes.
                        'replica_parallelism':
                            controller.parallelism_payload(),
                        # Disaggregation roles (url -> prefill/decode/
                        # colocated): the phase-aware LB policy's
                        # cold-probe fallback.
                        'replica_roles':
                            controller.replica_manager.replica_roles(),
                        # Gang health blocks (rank0 url -> gang view):
                        # the LB keeps follower addresses out of probe
                        # sweeps while accounting every rank's health.
                        'replica_gangs':
                            controller.replica_manager.replica_gangs(),
                        # Live LB-tier peers (lb_id -> url): every LB
                        # builds the same consistent-hash ring from
                        # this, so session-key ownership is agreed
                        # without LB-to-LB coordination.
                        'lb_peers': controller.note_lb_sync(
                            payload.get('lb_id'),
                            payload.get('lb_url')),
                    })
                elif self.path == '/controller/update':
                    try:
                        controller.apply_update()
                        self._json(200, {
                            'version': controller.replica_manager.version})
                    except Exception as e:  # pylint: disable=broad-except
                        self._json(400, {'error': f'{type(e).__name__}: '
                                                  f'{e}'})
                elif self.path == '/controller/terminate':
                    threading.Thread(target=controller.terminate,
                                     daemon=True).start()
                    self._json(200, {'terminating': True})
                else:
                    self._json(404, {'error': f'no route {self.path}'})

        return Handler

    def parallelism_payload(self) -> Dict[str, Any]:
        """The adaptive-TP plan as a wire dict (stable keys)."""
        plan = self.replica_manager.parallelism_plan()
        return {'tp': plan.tp, 'dp': plan.dp, 'chips': plan.chips,
                'reason': plan.reason,
                'policy': self.spec.parallelism_policy}

    def status_payload(self) -> Dict[str, Any]:
        par = self.parallelism_payload()
        return {
            'service_name': self.service_name,
            'target_num_replicas': self.autoscaler.target_num_replicas,
            'autoscaler': type(self.autoscaler).__name__,
            'replica_parallelism': par,
            'slo': self.fleet.slo_status(),
            'replicas': [{
                'replica_id': i.replica_id,
                'cluster_name': i.cluster_name,
                'status': i.status.value,
                'url': i.url,
                'version': i.version,
                'is_spot': i.is_spot,
                'role': i.role,
                'mesh': {'tp': par['tp'], 'dp': par['dp']},
                'gang_id': i.gang_id,
                'gang_rank': i.gang_rank,
                'gang_world': i.gang_world,
            } for i in self.replica_manager.replicas()],
        }

    # ---------------------------------------------------------- lifecycle
    def start(self) -> None:
        handler = self._make_handler()
        self._httpd = http.server.ThreadingHTTPServer(
            ('127.0.0.1', self.port), handler)
        t_http = threading.Thread(target=self._httpd.serve_forever,
                                  daemon=True)
        t_loop = threading.Thread(target=self._loop, daemon=True)
        t_http.start()
        t_loop.start()
        self._threads = [t_http, t_loop]
        logger.info(f'Serve controller for {self.service_name} on port '
                    f'{self.port}.')

    def crash(self) -> None:
        """Die like a crashed process (the chaos and controller-recovery
        tests): stop the loop and the HTTP API but
        tear NOTHING down and touch NO rows — replicas keep serving,
        the journal and notes stay exactly as written, and the next
        ``ServeController(..., recover=True)`` must reconcile it all
        back. The LB sees sync failures and enters its
        stale-while-revalidate mode."""
        self._stop.set()
        if self._httpd is not None:
            self._httpd.shutdown()
        self._done.set()

    def terminate(self) -> None:
        serve_state.set_service_status(
            self.service_name, serve_state.ServiceStatus.SHUTTING_DOWN)
        # Order matters: stop the autoscaler loop and refuse new launches
        # BEFORE tearing replicas down, or the loop relaunches replicas
        # that terminate_all never snapshotted (leaked clusters).
        self._stop.set()
        self.replica_manager.shutdown()
        self.replica_manager.terminate_all()
        if self._httpd is not None:
            self._httpd.shutdown()
        serve_state.remove_service(self.service_name)
        # Last: releases wait() — the service process must stay alive
        # until the teardown above completed (terminate() usually runs on
        # a daemon thread that dies with the process).
        self._done.set()

    def wait(self) -> None:
        # Event wait, not a sleep-poll loop (graftcheck GC112): blocks
        # until terminate() finishes the teardown.
        self._done.wait()
