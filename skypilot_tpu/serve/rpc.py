"""Serve RPC: runs on the serve-controller cluster's head, driven by the
client via the command runner (same fixed-command-surface pattern as
:mod:`skypilot_tpu.jobs.rpc`; replaces reference ``ServeCodeGen``
``sky/serve/serve_utils.py:951``).

Ops: up (register service + submit the service process to the agent),
status, down, update.
"""
from __future__ import annotations

import json
import random
import sys
import time
from typing import Any, Dict

from skypilot_tpu import tpu_logging
from skypilot_tpu.agent import job_lib as agent_job_lib
from skypilot_tpu.serve import serve_state
from skypilot_tpu.serve import wire
from skypilot_tpu.utils import common_utils

logger = tpu_logging.init_logger(__name__)

PAYLOAD_PREFIX = 'SKYTPU_RPC_PAYLOAD:'


def _ok(**kwargs) -> Dict[str, Any]:
    return {'ok': True, **kwargs}


def _reconcile_dead_services() -> None:
    """A service process that died uncleanly leaves its row non-terminal;
    map the agent job's terminal state back (reference: skylet's
    ``ServiceUpdateEvent``, ``sky/skylet/events.py:81``)."""
    services = [s for s in serve_state.get_services()
                if not s['status'].is_terminal()
                and s['status'] != serve_state.ServiceStatus.SHUTTING_DOWN]
    if not services:
        return
    agent_jobs = {j['name']: j for j in agent_job_lib.get_jobs()}
    for svc in services:
        job = agent_jobs.get(f'service-{svc["name"]}')
        if job is None:
            continue
        if job['status'].is_terminal() and \
                job['status'].value != 'SUCCEEDED':
            serve_state.set_service_status(
                svc['name'], serve_state.ServiceStatus.CONTROLLER_FAILED,
                failure_reason=(f'service process ended with '
                                f'{job["status"].value}'))


def _force_down(svc: Dict[str, Any]) -> None:
    """Clean up a service whose controller process is unreachable: stop
    the service agent job (so a hung controller stops relaunching
    replicas), tear down every replica cluster recorded in serve state,
    THEN drop the rows — never delete the only record of running
    clusters first."""
    from skypilot_tpu import core as sky_core
    name = svc['name']
    if svc.get('agent_job_id'):
        try:
            agent_job_lib.cancel_job(svc['agent_job_id'])
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'force-down {name}: cancel of controller '
                           f'job failed: {type(e).__name__}: {e}')
    for rep in serve_state.get_replicas(name):
        try:
            sky_core.down(rep['cluster_name'])
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'force-down {name}: teardown of '
                           f'{rep["cluster_name"]} failed (it may '
                           f'leak): {type(e).__name__}: {e}')
    serve_state.remove_service(name)


def handle(request: Dict[str, Any]) -> Dict[str, Any]:
    op = request.get('op')
    if op == 'up':
        name = request['service_name']
        task_config = request['task_config']
        # Allocate + record ports atomically under the serve-state lock:
        # a bind test alone can't see ports another 'up' just recorded
        # for a service process that hasn't started (and bound) yet.
        with serve_state.db_lock():
            taken = serve_state.allocated_ports()
            controller_port = common_utils.find_free_port(exclude=taken)
            lb_port = common_utils.find_free_port(
                start=controller_port + 1, exclude=taken)
            if not serve_state.add_service(name, task_config,
                                           controller_port, lb_port):
                return {'ok': False,
                        'error': f'Service {name!r} already exists.'}
        agent_job_id = agent_job_lib.add_job(
            name=f'service-{name}',
            username=request.get('username') or 'unknown',
            run_timestamp=request.get('run_timestamp') or
            common_utils.make_run_timestamp(),
            resources_str='serve-controller',
            spec={
                'run': (f'{sys.executable} -m skypilot_tpu.serve.service '
                        f'--service-name {name}'),
                'env': {},
                'workdir_target': None,
            })
        serve_state.set_service_agent_job(name, agent_job_id)
        agent_job_lib.schedule_step()
        return _ok(lb_port=lb_port, controller_port=controller_port,
                   agent_job_id=agent_job_id)
    if op == 'status':
        _reconcile_dead_services()
        services = []
        for svc in serve_state.get_services():
            replicas = serve_state.get_replicas(svc['name'])
            entry = serve_state.service_to_json(svc)
            entry['replicas'] = [serve_state.replica_to_json(r)
                                 for r in replicas]
            services.append(entry)
        names = request.get('service_names')
        if names:
            services = [s for s in services if s['name'] in names]
        return _ok(services=services)
    if op == 'update':
        name = request['service_name']
        svc = serve_state.get_service(name)
        if svc is None:
            return {'ok': False, 'error': f'Service {name!r} not found.'}
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        task_config = request['task_config']
        SkyServiceSpec.from_yaml_config(task_config['service'])  # validate
        version = serve_state.bump_service_version(name, task_config)
        if version is None:
            return {'ok': False, 'error': f'Service {name!r} not found.'}
        # The POST is only a NUDGE: the committed version is the source
        # of truth and the controller reconciles it every tick, so a
        # missed nudge must not be reported as a failed update (a retry
        # would double-bump the version).
        try:
            with wire.urlopen(
                    f'http://127.0.0.1:{svc["controller_port"]}'
                    '/controller/update', data=b'{}',
                    headers={'Content-Type': 'application/json'},
                    timeout=10):
                pass
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'update nudge to controller failed '
                         f'(reconciled next tick): '
                         f'{type(e).__name__}: {e}')
        return _ok(version=version)
    if op == 'down':
        name = request['service_name']
        svc = serve_state.get_service(name)
        if svc is None:
            return {'ok': False, 'error': f'Service {name!r} not found.'}
        # Ask the controller to terminate (it tears replicas down and
        # removes the service row); fall back to direct removal if the
        # controller is unreachable (e.g. it crashed).
        try:
            with wire.urlopen(
                    f'http://127.0.0.1:{svc["controller_port"]}'
                    '/controller/terminate', data=b'{}',
                    headers={'Content-Type': 'application/json'},
                    timeout=10):
                pass
            # Wait briefly for the row to disappear (terminate is
            # async). Jittered with mild backoff (graftcheck GC112):
            # many concurrent `serve down`s must not poll the DB in
            # lockstep.
            deadline = time.time() + float(request.get('timeout', 60))
            gap = 0.2
            while time.time() < deadline:
                if serve_state.get_service(name) is None:
                    break
                time.sleep(gap * (0.5 + random.random()))
                gap = min(gap * 1.5, 2.0)
            else:
                # Controller accepted the terminate but wedged mid-
                # teardown: escalate rather than reporting success with
                # replicas possibly still running.
                _force_down(svc)
        except Exception:  # pylint: disable=broad-except
            _force_down(svc)
        return _ok(terminated=True)
    raise ValueError(f'Unknown serve RPC op: {op!r}')


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == '--serve':
        # Persistent stdio channel (agent/channel.py): same wire
        # protocol as the agent RPC's --serve loop.
        from skypilot_tpu.agent import rpc as agent_rpc
        agent_rpc.serve(handle)
        return
    raw = sys.argv[1] if len(sys.argv) > 1 else sys.stdin.read()
    request = json.loads(raw)
    try:
        response = handle(request)
    except Exception as e:  # pylint: disable=broad-except
        response = {'ok': False, 'error': f'{type(e).__name__}: {e}'}
    print(PAYLOAD_PREFIX + json.dumps(response))


if __name__ == '__main__':
    main()
