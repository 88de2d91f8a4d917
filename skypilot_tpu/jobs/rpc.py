"""Managed-jobs RPC: runs on the controller cluster's head, driven by the
client via the command runner (same fixed-command-surface pattern as
:mod:`skypilot_tpu.agent.rpc`; replaces reference ``ManagedJobCodeGen``
``sky/jobs/utils.py:1121``).

Ops: queue (add job + submit controller process to the agent),
job_table, job_status, cancel, logs.
"""
from __future__ import annotations

import json
import sys
from typing import Any, Dict

from skypilot_tpu.agent import job_lib as agent_job_lib
from skypilot_tpu.agent import log_lib as agent_log_lib
from skypilot_tpu.jobs import state

PAYLOAD_PREFIX = 'SKYTPU_RPC_PAYLOAD:'


def _ok(**kwargs) -> Dict[str, Any]:
    return {'ok': True, **kwargs}


def _reconcile_dead_controllers() -> None:
    """A controller process that died uncleanly (OOM/SIGKILL) leaves its
    managed job non-terminal and may leak its LAUNCHING slot forever. The
    agent layer already marks the controller's agent job terminal
    (FAILED_DRIVER via pid-liveness); map that back to the managed-job
    table here, on every client poll (reference: skylet's
    ``ManagedJobEvent`` reconciles dead controllers,
    ``sky/skylet/events.py:72``)."""
    nonterminal = [r for r in state.get_jobs()
                   if not r['status'].is_terminal()]
    if not nonterminal:
        return
    agent_jobs = {j['name']: j for j in agent_job_lib.get_jobs()}
    for rec in nonterminal:
        agent_job = agent_jobs.get(f'controller-{rec["job_id"]}')
        if agent_job is None:
            continue   # queued but not yet visible; leave it
        if agent_job['status'].is_terminal() and \
                agent_job['status'].value != 'SUCCEEDED':
            state.set_status(
                rec['job_id'], state.ManagedJobStatus.FAILED_CONTROLLER,
                failure_reason=(f'controller process ended with '
                                f'{agent_job["status"].value}'))


def handle(request: Dict[str, Any]) -> Dict[str, Any]:
    op = request.get('op')
    if op == 'queue':
        dag_config = request['dag_config']
        run_timestamp = request['run_timestamp']
        name = request.get('name') or 'managed'
        job_id = state.add_job(name, dag_config,
                               num_tasks=len(dag_config['tasks']),
                               run_timestamp=run_timestamp)
        # The controller process runs as an ordinary agent job on this
        # cluster — logs/queue/liveness for free.
        agent_job_id = agent_job_lib.add_job(
            name=f'controller-{job_id}',
            username=request.get('username') or 'unknown',
            run_timestamp=run_timestamp,
            resources_str='controller',
            spec={
                'run': (f'{sys.executable} -m skypilot_tpu.jobs.controller '
                        f'--job-id {job_id}'),
                'env': {},
                'workdir_target': None,
            })
        agent_job_lib.schedule_step()
        return _ok(job_id=job_id, agent_job_id=agent_job_id)
    if op == 'job_table':
        _reconcile_dead_controllers()
        jobs = [state.record_to_json(r) for r in state.get_jobs()]
        return _ok(jobs=jobs)
    if op == 'job_status':
        _reconcile_dead_controllers()
        status = state.get_status(int(request['job_id']))
        return _ok(status=status.value if status else None)
    if op == 'cancel':
        return _ok(cancelled=state.request_cancel(int(request['job_id'])))
    if op == 'logs':
        # Controller-process log (launch/monitor/recovery trace). The
        # controller runs as agent job `controller-<id>`; find it by name.
        job_id = int(request['job_id'])
        for j in agent_job_lib.get_jobs():
            if j['name'] == f'controller-{job_id}':
                text = agent_log_lib.read_job_logs(
                    j['job_id'], tail=int(request.get('tail', 0)))
                return _ok(logs=text)
        return _ok(logs='')
    raise ValueError(f'Unknown jobs RPC op: {op!r}')


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
