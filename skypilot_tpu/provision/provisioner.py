"""Cloud-agnostic provisioning orchestration.

Role of reference ``sky/provision/provisioner.py`` (``bulk_provision``
``:100``, ``wait_for_ssh`` ``:348``, ``post_provision_runtime_setup``
``:631``): one retryable entry that creates instances in a zone, waits for
them, pushes the runtime onto every host in parallel, and starts the head
agent. Raises :class:`exceptions.ProvisionError` subclasses the failover
loop can blocklist on.
"""
from __future__ import annotations

import json
import os
import shlex
import tempfile
import time
from typing import Dict, Optional

from skypilot_tpu import exceptions
from skypilot_tpu import provision
from skypilot_tpu import tpu_logging
from skypilot_tpu.agent import rpc as agent_rpc
from skypilot_tpu.provision import common
from skypilot_tpu.utils import subprocess_utils

logger = tpu_logging.init_logger(__name__)

_AGENT_READY_TIMEOUT = float(os.environ.get('SKYTPU_AGENT_READY_TIMEOUT',
                                            '60'))


def _oneshot_rpc_timeout() -> float:
    """Bound on a one-shot RPC exec (interpreter start + handler), kept
    in line with the persistent channel's 120s request timeout."""
    return float(os.environ.get('SKYTPU_RPC_TIMEOUT', '120'))


def bulk_provision(provider_name: str,
                   region: str,
                   zone: Optional[str],
                   cluster_name: str,
                   config: common.ProvisionConfig) -> common.ClusterInfo:
    """Provision one cluster attempt in one zone, end to end.

    Steps: run_instances -> wait RUNNING -> get_cluster_info ->
    runtime setup on all hosts -> start agentd on head -> wait agent ready.
    """
    start = time.time()
    record = provision.run_instances(provider_name, region, zone,
                                     cluster_name, config)
    provision.wait_instances(provider_name, region, cluster_name,
                             common.STATUS_RUNNING)
    cluster_info = provision.get_cluster_info(provider_name, region,
                                              cluster_name)
    logger.debug(
        f'Provisioned {cluster_info.num_hosts} host(s) for '
        f'{cluster_name} in {zone or region} '
        f'({time.time() - start:.1f}s); setting up runtime.')
    post_provision_runtime_setup(cluster_info)
    return cluster_info


def post_provision_runtime_setup(
        cluster_info: common.ClusterInfo) -> None:
    """Push cluster_info to every host, start agentd on the head.

    (Reference ``_post_provision_setup``: internal file mounts + ray
    head/workers + skylet. No Ray here — the slice is the gang; only the
    head runs a daemon.)"""
    runners = common.get_command_runners(cluster_info)
    info_json = json.dumps(cluster_info.to_dict())

    # Remote hosts get the client's package as a hash-addressed source
    # zip on PYTHONPATH (version-skew restarts the agent); the local
    # provider already sees the repo via LocalProcessRunner's PYTHONPATH.
    ship_pkg = cluster_info.provider_name != 'local'
    if ship_pkg:
        from skypilot_tpu.utils import pkg_utils
        zip_path, digest = pkg_utils.build_package()

    with tempfile.NamedTemporaryFile('w', suffix='.json',
                                     delete=False) as f:
        f.write(info_json)
        tmp_path = f.name
    try:
        def push(runner) -> None:
            runner.run('mkdir -p ~/.skytpu_agent ~/sky_workdir '
                       '~/.skytpu_runtime',
                       log_path=os.devnull)
            runner.rsync(tmp_path, '~/.skytpu_agent/cluster_info.json',
                         up=True)
            if ship_pkg:
                runner.rsync(zip_path, pkg_utils.remote_zip_path(),
                             up=True)
                runner.run(pkg_utils.remote_setup_command(digest),
                           log_path=os.devnull)
        subprocess_utils.run_in_parallel(push, runners)
    finally:
        os.unlink(tmp_path)

    head = runners[0]
    start_agent_cmd = (
        'if [ -f ~/.skytpu_agent/agentd.pid ] && '
        'kill -0 $(cat ~/.skytpu_agent/agentd.pid) 2>/dev/null; then '
        '  echo "agentd already running"; '
        'else '
        f'  setsid {shlex.quote(head.remote_python)} -m '
        'skypilot_tpu.agent.agentd >> ~/.skytpu_agent/agentd.log 2>&1 '
        '< /dev/null & '
        'fi')
    head.run(start_agent_cmd, log_path=os.devnull)
    _wait_agent_ready(head)


def _wait_agent_ready(head_runner) -> None:
    deadline = time.time() + _AGENT_READY_TIMEOUT
    last_err = ''
    while time.time() < deadline:
        try:
            resp = agent_request(head_runner, {'op': 'agent_health'})
            if resp.get('agentd_alive'):
                return
            last_err = f'agentd not alive yet: {resp}'
        except exceptions.CommandError as e:
            last_err = str(e)
        time.sleep(0.2)
    raise exceptions.ProvisionError(
        f'Head agent failed to become ready in {_AGENT_READY_TIMEOUT}s: '
        f'{last_err}')


def agent_request(head_runner, request: Dict,
                  module: str = 'skypilot_tpu.agent.rpc',
                  error_cls: type = exceptions.ProvisionError) -> Dict:
    """Send one JSON RPC to a head-side module; return the parsed
    payload. The same wire protocol serves the agent RPC and the
    jobs/serve controller RPCs — pass ``module``/``error_cls``.

    Transport: a persistent ``--serve`` channel (one remote interpreter
    per client session, ``agent/channel.py``) when the runner supports
    it, falling back to a one-shot exec — so logs/cancel/status paths
    stop paying an interpreter start per op, and a broken channel never
    becomes a new failure mode. Raises CommandError / ``error_cls`` on
    failure."""
    from skypilot_tpu.agent import channel as channel_lib
    ch = channel_lib.channel_for(head_runner, module)
    if ch is not None:
        try:
            payload = ch.request(request)
            if not payload.get('ok'):
                raise error_cls(
                    f'RPC {module}:{request.get("op")} failed: '
                    f'{payload.get("error")}')
            return payload
        except channel_lib.ChannelError as e:
            if e.sent:
                # The op MAY have executed remotely: re-running it via
                # the fallback could double-submit writes (queue_job,
                # cancel). Surface the transport failure instead.
                raise error_cls(
                    f'RPC {module}:{request.get("op")}: channel failed '
                    f'after the request was sent ({e}); not retrying a '
                    f'possibly-executed op') from e
            # Startup failure (e.g. head running an older runtime):
            # negative-cache so later calls skip straight to one-shot.
            channel_lib.disable(head_runner, module)
            logger.debug(f'RPC channel unavailable '
                         f'({e}); falling back to one-shot exec')
    cmd = (f'{shlex.quote(head_runner.remote_python)} '
           f'-m {module} '
           f'{shlex.quote(json.dumps(request))}')
    # Bounded like the channel path (graftcheck GC103 discipline): a
    # wedged remote interpreter must not hang the caller's poll loop —
    # and any lock it holds — forever.
    out = head_runner.check_run(cmd, timeout=_oneshot_rpc_timeout())
    for line in out.splitlines():
        if line.startswith(agent_rpc.PAYLOAD_PREFIX):
            payload = json.loads(line[len(agent_rpc.PAYLOAD_PREFIX):])
            if not payload.get('ok'):
                raise error_cls(
                    f'RPC {module}:{request.get("op")} failed: '
                    f'{payload.get("error")}')
            return payload
    raise error_cls(
        f'RPC {module}:{request.get("op")}: no payload in output:\n'
        f'{out[-1000:]}')


def teardown_cluster(provider_name: str, region: str, cluster_name: str,
                     terminate: bool) -> None:
    if terminate:
        provision.terminate_instances(provider_name, region, cluster_name)
    else:
        provision.stop_instances(provider_name, region, cluster_name)
