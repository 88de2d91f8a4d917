"""TpuVmBackend: the orchestration engine.

Role of reference ``CloudVmRayBackend``
(``sky/backends/cloud_vm_ray_backend.py:2620``) redesigned TPU-first:

- No Ray. A slice is already a gang; jobs fan out from the head agent
  (:mod:`skypilot_tpu.agent.driver`) over every host.
- Provisioning failover: zone loop with blocklisting + re-optimize
  (reference ``RetryingVmProvisioner.provision_with_retries`` ``:1979``),
  consuming the :class:`exceptions.ProvisionError` classification
  (``blocklist_scope``) instead of parsing cloud stdout.
- Client<->head control is the JSON RPC (:mod:`skypilot_tpu.agent.rpc`),
  replacing codegen-over-SSH.
"""
from __future__ import annotations

import os
import time
import typing
import uuid
from typing import Any, Dict, List, Optional

import filelock

from skypilot_tpu import clouds as clouds_lib
from skypilot_tpu import exceptions
from skypilot_tpu import global_state
from skypilot_tpu import optimizer as optimizer_lib
from skypilot_tpu import provision
from skypilot_tpu import tpu_logging
from skypilot_tpu.agent import constants as agent_constants
from skypilot_tpu.backend import backend as backend_lib
from skypilot_tpu.dag import Dag
from skypilot_tpu.provision import common as provision_common
from skypilot_tpu.provision import provisioner
from skypilot_tpu.resources import Resources
from skypilot_tpu.task import Task
from skypilot_tpu.utils import common_utils, subprocess_utils

logger = tpu_logging.init_logger(__name__)

WORKDIR_TARGET = agent_constants.WORKDIR_TARGET


class TpuVmResourceHandle(backend_lib.ResourceHandle):
    """Pickleable record of a launched cluster (reference
    ``CloudVmRayResourceHandle`` ``:2156``). Hosts are first-class via
    the embedded ClusterInfo."""

    _VERSION = 1

    def __init__(self, *, cluster_name: str,
                 launched_resources: Resources,
                 num_nodes: int,
                 cluster_info: provision_common.ClusterInfo):
        self.cluster_name = cluster_name
        self.launched_resources = launched_resources
        self.num_nodes = num_nodes
        self.cluster_info = cluster_info
        self.cluster_hash = f'{cluster_name}-{uuid.uuid4().hex[:8]}'
        self._version = self._VERSION

    def get_cluster_name(self) -> str:
        return self.cluster_name

    @property
    def num_hosts(self) -> int:
        return self.cluster_info.num_hosts

    def runners(self) -> List[Any]:
        return provision_common.get_command_runners(self.cluster_info)

    def head_runner(self) -> Any:
        return self.runners()[0]

    def __setstate__(self, state):
        version = state.get('_version', 0)
        if version < self._VERSION:
            # Forward-compat hook for controller/client skew.
            pass
        self.__dict__.update(state)

    def __repr__(self) -> str:
        return (f'TpuVmResourceHandle({self.cluster_name}, '
                f'{self.launched_resources}, hosts={self.num_hosts})')


class FailoverError(Exception):
    """Internal: zone attempts for one optimized choice all failed;
    carries the blocked resources accumulated so far."""

    def __init__(self, blocked: List[Resources]):
        super().__init__('all zones failed')
        self.blocked = blocked


class RetryingProvisioner:
    """Zone loop -> region/cloud failover via re-optimization
    (reference ``RetryingVmProvisioner`` ``:1155``)."""

    def __init__(self, max_optimize_rounds: int = 10):
        self.max_optimize_rounds = max_optimize_rounds

    def provision_with_retries(
            self, task: Task, cluster_name: str,
            retry_until_up: bool = False
    ) -> provision_common.ClusterInfo:
        blocked: List[Resources] = []
        rounds = 0
        while True:
            rounds += 1
            dag = Dag()
            dag.add(task)
            try:
                optimizer_lib.optimize(dag, blocked_resources=blocked)
            except exceptions.ResourcesUnavailableError:
                if retry_until_up:
                    logger.warning(
                        f'All candidate resources failed for '
                        f'{cluster_name}; retrying from scratch in 10s '
                        '(--retry-until-up).')
                    blocked = []
                    rounds = 0
                    time.sleep(10)
                    continue
                raise
            if rounds > self.max_optimize_rounds and not retry_until_up:
                raise exceptions.ResourcesUnavailableError(
                    f'Exceeded {self.max_optimize_rounds} optimize/failover '
                    f'rounds for {cluster_name}; giving up. Blocked: '
                    f'{blocked}')
            to_provision = task.best_resources
            try:
                return self._retry_zones(task, to_provision, cluster_name,
                                         blocked)
            except FailoverError as e:
                blocked.extend(e.blocked)
                logger.info(
                    f'Failing over {cluster_name}: re-optimizing with '
                    f'{len(blocked)} blocked resource filter(s).')

    def _retry_zones(self, task: Task, to_provision: Resources,
                     cluster_name: str,
                     already_blocked: List[Resources]
                     ) -> provision_common.ClusterInfo:
        cloud = clouds_lib.from_name(to_provision.cloud or 'gcp')
        blocked: List[Resources] = []
        zone_iter = [
            z for z in cloud.zones_provision_loop(to_provision)
            if not optimizer_lib.resources_blocked(
                Resources(cloud=cloud.NAME, region=z.region, zone=z.name),
                already_blocked)
        ]
        if not zone_iter:
            # Every zone of this choice is already blocked (or none
            # exist): escalate to region scope so re-optimization moves
            # to a different region instead of re-picking this one.
            if to_provision.region is not None:
                raise FailoverError([Resources(cloud=cloud.NAME,
                                               region=to_provision.region)])
            raise FailoverError([to_provision.copy(zone=None)])
        for zone in zone_iter:
            attempt = to_provision.copy(region=zone.region, zone=zone.name)
            config = cloud.make_provision_config(attempt, task.num_nodes,
                                                 cluster_name)
            try:
                logger.info(
                    f'Launching {cluster_name} '
                    f'({attempt}) in {zone.name}...')
                return provisioner.bulk_provision(
                    cloud.PROVISIONER, zone.region, zone.name, cluster_name,
                    config)
            except exceptions.ProvisionError as e:
                scope = getattr(e, 'blocklist_scope', 'zone')
                logger.warning(f'Provision attempt in {zone.name} failed '
                               f'({type(e).__name__}: {e}); '
                               f'blocklisting {scope}.')
                _cleanup_failed_attempt(cloud.PROVISIONER, zone.region,
                                        cluster_name)
                if scope == 'zone':
                    blocked.append(Resources(cloud=cloud.NAME,
                                             region=zone.region,
                                             zone=zone.name))
                elif scope == 'region':
                    blocked.append(Resources(cloud=cloud.NAME,
                                             region=zone.region))
                else:
                    blocked.append(Resources(cloud=cloud.NAME))
                if getattr(e, 'no_failover', False):
                    raise exceptions.ResourcesUnavailableError(
                        str(e), no_failover=True) from e
        # All remaining zones of this choice failed. Zone-scoped entries
        # alone would never match the optimizer's region-level candidates,
        # so also blocklist each region whose zones are now exhausted.
        all_blocked = already_blocked + blocked
        for region in {z.region for z in zone_iter}:
            region_res = Resources(cloud=cloud.NAME, region=region)
            if optimizer_lib.resources_blocked(region_res, all_blocked):
                continue  # already covered by a region/cloud-scope entry
            region_zones = [
                z for z in cloud.zones_provision_loop(to_provision)
                if z.region == region]
            if all(optimizer_lib.resources_blocked(
                    Resources(cloud=cloud.NAME, region=z.region,
                              zone=z.name), all_blocked)
                   for z in region_zones):
                blocked.append(region_res)
        raise FailoverError(blocked)


def _cleanup_failed_attempt(provider: str, region: str,
                            cluster_name: str) -> None:
    """TPU creates leave debris on failure (reference
    ``need_cleanup_after_preemption_or_failure``); terminate best-effort."""
    try:
        provision.terminate_instances(provider, region, cluster_name)
    except Exception:  # pylint: disable=broad-except
        logger.debug(f'cleanup of failed attempt {cluster_name} errored',
                     exc_info=True)


class TpuVmBackend(backend_lib.Backend[TpuVmResourceHandle]):
    NAME = 'tpuvm'

    def __init__(self):
        self._provisioner = RetryingProvisioner()

    # ------------------------------------------------------------ provision
    def provision(self, task: Task, to_provision: Optional[Resources],
                  *, cluster_name: str, dryrun: bool = False,
                  retry_until_up: bool = False
                  ) -> Optional[TpuVmResourceHandle]:
        del to_provision  # the retry loop re-optimizes internally
        if dryrun:
            return None
        lock = filelock.FileLock(os.path.join(
            common_utils.state_dir(), f'.{cluster_name}.launch.lock'))
        with lock:
            existing = global_state.get_cluster_from_name(cluster_name)
            if existing is not None and existing['handle'] is not None:
                handle = self._reuse_existing(task, existing)
                if handle is not None:
                    return handle
            cluster_info = self._provisioner.provision_with_retries(
                task, cluster_name, retry_until_up=retry_until_up)
            launched = task.best_resources
            handle = TpuVmResourceHandle(
                cluster_name=cluster_name,
                launched_resources=launched,
                num_nodes=task.num_nodes,
                cluster_info=cluster_info)
            global_state.add_or_update_cluster(cluster_name, handle,
                                              ready=True)
            return handle

    def _reuse_existing(self, task: Task,
                        record: Dict[str, Any]
                        ) -> Optional[TpuVmResourceHandle]:
        """Reuse an UP cluster whose resources satisfy the request
        (reference ``sky exec`` / relaunch semantics)."""
        from skypilot_tpu.backend import backend_utils
        cluster_name = record['name']
        record, handle = backend_utils.refresh_cluster_status(cluster_name)
        if record is None or handle is None:
            return None
        status = record['status']
        if status == global_state.ClusterStatus.STOPPED:
            # Restart instances then reuse.
            info = handle.cluster_info
            provision.run_instances(
                info.provider_name, info.region, info.zone, cluster_name,
                self._restart_config(handle))
            provisioner.post_provision_runtime_setup(info)
            global_state.add_or_update_cluster(cluster_name, handle,
                                              ready=True)
            return handle
        if status != global_state.ClusterStatus.UP:
            return None
        requested = task.resources[0]
        if not requested.less_demanding_than(handle.launched_resources):
            raise exceptions.ResourcesMismatchError(
                f'Cluster {cluster_name!r} exists with '
                f'{handle.launched_resources}, which does not satisfy the '
                f'request {requested}. Use a new cluster name or down the '
                'existing one.')
        if task.num_nodes > handle.num_nodes:
            # Resources alone don't carry node/slice count; a multi-slice
            # request must not silently reuse a smaller cluster.
            raise exceptions.ResourcesMismatchError(
                f'Cluster {cluster_name!r} has {handle.num_nodes} '
                f'node(s)/slice(s); the task requests {task.num_nodes}. '
                'Use a new cluster name or down the existing one.')
        self._ensure_runtime_current(handle)
        global_state.update_last_use(cluster_name)
        return handle

    def _ensure_runtime_current(self, handle: TpuVmResourceHandle) -> None:
        """Version-skew guard on cluster REUSE: a newer client must not
        drive an agent running old code (the reference re-rsyncs its
        wheel on every launch; ``sky/backends/wheel_utils.py:140`` +
        ``tests/backward_compatibility_tests.sh``). One agent_health RPC
        compares the remote runtime hash with the client's; on mismatch
        the runtime re-ships and the agent restarts on the new code."""
        info = handle.cluster_info
        if info.provider_name == 'local':
            return          # local nodes import the client's tree directly
        from skypilot_tpu.utils import pkg_utils
        try:
            resp = provisioner.agent_request(handle.head_runner(),
                                             {'op': 'agent_health'})
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'agent_health on {handle.cluster_name} failed '
                         f'({type(e).__name__}: {e}); unreachable '
                         'agents are the refresh\'s problem')
            return
        remote = resp.get('runtime_version')
        local = pkg_utils.package_hash()
        if remote is not None and remote != local:
            logger.info(f'Runtime version skew on {handle.cluster_name} '
                        f'(agent {remote}, client {local}); re-shipping '
                        'runtime and restarting the agent.')
            provisioner.post_provision_runtime_setup(info)

    def _restart_config(self, handle: TpuVmResourceHandle):
        cloud = clouds_lib.from_name(
            handle.launched_resources.cloud or 'gcp')
        return cloud.make_provision_config(
            handle.launched_resources, handle.num_nodes,
            handle.cluster_name)

    # ------------------------------------------------------------ sync
    def sync_workdir(self, handle: TpuVmResourceHandle,
                     workdir: str) -> None:
        source = os.path.abspath(os.path.expanduser(workdir))
        if not os.path.isdir(source):
            raise exceptions.InvalidTaskError(
                f'workdir {workdir!r} is not a directory')
        if not source.endswith('/'):
            source += '/'

        def sync_one(runner):
            runner.run(f'mkdir -p {WORKDIR_TARGET}', log_path=os.devnull)
            runner.rsync(source, WORKDIR_TARGET + '/', up=True)

        subprocess_utils.run_in_parallel(sync_one, handle.runners())

    def sync_file_mounts(self, handle: TpuVmResourceHandle,
                         file_mounts: Optional[Dict[str, str]],
                         storage_mounts: Optional[Dict[str, Any]]) -> None:
        mounts = dict(file_mounts or {})

        def sync_host(runner):
            for dst, src in mounts.items():
                if _is_cloud_uri(src):
                    self._download_cloud_uri(runner, src, dst)
                else:
                    expanded = os.path.abspath(os.path.expanduser(src))
                    if os.path.isdir(expanded) and not expanded.endswith('/'):
                        expanded += '/'
                    parent = os.path.dirname(dst.rstrip('/')) or '.'
                    runner.run(f'mkdir -p {parent}', log_path=os.devnull)
                    runner.rsync(expanded, dst, up=True)

        if mounts:
            subprocess_utils.run_in_parallel(sync_host, handle.runners())
        if storage_mounts:
            from skypilot_tpu.data import storage_utils
            storage_utils.execute_storage_mounts(handle, storage_mounts)

    def _download_cloud_uri(self, runner, src: str, dst: str) -> None:
        from skypilot_tpu.data import cloud_stores
        cmd = cloud_stores.make_download_command(src, dst)
        runner.check_run(cmd)

    # ------------------------------------------------------------ setup
    def setup(self, handle: TpuVmResourceHandle, task: Task,
              detach_setup: bool = False) -> None:
        del detach_setup
        if not task.setup:
            return
        log_dir = os.path.join(common_utils.state_dir(), 'logs',
                               handle.cluster_name)
        env = dict(task.envs)
        # docker-runtime tasks run setup INSIDE the container image too,
        # or setup-installed deps would be invisible to the run command.
        # (Not on kubernetes: the pod already IS the container.)
        from skypilot_tpu.utils import docker_utils
        image = (docker_utils.docker_image_of(
                     handle.launched_resources.image_id)
                 if handle.cluster_info.provider_name != 'kubernetes'
                 else None)
        setup_cmd = (docker_utils.wrap_in_docker(task.setup, image, env)
                     if image else task.setup)

        def setup_one(rank_runner):
            rank, runner = rank_runner
            log_path = os.path.join(log_dir, f'setup-{rank}.log')
            rc = runner.run(setup_cmd, env=env, log_path=log_path,
                            cwd=None)
            rc = rc if isinstance(rc, int) else rc[0]
            if rc != 0:
                tail = common_utils.read_last_n_lines(log_path, 20)
                raise exceptions.CommandError(
                    rc, f'setup on host {rank}',
                    f'Setup failed. Log tail:\n{tail}')

        subprocess_utils.run_in_parallel(
            setup_one, list(enumerate(handle.runners())))

    # ------------------------------------------------------------ execute
    def execute(self, handle: TpuVmResourceHandle, task: Task,
                detach_run: bool = True,
                dryrun: bool = False) -> Optional[int]:
        if dryrun:
            return None
        if task.run is None:
            logger.info('Task has no run command; provisioning only.')
            return None
        run_cmd = task.run
        if not isinstance(run_cmd, str):
            raise exceptions.InvalidTaskError(
                'Command generators are resolved before execute().')
        from skypilot_tpu.utils import docker_utils
        spec = {
            'run': run_cmd,
            'env': {str(k): str(v) for k, v in task.envs.items()},
            # A workdir synced directly OR delivered via a translated
            # file_mount (controller_utils) both mean: run from there.
            'workdir_target': WORKDIR_TARGET
                              if (task.workdir
                                  or WORKDIR_TARGET in task.file_mounts)
                              else None,
            # 'docker:<image>' => the driver wraps the run command in a
            # container on each host (reference docker runtime,
            # ``sky/backends/local_docker_backend.py:47``). On
            # kubernetes the POD already runs that image — no second
            # docker layer.
            'docker_image': (
                docker_utils.docker_image_of(
                    handle.launched_resources.image_id)
                if handle.cluster_info.provider_name != 'kubernetes'
                else None),
        }
        resp = provisioner.agent_request(handle.head_runner(), {
            'op': 'queue_job',
            'name': task.name or 'task',
            'username': common_utils.get_cleaned_username(),
            'run_timestamp': common_utils.make_run_timestamp(),
            'resources': str(handle.launched_resources),
            'spec': spec,
        })
        job_id = int(resp['job_id'])
        logger.info(f'Job {job_id} submitted to {handle.cluster_name}.')
        if not detach_run:
            self.tail_logs(handle, job_id)
        return job_id

    # ------------------------------------------------------------ job ops
    def tail_logs(self, handle: TpuVmResourceHandle, job_id: int,
                  follow: bool = True) -> None:
        import json as json_lib
        import shlex
        req = {'op': 'tail', 'job_id': job_id, 'follow': follow}
        runner = handle.head_runner()
        cmd = (f'{shlex.quote(runner.remote_python)} '
               f'-m skypilot_tpu.agent.rpc '
               f'{shlex.quote(json_lib.dumps(req))}')
        runner.run(cmd, stream_logs=True, log_path=os.devnull)

    def get_job_logs(self, handle: TpuVmResourceHandle, job_id: int,
                     tail: int = 0) -> str:
        resp = provisioner.agent_request(
            handle.head_runner(),
            {'op': 'logs', 'job_id': job_id, 'tail': tail})
        return resp['logs']

    def get_job_status(self, handle: TpuVmResourceHandle,
                       job_id: int) -> Optional[str]:
        resp = provisioner.agent_request(
            handle.head_runner(), {'op': 'job_status', 'job_id': job_id})
        return resp['status']

    def get_job_queue(self, handle: TpuVmResourceHandle) -> List[Dict]:
        resp = provisioner.agent_request(handle.head_runner(),
                                         {'op': 'job_table'})
        return resp['jobs']

    def cancel_jobs(self, handle: TpuVmResourceHandle,
                    job_id: Optional[int]) -> List[int]:
        if job_id is None:
            resp = provisioner.agent_request(handle.head_runner(),
                                             {'op': 'cancel_all'})
            return resp['cancelled']
        resp = provisioner.agent_request(
            handle.head_runner(), {'op': 'cancel', 'job_id': job_id})
        return [job_id] if resp['cancelled'] else []

    def set_autostop(self, handle: TpuVmResourceHandle, idle_minutes: int,
                     down: bool = False) -> None:
        if idle_minutes >= 0:
            stop_reason = None
            if not down:
                stop_reason = clouds_lib.from_name(
                    handle.launched_resources.cloud
                    or 'gcp').check_stop_supported(
                        handle.launched_resources)
            if stop_reason is not None:
                raise exceptions.NotSupportedError(stop_reason)
        provisioner.agent_request(handle.head_runner(), {
            'op': 'set_autostop', 'idle_minutes': idle_minutes,
            'to_down': down})
        global_state.set_cluster_autostop(handle.cluster_name,
                                          idle_minutes, down)

    # ------------------------------------------------------------ teardown
    def teardown(self, handle: TpuVmResourceHandle,
                 terminate: bool) -> None:
        info = handle.cluster_info
        if not terminate:
            reason = clouds_lib.from_name(
                handle.launched_resources.cloud
                or 'gcp').check_stop_supported(
                    handle.launched_resources)
            if reason is not None:
                raise exceptions.NotSupportedError(reason)
        provisioner.teardown_cluster(info.provider_name, info.region,
                                     handle.cluster_name,
                                     terminate=terminate)
        global_state.remove_cluster(handle.cluster_name,
                                    terminate=terminate)


def _is_cloud_uri(path: str) -> bool:
    # file:// is the LOCAL store's URI (a directory pretending to be a
    # bucket) — it must take the download path, not client-side rsync,
    # so translated controller file mounts resolve on the REMOTE host.
    return path.startswith(('gs://', 's3://', 'r2://', 'https://',
                            'http://', 'file://'))
