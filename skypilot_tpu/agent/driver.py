"""Job driver: runs one job's command on EVERY host of the slice.

The TPU replacement for the reference's Ray-task-per-node driver program
(``RayCodeGen`` ``sky/backends/cloud_vm_ray_backend.py:220`` +
``_execute_task_n_nodes`` ``:5061``): multi-controller JAX means every host
runs the same program, so the driver is just a parallel fan-out over the
slice's hosts with the rank/coordinator env contract
(:mod:`skypilot_tpu.agent.constants`) exported per rank.

Spawned detached by the FIFO scheduler; exits after writing the terminal
job status and kicking the scheduler.
"""
from __future__ import annotations

import json
import os
import shlex
import sys
from typing import Dict

from skypilot_tpu.agent import constants
from skypilot_tpu.agent import job_lib
from skypilot_tpu.provision import common as provision_common
from skypilot_tpu.utils import subprocess_utils


def _load_cluster_info() -> provision_common.ClusterInfo:
    with open(constants.cluster_info_path(), encoding='utf-8') as f:
        return provision_common.ClusterInfo.from_dict(json.load(f))


def build_rank_env(cluster_info: provision_common.ClusterInfo,
                   rank: int, job_id: int) -> Dict[str, str]:
    """The per-host env contract (gang/rank + jax.distributed bootstrap).

    Multi-slice: SKYTPU_SLICE_ID/NUM_SLICES come from the cluster
    topology (each provisioned TPU node/queued-resource is one slice);
    the jax.distributed coordinator is global rank 0's host, so one
    coordinator spans all slices and the DCN mesh axis works."""
    ips = cluster_info.worker_ips()
    head_ip = cluster_info.head_host().internal_ip
    # Lookup by rank, not position: a gapped host list (partial failure)
    # must fail loudly, not hand out another host's slice id.
    slice_id = {h.rank: h for h in cluster_info.hosts}[rank].slice_id
    return {
        constants.ENV_NODE_RANK: str(rank),
        constants.ENV_NODE_IPS: '\n'.join(ips),
        constants.ENV_NUM_NODES: str(len(ips)),
        constants.ENV_NUM_CHIPS_PER_NODE: str(cluster_info.chips_per_host),
        constants.ENV_COORDINATOR_ADDRESS:
            f'{head_ip}:{constants.JAX_COORDINATOR_PORT}',
        constants.ENV_JOB_ID: str(job_id),
        constants.ENV_CLUSTER_NAME: cluster_info.cluster_name,
        constants.ENV_SLICE_ID: str(slice_id),
        constants.ENV_NUM_SLICES: str(cluster_info.num_slices),
    }


def run_job(job_id: int) -> int:
    job = job_lib.get_job(job_id)
    if job is None:
        print(f'driver: job {job_id} not found', file=sys.stderr)
        return 1
    spec = job['spec'] or {}
    cluster_info = _load_cluster_info()
    runners = provision_common.get_command_runners(cluster_info)
    log_dir = constants.job_log_dir(job['run_timestamp'])
    os.makedirs(log_dir, exist_ok=True)

    job_lib.set_status(job_id, job_lib.JobStatus.RUNNING)

    run_cmd = spec.get('run') or ''
    user_env = {str(k): str(v) for k, v in (spec.get('env') or {}).items()}
    workdir = spec.get('workdir_target')  # remote cwd, e.g. ~/sky_workdir

    def run_one(rank_runner) -> int:
        rank, runner = rank_runner
        env = build_rank_env(cluster_info, rank, job_id)
        env.update(user_env)
        log_path = os.path.join(log_dir,
                                constants.RANK_LOG_FMT.format(rank=rank))
        cmd = run_cmd
        if workdir:
            # Quote the path but keep ~ expandable by the remote shell
            # (shlex.quote('~/x') would suppress tilde expansion).
            if workdir.startswith('~/'):
                quoted = '"$HOME"/' + shlex.quote(workdir[2:])
            else:
                quoted = shlex.quote(workdir)
            cmd = f'cd {quoted} && {cmd}'
        docker_image = spec.get('docker_image')
        if docker_image:
            # Containerized run (image_id: docker:<image>); privileged
            # so the container sees the TPU devices.
            from skypilot_tpu.utils import docker_utils
            cmd = docker_utils.wrap_in_docker(cmd, docker_image, env)
        rc = runner.run(cmd, env=env, log_path=log_path)
        return rc if isinstance(rc, int) else rc[0]

    if run_cmd.strip():
        rcs = subprocess_utils.run_in_parallel(
            run_one, list(enumerate(runners)),
            num_threads=len(runners))
    else:
        rcs = [0]

    failed = [rc for rc in rcs if rc != 0]
    status = (job_lib.JobStatus.SUCCEEDED if not failed
              else job_lib.JobStatus.FAILED)
    job_lib.set_status(job_id, status)
    job_lib.schedule_step()
    return 0 if not failed else 1


def main() -> None:
    job_id = int(sys.argv[1])
    sys.exit(run_job(job_id))


if __name__ == '__main__':
    main()
