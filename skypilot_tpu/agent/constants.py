"""Agent-side paths and the per-host environment contract.

The env contract is the TPU equivalent of the reference's
``SKYPILOT_NODE_RANK/NODE_IPS/NUM_NODES/NUM_GPUS_PER_NODE`` exports
(``sky/backends/cloud_vm_ray_backend.py:519-536``,
``sky/skylet/constants.py:296-299``) plus the jax.distributed bootstrap:
every host of the slice runs the same program with these set.
"""
from __future__ import annotations

import os

# ---- env contract exported to every rank of a job ----
ENV_NODE_RANK = 'SKYTPU_NODE_RANK'
ENV_NODE_IPS = 'SKYTPU_NODE_IPS'            # newline-separated, rank order
ENV_NUM_NODES = 'SKYTPU_NUM_NODES'
ENV_NUM_CHIPS_PER_NODE = 'SKYTPU_NUM_CHIPS_PER_NODE'
ENV_COORDINATOR_ADDRESS = 'SKYTPU_COORDINATOR_ADDRESS'  # head_ip:port
ENV_JOB_ID = 'SKYTPU_JOB_ID'
ENV_CLUSTER_NAME = 'SKYTPU_CLUSTER_NAME'
ENV_TASK_ID = 'SKYTPU_TASK_ID'
# Multi-slice (DCN) contract: which slice this host belongs to and how many.
ENV_SLICE_ID = 'SKYTPU_SLICE_ID'
ENV_NUM_SLICES = 'SKYTPU_NUM_SLICES'

JAX_COORDINATOR_PORT = 8476

# Where a task's workdir lands on every cluster host — shared by the
# backend's direct sync, the controller-side file-mount translation, and
# the driver's cwd decision.
WORKDIR_TARGET = '~/sky_workdir'

# ---- agent filesystem layout (under $SKYTPU_AGENT_DIR) ----


def agent_dir() -> str:
    d = os.environ.get('SKYTPU_AGENT_DIR',
                       os.path.expanduser('~/.skytpu_agent'))
    os.makedirs(d, exist_ok=True)
    return d


def jobs_db_path() -> str:
    return os.path.join(agent_dir(), 'jobs.db')


def logs_dir() -> str:
    d = os.path.join(agent_dir(), 'logs')
    os.makedirs(d, exist_ok=True)
    return d


def job_log_dir(run_timestamp: str) -> str:
    return os.path.join(logs_dir(), run_timestamp)


def cluster_info_path() -> str:
    return os.path.join(agent_dir(), 'cluster_info.json')


def autostop_config_path() -> str:
    return os.path.join(agent_dir(), 'autostop.json')


def agentd_pid_path() -> str:
    return os.path.join(agent_dir(), 'agentd.pid')


def agentd_log_path() -> str:
    return os.path.join(agent_dir(), 'agentd.log')


def agentd_heartbeat_path() -> str:
    return os.path.join(agent_dir(), 'agentd.heartbeat')


# Agent daemon tick, seconds (reference skylet ticks every 20s,
# ``sky/skylet/skylet.py:17-33``). Env-overridable so tests run fast.
def agent_tick_seconds() -> float:
    return float(os.environ.get('SKYTPU_AGENT_TICK', '20'))


SETUP_LOG = 'setup.log'
RANK_LOG_FMT = 'rank-{rank}.log'   # per-host job output
DRIVER_LOG = 'driver.log'
