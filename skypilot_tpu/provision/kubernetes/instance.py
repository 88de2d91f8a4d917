"""Kubernetes (GKE-TPU) provisioner: TPU slices as gangs of pods.

Role of reference ``sky/provision/kubernetes/instance.py`` (1,129 LoC) +
the GKE TPU parts of ``utils.py`` (labels ``cloud.google.com/
gke-tpu-accelerator`` / ``gke-tpu-topology`` at ``:340-390``,
``TPU_RESOURCE_KEY='google.com/tpu'`` at ``:57``). TPU-first design:

- One *slice* = ``hosts_per_node`` pods sharing a ``skytpu/slice``
  label; ``config.count`` slices form one logical cluster (the same
  shape as the GCP provisioner's one-QR-per-slice and the multi-slice
  env contract).
- GKE schedules all pods of a multi-host slice onto the same TPU node
  pool via the accelerator+topology node selectors; the ``google.com/
  tpu`` resource request claims the chips of each host.
- Gang semantics: a slice that cannot fully schedule is torn down and
  the error enters the blocklist-scoped classification so the failover loop
  moves on (Unschedulable == stockout).
- A headless Service per cluster gives pods stable DNS names
  (``<pod>.<cluster>``) for the jax.distributed coordinator.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu import exceptions
from skypilot_tpu import tpu_logging
from skypilot_tpu.provision import common
from skypilot_tpu.provision.kubernetes import k8s_client as kc
from skypilot_tpu.utils import common_utils

logger = tpu_logging.init_logger(__name__)

_LABEL_CLUSTER = 'skytpu/cluster'
_LABEL_SLICE = 'skytpu/slice'
_LABEL_HOST = 'skytpu/host'

# GKE TPU node-pool selector values per generation (reference
# ``sky/provision/kubernetes/utils.py:340-390``).
GKE_TPU_ACCELERATOR = {
    'v4': 'tpu-v4-podslice',
    'v5e': 'tpu-v5-lite-podslice',
    'v5p': 'tpu-v5p-slice',
    'v6e': 'tpu-v6e-slice',
}
TPU_RESOURCE_KEY = 'google.com/tpu'

_DEFAULT_IMAGE = 'python:3.11-slim'


def default_schedule_timeout() -> float:
    return float(os.environ.get('SKYTPU_K8S_SCHEDULE_TIMEOUT', '600'))


# ------------------------------------------------------------ placement
def _placement_dir() -> str:
    d = os.path.join(common_utils.state_dir(), 'k8s_clusters')
    os.makedirs(d, exist_ok=True)
    return d


def _placement_path(cluster_name: str) -> str:
    return os.path.join(_placement_dir(), f'{cluster_name}.json')


def _save_placement(cluster_name: str, namespace: str,
                    context: Optional[str],
                    node_config: Dict[str, Any]) -> None:
    with open(_placement_path(cluster_name), 'w', encoding='utf-8') as f:
        json.dump({'namespace': namespace, 'context': context,
                   'node_config': node_config}, f)


def _load_placement(cluster_name: str) -> Optional[Dict[str, Any]]:
    try:
        with open(_placement_path(cluster_name), encoding='utf-8') as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def _drop_placement(cluster_name: str) -> None:
    try:
        os.remove(_placement_path(cluster_name))
    except FileNotFoundError:
        pass


def _client_for(cluster_name: str) -> kc.K8sClient:
    placement = _load_placement(cluster_name)
    if placement is None:
        raise exceptions.ClusterDoesNotExist(cluster_name)
    return kc.K8sClient(namespace=placement['namespace'],
                        context=placement.get('context'))


# ------------------------------------------------------------ manifests
# Pinned per-generation chips -> topology selector values. GKE node
# pools expose SPECIFIC topology strings (cloud.google.com/tpu docs;
# reference pins the same values, sky/provision/kubernetes/utils.py:
# 340-390) — a computed "near-equal factorization" can produce a string
# no node pool carries (e.g. 4x2x1 for v4-16 instead of 2x2x2), which
# never schedules and surfaces as a phantom stockout.
# v5e/v6e are 2-D (chip-count naming); v4/v5p are 3-D torus slices
# (TensorCore naming halved to chips), dims ascending powers of two.
_GKE_TOPOLOGY_2D = {
    1: '1x1', 4: '2x2', 8: '2x4', 16: '4x4', 32: '4x8', 64: '8x8',
    128: '8x16', 256: '16x16',
}
_GKE_TOPOLOGY_3D = {
    4: '2x2x1', 8: '2x2x2', 16: '2x2x4', 32: '2x4x4', 64: '4x4x4',
    128: '4x4x8', 256: '4x8x8', 512: '8x8x8', 1024: '8x8x16',
    2048: '8x16x16', 4096: '16x16x16',
}
GKE_TPU_TOPOLOGIES = {
    'v4': _GKE_TOPOLOGY_3D,
    'v5p': _GKE_TOPOLOGY_3D,
    'v5e': _GKE_TOPOLOGY_2D,
    'v6e': _GKE_TOPOLOGY_2D,
}


def gke_topology(generation: str, num_chips: int,
                 chips_per_host: int) -> str:
    """GKE topology selector value for a slice size, from the pinned
    table; unknown sizes fail loudly with the valid options."""
    del chips_per_host
    table = GKE_TPU_TOPOLOGIES.get(generation)
    if table is None:
        raise exceptions.InvalidResourcesError(
            f'No GKE topology table for TPU generation {generation!r}; '
            f'known: {sorted(GKE_TPU_TOPOLOGIES)}')
    topo = table.get(num_chips)
    if topo is None:
        raise exceptions.InvalidResourcesError(
            f'{generation} has no GKE node-pool topology for '
            f'{num_chips} chips; valid sizes: {sorted(table)}')
    return topo


def _pod_name(cluster_name: str, slice_idx: int, host_idx: int) -> str:
    return f'{cluster_name}-{slice_idx}-{host_idx}'


def _pod_manifest(cluster_name: str, slice_idx: int, host_idx: int,
                  node_config: Dict[str, Any]) -> Dict[str, Any]:
    accel = node_config.get('accelerator')
    manifest: Dict[str, Any] = {
        'apiVersion': 'v1',
        'kind': 'Pod',
        'metadata': {
            'name': _pod_name(cluster_name, slice_idx, host_idx),
            'labels': {
                _LABEL_CLUSTER: cluster_name,
                _LABEL_SLICE: str(slice_idx),
                _LABEL_HOST: str(host_idx),
            },
        },
        'spec': {
            'restartPolicy': 'Never',
            'hostname': _pod_name(cluster_name, slice_idx, host_idx),
            'subdomain': cluster_name,
            'containers': [{
                'name': 'skytpu',
                'image': node_config.get('image') or _DEFAULT_IMAGE,
                'command': ['/bin/sh', '-c', 'sleep infinity'],
                'resources': {},
            }],
        },
    }
    if accel:
        gen = node_config['generation']
        chips = int(node_config.get('chips_per_host', 0))
        sel = GKE_TPU_ACCELERATOR.get(gen)
        if sel is None:
            raise exceptions.InvalidResourcesError(
                f'No GKE TPU node pool mapping for generation {gen!r}')
        manifest['spec']['nodeSelector'] = {
            'cloud.google.com/gke-tpu-accelerator': sel,
            'cloud.google.com/gke-tpu-topology': gke_topology(
                gen, int(node_config['num_chips']), chips),
        }
        req = {TPU_RESOURCE_KEY: str(chips)}
        manifest['spec']['containers'][0]['resources'] = {
            'requests': dict(req), 'limits': dict(req)}
    return manifest


def _service_manifest(cluster_name: str) -> Dict[str, Any]:
    """Headless service: stable pod DNS for the coordinator."""
    return {
        'apiVersion': 'v1',
        'kind': 'Service',
        'metadata': {'name': cluster_name,
                     'labels': {_LABEL_CLUSTER: cluster_name}},
        'spec': {'clusterIP': 'None',
                 'selector': {_LABEL_CLUSTER: cluster_name}},
    }


# ------------------------------------------------------------------ ops
def run_instances(region: str, zone: Optional[str], cluster_name: str,
                  config: common.ProvisionConfig) -> common.ProvisionRecord:
    del region
    node_config = dict(config.node_config)
    namespace = (config.provider_config or {}).get('namespace', 'default')
    context = zone if zone not in (None, 'default', 'in-cluster') else None
    client = kc.K8sClient(namespace=namespace, context=context)
    _save_placement(cluster_name, namespace, context, node_config)

    hosts_per_slice = int(node_config.get('hosts_per_node', 1)) or 1
    existing = {p['metadata']['name']
                for p in client.list_pods(f'{_LABEL_CLUSTER}={cluster_name}')
                if (p.get('status') or {}).get('phase')
                in ('Pending', 'Running')}
    created: List[str] = []
    try:
        client.apply(_service_manifest(cluster_name))
        for s in range(config.count):
            for h in range(hosts_per_slice):
                name = _pod_name(cluster_name, s, h)
                if name in existing:
                    continue
                created.append(name)
                client.apply(_pod_manifest(cluster_name, s, h, node_config))
    except exceptions.SkyTpuError:
        # Gang semantics: tear down what this attempt created.
        for name in created:
            try:
                client.delete_pod(name)
            except exceptions.SkyTpuError:
                pass
        raise
    return common.ProvisionRecord(
        provider_name='kubernetes', cluster_name=cluster_name,
        region='kubernetes', zone=zone,
        head_instance_id=_pod_name(cluster_name, 0, 0),
        created_instance_ids=created, resumed_instance_ids=[])


def _pod_unschedulable(pod: Dict[str, Any]) -> Optional[str]:
    for cond in ((pod.get('status') or {}).get('conditions') or []):
        if (cond.get('type') == 'PodScheduled'
                and cond.get('status') == 'False'
                and cond.get('reason') == 'Unschedulable'):
            return cond.get('message') or 'Unschedulable'
    return None


def wait_instances(region: str, cluster_name: str, state: str,
                   timeout: Optional[float] = None) -> None:
    """Wait until every pod of the cluster is Running. Unschedulable
    pods (no TPU node pool capacity) fail over zone-scoped — the k8s
    equivalent of a stockout."""
    del region, state
    client = _client_for(cluster_name)
    deadline = time.time() + (timeout if timeout is not None
                              else default_schedule_timeout())
    while True:
        pods = client.list_pods(f'{_LABEL_CLUSTER}={cluster_name}')
        phases = [(p.get('status') or {}).get('phase') for p in pods]
        if pods and all(ph == 'Running' for ph in phases):
            return
        for p in pods:
            if (p.get('status') or {}).get('phase') in ('Failed',
                                                        'Succeeded'):
                raise exceptions.ProvisionError(
                    f'pod {p["metadata"]["name"]} exited during '
                    f'provisioning')
        if time.time() > deadline:
            msgs = [m for m in (_pod_unschedulable(p) for p in pods) if m]
            err = exceptions.InsufficientCapacityError(
                f'kubernetes: cluster {cluster_name} did not schedule in '
                f'time{": " + msgs[0] if msgs else ""}')
            raise err
        time.sleep(min(2.0, max(0.05, deadline - time.time())))


def query_instances(region: str, cluster_name: str) -> Dict[str, str]:
    del region
    if _load_placement(cluster_name) is None:
        return {}
    client = _client_for(cluster_name)
    out = {}
    for p in client.list_pods(f'{_LABEL_CLUSTER}={cluster_name}'):
        phase = (p.get('status') or {}).get('phase')
        status = {
            'Pending': common.STATUS_PENDING,
            'Running': common.STATUS_RUNNING,
        }.get(phase, common.STATUS_TERMINATED)
        if p.get('metadata', {}).get('deletionTimestamp'):
            status = common.STATUS_TERMINATED
        out[p['metadata']['name']] = status
    return out


def stop_instances(region: str, cluster_name: str) -> None:
    raise exceptions.NotSupportedError(
        'kubernetes pods cannot be stopped; use down (terminate)')


def terminate_instances(region: str, cluster_name: str) -> None:
    del region
    if _load_placement(cluster_name) is None:
        return
    client = _client_for(cluster_name)
    client.delete_collection(f'{_LABEL_CLUSTER}={cluster_name}')
    _drop_placement(cluster_name)


def get_cluster_info(region: str, cluster_name: str) -> common.ClusterInfo:
    del region
    placement = _load_placement(cluster_name)
    if placement is None:
        raise exceptions.ClusterDoesNotExist(cluster_name)
    client = _client_for(cluster_name)
    node_config = placement.get('node_config', {})
    pods = client.list_pods(f'{_LABEL_CLUSTER}={cluster_name}')

    def key(p):
        lbl = p['metadata'].get('labels', {})
        return (int(lbl.get(_LABEL_SLICE, 0)), int(lbl.get(_LABEL_HOST, 0)))

    hosts: List[common.HostInfo] = []
    for rank, p in enumerate(sorted(pods, key=key)):
        lbl = p['metadata'].get('labels', {})
        hosts.append(common.HostInfo(
            instance_id=p['metadata']['name'],
            rank=rank,
            internal_ip=(p.get('status') or {}).get('podIP', ''),
            slice_id=int(lbl.get(_LABEL_SLICE, 0)),
        ))
    return common.ClusterInfo(
        cluster_name=cluster_name,
        provider_name='kubernetes',
        region='kubernetes',
        zone=placement.get('context'),
        hosts=hosts,
        head_instance_id=_pod_name(cluster_name, 0, 0),
        chips_per_host=int(node_config.get('chips_per_host', 0)),
        accelerator=node_config.get('accelerator'),
        provider_config={'namespace': placement['namespace'],
                         'context': placement.get('context')},
    )
