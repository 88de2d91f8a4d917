"""Kubernetes (GKE-TPU) provisioner against a fake kubectl: the same
hermetic matrix the GCP provisioner passes (create/query/terminate,
multi-slice gangs, stockout->failover classification, partial-failure cleanup)
— proving the cloud abstraction holds a third implementation
(VERDICT r2 item 6; reference ``sky/provision/kubernetes/``).
"""
import json

import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.provision import common
from skypilot_tpu.provision.kubernetes import instance as k8s_instance
from skypilot_tpu.provision.kubernetes import k8s_client as kc


class FakeK8s:
    """In-memory pods/services + a kubectl-argv interpreter."""

    def __init__(self):
        self.pods = {}        # name -> manifest (with injected status)
        self.services = {}
        self.fail_next_apply = None   # (rc, stderr) injected once
        self.schedulable = True

    # -- kubectl emulation -------------------------------------------
    def runner(self, args, stdin):
        a = list(args)
        # strip --namespace/--context pairs
        flags = {}
        i = 0
        rest = []
        while i < len(a):
            if a[i] in ('--namespace', '--context', '-l'):
                flags[a[i]] = a[i + 1]
                i += 2
            elif a[i].startswith('--'):
                i += 1
            elif a[i] == '-o':
                i += 2
            else:
                rest.append(a[i])
                i += 1
        verb = rest[0] if rest else ''
        if verb == 'apply':
            if self.fail_next_apply is not None:
                rc, err = self.fail_next_apply
                self.fail_next_apply = None
                return rc, '', err
            manifest = json.loads(stdin)
            return self._apply(manifest)
        if verb == 'get':
            return self._get(rest[1:], flags.get('-l'))
        if verb == 'delete':
            return self._delete(rest[1:], flags.get('-l'))
        if verb == 'version':
            return 0, '{"clientVersion": {}}', ''
        return 1, '', f'unknown verb {verb}'

    def _apply(self, manifest):
        kind = manifest['kind']
        name = manifest['metadata']['name']
        if kind == 'Service':
            self.services[name] = manifest
            return 0, json.dumps(manifest), ''
        manifest = json.loads(json.dumps(manifest))    # deep copy
        if self.schedulable:
            idx = len(self.pods)
            manifest['status'] = {
                'phase': 'Running',
                'podIP': f'10.0.0.{idx + 1}',
            }
        else:
            manifest['status'] = {
                'phase': 'Pending',
                'conditions': [{
                    'type': 'PodScheduled', 'status': 'False',
                    'reason': 'Unschedulable',
                    'message': ('0/3 nodes are available: insufficient '
                                'google.com/tpu'),
                }],
            }
        self.pods[name] = manifest
        return 0, json.dumps(manifest), ''

    def _get(self, rest, selector):
        if rest[0] == 'pods':
            items = [p for p in self.pods.values()
                     if self._match(p, selector)]
            return 0, json.dumps({'items': items}), ''
        if rest[0] == 'pod':
            name = rest[1]
            if name in self.pods:
                return 0, json.dumps(self.pods[name]), ''
            return 1, '', f'pods "{name}" not found'
        return 1, '', f'cannot get {rest}'

    def _delete(self, rest, selector):
        if selector is not None:
            for name in [n for n, p in self.pods.items()
                         if self._match(p, selector)]:
                del self.pods[name]
            for name in [n for n, s in self.services.items()
                         if self._match(s, selector)]:
                del self.services[name]
            return 0, '', ''
        if rest[0] == 'pod':
            self.pods.pop(rest[1], None)
            return 0, '', ''
        return 1, '', f'cannot delete {rest}'

    @staticmethod
    def _match(obj, selector):
        if not selector:
            return True
        key, val = selector.split('=', 1)
        return obj['metadata'].get('labels', {}).get(key) == val


@pytest.fixture()
def fake(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_STATE_DIR', str(tmp_path))
    monkeypatch.setenv('SKYTPU_K8S_SCHEDULE_TIMEOUT', '0.2')
    k8s = FakeK8s()
    kc.set_runner_factory(lambda: k8s.runner)
    yield k8s
    kc.set_runner_factory(None)


def _config(count=1, hosts_per_node=2):
    return common.ProvisionConfig(
        provider_config={'namespace': 'default'},
        node_config={
            'accelerator': 'tpu-v5e-16',
            'generation': 'v5e',
            'num_chips': 16,
            'hosts_per_node': hosts_per_node,
            'chips_per_host': 8,
            'use_spot': False,
        },
        count=count)


def test_create_query_info_terminate(fake):
    rec = k8s_instance.run_instances('kubernetes', None, 'kc', _config())
    assert len(rec.created_instance_ids) == 2
    k8s_instance.wait_instances('kubernetes', 'kc', 'RUNNING')

    st = k8s_instance.query_instances('kubernetes', 'kc')
    assert set(st.values()) == {common.STATUS_RUNNING}

    info = k8s_instance.get_cluster_info('kubernetes', 'kc')
    assert info.num_hosts == 2 and info.num_slices == 1
    assert info.head_instance_id == 'kc-0-0'
    assert all(h.internal_ip for h in info.hosts)
    assert info.chips_per_host == 8
    # GKE node selectors on the pod manifests.
    pod = fake.pods['kc-0-0']
    sel = pod['spec']['nodeSelector']
    assert sel['cloud.google.com/gke-tpu-accelerator'] == \
        'tpu-v5-lite-podslice'
    assert sel['cloud.google.com/gke-tpu-topology'] == '4x4'
    res = pod['spec']['containers'][0]['resources']
    assert res['limits']['google.com/tpu'] == '8'

    k8s_instance.terminate_instances('kubernetes', 'kc')
    assert fake.pods == {} and fake.services == {}
    assert k8s_instance.query_instances('kubernetes', 'kc') == {}


def test_multislice_pods_and_slice_ids(fake):
    k8s_instance.run_instances('kubernetes', None, 'kms',
                               _config(count=2, hosts_per_node=2))
    info = k8s_instance.get_cluster_info('kubernetes', 'kms')
    assert info.num_hosts == 4 and info.num_slices == 2
    assert [h.slice_id for h in
            sorted(info.hosts, key=lambda h: h.rank)] == [0, 0, 1, 1]


def test_unschedulable_maps_to_capacity_error(fake):
    fake.schedulable = False
    k8s_instance.run_instances('kubernetes', None, 'kstock', _config())
    with pytest.raises(exceptions.InsufficientCapacityError) as ei:
        k8s_instance.wait_instances('kubernetes', 'kstock', 'RUNNING')
    assert 'insufficient google.com/tpu' in str(ei.value)
    assert ei.value.blocklist_scope == 'zone'


def test_quota_error_classification(fake):
    fake.fail_next_apply = (1, 'pods "x" is forbidden: exceeded quota')
    with pytest.raises(exceptions.QuotaExceededError):
        k8s_instance.run_instances('kubernetes', None, 'kq', _config())


def test_partial_failure_cleans_up_gang(fake):
    created = []
    orig = fake._apply

    def flaky(manifest):
        if manifest['kind'] == 'Pod' and len(created) == 1:
            return 1, '', 'server error'
        if manifest['kind'] == 'Pod':
            created.append(manifest['metadata']['name'])
        return orig(manifest)

    fake._apply = flaky
    with pytest.raises(exceptions.ProvisionError):
        k8s_instance.run_instances('kubernetes', None, 'kpf',
                                   _config(count=1, hosts_per_node=2))
    # The successfully-created pod of the failed gang was deleted.
    assert fake.pods == {}


def test_stop_unsupported(fake):
    k8s_instance.run_instances('kubernetes', None, 'kstop', _config())
    with pytest.raises(exceptions.NotSupportedError):
        k8s_instance.stop_instances('kubernetes', 'kstop')


def test_terminated_pod_reported(fake):
    k8s_instance.run_instances('kubernetes', None, 'kdead', _config())
    fake.pods['kdead-0-1']['status']['phase'] = 'Failed'
    st = k8s_instance.query_instances('kubernetes', 'kdead')
    assert st['kdead-0-1'] == common.STATUS_TERMINATED
    assert st['kdead-0-0'] == common.STATUS_RUNNING


def test_gke_topology_strings():
    """Pinned GKE node-pool topology values (a wrong selector never
    schedules; VERDICT r4 task 8). Sources: cloud.google.com/tpu docs
    tables; ref sky/provision/kubernetes/utils.py:349-363."""
    cases = [
        ('v5e', 1, '1x1'), ('v5e', 4, '2x2'), ('v5e', 8, '2x4'),
        ('v5e', 16, '4x4'), ('v5e', 32, '4x8'), ('v5e', 64, '8x8'),
        ('v5e', 256, '16x16'),
        ('v6e', 8, '2x4'), ('v6e', 16, '4x4'),
        ('v4', 8, '2x2x2'),        # v4-16 (16 TensorCores = 8 chips)
        ('v4', 16, '2x2x4'), ('v4', 32, '2x4x4'), ('v4', 64, '4x4x4'),
        ('v5p', 4, '2x2x1'), ('v5p', 8, '2x2x2'), ('v5p', 512, '8x8x8'),
    ]
    for gen, chips, want in cases:
        assert k8s_instance.gke_topology(gen, chips, 4) == want, \
            (gen, chips)
    # unknown sizes fail loudly instead of inventing a selector
    import pytest as _pytest
    from skypilot_tpu import exceptions as _exc
    with _pytest.raises(_exc.InvalidResourcesError, match='valid sizes'):
        k8s_instance.gke_topology('v5e', 12, 4)
    with _pytest.raises(_exc.InvalidResourcesError, match='generation'):
        k8s_instance.gke_topology('v9x', 8, 4)


def test_cloud_feasibility_and_provision_config():
    import skypilot_tpu as sky
    from skypilot_tpu.clouds import Kubernetes
    cloud = Kubernetes()
    res = sky.Resources(cloud='kubernetes', accelerators='tpu-v5e-16')
    feasible, hints = cloud.get_feasible_launchable_resources(res)
    assert feasible and not hints
    cfg = cloud.make_provision_config(res, num_nodes=2, cluster_name='c')
    assert cfg.count == 2
    assert cfg.node_config['hosts_per_node'] == 2
    assert cfg.node_config['generation'] == 'v5e'
    assert cloud.instance_type_to_hourly_cost(res, use_spot=False) == 0.0


def test_pod_manifest_image_and_selectors():
    """A task image_id reaches the pod spec; the shipped Dockerfiles
    document the image contract (VERDICT r4 task 8)."""
    m = k8s_instance._pod_manifest(
        'c1', 0, 0, {'accelerator': 'tpu-v5litepod-8',
                     'generation': 'v5e', 'num_chips': 8,
                     'chips_per_host': 4,
                     'image': 'gcr.io/proj/skypilot-tpu-k8s:latest'})
    spec = m['spec']
    assert spec['containers'][0]['image'] == \
        'gcr.io/proj/skypilot-tpu-k8s:latest'
    assert spec['nodeSelector'][
        'cloud.google.com/gke-tpu-accelerator'] == 'tpu-v5-lite-podslice'
    assert spec['nodeSelector'][
        'cloud.google.com/gke-tpu-topology'] == '2x4'


def test_dockerfiles_ship():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ('Dockerfile', 'Dockerfile_k8s'):
        path = os.path.join(root, name)
        assert os.path.exists(path), name
        content = open(path, encoding='utf-8').read()
        assert content.startswith('#')
        assert 'FROM ' in content
    assert 'jax[tpu]' in open(os.path.join(root, 'Dockerfile'),
                              encoding='utf-8').read()
