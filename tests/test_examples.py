"""Every shipped example must parse, validate, and (where hermetic)
actually run — the reference ships ~50 example YAMLs exercised by smoke
tests (SURVEY §4); ours are exercised in CI via dryrun + the local
provider."""
import os
import subprocess
import sys

import pytest

import skypilot_tpu as sky
from skypilot_tpu.task import Task

EXAMPLES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    'examples')


def _example(name: str) -> str:
    return os.path.join(EXAMPLES, name)


ALL_YAMLS = sorted(f for f in os.listdir(EXAMPLES) if f.endswith('.yaml'))


class TestParseAll:

    def test_inventory(self):
        """The documented example set ships."""
        expected = {'minimal.yaml', 'tpu_hello.yaml', 'tpuvm_mnist.yaml',
                    'train_llama_job.yaml', 'serve_llama.yaml',
                    'k8s_hello.yaml', 'multislice_train.yaml',
                    'finetune_lora.yaml', 'serve_mixtral.yaml',
                    'serve_qwen2.yaml', 'train_gemma.yaml'}
        assert expected.issubset(set(ALL_YAMLS)), ALL_YAMLS

    @pytest.mark.parametrize('yaml_name', ALL_YAMLS)
    def test_parses_and_validates(self, yaml_name):
        task = Task.from_yaml(_example(yaml_name))
        assert task.name
        assert task.run

    def test_tpu_examples_resolve_topology(self):
        for name in ('tpu_hello.yaml', 'tpuvm_mnist.yaml',
                     'multislice_train.yaml'):
            task = Task.from_yaml(_example(name))
            res = list(task.resources)[0]
            assert res.accelerators, name

    @pytest.mark.parametrize('yaml_name', ['serve_llama.yaml',
                                           'serve_mixtral.yaml',
                                           'serve_qwen2.yaml'])
    def test_serve_example_has_service(self, yaml_name):
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        task = Task.from_yaml(_example(yaml_name))
        assert task.service is not None
        spec = SkyServiceSpec.from_yaml_config(task.service)
        assert spec.readiness_path == '/readiness'

    def test_multislice_is_two_slices(self):
        task = Task.from_yaml(_example('multislice_train.yaml'))
        assert task.num_nodes == 2


@pytest.fixture()
def fast_agent(monkeypatch):
    monkeypatch.setenv('SKYTPU_AGENT_TICK', '0.1')
    monkeypatch.setenv('SKYTPU_AGENT_READY_TIMEOUT', '30')


@pytest.mark.slow
class TestRunnable:
    """Hermetic execution: dryrun through the optimizer for cloud
    examples; a real local-provider launch for minimal.yaml; the mnist
    script end-to-end on CPU."""

    def test_tpu_examples_dryrun(self, tmp_state_dir):
        from skypilot_tpu import execution
        for i, name in enumerate(('tpu_hello.yaml', 'tpuvm_mnist.yaml',
                                  'multislice_train.yaml')):
            task = Task.from_yaml(_example(name))
            result = execution.launch(task, cluster_name=f'dry-ex{i}',
                                      dryrun=True)
            assert result is not None, name

    def test_minimal_launches_locally(self, tmp_state_dir, fast_agent):
        import time

        from skypilot_tpu import core, execution
        task = Task.from_yaml(_example('minimal.yaml'))
        task.set_resources(sky.Resources(cloud='local', cpus='1+'))
        job_id, handle = execution.launch(task, cluster_name='ex-min')
        try:
            deadline = time.time() + 60
            status = None
            while time.time() < deadline:
                status = core.job_status('ex-min', job_id)
                if status in ('SUCCEEDED', 'FAILED', 'FAILED_DRIVER'):
                    break
                time.sleep(0.2)
            assert status == 'SUCCEEDED', status
            from skypilot_tpu.backend import tpu_backend
            logs = tpu_backend.TpuVmBackend().get_job_logs(handle, job_id)
            assert 'hello from' in logs
        finally:
            core.down('ex-min')

    def test_mnist_script_runs(self):
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        r = subprocess.run(
            [sys.executable, 'train_mnist.py', '--epochs', '1',
             '--batch', '64'],
            cwd=os.path.join(EXAMPLES, 'mnist'), env=env,
            capture_output=True, text=True, timeout=300, check=False)
        assert r.returncode == 0, r.stderr[-2000:]
        assert 'final accuracy' in r.stdout
