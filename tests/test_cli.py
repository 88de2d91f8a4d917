"""CLI tests: click commands driven through CliRunner against the local
provisioner (hermetic counterpart of the reference's CLI smoke tests;
command surface per ``sky/cli.py``)."""
import time

import pytest
from click.testing import CliRunner

from skypilot_tpu import cli

pytestmark = pytest.mark.usefixtures('tmp_state_dir', 'fast_agent')


@pytest.fixture()
def fast_agent(monkeypatch):
    monkeypatch.setenv('SKYTPU_AGENT_TICK', '0.1')
    monkeypatch.setenv('SKYTPU_AGENT_READY_TIMEOUT', '30')


@pytest.fixture()
def runner():
    return CliRunner()


@pytest.fixture()
def task_yaml(tmp_path):
    p = tmp_path / 'task.yaml'
    p.write_text(
        'name: clitask\n'
        'resources:\n'
        '  cloud: local\n'
        '  cpus: 1+\n'
        f'run: echo cli-out-$((40+2)) > {tmp_path}/out.txt\n')
    return str(p)


def _ok(result):
    assert result.exit_code == 0, result.output
    return result.output


class TestBasics:

    def test_help_lists_commands(self, runner):
        out = _ok(runner.invoke(cli.cli, ['--help']))
        for cmd in ('launch', 'status', 'queue', 'logs', 'down', 'jobs',
                    'serve', 'show-tpus', 'check', 'cost-report'):
            assert cmd in out

    def test_version(self, runner):
        assert '0.1.0' in _ok(runner.invoke(cli.cli, ['--version']))

    def test_model_server_help_and_validation(self, runner):
        out = _ok(runner.invoke(cli.cli, ['model-server', '--help']))
        for opt in ('--speculate-k', '--kv-cache-dtype', '--quantize',
                    '--prefill-chunk-tokens', '--page-size'):
            assert opt in out

    def test_model_server_has_no_engine_choice(self, runner):
        """One engine: ``--kv-cache`` is no option any more, under
        either spelling of its old values."""
        for value in ('paged', 'slot'):
            bad = runner.invoke(cli.cli, ['model-server', '--kv-cache',
                                          value])
            assert bad.exit_code != 0
            assert 'No such option' in bad.output, bad.output

    def test_server_module_has_no_engine_choice(self):
        """The same for ``python -m skypilot_tpu.serve.server``: the flag
        is unknown, and is not read as a prefix of ``--kv-cache-dtype``."""
        import subprocess
        import sys
        for value in ('paged', 'int8'):
            proc = subprocess.run(
                [sys.executable, '-m', 'skypilot_tpu.serve.server',
                 '--kv-cache', value], capture_output=True, text=True,
                timeout=120, check=False)
            assert proc.returncode == 2, proc.stderr[-2000:]
            assert 'unrecognized arguments: --kv-cache' in proc.stderr, \
                proc.stderr[-2000:]

    def test_status_empty(self, runner):
        assert 'No existing clusters' in _ok(
            runner.invoke(cli.cli, ['status']))

    def test_jobs_queue_without_controller(self, runner):
        assert 'No managed jobs' in _ok(
            runner.invoke(cli.cli, ['jobs', 'queue']))

    def test_serve_status_without_controller(self, runner):
        assert 'No services' in _ok(
            runner.invoke(cli.cli, ['serve', 'status']))

    def test_show_tpus(self, runner):
        out = _ok(runner.invoke(cli.cli, ['show-tpus']))
        assert 'tpu-v5litepod-8' in out or 'tpu-v' in out

    def test_check(self, runner):
        out = _ok(runner.invoke(cli.cli, ['check']))
        assert 'local: enabled' in out

    def test_down_requires_target(self, runner):
        result = runner.invoke(cli.cli, ['down'])
        assert result.exit_code != 0
        assert '--all' in result.output

    def test_env_validation(self, runner, task_yaml):
        result = runner.invoke(
            cli.cli, ['launch', task_yaml, '--dryrun', '--env', 'NOEQUALS'])
        assert result.exit_code != 0
        assert 'KEY=VALUE' in result.output

    def test_env_override_interpolates_outside_run(self, tmp_path):
        """--env must take effect before ${VAR} interpolation, so it can
        steer fields like workdir, not just the run script's env."""
        (tmp_path / 'wd-b').mkdir()
        p = tmp_path / 'envtask.yaml'
        p.write_text(
            'name: envtask\n'
            'envs:\n'
            '  WD: wd-a\n'
            f'workdir: {tmp_path}/${{WD}}\n'
            'run: echo hi\n')
        task = cli._load_task(str(p), env=('WD=wd-b',))
        assert task.workdir == f'{tmp_path}/wd-b'

    def test_all_excludes_controller_clusters(self, runner, monkeypatch):
        import skypilot_tpu as sky
        # Patch the sky-module bindings (the lazy SDK caches resolved
        # attrs in skypilot_tpu's globals, which is what cli calls).
        monkeypatch.setattr(
            sky, 'status',
            lambda *a, **k: [{'name': 'skytpu-jobs-controller'},
                             {'name': 'skytpu-serve-controller'},
                             {'name': 'usercluster'}], raising=False)
        downed = []
        monkeypatch.setattr(sky, 'down', downed.append, raising=False)
        out = _ok(runner.invoke(cli.cli, ['down', '--all', '-y']))
        assert downed == ['usercluster'], out

    def test_all_with_no_clusters_is_noop(self, runner):
        out = _ok(runner.invoke(cli.cli, ['down', '--all', '-y']))
        assert 'No existing clusters' in out


class TestFleetCli:
    """`skytpu fleet` / `skytpu telemetry dump --fleet` against a REAL
    controller whose aggregator was populated by the simulator (the
    sim drives the identical FleetAggregator code on the virtual
    clock), served over its real HTTP handler."""

    @pytest.fixture()
    def fleet_controller_url(self):
        import http.server as hs
        import threading

        from skypilot_tpu.serve import replica_managers
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        from skypilot_tpu.serve.sim import replica as sim_replica
        from skypilot_tpu.serve.sim import traffic as sim_traffic
        from skypilot_tpu.serve.sim.fleet import FleetSimulator
        from skypilot_tpu.utils import common_utils
        sim = FleetSimulator(
            spec=SkyServiceSpec(
                readiness_path='/readiness', min_replicas=2,
                max_replicas=2,
                slos={'latency': {'ttft_ms': 2000.0, 'target': 0.9}}),
            trace=sim_traffic.constant(4.0, 120.0), seed=0,
            curve=sim_replica.ServiceCurve(
                ttft_base_s=0.1, warm_ttft_base_s=0.05,
                prefill_tok_per_s=2000.0, tpot_s=0.02, slots=4,
                max_queue_wait_s=5.0, kv_pool_tokens=4000),
            provision_s=10.0, provision_jitter=0.0, keep_log=False)
        sim.run()
        assert sim.controller.fleet.source_count() > 0
        port = common_utils.find_free_port(21500)
        httpd = hs.ThreadingHTTPServer(('127.0.0.1', port),
                                       sim.controller._make_handler())
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        try:
            yield f'http://127.0.0.1:{port}'
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_fleet_top_smoke(self, runner, fleet_controller_url):
        out = _ok(runner.invoke(
            cli.cli, ['fleet', 'top', '--url', fleet_controller_url]))
        assert 'sources' in out and 'scrapes' in out
        assert 'TTFT_MEAN_MS' in out           # sim traffic was scraped
        assert 'slo latency' in out
        assert 'burn_5m=' in out and 'burn_1h=' in out

    def test_fleet_slo_and_trace_listing(self, runner,
                                         fleet_controller_url):
        import json
        out = _ok(runner.invoke(
            cli.cli, ['fleet', 'slo', '--url', fleet_controller_url]))
        slo = json.loads(out)
        assert 'latency' in slo
        assert {'attainment', 'burn_5m', 'burn_1h'} <= set(
            slo['latency'])
        out = _ok(runner.invoke(
            cli.cli, ['fleet', 'trace', '--url', fleet_controller_url]))
        ids = [line for line in out.splitlines() if line]
        assert ids                              # completed traces shipped
        assembled = json.loads(_ok(runner.invoke(
            cli.cli, ['fleet', 'trace', '--url', fleet_controller_url,
                      ids[0]])))
        assert assembled['trace_id'] == ids[0]
        assert assembled['spans']

    def test_fleet_trace_unknown_id_fails(self, runner,
                                          fleet_controller_url):
        result = runner.invoke(
            cli.cli, ['fleet', 'trace', '--url', fleet_controller_url,
                      'ff' * 16])
        assert result.exit_code != 0
        assert 'not found' in result.output

    def test_telemetry_dump_fleet_flags_require_url(self, runner):
        for args in (['telemetry', 'dump', '--fleet'],
                     ['telemetry', 'dump', '--trace', 'ab' * 16]):
            result = runner.invoke(cli.cli, args)
            assert result.exit_code != 0
            assert 'require --url' in result.output

    def test_telemetry_dump_fleet_view(self, runner,
                                       fleet_controller_url):
        out = _ok(runner.invoke(
            cli.cli, ['telemetry', 'dump', '--fleet', '--url',
                      fleet_controller_url]))
        assert 'skytpu_fleet_sources' in out    # prometheus exposition
        assert 'skytpu_slo_burn_rate' in out


class TestLifecycle:

    def test_launch_dryrun(self, runner, task_yaml):
        out = _ok(runner.invoke(cli.cli, ['launch', task_yaml, '--dryrun']))
        assert 'Optimizer plan' in out

    def test_launch_status_queue_logs_down(self, runner, task_yaml,
                                           tmp_path):
        out = _ok(runner.invoke(
            cli.cli, ['launch', task_yaml, '-c', 'clic', '-y', '-d']))
        assert 'Job submitted (id: 1)' in out

        out = _ok(runner.invoke(cli.cli, ['status']))
        assert 'clic' in out and 'UP' in out

        deadline = time.time() + 45
        while time.time() < deadline:
            out = _ok(runner.invoke(cli.cli, ['queue', 'clic']))
            if 'SUCCEEDED' in out:
                break
            time.sleep(0.5)
        assert 'SUCCEEDED' in out
        assert (tmp_path / 'out.txt').read_text().strip() == 'cli-out-42'

        out = _ok(runner.invoke(
            cli.cli, ['logs', 'clic', '1', '--no-follow']))
        assert 'cli-out' in out or 'SUCCEEDED' in out

        out = _ok(runner.invoke(cli.cli, ['cost-report']))
        assert 'clic' in out

        out = _ok(runner.invoke(cli.cli, ['down', 'clic', '-y']))
        assert 'terminated' in out
        assert 'No existing clusters' in _ok(
            runner.invoke(cli.cli, ['status']))

    def test_autostop_arm_and_cancel(self, runner, task_yaml):
        _ok(runner.invoke(
            cli.cli, ['launch', task_yaml, '-c', 'autoc', '-y', '-d']))
        out = _ok(runner.invoke(
            cli.cli, ['autostop', 'autoc', '-i', '30']))
        assert 'autostop after 30' in out
        out = _ok(runner.invoke(cli.cli, ['autostop', 'autoc', '--cancel']))
        assert 'cancelled' in out
        _ok(runner.invoke(cli.cli, ['down', 'autoc', '-y']))
