"""Test configuration.

Tests run on the CPU: multi-chip logic on a virtual 8-device CPU mesh
(the approach SURVEY.md §4 recommends over the reference's
monkeypatched-catalog-only strategy), Pallas kernels in interpret mode.
The chip is checked by ``chip_smoke.py`` and, without a chip, by the
compiles in ``tests/test_tpu_compile.py``. The platform is pinned in the
environment (for the subprocesses tests spawn) and through jax.config
(for this process, should jax already be imported).
"""
import os

os.environ['JAX_PLATFORMS'] = 'cpu'
prev = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in prev:
    os.environ['XLA_FLAGS'] = (
        prev + ' --xla_force_host_platform_device_count=8').strip()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

# Persistent compilation cache shared by every test AND every
# subprocess they spawn (model-server replicas, job drivers — each is a
# fresh python paying full XLA compiles otherwise). The env var reaches
# subprocesses; the config.update covers this process, whose jax is
# already imported. Round-4's 21-minute slow tier was dominated by
# recompiling the same tiny-model programs per test/process.
_cache_dir = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), '.bench_cache', 'jax_test_cache')
os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', _cache_dir)
os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS', '1')
jax.config.update('jax_compilation_cache_dir',
                  os.environ['JAX_COMPILATION_CACHE_DIR'])
jax.config.update('jax_persistent_cache_min_compile_time_secs', 1.0)

import pytest  # noqa: E402


@pytest.fixture(scope='session', autouse=True)
def _sweep_stray_control_plane():
    """Kill control-plane processes leaked by a previous CRASHED test
    run (a SIGABRT'd pytest never runs its cleanup, and a leftover
    agentd/replica server squatting on localhost ports poisons every
    later serve/jobs test).

    Scoped to TEST-spawned processes only: their state/agent dirs always
    live under the system tempdir (tmp_state_dir / mktemp fixtures), so
    a process whose env points elsewhere — a real local deployment — is
    left alone."""
    import tempfile

    import psutil
    me = os.getpid()
    tmp = tempfile.gettempdir()
    needles = ('skypilot_tpu.agent', 'skypilot_tpu.serve.service',
               'skypilot_tpu.jobs.controller', 'replica_server.py')
    for proc in psutil.process_iter(['pid', 'cmdline']):
        try:
            if proc.pid == me:
                continue
            cmd = ' '.join(proc.info['cmdline'] or ())
            if not any(n in cmd for n in needles):
                continue
            env = proc.environ()
            markers = (env.get('SKYTPU_STATE_DIR', ''),
                       env.get('SKYTPU_AGENT_DIR', ''),
                       env.get('HOME', ''))
            if any(m.startswith(tmp) for m in markers if m):
                proc.kill()
        except (psutil.NoSuchProcess, psutil.AccessDenied):
            continue
    yield


@pytest.fixture()
def tmp_state_dir(tmp_path, monkeypatch):
    """Isolate global sqlite state (and ssh keys) per test."""
    monkeypatch.setenv('SKYTPU_STATE_DIR', str(tmp_path / 'state'))
    monkeypatch.setenv('SKYTPU_KEYS_DIR', str(tmp_path / 'keys'))
    yield tmp_path / 'state'


@pytest.fixture()
def tp_devices():
    """Devices for tensor-parallel (multi-chip serving) tests. This
    conftest forces an 8-device virtual CPU mesh before jax
    initializes, so the skip below should never fire in CI — when it
    does (XLA_FLAGS overridden, or a real single-chip backend won the
    platform race), it says so LOUDLY instead of letting the TP suite
    vanish silently."""
    if jax.device_count() < 2:
        pytest.skip(
            'tensor-parallel tests need >= 2 devices but only '
            f'{jax.device_count()} visible. tests/conftest.py forces '
            'XLA_FLAGS=--xla_force_host_platform_device_count=8; this '
            'environment overrode it — run with that flag (and '
            'JAX_PLATFORMS=cpu) to exercise the TP serving path.')
    return jax.devices()
