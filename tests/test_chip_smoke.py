"""``chip_smoke.py`` off the chip: it must fail, and say nothing that
could be read as a pass. (What it does on the chip only a chip run
shows: ``chiprun -- python chip_smoke.py``.)"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, timeout):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)     # conftest's 8 virtual devices: one here
    return subprocess.run(
        [sys.executable, os.path.join(cwd, 'chip_smoke.py')] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout,
        check=False)


def _assert_no_result(proc):
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert '"ok"' not in proc.stdout, proc.stdout[-2000:]


def _checkout(tmp_path):
    """The script and the package, linked into a scratch directory: the
    script keeps its work and logs beside itself."""
    work = tmp_path / 'checkout'
    work.mkdir()
    for name in ('chip_smoke.py', 'skypilot_tpu'):
        os.symlink(os.path.join(REPO, name), work / name)
    return str(work)


def test_fails_without_an_accelerator(tmp_path):
    """No TPU: non-zero exit after the first child says where it ran, no
    result line; and the same from a directory that holds the script and
    nothing else of the repo."""
    proc = _run([], _checkout(tmp_path), 120)
    _assert_no_result(proc)
    assert 'not on a TPU' in proc.stderr, proc.stderr[-2000:]
    alone = tmp_path / 'alone'
    alone.mkdir()
    with open(os.path.join(REPO, 'chip_smoke.py'), 'rb') as f:
        (alone / 'chip_smoke.py').write_bytes(f.read())
    proc = _run([], str(alone), 120)
    _assert_no_result(proc)
    assert 'No module named' in proc.stderr, proc.stderr[-2000:]


@pytest.mark.slow
def test_rehearsal_runs_every_phase_and_never_passes(tmp_path):
    """The whole control flow at a tiny size on the CPU: every phase
    passes, and the run still fails, listing what only a chip shows."""
    proc = _run(['--rehearse'], _checkout(tmp_path), 900)
    _assert_no_result(proc)
    assert 'all phases passed' in proc.stdout, (proc.stdout[-2000:],
                                                proc.stderr[-2000:])
    assert "decode_impl is 'gather', not 'pallas'" in proc.stderr
