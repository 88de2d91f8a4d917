"""The harness is driven by data: a configuration, a traffic mix, a cell
and a per-layer reader added as NEW files (and entries) in a temporary
copy are found and run with no edit to a file that is there. There is no
chip here, so the tests steer: they run ``run.py`` in a child whose
``device_identity`` reports a v5e, and hand the traced run the recorded
trace. ``run.py`` itself has no switch for any of this.
"""
import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}

TINY_CONFIG = {
    'source': 'skypilot_tpu/models/configs.py TINY_QWEN (a test size)',
    'model': {'name': 'tiny-qwen', 'vocab_size': 256, 'dim': 64,
              'n_layers': 2, 'n_heads': 4, 'n_kv_heads': 2, 'ffn_dim': 128,
              'max_seq_len': 128, 'remat': 'none', 'qkv_bias': True},
    'reduced': [], 'assumed': [],
    'deployment': {'chips': 1, 'quantize': 'int8', 'max_batch': 4,
                   'max_seq': 128},
    'training': {'batch': 2, 'mu_dtype': 'float32', 'attn_impl': 'xla',
                 'learning_rate': 1e-3, 'warmup_steps': 2,
                 'total_steps': 100},
}
TINY_CHAT = {
    'runner': 'serve', 'rate_per_s': 4.0,
    'prompt_tokens': {'dist': 'lognormal', 'median': 24, 'sigma': 0.5,
                      'min': 8, 'max': 48},
    'output_tokens': {'dist': 'lognormal', 'median': 8, 'sigma': 0.3,
                      'min': 4, 'max': 12},
    'warmup': {'concurrency': [1, 2, 4], 'horizons': [8, 32]},
}
TINY_TRAIN = {'runner': 'train', 'seq': 64, 'log_every': 2,
              'corpus_bytes': 65536}
TINY_READER = '''
LAYER = 'entry points'
UNIT = 'count'
MOVES = 'ttft_p95_ms'
CELLS = ['tiny.chat']
SOURCE = 'program_counter'


def read(run):
    return len(run['records']['requests'])
'''
# What the child runs in place of ``python3 perfbench/run.py``: the same
# file, after the steering.
STEER = '''
import runpy, sys, gzip
from skypilot_tpu.telemetry import device as device_lib
kind, trace_gz = sys.argv[1], sys.argv[2]
real = device_lib.device_identity
device_lib.device_identity = lambda: dict(
    real(), platform='tpu' if kind else 'cpu', device_kind=kind or 'cpu')
if trace_gz:
    from perfbench import trace
    trace.find_xplane = lambda trace_dir: trace_gz
    trace.reduce_xplane = lambda path: trace.reduce_xspace(
        gzip.open(path).read())
sys.argv = ['perfbench/run.py'] + sys.argv[3:]
runpy.run_path('perfbench/run.py', run_name='__main__')
'''


@pytest.fixture(scope='module')
def copy(tmp_path_factory):
    """BENCHMARK.json and perfbench/ in a directory of their own, with a
    tiny configuration, two mixes, two cells and a reader ADDED."""
    root = tmp_path_factory.mktemp('checkout')
    shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), root)
    shutil.copytree(os.path.join(REPO, 'perfbench'), root / 'perfbench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    before = {p: p.read_bytes() for p in (root / 'perfbench').rglob('*')
              if p.is_file()}
    pb = root / 'perfbench'
    (pb / 'configs' / 'tiny.json').write_text(json.dumps(TINY_CONFIG))
    (pb / 'traffic' / 'tinychat.json').write_text(json.dumps(TINY_CHAT))
    (pb / 'traffic' / 'tinytrain.json').write_text(json.dumps(TINY_TRAIN))
    (pb / 'layer_metrics' / 'tiny_requests.py').write_text(TINY_READER)
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    bench['configs'].append({'name': 'tiny', 'source': 'test',
                             'file': 'perfbench/configs/tiny.json',
                             'reduced': [], 'why': 'test'})
    for name, mix in (('tiny.chat', 'tinychat'), ('tiny.train', 'tinytrain')):
        bench['workloads'].append({'name': name, 'config': 'tiny',
                                   'traffic': mix, 'chips': 1,
                                   'why': 'test'})
    for m in bench['end_to_end']:
        if m['name'] in ('ttft_p95_ms', 'tpot_p95_ms'):
            m['workloads'].append('tiny.chat')
        if m['name'] == 'train_tok_s':
            m['workloads'].append('tiny.train')
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))
    yield root
    after = {p: p.read_bytes() for p in before}
    assert after == before, 'a file that was there was edited'


def run_cell(root, *args, kind='TPU v5 lite', trace_gz=''):
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(root / '.cache'))
    return subprocess.run(
        [sys.executable, '-c', STEER, kind, trace_gz, *args], cwd=root,
        env=env, capture_output=True, text=True, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_added_serving_cell_runs_end_to_end(copy):
    out = last_line(run_cell(copy, '--workload', 'tiny.chat', '--seed',
                             str(2**31 + 11), '--seconds', '4',
                             '--trace', '0'))
    assert set(out) == RESULT_KEYS
    assert set(out['metrics']) == {'ttft_p95_ms', 'tpot_p95_ms', 'setup_s'}
    assert out['attempted'] == 16 and out['failed'] == 0
    assert out['device']['platform'] == 'tpu' and out['device']['count'] == 1
    for m in out['metrics'].values():
        assert set(m) == {'value', 'unit'} and m['value'] > 0


def test_added_reader_is_found_in_the_traced_run(copy):
    gz = os.path.join(HERE, 'data', 'tiny.xplane.pb.gz')
    out = last_line(run_cell(copy, '--workload', 'tiny.chat', '--seed', '3',
                             '--seconds', '4', '--trace', '1',
                             trace_gz=gz))
    assert set(out) == RESULT_KEYS | {'breakdown'}
    assert out['metrics']['tiny_requests'] == {'value': 16.0,
                                               'unit': 'count'}
    assert out['device']['busy_s'] > 0 and out['device']['window_s'] > 0
    assert len(out['breakdown']['device_ops']) <= 10


def test_added_training_cell_runs(copy):
    out = last_line(run_cell(copy, '--workload', 'tiny.train', '--seed',
                             '5', '--seconds', '3', '--trace', '0'))
    assert set(out['metrics']) == {'train_tok_s', 'setup_s'}
    assert out['attempted'] > 0 and out['failed'] == 0


@pytest.mark.parametrize('args, kind', [
    (['--workload', 'no.such.cell'], 'TPU v5 lite'),     # unknown cell
    (['--workload', 'tiny.chat'], ''),                   # no TPU
    (['--workload', 'tiny.chat'], 'TPU v9 imagined'),    # not in peaks.json
])
def test_refusals_exit_nonzero_and_print_no_result(copy, args, kind):
    proc = run_cell(copy, *args, '--seed', '1', '--seconds', '2',
                    '--trace', '0', kind=kind)
    assert proc.returncode != 0
    assert not any(line.startswith('{') for line in
                   proc.stdout.splitlines())


def test_benchmark_json_agrees_with_the_files():
    """Every cell, configuration, mix and per-layer entry names files
    that exist, and each reader says what its entry says."""
    sys.path.insert(0, REPO)
    from perfbench.run import load_module
    with open(os.path.join(REPO, 'BENCHMARK.json'), encoding='utf-8') as f:
        bench = json.load(f)
    e2e = {m['name']: m for m in bench['end_to_end']}
    cells = {w['name'] for w in bench['workloads']}
    for w in bench['workloads']:
        assert os.path.exists(os.path.join(
            REPO, 'perfbench', 'traffic', w['traffic'] + '.json'))
    for c in bench['configs']:
        assert os.path.exists(os.path.join(REPO, c['file']))
    for m in bench['per_layer']:
        mod = load_module(os.path.join(REPO, 'perfbench', 'layer_metrics',
                                       m['name'] + '.py'))
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m['layer'], m['unit'], m['moves'], m['source'])
        assert set(mod.CELLS) == set(m['workloads']) <= cells
        assert set(m['workloads']) <= set(e2e[m['moves']].get(
            'workloads', cells))
