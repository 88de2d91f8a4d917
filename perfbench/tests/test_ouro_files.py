"""The files Ouro-2.6B's cell brings: the benchmark's own copy of the
reference against the program's, the on-device weight maker against
``init_params``, the roofline counts against the configuration's
arithmetic (ISSUE 35), the runner end to end at a small size, each new
reader on a made-up run, and ``correct``'s decision against five wrong
mathematics at a small size."""
import dataclasses
import json
import math
import os
import shutil
import sys
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_harness as th
from test_glm_files import _int8_round

sys.path.insert(0, th.REPO)

from perfbench import roofline_ouro, weights_ouro  # noqa: E402
from perfbench.reference import ouro as bench_ref  # noqa: E402
from perfbench.run import load_module  # noqa: E402
from skypilot_tpu.models import configs, llama  # noqa: E402
from skypilot_tpu.models.reference import ouro as prog_ref  # noqa: E402

CELL = 'ouro-2.6b.reason'
READERS = ['decode_step_ms.reason', 'decode_roofline_share.reason',
           'attn_decode_roofline_share.reason', 'prefill_chunk_ms.reason',
           'prefill_mxu_share.reason', 'pool_used_share.reason',
           'idle_share.reason']
runner = load_module(os.path.join(th.REPO, 'perfbench', 'runners',
                                  'serve_ref_ouro.py'))


def model_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != 'dtype'}


@pytest.fixture(scope='module')
def tiny():
    cfg = dataclasses.replace(configs.TINY_OURO, dtype=jnp.dtype('float32'))
    return cfg, weights_ouro.make_tree(cfg, 2**31 + 5)


def test_the_two_copies_of_the_reference_agree(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    a, pdf_a = prog_ref.forward(params, tokens, cfg, q_block=5)
    b, pdf_b = bench_ref.forward(params, tokens, model_dict(cfg),
                                 q_block=24, rows=np.arange(24),
                                 wrap=jax.jit)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                               atol=2e-5)      # jit reorders float32 sums
    np.testing.assert_allclose(np.asarray(pdf_a), np.asarray(pdf_b),
                               rtol=0, atol=2e-6)
    assert pdf_a.shape == (3, 24)
    with open(prog_ref.__file__, 'rb') as f, \
            open(bench_ref.__file__, 'rb') as g:
        assert f.read() == g.read()             # one text, two homes


def test_weights_are_init_params_shaped_seeded_and_fan_in_scaled():
    cfg = configs.TINY_OURO
    tree = weights_ouro.make_tree(cfg, 2**31 + 7)
    want = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for got, exp in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert (got.shape, got.dtype) == (exp.shape, exp.dtype)
    again = weights_ouro.make_tree(cfg, 2**31 + 7)
    other = weights_ouro.make_tree(cfg, 2**31 + 8)
    flat = jax.tree_util.tree_leaves_with_path(tree)
    for (path, leaf), same, diff in zip(flat, jax.tree.leaves(again),
                                        jax.tree.leaves(other)):
        name = path[-1].key
        x = np.asarray(leaf, np.float32)
        np.testing.assert_array_equal(x, np.asarray(same, np.float32))
        if name.endswith('norm'):
            assert (x == 1).all()
            continue
        if name == 'b':
            assert (x == 0).all()
            continue
        assert not np.array_equal(x, np.asarray(diff, np.float32))
        if name == 'w':                 # 64 values: no std to speak of
            continue
        layer_shape = leaf.shape[1:] if path[0].key == 'layers' \
            else leaf.shape
        fan = weights_ouro.fan_in(name, layer_shape)
        assert abs(x.std() * math.sqrt(fan) - 1) < 0.1, (name, fan)
    # the layers of a stack differ from each other
    gate = np.asarray(tree['layers']['w_gate'], np.float32)
    assert not np.array_equal(gate[0], gate[1])
    assert {'attn_post_norm', 'ffn_post_norm'} <= set(tree['layers'])
    assert set(tree['exit_gate']) == {'w', 'b'}


def test_roofline_counts_match_the_configuration_arithmetic():
    with open(os.path.join(th.REPO, 'perfbench', 'configs',
                           'ouro-2.6b.json'), encoding='utf-8') as f:
        config = json.load(f)
    m, r = config['model'], roofline_ouro
    assert r.attn_params(m) == 4 * 2048 * 2048
    assert r.layer_matrix_params(m) + r.norm_params(m) == (
        4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048) == 51_388_416
    assert r.total_params(m) == 2_667_974_657
    assert round(r.total_params(m) * r.BYTES / 1e9, 2) == 5.34
    assert r.cache_layers(m) == 192
    assert r.kv_token_bytes(m) == 192 * 16 * 256 * 2 == 1_572_864
    assert r.kv_token_bytes(m) * 128 == 201_326_592       # a page: 201 MB
    assert round(r.decode_step_bytes(m, 0) / 1e9, 1) == 19.9
    assert round(r.decode_step_bytes(m, 0) / 819e9 * 1e3, 1) == 24.3
    assert r.decode_step_bytes(m, 345) - r.decode_step_bytes(m, 0) \
        == 345 * 1_572_864
    assert round(r.flops_per_token(m) / 1e9, 1) == 19.9
    assert round(256 * r.flops_per_token(m) / 197e12 * 1e3, 1) == 25.9
    assert round(r.attn_decode_bytes(m, 0) / 1e9, 2) == 6.44
    # the program's own counts agree
    cfg = configs.ModelConfig(**m)
    assert cfg.num_params == r.total_params(m)
    assert cfg.kv_spec.row_values * cfg.n_cache_layers * 2 \
        == r.kv_token_bytes(m)
    assert cfg == dataclasses.replace(configs.OURO_2_6B)
    # the file is the catalog's config, nothing cut
    assert config['reduced'] == []
    assert all(config[k] == v for k, v in config['published'].items())
    assert (config['total_ut_steps'], config['num_hidden_layers']) == (4, 48)
    assert config['deployment']['quantize'] is None
    assert (config['deployment']['max_batch'],
            config['deployment']['max_seq']) == (16, 2048)


# ------------------------------------------------------------- the readers
def reader(name):
    return load_module(os.path.join(th.REPO, 'perfbench', 'layer_metrics',
                                    name + '.py'))


def made_up_run(cell=CELL, samples=True):
    """The traced part holds 20 decode steps of 40 ms and two prefill
    chunks of 50 and 70 ms; the pool held 2,000 and 3,000 tokens of
    5,120; a 3 s trace."""
    from perfbench import trace
    with open(os.path.join(th.REPO, 'perfbench', 'configs',
                           'ouro-2.6b.json'), encoding='utf-8') as f:
        config = json.load(f)
    reduced = trace.Reduced(
        window_s=3.0, busy_s=2.7, devices=1,
        programs={'decode_steps': [trace.Execution(0.32, 8),
                                   trace.Execution(0.48, 12)],
                  'prefill': [trace.Execution(0.05, 2),
                              trace.Execution(0.07, 2)]},
        top_ops=[], idle_gaps=[])
    return {
        'trace': reduced, 'trace_dir': None,
        'records': {'metrics_start': {}, 'seconds': 51.0,
                    'metrics_end': {'kv_pool_token_capacity': 5120},
                    'samples': [{'kv_pool_tokens_used': 2000},
                                {'kv_pool_tokens_used': 3000},
                                {'error': 'x'}] if samples else []},
        'ctx': types.SimpleNamespace(
            cell={'name': cell}, config=config,
            peak={'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}),
    }


def test_readers_compute_what_they_say(monkeypatch):
    from perfbench import host_plane, scopes
    run = made_up_run()
    m = run['ctx'].config['model']
    assert reader('decode_step_ms.reason').read(run) == 40.0
    assert reader('prefill_chunk_ms.reason').read(run) == pytest.approx(60.0)
    assert reader('idle_share.reason').read(run) == pytest.approx(10.0)
    assert reader('pool_used_share.reason').read(run) == \
        pytest.approx(100 * 3000 / 5120)
    need = roofline_ouro.decode_step_bytes(m, 2500.0)
    assert reader('decode_roofline_share.reason').read(run) == \
        pytest.approx(100 * need / 819e9 / 0.040)
    monkeypatch.setattr(
        scopes, 'of_run', lambda run, prog, scope:
        {('decode_steps', 'gqa_attn'): 0.4}.get((prog, scope)))
    attn = 20 * roofline_ouro.attn_decode_bytes(m, 2500.0) / 819e9
    assert reader('attn_decode_roofline_share.reason').read(run) == \
        pytest.approx(100 * attn / 0.4)
    monkeypatch.setattr(host_plane, 'load', lambda trace_dir: 'data')
    monkeypatch.setattr(host_plane, 'executions', lambda data, prog: [])
    ms = 1e6
    notes = {'admit_upload': [(0, 1, (('pairs', '9'), ('tokens', '256'))),
                              (2, 3, (('pairs', '9'), ('tokens', '300')))]}
    monkeypatch.setattr(host_plane, 'annotations',
                        lambda data, phase: notes.get(phase, []))
    flops = 556 * roofline_ouro.flops_per_token(m)
    assert reader('prefill_mxu_share.reason').read(run) == \
        pytest.approx(100 * flops / 197e12 / 0.12)
    # The steps of a call are its dispatch's horizon, not the loops the
    # trace shows inside it (8 and 12 above): three calls of 8, 2 and 2
    # steps, the first execution dispatched before the trace began.
    notes['decode_enqueue'] = [
        (10 * ms, 12 * ms, (('horizon', '8'), ('pages', '4'))),
        (300 * ms, 302 * ms, (('horizon', '2'), ('pages', '4'))),
        (380 * ms, 382 * ms, (('horizon', '2'), ('pages', '4')))]
    monkeypatch.setattr(host_plane, 'executions', lambda data, prog: [
        (0, 5 * ms, 'jit_decode_steps(1)'),
        (11 * ms, 331 * ms, 'jit_decode_steps(1)'),
        (331 * ms, 413 * ms, 'jit_decode_steps(2)'),
        (413 * ms, 491 * ms, 'jit_decode_steps(2)')])
    assert reader('decode_step_ms.reason').read(run) == pytest.approx(40.0)
    for name in READERS:
        mod = reader(name)
        assert mod.CELLS == [CELL]
        if mod.UNIT == '%':
            assert 0 < mod.read(run) < 100, name


def test_readers_are_silent_where_there_is_nothing_to_read(monkeypatch):
    """The parent's runs and another cell's: no ``gqa_attn`` scope, no
    ``tokens`` on the upload's annotation, no samples, no such program in
    the trace. Each reader that needs one returns None and does not
    raise; ``run.py`` hands a reader only the cells it lists."""
    from perfbench import host_plane, run as run_mod, scopes, trace
    gz = os.path.join(th.HERE, 'data', 'tiny.xplane.pb.gz')
    assert scopes.scope_seconds(gz, 'decode_steps', 'gqa_attn') is None
    run = made_up_run(samples=False)
    run['trace'] = trace.Reduced(3.0, 2.7, 1, {}, [], [])
    monkeypatch.setattr(host_plane, 'load', lambda trace_dir: 'data')
    monkeypatch.setattr(host_plane, 'executions', lambda data, prog: [])
    monkeypatch.setattr(
        host_plane, 'annotations',
        lambda data, phase: [(0, 1, (('pairs', '9'),))])
    for name in READERS:
        if name != 'idle_share.reason':
            assert reader(name).read(run) is None, name
    run = made_up_run()         # chunks traced, their tokens not annotated
    assert reader('prefill_mxu_share.reason').read(run) is None
    for other in ('qwen2-7b.chat', 'glm-4.7-flash.longctx',
                  'qwen2.5-1.5b.train'):
        assert not {n for n, _ in run_mod.layer_readers(other)} \
            & set(READERS)
    assert {n for n, _ in run_mod.layer_readers(CELL)} == set(READERS)


# ------------------------------------------------- the runner, end to end
TINY_CONFIG = {
    'source': 'skypilot_tpu/models/configs.py TINY_OURO (a test size)',
    'model': dict(model_dict(configs.TINY_OURO), dtype='float32'),
    'reduced': [], 'assumed': [],
    'deployment': {'chips': 1, 'quantize': None, 'max_batch': 4,
                   'max_seq': 128},
}
TINY_REASON = {
    'runner': 'serve_ref_ouro', 'weights': 'weights_ouro',
    'reference': 'ouro', 'rate_per_s': 4.0,
    'prompt_tokens': {'dist': 'lognormal', 'median': 24, 'sigma': 0.5,
                      'min': 8, 'max': 48},
    'output_tokens': {'dist': 'lognormal', 'median': 8, 'sigma': 0.3,
                      'min': 4, 'max': 12},
    'warmup': {'concurrency': [1, 2, 4], 'horizons': [8, 32]},
}


@pytest.fixture(scope='module')
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp('checkout')
    shutil.copy(os.path.join(th.REPO, 'BENCHMARK.json'), root)
    shutil.copytree(os.path.join(th.REPO, 'perfbench'), root / 'perfbench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    pb = root / 'perfbench'
    (pb / 'configs' / 'tinyouro.json').write_text(json.dumps(TINY_CONFIG))
    (pb / 'traffic' / 'tinyreason.json').write_text(json.dumps(TINY_REASON))
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    bench['configs'].append({'name': 'tinyouro', 'source': 'test',
                             'file': 'perfbench/configs/tinyouro.json',
                             'reduced': [], 'why': 'test'})
    bench['workloads'].append({'name': 'tinyouro.reason',
                               'config': 'tinyouro',
                               'traffic': 'tinyreason', 'chips': 1,
                               'why': 'test'})
    for m in bench['end_to_end']:
        if m['name'] in ('ttft_p95_ms', 'tpot_p95_ms'):
            m['workloads'].append('tinyouro.reason')
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))
    return root


def test_the_runner_runs_end_to_end_and_says_what_a_token_is(copy):
    proc = th.run_cell(copy, '--workload', 'tinyouro.reason', '--seed',
                       str(2**31 + 21), '--seconds', '4', '--trace', '0')
    out = th.last_line(proc)
    assert set(out['metrics']) == {'ttft_p95_ms', 'tpot_p95_ms', 'setup_s'}
    assert out['correct'] is True
    assert out['attempted'] == 16 and out['failed'] == 0
    lines = [json.loads(line.split('] engine: ', 1)[1])
             for line in proc.stdout.splitlines() if '] engine: ' in line]
    facts = next(x for x in lines if 'cache_layers' in x)
    assert facts['cache_layers'] == 6
    assert facts['kv_token_bytes'] == 6 * 2 * 48 * 4
    assert facts['pool_tokens'] == (facts['pool_pages'] - 1) * 16
    assert facts['params'] == configs.TINY_OURO.num_params
    score = next(line for line in proc.stdout.splitlines()
                 if 'served tokens over contexts' in line)
    assert f'limit {runner.WORST_DEFICIT}' in score
    assert f'limit {runner.MEAN_DEFICIT}' in score


# ---------------------------------------------------------------- correct
@pytest.fixture(scope='module')
def served(tiny):
    """What the program serves at the small size: greedy tokens of four
    prompts through the paged engine, in one batch."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = tiny
    eng = PagedInferenceEngine(cfg, params=params, max_batch=4,
                               max_seq=128, page_size=8, chunk=16)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (30, 9, 50, 21)]
    ids = [eng.add_request(p, max_new_tokens=40) for p in prompts]
    done = eng.run_to_completion(horizon=4)
    return prompts, [done[i].output for i in ids]


# The runner's rule at this size: the float32 program reads 0 and the
# mildest wrong mathematics (int8 weights) a worst deficit of 0.045 and a
# mean of 0.0009; the limits lie between.
TINY_LIMITS = dict(worst=0.02, mean=0.0004)


@pytest.mark.parametrize('variant', [
    'right', 'int8_tree', 'one_pass_fewer', 'no_post_norms',
    'final_norm_after_last_pass_only', 'previous_pass_cache'])
def test_correct_refuses_wrong_mathematics(tiny, served, variant):
    """The served tokens scored against the reference hold both limits;
    against a reference that rounds the tree to int8, runs one pass
    fewer, leaves out the post-branch norms, norms only after the last
    pass, or attends to the previous pass's keys and values, they break
    at least one."""
    cfg, params = tiny
    prompts, outputs = served
    model, patches = model_dict(cfg), {'rms_norm': bench_ref.rms_norm}
    if variant == 'int8_tree':
        params = _int8_round(params)
    elif variant == 'one_pass_fewer':
        model = dict(model, n_loops=cfg.n_loops - 1)
    elif variant == 'no_post_norms':
        patches['post_norm'] = lambda x, w, eps: x
    elif variant == 'final_norm_after_last_pass_only':
        patches['pass_norm'] = lambda x, w, eps, t, n: (
            bench_ref.rms_norm(x, w, eps) if t == n - 1 else x)
    elif variant == 'previous_pass_cache':
        patches['kv_pass'] = lambda t: max(t - 1, 0)
    with mock.patch.object(runner.serve_ref, 'PAD_TO', 16), \
            mock.patch.multiple(bench_ref, **patches):
        scored = [runner.deficits(bench_ref, params, model, p, t)
                  for p, t in zip(prompts, outputs)]
    deficit = np.concatenate([d for d, _ in scored])
    finite = all(ok for _, ok in scored)
    holds = runner.serve_ref.within_limits.func(deficit, finite,
                                                **TINY_LIMITS)
    assert holds == (variant == 'right'), (variant, deficit.max(),
                                           deficit.mean())
