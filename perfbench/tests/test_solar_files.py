"""The files Solar-Open2-250B's cell brings: the benchmark's own copy of
the reference against the program's, the on-device weight maker against
``init_params``, the roofline counts against the configuration's
arithmetic (ISSUE 37), the runner end to end at a small size, each new
reader on a made-up run, and ``correct``'s decision against wrong
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

sys.path.insert(0, th.REPO)

from perfbench import roofline_solar, weights_solar  # noqa: E402
from perfbench.reference import solar_open2 as bench_ref  # noqa: E402
from perfbench.run import load_module  # noqa: E402
from skypilot_tpu.models import configs, llama  # noqa: E402
from skypilot_tpu.models.reference import solar_open2 as prog_ref  # noqa: E402

CELL = 'solar-open2-250b.longgen'
READERS = ['decode_step_ms.longgen', 'prefill_chunk_ms.longgen',
           'ring_merge_ms.longgen', 'idle_share.longgen',
           'decode_live_rows_mean.longgen', 'moe_held_share.longgen',
           'moe_distinct_experts_mean.longgen',
           'moe_expert_roofline_share.longgen',
           'decode_roofline_share.longgen', 'kda_decode_roofline_share',
           'kda_prefill_mxu_share',
           'sched_wait_p95_ms.longgen', 'queue_wait_p50_ms.longgen',
           'prefill_span_p95_ms.longgen', 'first_token_lag_p95_ms.longgen',
           'emit_first_p95_ms.longgen', 'engine_lock_held_share.longgen']
runner = load_module(os.path.join(th.REPO, 'perfbench', 'runners',
                                  'serve_ref_solar.py'))


def model_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != 'dtype'}


@pytest.fixture(scope='module')
def tiny():
    cfg = dataclasses.replace(configs.TINY_SOLAR,
                              dtype=jnp.dtype('float32'))
    return cfg, weights_solar.make_tree(cfg, 0, weights_seed=2**31 + 5)


def test_the_two_copies_of_the_reference_agree(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    a, chosen_a = prog_ref.forward(params, tokens, cfg, q_block=5)
    b, chosen_b = bench_ref.forward(params, tokens, model_dict(cfg),
                                    q_block=24, rows=np.arange(24),
                                    wrap=jax.jit)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                               atol=2e-5)      # jit reorders float32 sums
    assert chosen_a.shape == (8, 24, 2)
    assert np.array_equal(np.asarray(chosen_a), np.asarray(chosen_b))
    with open(prog_ref.__file__, 'rb') as f, \
            open(bench_ref.__file__, 'rb') as g:
        assert f.read() == g.read()             # one text, two homes


def matrix_fans(cfg):
    """leaf path -> fan-in, of every matrix of the tree (``kda.leaf_plan``
    and the two above the stacks)."""
    from skypilot_tpu.models import kda
    fans = {('embed',): cfg.dim, ('unembed',): cfg.dim}

    def walk(plan, path):
        for name, spec in plan.items():
            if isinstance(spec, dict):
                walk(spec, path + (name,))
            elif isinstance(spec, tuple):
                fans[path + (name,)] = spec[1]
    walk(kda.leaf_plan(cfg), ())
    return fans


def key_path(path):
    return tuple(k.key for k in path)


def test_weights_are_init_params_shaped_seeded_and_drawn_as_assumed():
    cfg = configs.TINY_SOLAR
    tree = weights_solar.make_tree(cfg, 1, weights_seed=2**31 + 7)
    want = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for got, exp in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert (got.shape, got.dtype) == (exp.shape, exp.dtype)
    # a run's seed is not read: one tree for every run (the file's
    # docstring says why); the tree's own seed draws it
    again = weights_solar.make_tree(cfg, 2, weights_seed=2**31 + 7)
    other = weights_solar.make_tree(cfg, 1, weights_seed=2**31 + 8)
    a, b = (weights_solar.make_tree(cfg, s) for s in (2**31 + 7, 5))
    assert all(np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    flat = jax.tree_util.tree_leaves_with_path(tree)
    for (path, leaf), same, diff in zip(flat, jax.tree.leaves(again),
                                        jax.tree.leaves(other)):
        name = path[-1].key
        x = np.asarray(leaf, np.float32)
        np.testing.assert_array_equal(x, np.asarray(same, np.float32))
        if name.endswith('norm'):
            assert (x == 1).all()
            continue
        if name == 'g_bias':
            assert (x == 0).all()
            continue
        assert not np.array_equal(x, np.asarray(diff, np.float32))
        if name == 'router_bias':
            assert 0.005 < x.std() < 0.015
        elif name == 'A_log':
            assert (0 <= x).all() and (x < math.log(16)).all()
        elif name == 'dt_bias':         # softplus lands in [0.001, 0.1)
            dt = np.log1p(np.exp(x))
            assert (dt > 0.0009).all() and (dt < 0.1001).all()
        else:
            fan = matrix_fans(cfg)[key_path(path)]
            if x.size >= 2048:
                assert abs(x.std() * math.sqrt(fan) - 1) < 0.1, (name, fan)
    # the layers of a stack differ from each other, the held experts too
    gate = np.asarray(tree['kda_layers']['experts']['w_gate'], np.float32)
    assert gate.shape[:2] == (6, 4)
    assert not np.array_equal(gate[0], gate[1])
    assert not np.array_equal(gate[0, 0], gate[0, 1])
    assert tree['layers']['router'].shape == (2, 64, 16)     # ALL routed
    assert tree['layers']['router'].dtype == jnp.float32


def load_config():
    with open(os.path.join(th.REPO, 'perfbench', 'configs',
                           'solar-open2-250b.json'), encoding='utf-8') as f:
        return json.load(f)


def test_roofline_counts_match_the_configuration_arithmetic():
    config = load_config()
    m, r = config['model'], roofline_solar
    assert r.gqa_mixer_params(m) == 109_051_904
    assert r.kda_mixer_params(m) == 137_740_480
    assert r.layer_ffn_params(m) == 646_193_472
    assert r.gqa_mixer_params(m) + r.layer_ffn_params(m) == 755_245_376
    assert r.kda_mixer_params(m) + r.layer_ffn_params(m) == 783_933_952
    assert r.total_params(m) == 3_308_377_920
    assert round(r.total_params(m) * r.BYTES / 1e9, 2) == 6.62
    assert (r.gqa_layers(m), r.kda_layers(m)) == (1, 3)
    assert r.kv_token_bytes(m) == 4096
    assert r.state_slot_bytes(m) == 12_582_912
    assert r.expert_params(m) * r.BYTES == 31_457_280
    assert round(r.fixed_weight_bytes(m) / 1e9, 3) == 1.191
    assert round(r.head_bytes(m) / 1e9, 3) == 0.201
    assert round(r.kda_weight_bytes(m) / 3 / 1e6, 1) == 275.5
    # 16 live rows, 13.3 experts a layer, ~900 tokens a row: 3.5 GB
    assert round(r.decode_step_bytes(m, 13.3, 16, 16 * 900) / 1e9, 1) == 3.5
    assert r.decode_step_bytes(m, 0, 1, 0) - r.decode_step_bytes(m, 0, 0, 0) \
        == 2 * 12_582_912
    assert r.kda_decode_bytes(m, 2) - r.kda_decode_bytes(m, 0) \
        == 4 * 12_582_912
    assert r.delta_rule_flops_per_token(m) == 12_058_624
    assert r.kda_prefill_flops_per_token(m) == 3 * (
        2 * r.kda_matrix_params(m) + 12_058_624)
    # the program's own counts agree
    from skypilot_tpu.inference.engine import kv_token_bytes
    cfg = configs.ModelConfig(**m)
    assert cfg == configs.SOLAR_OPEN2_250B
    assert cfg.num_params == r.total_params(m)
    assert kv_token_bytes(cfg, 'bf16') == r.kv_token_bytes(m)
    assert cfg.n_recurrent_layers * cfg.state_spec.slot_bytes(2) \
        == r.state_slot_bytes(m) + 442_368
    # the file is the catalog's config but for the three cuts; no width
    # differs from the published one
    assert config['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                                 'vocab_size']
    pub = config['published']
    assert {k for k, v in pub.items() if config[k] != v} \
        == set(config['reduced'])
    assert (config['num_hidden_layers'], config['n_routed_experts'],
            config['vocab_size']) == (4, 40, 24576)
    assert (pub['num_hidden_layers'], pub['n_routed_experts'],
            pub['vocab_size']) == (48, 320, 196608)
    assert '8 chips share each layer' in config['reduced_why']['deployment']
    assert (m['dim'], m['n_heads'], m['n_kv_heads'], m['head_dim_override'],
            m['moe_ffn_dim'], m['n_experts_per_token'],
            m['n_routed_experts'], m['kda_heads'], m['kda_head_dim']) == (
        pub['hidden_size'], pub['num_attention_heads'],
        pub['num_key_value_heads'], pub['head_dim'],
        pub['moe_intermediate_size'], pub['num_experts_per_tok'],
        pub['n_routed_experts'], pub['linear_attn_config']['num_heads'],
        pub['linear_attn_config']['head_dim'])
    dep = config['deployment']
    assert (dep['quantize'], dep['max_batch'], dep['max_seq']) == (
        None, 32, 4096)


def test_the_mix_is_the_issues():
    with open(os.path.join(th.REPO, 'perfbench', 'traffic',
                           'longgen.json'), encoding='utf-8') as f:
        mix = json.load(f)
    assert mix['prompt_tokens'] == {'dist': 'lognormal', 'median': 512,
                                    'sigma': 0.8, 'min': 64, 'max': 2048}
    assert mix['output_tokens'] == {'dist': 'lognormal', 'median': 384,
                                    'sigma': 0.7, 'min': 64, 'max': 1024}
    assert mix['warmup'] == {'concurrency': [1, 2, 4, 8, 16],
                             'horizons': [8, 32]}
    assert (mix['order_seed'], mix['loop']) == (0, 'open')
    knee = mix['knee']
    assert (knee['ttft_limit_ms'], knee['tpot_limit_ms'],
            knee['share_meeting']) == (2000, 100, 0.9)
    assert mix['rate_per_s'] == pytest.approx(0.6 * knee['rate_per_s'])


# ------------------------------------------------------------- the readers
def reader(name):
    return load_module(os.path.join(th.REPO, 'perfbench', 'layer_metrics',
                                    name + '.py'))


def made_up_run(cell=CELL, counters=True):
    """The traced part holds 20 decode steps of 6 ms, two prefill chunks
    of 10 and 14 ms and two ring merges of 0.2 and 0.4 ms; over the
    window 1,000 steps carried 12,000 live rows, whose 384,000
    assignments in 4,000 layer steps found 46,000 held and 40,000
    distinct experts; the pool held 8,000 and 12,000 tokens; a 3 s
    trace."""
    from perfbench import trace
    reduced = trace.Reduced(
        window_s=3.0, busy_s=2.1, devices=1,
        programs={'decode_steps': [trace.Execution(0.048, 8),
                                   trace.Execution(0.072, 12)],
                  'prefill': [trace.Execution(0.010, 2),
                              trace.Execution(0.014, 2)],
                  'merge_ring_into_pool': [trace.Execution(0.0002, 0),
                                           trace.Execution(0.0004, 0)]},
        top_ops=[], idle_gaps=[])
    end = {'decode_substeps_total': 1000, 'decode_live_rows_total': 12000,
           'moe_layer_steps_total': 4000, 'moe_assignments_total': 384000,
           'moe_assignments_held_total': 46000,
           'moe_distinct_experts_total': 40000,
           'lock_held_seconds_total': 40.0, 'clock_s': 50.0,
           } if counters else {}
    stages = {name: {'n': 100, 'p95': 10.0 * (i + 1)} for i, name in
              enumerate(('sched_wait', 'prefill', 'first_token_lag',
                         'emit_first'))}
    stages['emit_first']['n'] = 0           # no request passed it
    host = {'ttft_stages': stages,
            'queue_wait_ms_median': 7.5} if counters else {}
    return {
        'trace': reduced, 'trace_dir': None,
        'records': {'metrics_start': {'engine_loop': {k: 0 for k in end}},
                    'seconds': 51.0,
                    'metrics_end': dict(host, engine_loop=end),
                    'samples': [{'kv_pool_tokens_used': 8000},
                                {'kv_pool_tokens_used': 12000},
                                {'error': 'x'}] if counters else []},
        'ctx': types.SimpleNamespace(
            cell={'name': cell}, config=load_config(),
            peak={'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}),
    }


def test_readers_compute_what_they_say(monkeypatch):
    from perfbench import host_plane, scopes
    run = made_up_run()
    m, r = run['ctx'].config['model'], roofline_solar
    assert reader('decode_step_ms.longgen').read(run) == pytest.approx(6.0)
    assert reader('prefill_chunk_ms.longgen').read(run) == \
        pytest.approx(12.0)
    assert reader('ring_merge_ms.longgen').read(run) == pytest.approx(0.3)
    assert reader('idle_share.longgen').read(run) == pytest.approx(30.0)
    assert reader('decode_live_rows_mean.longgen').read(run) == 12.0
    assert reader('moe_held_share.longgen').read(run) == \
        pytest.approx(100 * 46000 / 384000)
    assert reader('moe_distinct_experts_mean.longgen').read(run) == 10.0
    need = r.decode_step_bytes(m, 10.0, 12.0, 10000.0)
    assert reader('decode_roofline_share.longgen').read(run) == \
        pytest.approx(100 * need / 819e9 / 0.006)
    monkeypatch.setattr(
        scopes, 'of_run', lambda run, prog, scope:
        {('decode_steps', 'kda_mix'): 0.05,
         ('decode_steps', 'moe_experts'): 0.04,
         ('prefill', 'kda_mix'): 0.008}.get((prog, scope)))
    kda = 20 * r.kda_decode_bytes(m, 12.0) / 819e9
    assert reader('kda_decode_roofline_share').read(run) == \
        pytest.approx(100 * kda / 0.05)
    monkeypatch.setattr(host_plane, 'load', lambda trace_dir: 'data')
    monkeypatch.setattr(host_plane, 'executions', lambda data, prog: [])
    notes = {'admit_upload': [(0, 1, (('pairs', '9'), ('tokens', '256'))),
                              (2, 3, (('pairs', '9'), ('tokens', '300')))],
             'moe_readback': [(0, 1, (('distinct', '90'),
                                      ('layer_steps', '8')))]}
    monkeypatch.setattr(host_plane, 'annotations',
                        lambda data, phase: notes.get(phase, []))
    flops = 556 * r.kda_prefill_flops_per_token(m)
    assert reader('kda_prefill_mxu_share').read(run) == \
        pytest.approx(100 * flops / 197e12 / 0.008)
    experts = 20 * r.expert_bytes_read(m, 90 / 8) / 819e9
    assert reader('moe_expert_roofline_share.longgen').read(run) == \
        pytest.approx(100 * experts / 0.04)
    # the host's side of the time to first token, as chat's readers
    assert reader('sched_wait_p95_ms.longgen').read(run) == 10.0
    assert reader('prefill_span_p95_ms.longgen').read(run) == 20.0
    assert reader('first_token_lag_p95_ms.longgen').read(run) == 30.0
    assert reader('emit_first_p95_ms.longgen').read(run) is None
    assert reader('queue_wait_p50_ms.longgen').read(run) == 7.5
    assert reader('engine_lock_held_share.longgen').read(run) == \
        pytest.approx(80.0)
    for name in READERS:
        mod = reader(name)
        assert mod.CELLS == [CELL]
        if mod.UNIT == '%':
            assert 0 < mod.read(run) < 100, name


def test_readers_are_silent_where_there_is_nothing_to_read(monkeypatch):
    """The parent's runs and another cell's: no ``kda_mix`` scope, no
    held-assignment counter, no samples, no such program in the trace.
    Each reader that needs one returns None and does not raise."""
    from perfbench import host_plane, run as run_mod, scopes, trace
    gz = os.path.join(th.HERE, 'data', 'tiny.xplane.pb.gz')
    assert scopes.scope_seconds(gz, 'decode_steps', 'kda_mix') is None
    run = made_up_run(counters=False)
    run['trace'] = trace.Reduced(3.0, 2.7, 1, {}, [], [])
    monkeypatch.setattr(host_plane, 'load', lambda trace_dir: 'data')
    monkeypatch.setattr(host_plane, 'executions', lambda data, prog: [])
    monkeypatch.setattr(
        host_plane, 'annotations',
        lambda data, phase: [(0, 1, (('pairs', '9'),))])
    for name in READERS:
        if name != 'idle_share.longgen':
            assert reader(name).read(run) is None, name
    run = made_up_run()
    del run['records']['metrics_end']['engine_loop'][
        'moe_assignments_held_total']        # a program without the counter
    assert reader('moe_held_share.longgen').read(run) is None
    for other in ('qwen2-7b.chat', 'glm-4.7-flash.longctx',
                  'qwen2.5-1.5b.train', 'ouro-2.6b.reason'):
        assert not {n for n, _ in run_mod.layer_readers(other)} \
            & set(READERS)
    # a SUBSET: a later PR may bring the cell one more reader
    assert set(READERS) <= {n for n, _ in run_mod.layer_readers(CELL)}


# ------------------------------------------------- the runner, end to end
TINY_CONFIG = {
    'source': 'skypilot_tpu/models/configs.py TINY_SOLAR (a test size)',
    'model': dict(model_dict(configs.TINY_SOLAR), dtype='float32'),
    'reduced': [], 'assumed': [],
    'deployment': {'chips': 1, 'quantize': None, 'max_batch': 4,
                   'max_seq': 128},
}
TINY_LONGGEN = {
    'runner': 'serve_ref_solar', 'weights': 'weights_solar',
    'reference': 'solar_open2', 'rate_per_s': 4.0,
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
    (pb / 'configs' / 'tinysolar.json').write_text(json.dumps(TINY_CONFIG))
    (pb / 'traffic' / 'tinylonggen.json').write_text(
        json.dumps(TINY_LONGGEN))
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    bench['configs'].append({'name': 'tinysolar', 'source': 'test',
                             'file': 'perfbench/configs/tinysolar.json',
                             'reduced': [], 'why': 'test'})
    bench['workloads'].append({'name': 'tinysolar.longgen',
                               'config': 'tinysolar',
                               'traffic': 'tinylonggen', 'chips': 1,
                               'why': 'test'})
    for m in bench['end_to_end']:
        if m['name'] in ('ttft_p95_ms', 'tpot_p95_ms'):
            m['workloads'].append('tinysolar.longgen')
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))
    return root


def test_the_runner_runs_end_to_end_and_says_what_it_keeps(copy):
    proc = th.run_cell(copy, '--workload', 'tinysolar.longgen', '--seed',
                       str(2**31 + 21), '--seconds', '4', '--trace', '0')
    out = th.last_line(proc)
    assert set(out['metrics']) == {'ttft_p95_ms', 'tpot_p95_ms', 'setup_s'}
    assert out['correct'] is True
    assert out['attempted'] == 16 and out['failed'] == 0
    lines = [json.loads(line.split('] engine: ', 1)[1])
             for line in proc.stdout.splitlines() if '] engine: ' in line]
    facts = next(x for x in lines if 'cache_layers' in x)
    c = configs.TINY_SOLAR
    assert (facts['cache_layers'], facts['recurrent_layers']) == (2, 6)
    assert facts['kv_token_bytes'] == 2 * 2 * 48 * 4
    assert facts['state_slot_bytes'] == 6 * c.state_spec.slot_bytes(4)
    assert facts['state_bytes'] == 4 * facts['state_slot_bytes']
    assert facts['held_experts'] == 4
    assert facts['params'] == c.num_params
    score = next(line for line in proc.stdout.splitlines()
                 if 'served tokens over contexts' in line)
    assert f'limit {runner.WORST_DEFICIT}' in score
    assert f'limit {runner.MEAN_DEFICIT}' in score
    state = next(line for line in proc.stdout.splitlines()
                 if 'recurrent state after' in line)
    assert f'(limit {runner.STATE_KEPT_SHARE})' in state
    assert f'(limit {runner.STATE_GAP};' in state
    said = state.split('gap to the reference ')[1].split(' ')[0]
    assert float(said) < 1e-4               # float32 at this size
    assert state.rstrip().endswith('within the limits False')


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


def _rope(x, positions, theta=10000.0):
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _int8_round(tree):
    """Every matrix rounded to 8 bits a column (its norms, biases and
    the decay's vectors kept)."""
    def rt(path, a):
        if key_path(path) not in matrix_fans(configs.TINY_SOLAR):
            return a
        af = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(af), axis=-2, keepdims=True) / 127.0
        return (jnp.round(af / scale) * scale).astype(a.dtype)
    return jax.tree_util.tree_map_with_path(rt, tree)


def _decay_per_head(p, a):
    g = VARIANT_REAL['decay'](p, a)
    return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)


VARIANT_REAL = {'decay': bench_ref.decay}
VARIANTS = {
    'right': {},
    'beta_not_doubled': {'write_strength': lambda p, a: jax.nn.sigmoid(
        a @ bench_ref._f32(p['w_beta']))},
    'decay_per_head': {'decay': _decay_per_head},
    'no_conv': {'short_conv': lambda x, w: jax.nn.silu(x * w[-1])},
    'qk_not_normalised': {'qk_normalise': lambda q, k: (
        q * q.shape[-1] ** -0.5, k)},
    'no_output_gate': {'output_gate': lambda o, pre: o},
    'rotary_in_gqa': {'position_encoding': _rope},
    'state_in_bf16': {'state_dtype': lambda: jnp.bfloat16},
}
# The deficits' rule at this size: the float32 program reads 0; the
# mildest wrong variant (the state rounded to bf16 after every token) a
# worst deficit of 0.61 and a mean of 0.0061, every other a mean above
# 0.8 or a logit that is not finite (q and k not normalised: the state
# grows without bound); the limits lie between. At the runner's own
# deficit limits (1.0 / 0.013) the state in bf16 would pass here as it
# does on the chip: the state's own numbers refuse it, at the runner's
# limits, in the last test of this file.
TINY_LIMITS = dict(worst=0.05, mean=0.0005)


@pytest.mark.parametrize('variant', list(VARIANTS) + ['int8_tree'])
def test_correct_refuses_wrong_mathematics(tiny, served, variant):
    """The served tokens scored against the reference hold both limits;
    against a reference with beta not doubled, one decay a head in place
    of one a channel, the convolution left out, q and k not normalised,
    the output gates left out, rotary applied in the GQA layer, the
    state kept in bf16, or the tree rounded to int8, they break at least
    one."""
    cfg, params = tiny
    prompts, outputs = served
    model = model_dict(cfg)
    if variant == 'int8_tree':
        params = _int8_round(params)
    with mock.patch.object(runner.serve_ref, 'PAD_TO', 16), \
            mock.patch.multiple(bench_ref, **(VARIANTS.get(variant) or {
                'rms_norm': bench_ref.rms_norm})):
        scored = [runner.deficits(bench_ref, params, model, p, t)
                  for p, t in zip(prompts, outputs)]
    deficit = np.concatenate([d for d, _ in scored])
    finite = all(ok for _, ok in scored)
    holds = runner._deficits_within(deficit, finite, **TINY_LIMITS)
    assert holds == (variant == 'right'), (variant, deficit.max(),
                                           deficit.mean())


def test_correct_sees_the_precision_of_the_recurrent_state(tiny):
    """The state's two numbers of ``correct``, at the RUNNER's limits:
    the state the engine's programs leave a slot (chunked prefill, then
    the decode kernel a token at a time) is kept in more than bfloat16
    or float16 hold and lies by the reference's after the same tokens.
    The reference that keeps its own state in bfloat16, which is what a
    program with such a state leaves, fails the first; a state never
    written or another request's fails the second; and the decision
    says so each time."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = tiny
    eng = PagedInferenceEngine(cfg, params=params, max_batch=2,
                               max_seq=256, page_size=8, chunk=16)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 150).tolist()
    tokens, state = runner.serve_for_state(eng, prompt)
    assert len(tokens) == 150 + runner.STATE_STEPS
    model, none = model_dict(cfg), np.zeros(1)
    want = runner.reference_states(bench_ref, params, model, tokens)
    control = runner.reference_states(bench_ref, params, model, tokens,
                                      jnp.bfloat16)
    assert bench_ref.state_dtype() == jnp.float32       # put back
    kept, gap = runner.kept_share(state), runner.relative_gap(state, want)
    assert kept > 0.99 and gap < 1e-4
    assert runner.within_limits(none, True, kept, gap)
    # the control: its state holds nothing bfloat16 does not, and lies
    # nearer the reference's than the timed program's does on the chip
    assert runner.kept_share(control) == 0.0
    assert runner.relative_gap(control, want) < runner.STATE_GAP
    assert not runner.within_limits(
        none, True, runner.kept_share(control),
        runner.relative_gap(control, want))
    assert runner.kept_share(state.astype(np.float16)) == 0.0
    # a state that is not this request's
    _, other = runner.serve_for_state(
        eng, rng.integers(0, cfg.vocab_size, 150).tolist())
    for wrong in (np.zeros_like(state), other):
        assert runner.relative_gap(wrong, want) >= 1.0
        assert not runner.within_limits(
            none, True, runner.kept_share(other),
            runner.relative_gap(wrong, want))
