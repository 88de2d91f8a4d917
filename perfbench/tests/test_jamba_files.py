"""The files AI21-Jamba2-3B's cell brings: the configuration file against
the preset and the catalog, the benchmark's own copy of the reference
against the program's, the on-device weight maker against
``init_params``, the roofline counts against the configuration's
arithmetic, each new reader on a made-up run and on an empty one, the
runner end to end at a small size, and ``correct``'s decision against
wrong mathematics at a small size."""
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

from perfbench import roofline_jamba, weights_jamba  # noqa: E402
from perfbench.reference import jamba as bench_ref  # noqa: E402
from perfbench.run import load_module  # noqa: E402
from skypilot_tpu.models import configs, llama, mamba  # noqa: E402
from skypilot_tpu.models.reference import jamba as prog_ref  # noqa: E402

CELL = 'jamba2-3b.docreason'
READERS = ['decode_step_ms.docreason', 'prefill_chunk_ms.docreason',
           'idle_share.docreason', 'decode_live_rows_mean.docreason',
           'decode_roofline_share.docreason', 'mamba_decode_roofline_share',
           'mamba_prefill_mxu_share', 'mamba_scan_ms']
runner = load_module(os.path.join(th.REPO, 'perfbench', 'runners',
                                  'serve_ref_jamba.py'))


def model_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != 'dtype'}


def load_config():
    with open(os.path.join(th.REPO, 'perfbench', 'configs',
                           'jamba2-3b.json'), encoding='utf-8') as f:
        return json.load(f)


@pytest.fixture(scope='module')
def tiny():
    cfg = dataclasses.replace(configs.TINY_JAMBA,
                              dtype=jnp.dtype('float32'))
    return cfg, weights_jamba.make_tree(cfg, 0, weights_seed=2**31 + 5)


def test_the_configuration_is_the_preset_and_the_catalogs():
    """Every published number at the top level as under ``published``,
    nothing reduced; the ``model`` object is the preset; no width
    differs from the published one."""
    config = load_config()
    assert config['reduced'] == []
    cfg = configs.ModelConfig(**config['model'])
    assert cfg == configs.JAMBA2_3B
    pub = config['published']
    assert {k: config[k] for k in pub} == pub
    assert config['source'] == ('https://huggingface.co/ai21labs/'
                                'AI21-Jamba2-3B/blob/main/config.json')
    m = config['model']
    assert (m['dim'], m['n_layers'], m['n_heads'], m['n_kv_heads'],
            m['ffn_dim'], m['vocab_size'], m['mamba_d_state'],
            m['mamba_d_conv'], m['mamba_expand'], m['mamba_dt_rank'],
            m['norm_eps'], m['tie_embeddings']) == (
        pub['hidden_size'], pub['num_hidden_layers'],
        pub['num_attention_heads'], pub['num_key_value_heads'],
        pub['intermediate_size'], pub['vocab_size'], pub['mamba_d_state'],
        pub['mamba_d_conv'], pub['mamba_expand'], pub['mamba_dt_rank'],
        pub['rms_norm_eps'], pub['tie_word_embeddings'])
    kinds = cfg.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == 'gqa'] == [
        i for i in range(pub['num_hidden_layers'])
        if i % pub['attn_layer_period'] == pub['attn_layer_offset']]
    dep = config['deployment']
    assert (dep['chips'], dep['quantize'], dep['max_batch'],
            dep['max_seq']) == (1, None, 64, 10240)


def test_the_two_copies_of_the_reference_agree(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    a, _ = prog_ref.forward(params, tokens, cfg, q_block=5)
    b, _ = bench_ref.forward(params, tokens, model_dict(cfg), q_block=24,
                             rows=np.arange(24), wrap=jax.jit)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                               atol=2e-5)      # jit reorders float32 sums
    with open(prog_ref.__file__, 'rb') as f, \
            open(bench_ref.__file__, 'rb') as g:
        assert f.read() == g.read()             # one text, two homes


def matrix_fans(cfg):
    """leaf path -> fan-in, of every matrix of the tree."""
    fans = {('embed',): cfg.dim}

    def walk(plan, path):
        for name, spec in plan.items():
            if isinstance(spec, dict):
                walk(spec, path + (name,))
            elif isinstance(spec, tuple):
                fans[path + (name,)] = spec[1]
    walk(mamba.leaf_plan(cfg), ())
    return fans


def test_weights_are_init_params_shaped_seeded_and_drawn_as_assumed():
    cfg = configs.TINY_JAMBA
    tree = weights_jamba.make_tree(cfg, 1, weights_seed=2**31 + 7)
    want = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for got, exp in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert (got.shape, got.dtype) == (exp.shape, exp.dtype)
    # a run's seed is not read; the tree's own seed draws it
    again = weights_jamba.make_tree(cfg, 2, weights_seed=2**31 + 7)
    other = weights_jamba.make_tree(cfg, 1, weights_seed=2**31 + 8)
    fans = matrix_fans(cfg)
    flat = jax.tree_util.tree_leaves_with_path(tree)
    for (path, leaf), same, diff in zip(flat, jax.tree.leaves(again),
                                        jax.tree.leaves(other)):
        name = path[-1].key
        x = np.asarray(leaf, np.float32)
        np.testing.assert_array_equal(x, np.asarray(same, np.float32))
        if name.endswith('norm') or name == 'D':
            assert (x == 1).all(), name
            continue
        if name == 'A_log':             # log(1..N) along the state
            want_a = np.log(np.arange(1, cfg.mamba_d_state + 1))
            np.testing.assert_allclose(x, np.broadcast_to(
                want_a[None, :, None], x.shape), rtol=1e-6)
            continue
        assert not np.array_equal(x, np.asarray(diff, np.float32)), name
        if name == 'dt_bias':           # softplus lands in [0.001, 0.1)
            dt = np.log1p(np.exp(x))
            assert (dt > 0.0009).all() and (dt < 0.1001).all()
        elif name == 'conv_bias':
            assert (np.abs(x) <= 0.5).all() and x.std() > 0.2
        else:
            fan = fans[tuple(k.key for k in path)]
            if x.size >= 2048:
                assert abs(x.std() * math.sqrt(fan) - 1) < 0.1, (name, fan)
    w = np.asarray(tree['mamba_layers']['mamba']['in_proj'], np.float32)
    assert w.shape[0] == 6 and not np.array_equal(w[0], w[1])
    assert 'unembed' not in tree                 # tied


def test_roofline_counts_match_the_configuration_arithmetic():
    config = load_config()
    m, r = config['model'], roofline_jamba
    assert r.mamba_mixer_params(m) == 41_241_792
    assert r.mamba_matrix_params(m) == 41_123_840
    assert 2 * r.mamba_matrix_params(m) == 82_247_680      # 82.25 MFLOP
    assert r.gqa_mixer_params(m) == 13_762_560
    assert r.ffn_params(m) == 62_914_560
    assert r.total_params(m) == 3_029_337_472
    assert (r.gqa_layers(m), r.mamba_layers(m)) == (2, 26)
    assert round(r.weight_bytes(m) / 1e9, 2) == 6.06
    assert round(r.mamba_mixer_bytes(m) / 1e6, 1) == 82.7
    assert r.kv_token_bytes(m) == 1024
    assert (r.state_bytes(m), r.tail_bytes(m)) == (327_680, 30_720)
    assert r.state_slot_bytes(m) == 9_318_400
    assert r.decode_step_bytes(m, 1, 0) - r.decode_step_bytes(m, 0, 0) \
        == 2 * 9_318_400
    assert r.decode_step_bytes(m, 0, 1000) - r.decode_step_bytes(m, 0, 0) \
        == 1_024_000
    assert r.mamba_decode_bytes(m, 2) - r.mamba_decode_bytes(m, 0) \
        == 4 * 9_318_400
    assert r.mamba_prefill_flops_per_token(m) == 26 * 82_247_680
    # the program's own counts agree
    from skypilot_tpu.inference.engine import kv_token_bytes
    cfg = configs.ModelConfig(**m)
    assert cfg.num_params == r.total_params(m)
    assert kv_token_bytes(cfg, 'bf16') == r.kv_token_bytes(m)
    assert cfg.n_recurrent_layers * cfg.state_spec.slot_bytes(2) \
        == r.state_slot_bytes(m)
    tree = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree)) \
        == r.weight_bytes(m)
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        tree['mamba_layers']['mamba'])) == 26 * r.mamba_mixer_bytes(m)


def test_the_mix_is_the_cells():
    with open(os.path.join(th.REPO, 'perfbench', 'traffic',
                           'docreason.json'), encoding='utf-8') as f:
        mix = json.load(f)
    assert (mix['runner'], mix['weights'], mix['reference']) == (
        'serve_ref_jamba', 'weights_jamba', 'jamba')
    assert mix['prompt_tokens'] == {'dist': 'lognormal', 'median': 1536,
                                    'sigma': 0.8, 'min': 128, 'max': 8192}
    assert mix['output_tokens'] == {'dist': 'lognormal', 'median': 512,
                                    'sigma': 0.6, 'min': 64, 'max': 1536}
    assert mix['warmup'] == {'concurrency': [1, 2, 4, 8, 16],
                             'horizons': [8, 32]}
    assert (mix['order_seed'], mix['loop']) == (0, 'open')
    knee = mix['knee']
    assert mix['rate_per_s'] == pytest.approx(0.6 * knee['rate_per_s'])
    config = load_config()
    assert (mix['prompt_tokens']['max'] + mix['output_tokens']['max']
            <= config['deployment']['max_seq'])


# ------------------------------------------------------------- the readers
def reader(name):
    return load_module(os.path.join(th.REPO, 'perfbench', 'layer_metrics',
                                    name + '.py'))


def made_up_run(counters=True):
    """The traced part holds 20 decode steps of 10 ms and two prefill
    chunks of 40 and 60 ms; over the window 1,000 steps carried 30,000
    live rows; the pool held 40,000 and 60,000 tokens; a 3 s trace."""
    from perfbench import trace
    reduced = trace.Reduced(
        window_s=3.0, busy_s=2.4, devices=1,
        programs={'decode_steps': [trace.Execution(0.080, 8),
                                   trace.Execution(0.120, 12)],
                  'prefill': [trace.Execution(0.040, 2),
                              trace.Execution(0.060, 2)]},
        top_ops=[], idle_gaps=[])
    end = ({'decode_substeps_total': 1000, 'decode_live_rows_total': 30000}
           if counters else {})
    return {
        'trace': reduced, 'trace_dir': None,
        'records': {'metrics_start': {'engine_loop': {k: 0 for k in end}},
                    'seconds': 51.0,
                    'metrics_end': {'engine_loop': end},
                    'samples': [{'kv_pool_tokens_used': 40000},
                                {'kv_pool_tokens_used': 60000},
                                {'error': 'x'}] if counters else []},
        'ctx': types.SimpleNamespace(
            cell={'name': CELL}, config=load_config(),
            peak={'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}),
    }


def test_readers_compute_what_they_say(monkeypatch):
    from perfbench import host_plane, scopes
    run = made_up_run()
    m, r = run['ctx'].config['model'], roofline_jamba
    assert reader('decode_step_ms.docreason').read(run) == \
        pytest.approx(10.0)
    assert reader('prefill_chunk_ms.docreason').read(run) == \
        pytest.approx(50.0)
    assert reader('idle_share.docreason').read(run) == pytest.approx(20.0)
    assert reader('decode_live_rows_mean.docreason').read(run) == 30.0
    need = r.decode_step_bytes(m, 30.0, 50000.0)
    assert reader('decode_roofline_share.docreason').read(run) == \
        pytest.approx(100 * need / 819e9 / 0.010)
    monkeypatch.setattr(
        scopes, 'of_run', lambda run, prog, scope:
        {('decode_steps', 'mamba_mix'): 0.09,
         ('prefill', 'mamba_mix'): 0.05,
         ('prefill', 'mamba_scan'): 0.012}.get((prog, scope)))
    want = 20 * r.mamba_decode_bytes(m, 30.0) / 819e9
    assert reader('mamba_decode_roofline_share').read(run) == \
        pytest.approx(100 * want / 0.09)
    assert reader('mamba_scan_ms').read(run) == pytest.approx(6.0)
    monkeypatch.setattr(host_plane, 'load', lambda trace_dir: 'data')
    monkeypatch.setattr(host_plane, 'executions', lambda data, prog: [])
    notes = {'admit_upload': [(0, 1, (('pairs', '9'), ('tokens', '256'))),
                              (2, 3, (('pairs', '9'), ('tokens', '300')))]}
    monkeypatch.setattr(host_plane, 'annotations',
                        lambda data, phase: notes.get(phase, []))
    flops = 556 * r.mamba_prefill_flops_per_token(m)
    assert reader('mamba_prefill_mxu_share').read(run) == \
        pytest.approx(100 * flops / 197e12 / 0.05)
    for name in READERS:
        mod = reader(name)
        assert mod.CELLS == [CELL]
        if mod.UNIT == '%':
            assert 0 < mod.read(run) < 100, name


def test_readers_are_silent_where_there_is_nothing_to_read(monkeypatch):
    """The parent's runs and another cell's: no ``mamba_mix`` or
    ``mamba_scan`` scope, no live-row counters, no samples, no such
    program in the trace. Each reader that needs one returns None and
    does not raise."""
    from perfbench import host_plane, run as run_mod, scopes, trace
    gz = os.path.join(th.HERE, 'data', 'tiny.xplane.pb.gz')
    assert scopes.scope_seconds(gz, 'prefill', 'mamba_scan') is None
    run = made_up_run(counters=False)
    run['trace'] = trace.Reduced(3.0, 2.7, 1, {}, [], [])
    monkeypatch.setattr(host_plane, 'load', lambda trace_dir: 'data')
    monkeypatch.setattr(host_plane, 'executions', lambda data, prog: [])
    monkeypatch.setattr(
        host_plane, 'annotations',
        lambda data, phase: [(0, 1, (('pairs', '9'),))])
    for name in READERS:
        if name != 'idle_share.docreason':
            assert reader(name).read(run) is None, name
    for other in ('qwen2-7b.chat', 'glm-4.7-flash.longctx',
                  'qwen2.5-1.5b.train', 'ouro-2.6b.reason',
                  'solar-open2-250b.longgen'):
        assert not {n for n, _ in run_mod.layer_readers(other)} \
            & set(READERS)
    # a SUBSET: a later PR may bring the cell one more reader
    assert set(READERS) <= {n for n, _ in run_mod.layer_readers(CELL)}


# ------------------------------------------------- the runner, end to end
TINY_CONFIG = {
    'source': 'skypilot_tpu/models/configs.py TINY_JAMBA (a test size)',
    'model': dict(model_dict(configs.TINY_JAMBA), dtype='float32'),
    'reduced': [], 'assumed': [],
    'deployment': {'chips': 1, 'quantize': None, 'max_batch': 4,
                   'max_seq': 128},
}
TINY_DOCREASON = {
    'runner': 'serve_ref_jamba', 'weights': 'weights_jamba',
    'reference': 'jamba', 'rate_per_s': 4.0,
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
    (pb / 'configs' / 'tinyjamba.json').write_text(json.dumps(TINY_CONFIG))
    (pb / 'traffic' / 'tinydocreason.json').write_text(
        json.dumps(TINY_DOCREASON))
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    bench['configs'].append({'name': 'tinyjamba', 'source': 'test',
                             'file': 'perfbench/configs/tinyjamba.json',
                             'reduced': [], 'why': 'test'})
    bench['workloads'].append({'name': 'tinyjamba.docreason',
                               'config': 'tinyjamba',
                               'traffic': 'tinydocreason', 'chips': 1,
                               'why': 'test'})
    for m in bench['end_to_end']:
        if m['name'] in ('ttft_p95_ms', 'tpot_p95_ms'):
            m['workloads'].append('tinyjamba.docreason')
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))
    return root


def test_the_runner_runs_end_to_end_and_says_what_it_keeps(copy):
    proc = th.run_cell(copy, '--workload', 'tinyjamba.docreason', '--seed',
                       str(2**31 + 21), '--seconds', '4', '--trace', '0')
    out = th.last_line(proc)
    assert set(out['metrics']) == {'ttft_p95_ms', 'tpot_p95_ms', 'setup_s'}
    assert out['correct'] is True
    assert out['attempted'] == 16 and out['failed'] == 0
    lines = [json.loads(line.split('] engine: ', 1)[1])
             for line in proc.stdout.splitlines() if '] engine: ' in line]
    facts = next(x for x in lines if 'cache_layers' in x)
    c = configs.TINY_JAMBA
    assert (facts['cache_layers'], facts['recurrent_layers'],
            facts['recurrent_kind']) == (2, 6, 'mamba')
    assert facts['kv_token_bytes'] == 2 * 1 * 32 * 4
    assert facts['state_slot_bytes'] == 6 * c.state_spec.slot_bytes(4)
    assert facts['state_bytes'] == 4 * facts['state_slot_bytes']
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


def _int8_round(tree):
    """Every matrix rounded to 8 bits a column (norms and the scan's
    vectors kept)."""
    fans = matrix_fans(configs.TINY_JAMBA)

    def rt(path, a):
        if tuple(k.key for k in path) not in fans:
            return a
        af = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(af), axis=-2, keepdims=True) / 127.0
        return (jnp.round(af / scale) * scale).astype(a.dtype)
    return jax.tree_util.tree_map_with_path(rt, tree)


def _without_conv_bias(p, a, *, eps, real=bench_ref.mamba):
    return real(dict(p, conv_bias=None), a, eps=eps)


def _rms_but_the_mixers_norms(x, w, eps):
    """``rms_norm``, but the mixer's three small norms (of dt_rank and
    d_state values) are left out."""
    if x.shape[-1] in (configs.TINY_JAMBA.mamba_dt_rank,
                       configs.TINY_JAMBA.mamba_d_state):
        return x
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * bench_ref._f32(w)


VARIANTS = {
    'right': {},
    'state_in_bf16': {'state_dtype': lambda: jnp.bfloat16},
    'no_dt_b_c_norms': {'rms_norm': _rms_but_the_mixers_norms},
    'no_conv_bias': {'mamba': _without_conv_bias},
}
# The deficits' rule at this size: the float32 program reads 0 (every
# served token the reference's argmax); the mildest wrong variant, the
# state rounded to bf16 after every token, a worst deficit of 0.0068
# and a mean of 4.2e-5; the int8 tree 0.15 and 0.0031; the norms or the
# conv bias left out a worst above 4 and a mean above 0.6. The limits
# lie between the right reading and the mildest wrong one. At the
# runner's own deficit limits the bf16 state would pass here: the
# state's own numbers refuse it, at the runner's limits, in the last
# test of this file.
TINY_LIMITS = dict(worst=0.002, mean=1e-5)


@pytest.mark.parametrize('variant', list(VARIANTS) + ['int8_tree'])
def test_correct_refuses_wrong_mathematics(tiny, served, variant):
    """The served tokens scored against the reference hold both limits;
    against a reference with the state kept in bf16, the norms of dt, B
    and C left out or the conv bias left out, or the tree rounded to
    int8, they break at least one."""
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
    holds = runner.solar._deficits_within(deficit, finite, **TINY_LIMITS)
    assert holds == (variant == 'right'), (variant, deficit.max(),
                                           deficit.mean())


def test_correct_sees_the_precision_of_the_recurrent_state(tiny):
    """The state's two numbers of ``correct``, at the RUNNER's limits:
    the state the engine's programs leave a slot (chunked prefill, then
    the decode program a token at a time) is kept in more than bfloat16
    or float16 hold and lies by the reference's after the same tokens.
    The reference that keeps its own state in bfloat16 fails the first;
    a state never written or another request's fails the second."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = tiny
    eng = PagedInferenceEngine(cfg, params=params, max_batch=2,
                               max_seq=256, page_size=8, chunk=16)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 150).tolist()
    tokens, state = runner.serve_for_state(eng, prompt)
    assert len(tokens) == 150 + runner.solar.STATE_STEPS
    assert state.shape == (6, 1, 8, 128)
    model, none = model_dict(cfg), np.zeros(1)
    want = runner.reference_states(bench_ref, params, model, tokens)
    control = runner.reference_states(bench_ref, params, model, tokens,
                                      jnp.bfloat16)
    assert bench_ref.state_dtype() == jnp.float32       # put back
    kept, gap = runner.kept_share(state), runner.relative_gap(state, want)
    assert kept > 0.99 and gap < 1e-4
    assert runner.within_limits(none, True, kept, gap)
    assert runner.kept_share(control) == 0.0
    assert not runner.within_limits(
        none, True, runner.kept_share(control),
        runner.relative_gap(control, want))
    _, other = runner.serve_for_state(
        eng, rng.integers(0, cfg.vocab_size, 150).tolist())
    for wrong in (np.zeros_like(state), other):
        assert runner.relative_gap(wrong, want) >= runner.STATE_GAP
        assert not runner.within_limits(
            none, True, runner.kept_share(other),
            runner.relative_gap(wrong, want))
