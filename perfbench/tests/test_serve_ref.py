"""The reference runner, its mix, its weight maker and the expert-model
readers run end to end in a temporary copy, as NEW files beside the
harness's own (``test_harness.py``'s way: a steered device, the recorded
trace), and no file that was there is edited. Then the readers, each on
a made-up run: what they compute from which counter, scope and shape,
and that each returns None, and does not raise, on a run of a program
that has no such counter or scope (the parent's)."""
import json
import os
import shutil
import types

import pytest

import test_harness as th

TINY_GLM = {
    'source': 'skypilot_tpu/models/configs.py TINY_GLM (a test size)',
    'model': {'name': 'tiny-glm', 'vocab_size': 256, 'dim': 64,
              'n_layers': 3, 'n_heads': 4, 'n_kv_heads': 4, 'ffn_dim': 160,
              'max_seq_len': 128, 'remat': 'none', 'dtype': 'float32',
              'norm_eps': 1e-5, 'rope_theta': 500000.0,
              'attn_kind': 'latent', 'q_lora_rank': 48, 'kv_lora_rank': 32,
              'qk_nope_head_dim': 24, 'qk_rope_head_dim': 8,
              'v_head_dim': 16, 'ffn_kind': 'routed_shared',
              'n_dense_layers': 1, 'n_routed_experts': 8,
              'n_experts_per_token': 2, 'n_shared_experts': 1,
              'moe_ffn_dim': 96, 'routed_scaling_factor': 1.8},
    'reduced': [], 'assumed': [],
    'deployment': {'chips': 1, 'quantize': None, 'max_batch': 4,
                   'max_seq': 128},
}
TINY_LONG = {
    'runner': 'serve_ref', 'weights': 'weights_glm',
    'reference': 'glm4_moe_lite', 'rate_per_s': 4.0,
    'prompt_tokens': {'dist': 'lognormal', 'median': 24, 'sigma': 0.5,
                      'min': 8, 'max': 48},
    'output_tokens': {'dist': 'lognormal', 'median': 8, 'sigma': 0.3,
                      'min': 4, 'max': 12},
    'warmup': {'concurrency': [1, 2, 4], 'horizons': [8, 32]},
}
TINY_READER = '''
from perfbench import moe_window
LAYER = 'model + kernels'
UNIT = 'experts'
MOVES = 'tpot_p95_ms'
CELLS = ['tinyglm.long']
SOURCE = 'program_counter'


def read(run):
    means = moe_window.window_means(run)
    return None if means is None else means[0]
'''


@pytest.fixture(scope='module')
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp('checkout')
    shutil.copy(os.path.join(th.REPO, 'BENCHMARK.json'), root)
    shutil.copytree(os.path.join(th.REPO, 'perfbench'), root / 'perfbench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    before = {p: p.read_bytes() for p in (root / 'perfbench').rglob('*')
              if p.is_file()}
    pb = root / 'perfbench'
    (pb / 'configs' / 'tinyglm.json').write_text(json.dumps(TINY_GLM))
    (pb / 'traffic' / 'tinylong.json').write_text(json.dumps(TINY_LONG))
    (pb / 'layer_metrics' / 'tiny_distinct.py').write_text(TINY_READER)
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    bench['configs'].append({'name': 'tinyglm', 'source': 'test',
                             'file': 'perfbench/configs/tinyglm.json',
                             'reduced': [], 'why': 'test'})
    bench['workloads'].append({'name': 'tinyglm.long', 'config': 'tinyglm',
                               'traffic': 'tinylong', 'chips': 1,
                               'why': 'test'})
    for m in bench['end_to_end']:
        if m['name'] in ('ttft_p95_ms', 'tpot_p95_ms'):
            m['workloads'].append('tinyglm.long')
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))
    yield root
    after = {p: p.read_bytes() for p in before}
    assert after == before, 'a file that was there was edited'


def test_reference_cell_runs_end_to_end(copy):
    proc = th.run_cell(copy, '--workload', 'tinyglm.long', '--seed',
                       str(2**31 + 21), '--seconds', '4', '--trace', '0')
    out = th.last_line(proc)
    assert set(out) == th.RESULT_KEYS
    assert set(out['metrics']) == {'ttft_p95_ms', 'tpot_p95_ms', 'setup_s'}
    assert out['correct'] is True
    assert out['attempted'] == 16 and out['failed'] == 0
    engine = next(line for line in proc.stdout.splitlines()
                  if '] engine: ' in line)
    stats = json.loads(engine.split('] engine: ', 1)[1])
    assert stats['decode_impl'] == 'gather'
    assert stats['pool']['kv_token_bytes'] == 3 * (32 + 8) * 4
    assert 'score: ' in proc.stdout and 'mean ' in proc.stdout


def test_traced_run_reads_the_expert_counters(copy):
    gz = os.path.join(th.HERE, 'data', 'tiny.xplane.pb.gz')
    out = th.last_line(th.run_cell(
        copy, '--workload', 'tinyglm.long', '--seed', '3', '--seconds', '4',
        '--trace', '1', trace_gz=gz))
    assert out['correct'] is True
    assert 1.0 <= out['metrics']['tiny_distinct']['value'] <= 8.0


# ------------------------------------------------------------- the readers
def reader(name):
    import sys
    sys.path.insert(0, th.REPO)
    from perfbench.run import load_module
    return load_module(os.path.join(th.REPO, 'perfbench', 'layer_metrics',
                                    name + '.py'))


def made_up_run(counters=True, trace_dir=None):
    """A window of 100 decode steps at 8 live rows reading 26 experts a
    layer step, 40,000 live tokens; the traced part holds 20 steps of 10
    ms; a 3 s trace of a 51 s window."""
    from perfbench import trace
    with open(os.path.join(th.REPO, 'perfbench', 'configs',
                           'glm-4.7-flash.json'), encoding='utf-8') as f:
        config = json.load(f)
    loop = {'clock_s': 0.0}
    end = dict(loop, decode_substeps_total=100, decode_live_rows_total=800,
               moe_layer_steps_total=700, moe_distinct_experts_total=18200,
               moe_assignments_total=22400,
               prefill_attn_pairs_total=1e9) if counters else loop
    start = {k: 0 for k in end} if counters else loop
    reduced = trace.Reduced(
        window_s=3.0, busy_s=2.4, devices=1,
        programs={'decode_steps': [trace.Execution(0.08, 8),
                                   trace.Execution(0.12, 12)],
                  'prefill': [trace.Execution(0.03, 2),
                              trace.Execution(0.05, 2)]},
        top_ops=[], idle_gaps=[])
    return {
        'trace': reduced, 'trace_dir': trace_dir,
        'records': {'metrics_start': {'engine_loop': start},
                    'metrics_end': {'engine_loop': end}, 'seconds': 51.0,
                    'traced': [100.0, 103.0],
                    'samples': [{'kv_pool_tokens_used': 30000},
                                {'kv_pool_tokens_used': 50000},
                                {'error': 'x'}]},
        'ctx': types.SimpleNamespace(
            config=config, peak={'bf16_flops_per_s': 197e12,
                                 'hbm_bytes_per_s': 819e9}),
    }


def test_readers_compute_what_they_say(monkeypatch):
    from perfbench import roofline_glm, scopes
    run = made_up_run()
    model = run['ctx'].config['model']
    assert reader('moe_distinct_experts_mean').read(run) == 26.0
    assert reader('decode_step_ms.longctx').read(run) == 10.0
    assert reader('prefill_chunk_ms.longctx').read(run) == 40.0
    assert round(reader('idle_share.longctx').read(run), 6) == 20.0
    need = roofline_glm.decode_step_bytes(model, 26.0, 40000.0)
    assert reader('decode_roofline_share.longctx').read(run) == \
        pytest.approx(100 * need / 819e9 / 0.010)
    # scopes: 20 traced steps; 0.1 s under moe_experts, 0.05 under
    # mla_attn of decode_steps; 2 s under mla_attn of prefill
    times = {('decode_steps', 'moe_experts'): 0.1,
             ('decode_steps', 'mla_attn'): 0.05,
             ('prefill', 'mla_attn'): 2.0}
    monkeypatch.setattr(scopes, 'of_run',
                        lambda run, prog, scope: times.get((prog, scope)))
    from perfbench import moe_window
    monkeypatch.setattr(moe_window, 'traced_distinct_mean', lambda run: 24.0)
    experts = 20 * roofline_glm.expert_bytes_read(model, 24.0) / 819e9
    assert reader('moe_expert_roofline_share').read(run) == \
        pytest.approx(100 * experts / 0.1)
    mla = 20 * roofline_glm.mla_decode_bytes(model, 40000.0) / 819e9
    assert reader('mla_decode_roofline_share').read(run) == \
        pytest.approx(100 * mla / 0.05)
    from perfbench import host_plane
    monkeypatch.setattr(host_plane, 'load', lambda trace_dir: 'data')
    monkeypatch.setattr(
        host_plane, 'annotations', lambda data, phase: [
            (0, 1, (('pairs', '600000000'),)),
            (2, 3, (('pairs', '400000000'),))] if phase == 'admit_upload'
        else [])
    flops = 1e9 * 20480 * 8
    assert reader('mla_prefill_mxu_share').read(run) == \
        pytest.approx(100 * flops / 197e12 / 2.0)
    for name in ('moe_expert_roofline_share', 'mla_decode_roofline_share',
                 'mla_prefill_mxu_share', 'decode_roofline_share.longctx'):
        assert 0 < reader(name).read(run) < 100


def test_readers_are_silent_on_a_program_without_the_counters():
    """The parent's runs: no expert counter in ``engine_loop``, no scope
    in the trace. Each new reader returns None and does not raise."""
    gz = os.path.join(th.HERE, 'data', 'tiny.xplane.pb.gz')
    from perfbench import scopes
    assert scopes.scope_seconds(gz, 'decode_steps', 'mla_attn') is None
    assert scopes.scope_seconds(gz, 'decode_steps', 'closed_call') > 0
    run = made_up_run(counters=False)
    for name in ('moe_distinct_experts_mean', 'moe_expert_roofline_share',
                 'mla_decode_roofline_share', 'mla_prefill_mxu_share',
                 'decode_roofline_share.longctx'):
        assert reader(name).read(run) is None
