"""The reduction of ``.xplane.pb`` on one small trace recorded on the v5e
(``data/tiny.xplane.pb.gz``: the tiny-qwen paged engine serving one
request of 8 tokens at horizon 4, host tracing off; my chip run, PR 25),
and ``roofline.py`` against numbers worked out by hand."""
import gzip
import json
import os

import pytest

from perfbench import roofline, trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope='module')
def reduced():
    with gzip.open(os.path.join(HERE, 'data', 'tiny.xplane.pb.gz')) as f:
        return trace.reduce_xspace(f.read())


def test_busy_union_and_idle_share(reduced):
    assert reduced.devices == 1
    assert reduced.window_s == pytest.approx(0.017410576, rel=1e-6)
    assert reduced.busy_s == pytest.approx(0.000233117, rel=1e-6)
    # a tiny model leaves the chip idle nearly always
    assert 1 - reduced.busy_s / reduced.window_s == pytest.approx(0.9866,
                                                                 abs=1e-4)


def test_programs_and_their_fused_steps(reduced):
    decode = reduced.programs['decode_steps']
    assert [e.inner_loops for e in decode] == [4, 4]
    assert sum(e.duration_s for e in decode) == pytest.approx(154.03e-6,
                                                              rel=1e-3)
    assert len(reduced.programs['prefill']) == 1
    assert reduced.programs['prefill'][0].inner_loops == 0
    assert trace.per_step_ms(reduced, 'decode_steps') == pytest.approx(
        154.03e-3 / 8, rel=0.05)
    assert trace.per_step_ms(reduced, 'no_such_program') is None


def test_top_operations_are_self_times(reduced):
    assert len(reduced.top_ops) == 10
    # no op is counted inside the loop that runs it: self times sum to
    # no more than the busy time
    assert sum(s for _, s in reduced.top_ops) <= reduced.busy_s * (1 + 1e-9)
    assert reduced.top_ops[0][0].startswith('%while.42')
    # no host plane was recorded, so no gap has a name
    assert [n for n, _ in reduced.idle_gaps] == ['unattributed']


def test_nest_and_union_on_a_hand_made_line():
    ops = [(0, 100, 'outer while('), (10, 40, 'a'), (50, 90, 'inner while('),
           (60, 70, 'b'), (120, 130, 'c')]
    depth, self_ns = trace.nest(ops)
    assert depth == [0, 1, 1, 2, 0]
    assert self_ns == [30, 30, 30, 10, 10]
    assert trace.union([(s, e) for s, e, _ in ops]) == [(0, 100), (120, 130)]
    assert trace.program_name('jit_decode_steps(123)') == 'decode_steps'


def test_roofline_for_qwen2_7b_by_hand():
    with open(os.path.join(HERE, '..', 'configs', 'qwen2-7b.json'),
              encoding='utf-8') as f:
        m = json.load(f)['model']
    d, f_, v, q, kv, layers = 3584, 18944, 152064, 28 * 128, 4 * 128, 28
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f_    # 233,046,016
    assert per_layer == 233046016
    params = layers * per_layer + d * v                    # 7.07 B
    assert roofline.matmul_params(m) == params == 7070285824
    channels = layers * (q + 2 * kv + d + 2 * f_ + d) + v  # bf16 scales
    assert roofline.decode_weight_bytes(m) == (
        params + 2 * channels + (2 * layers + 1) * d * 4
        + layers * (q + 2 * kv) * 4) == 7074704384
    assert roofline.kv_token_bytes(m) == layers * 2 * 4 * (128 + 4) == 29568
    assert roofline.decode_step_bytes(m, 10000) == 7074704384 + 295680000
    pairs = 4096 * 4097 // 2
    assert roofline.attention_flops(m, 4096, 0) == 4 * layers * q * pairs
    assert roofline.prefill_flops(m, 4096, 0) == pytest.approx(
        2 * params * 4096 + 4 * layers * q * pairs) == pytest.approx(
            6.1288e13, rel=1e-4)
    # a piece appended to a context attends to all of it
    assert roofline.attention_flops(m, 256, 1024) == 4 * layers * q * (
        256 * 1024 + 256 * 257 // 2)


def test_train_flops_for_qwen2_5_1_5b_by_hand():
    with open(os.path.join(HERE, '..', 'configs', 'qwen2.5-1.5b.json'),
              encoding='utf-8') as f:
        m = json.load(f)['model']
    d, f_, v, q, kv, layers = 1536, 8960, 151936, 12 * 128, 2 * 128, 28
    params = layers * (2 * d * q + 2 * d * kv + 3 * d * f_) + d * v
    assert roofline.matmul_params(m) == params == 1543569408
    attention = 3 * 4 * layers * q * (2048 * 2049 // 2) / 2048
    assert roofline.train_flops_per_token(m, 2048) == pytest.approx(
        6 * params + attention) == pytest.approx(9.79e9, rel=1e-3)
