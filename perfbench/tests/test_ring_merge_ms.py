"""``ring_merge_ms.longctx`` against hand-made executions: the mean of the
``merge_ring_into_pool`` program's executions, ``None`` where the traced
part holds none, and an entry of ``BENCHMARK.json`` that says what the
reader says."""
import json
import os

from perfbench import trace
from perfbench.run import layer_readers, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = 'ring_merge_ms.longctx'
READER = load_module(os.path.join(os.path.dirname(HERE), 'layer_metrics',
                                  NAME + '.py'))


def reduced(programs):
    return trace.Reduced(window_s=1.0, busy_s=1.0, devices=1,
                         programs=programs, top_ops=[], idle_gaps=[])


def test_mean_over_the_merge_executions():
    run = {'trace': reduced({
        'merge_ring_into_pool': [trace.Execution(0.0081, 0),
                                 trace.Execution(0.0079, 0),
                                 trace.Execution(0.0086, 0)],
        'prefill': [trace.Execution(0.0234, 0)],
        'decode_steps': [trace.Execution(0.025, 8)]})}
    assert abs(READER.read(run) - 8.2) < 1e-9


def test_no_merge_execution_reads_none():
    assert READER.read({'trace': reduced({'prefill': []})}) is None


def test_the_traced_event_name_is_the_program_the_reader_asks_for():
    assert trace.program_name('jit_merge_ring_into_pool(1234567)') == \
        'merge_ring_into_pool'


def test_declared_as_the_reader_says_and_read_in_its_cell_only():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        (entry,) = [m for m in json.load(f)['per_layer']
                    if m['name'] == NAME]
    assert entry == {'name': NAME, 'unit': READER.UNIT, 'better': 'lower',
                     'source': READER.SOURCE, 'layer': READER.LAYER,
                     'moves': READER.MOVES, 'workloads': READER.CELLS}
    assert NAME in dict(layer_readers('glm-4.7-flash.longctx'))
    assert NAME not in dict(layer_readers('qwen2-7b.chat'))
