"""``ring_merge_ms.reason`` beside ``ring_merge_ms.longctx`` (whose own
cases ``test_ring_merge_ms.py`` keeps), each against hand-made
executions: the mean of the ``merge_ring_into_pool`` program's
executions, ``None`` where the traced part holds none, and an entry of
``BENCHMARK.json`` that says what the reader says and is read in its own
cell only."""
import json
import os

import pytest

from perfbench import trace
from perfbench.run import layer_readers, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = {'ring_merge_ms.longctx': 'glm-4.7-flash.longctx',
        'ring_merge_ms.reason': 'ouro-2.6b.reason'}


@pytest.fixture(params=list(CELL))
def name(request):
    return request.param


@pytest.fixture
def reader(name):
    return load_module(os.path.join(os.path.dirname(HERE), 'layer_metrics',
                                    name + '.py'))


def reduced(programs):
    return trace.Reduced(window_s=1.0, busy_s=1.0, devices=1,
                         programs=programs, top_ops=[], idle_gaps=[])


def test_mean_over_the_merge_executions(reader):
    run = {'trace': reduced({
        'merge_ring_into_pool': [trace.Execution(0.0548, 0),
                                 trace.Execution(0.0120, 0),
                                 trace.Execution(0.0142, 0)],
        'prefill': [trace.Execution(0.0841, 0)],
        'decode_steps': [trace.Execution(0.294, 8)]})}
    assert abs(reader.read(run) - 27.0) < 1e-9


def test_no_merge_execution_reads_none(reader):
    assert reader.read({'trace': reduced({'prefill': []})}) is None
    assert reader.read({'trace': reduced({})}) is None


def test_declared_as_the_reader_says_and_read_in_its_cell_only(name, reader):
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench['per_layer'] if m['name'] == name]
    assert entry == {'name': name, 'unit': reader.UNIT, 'better': 'lower',
                     'source': reader.SOURCE, 'layer': reader.LAYER,
                     'moves': reader.MOVES, 'workloads': reader.CELLS}
    assert reader.CELLS == [CELL[name]]
    for cell in (w['name'] for w in bench['workloads']):
        assert (name in dict(layer_readers(cell))) == (cell == CELL[name])
