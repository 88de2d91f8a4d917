"""``prefill_chunk_ms`` against hand-made executions and a recorded
trace: the mean of the ``prefill`` program's executions, ``None`` where
the traced part holds none."""
import gzip
import os

from perfbench import trace
from perfbench.run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
READER = load_module(os.path.join(os.path.dirname(HERE), 'layer_metrics',
                                  'prefill_chunk_ms.py'))


def reduced(programs):
    return trace.Reduced(window_s=1.0, busy_s=1.0, devices=1,
                         programs=programs, top_ops=[], idle_gaps=[])


def test_mean_over_the_prefill_executions():
    run = {'trace': reduced({
        'prefill': [trace.Execution(0.020, 0), trace.Execution(0.040, 0),
                    trace.Execution(0.033, 0)],
        'decode_steps': [trace.Execution(0.098, 8)]})}
    assert abs(READER.read(run) - 31.0) < 1e-9


def test_no_prefill_execution_reads_none():
    assert READER.read({'trace': reduced({'decode_steps': []})}) is None


def test_recorded_tiny_trace():
    """``data/tiny.xplane.pb.gz`` (my chip run, PR 25) holds one prefill
    chunk of the tiny-qwen paged engine."""
    with gzip.open(os.path.join(HERE, 'data', 'tiny.xplane.pb.gz')) as f:
        tr = trace.reduce_xspace(f.read())
    (chunk,) = tr.programs['prefill']
    assert READER.read({'trace': tr}) == chunk.duration_s * 1e3 > 0
