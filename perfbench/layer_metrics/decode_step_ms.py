"""Median device time of one decode step: each execution of the
``decode_steps`` program in the trace, divided by the steps it fused
(the layer-scan loops inside its horizon loop)."""
from perfbench import trace

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'device_trace'


def read(run):
    return trace.per_step_ms(run['trace'], 'decode_steps')
