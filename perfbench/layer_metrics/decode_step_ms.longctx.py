"""Median device time of one decode step of the long-context cell: each
execution of the ``decode_steps`` program in the trace, divided by the
steps it fused (``decode_step_ms``'s reduction, for this cell)."""
from perfbench import trace

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['glm-4.7-flash.longctx']
SOURCE = 'device_trace'


def read(run):
    return trace.per_step_ms(run['trace'], 'decode_steps')
