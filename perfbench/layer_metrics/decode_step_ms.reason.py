"""Median device time of one decode step of the looped cell: each
execution of the ``decode_steps`` program in the trace, divided by the
steps it fused. A step is all 4 passes over the 48 layers. The steps are
the horizon its dispatch was tagged with (``pool_window.decode_step_ms``
says why the loops in the trace do not do for this model)."""
from perfbench import pool_window

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['ouro-2.6b.reason']
SOURCE = 'device_trace'


def read(run):
    return pool_window.decode_step_ms(run)
