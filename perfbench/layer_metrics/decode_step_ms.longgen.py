"""Median device time of one decode step of the ``longgen`` cell: each
execution of the ``decode_steps`` program in the trace, divided by the
steps it fused (the horizon its dispatch was tagged with,
``pool_window.decode_step_ms``). A step is the 4 layers of the period
over all 32 padded slots: the GQA layer's paged kernel, three KDA
mixers each reading and writing the slots' state, four routed FFNs."""
from perfbench import pool_window

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    return pool_window.decode_step_ms(run)
