"""Median device time of one decode step of the ``docreason`` cell: each
execution of the ``decode_steps`` program in the trace, divided by the
steps it fused (the horizon its dispatch was tagged with,
``pool_window.decode_step_ms``). A step is the 28 layers over all 64
padded slots: two GQA layers' paged kernel over one KV head, 26 Mamba
mixers each advancing the live slots' state in place, 28 dense FFNs,
the tied head."""
from perfbench import pool_window

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['jamba2-3b.docreason']
SOURCE = 'device_trace'


def read(run):
    return pool_window.decode_step_ms(run)
