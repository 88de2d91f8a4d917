"""Mean number of distinct experts an expert layer read in a decode step
of the window: the device's count (read back with each call's tokens)
over the expert-layer steps, both cumulative counters of ``engine_loop``
in ``/metrics?format=json``, differenced between the window's two ends.
Of 64; a dropless layer reads only these, a dense or capacity layer all.
None where the program has no such counters."""
from perfbench import moe_window

LAYER = 'model + kernels'
UNIT = 'experts'
MOVES = 'tpot_p95_ms'
CELLS = ['glm-4.7-flash.longctx']
SOURCE = 'program_counter'


def read(run):
    steps = moe_window.counter_delta(run, 'moe_layer_steps_total')
    distinct = moe_window.counter_delta(run, 'moe_distinct_experts_total')
    if not steps or distinct is None:
        return None
    return distinct / steps
