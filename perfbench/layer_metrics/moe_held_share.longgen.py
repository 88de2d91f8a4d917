"""Share of the live rows' token-to-expert assignments that fell to
experts this chip holds, over the decode steps of the window: the
device's count of held assignments (read back with each call's tokens)
over the assignments (live rows x 8 x expert layers), both cumulative
counters of ``engine_loop``. 40 of 320 held: 12.5 % if the router spreads
evenly. None where the program has no such counters (the parent's)."""
from perfbench import solar_window

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    every = solar_window.counter_delta(run, 'moe_assignments_total')
    held = solar_window.counter_delta(run, 'moe_assignments_held_total')
    if not every or held is None:
        return None
    return 100.0 * held / every
