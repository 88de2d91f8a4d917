"""Share of the ``longgen`` cell's window in which the engine loop held
the engine lock (fill + step, its blocking readback included), as its
chat namesake reads it: the cumulative ``lock_held_seconds_total`` of
``engine_loop`` differenced between the window's two ends, over the
clock both were read on. None where the program has no such block."""
from perfbench import solar_window

LAYER = 'entry points'
UNIT = '%'
MOVES = 'ttft_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    held = solar_window.counter_delta(run, 'lock_held_seconds_total')
    clock = solar_window.counter_delta(run, 'clock_s')
    if held is None or clock is None or clock <= 0:
        return None
    return 100.0 * held / clock
