"""Mean number of batch rows that carried a request in a decode step of
the window (``decode_live_rows_mean``'s two counters of ``engine_loop``,
for this cell). Each live row is 25.2 MB of recurrent state read and
written a step, whatever its context; the program runs 32 padded rows."""
from perfbench import solar_window

LAYER = 'engine step'
UNIT = 'rows'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return solar_window.live_rows_mean(run)
