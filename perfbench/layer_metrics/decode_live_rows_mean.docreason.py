"""Mean number of batch rows that carried a request in a decode step of
the window (``decode_live_rows_mean``'s two counters of ``engine_loop``,
for this cell). Each live row is 17.9 MB of recurrent state and conv
tail read and written a step, whatever its context; the program runs 64
padded rows."""
from perfbench import solar_window

LAYER = 'engine step'
UNIT = 'rows'
MOVES = 'tpot_p95_ms'
CELLS = ['jamba2-3b.docreason']
SOURCE = 'program_counter'


def read(run):
    return solar_window.live_rows_mean(run)
