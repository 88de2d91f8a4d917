"""95th percentile of the ``sched_wait`` stage of the ``longgen`` cell's
time to first token (``RequestScheduler.submit`` to ``engine.add_request``:
the wait for the engine loop to come round, which here is a decode call
of 8 steps over ~11 live rows or a prefill chunk), as its chat namesake
reads it. None where no request has passed the stage."""
from perfbench import solar_window

LAYER = 'scheduler'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return solar_window.ttft_stage_p95(run, 'sched_wait')
