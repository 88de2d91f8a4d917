"""Share of the window in which the engine loop waited for work: the
difference of ``loop_wait_work_seconds_total`` between the scrapes at the
window's two ends over the difference of the clock both were read on
(``engine_loop`` of ``/metrics?format=json``). At a fixed offered rate,
more waiting is the same work done in less time. None where the program
keeps no loop account."""
from perfbench import loop_account

LAYER = 'entry points'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['glm-4.7-flash.longctx', 'solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return loop_account.wait_work_share(run)
