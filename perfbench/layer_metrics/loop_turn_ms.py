"""Mean length of a working turn of the engine loop over the window: the
clock not spent waiting for work over the turns that had work
(``clock_s``, ``loop_wait_work_seconds_total`` and ``loop_turns_total`` of
``engine_loop``, differenced between the window's two scrapes). None where
the program keeps no loop account."""
from perfbench import loop_account

LAYER = 'entry points'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat', 'solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return loop_account.turn_ms(run)
