"""Seconds the engine loop spent compiling inside the window: the
difference of ``loop_first_call_seconds_total`` (first calls of a program
key) between the window's two scrapes. Logs the window's whole loop
account. None where the program keeps no loop account."""
from perfbench import loop_account

LAYER = 'engine step'
UNIT = 's'
MOVES = 'tpot_p95_ms'
CELLS = ['qwen2-7b.chat', 'glm-4.7-flash.longctx', 'ouro-2.6b.reason',
         'solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    loop_account.log_account(run)
    return loop_account.cause_delta(run, 'first_call')
