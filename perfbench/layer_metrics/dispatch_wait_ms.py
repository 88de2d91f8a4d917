"""Host time a working turn of the engine loop spent handing work to the
runtime over the window: the self seconds of the ``dispatch`` (a jitted
program called), ``upload`` (a host-to-device copy) and ``eager`` (a
device op outside the jitted programs) causes of ``engine_loop``,
differenced between the window's two scrapes, over the working turns.
None where the program keeps no loop account."""
from perfbench import loop_account

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat', 'solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return loop_account.dispatch_wait_ms(run)
