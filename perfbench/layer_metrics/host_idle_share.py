"""Share of the traced window in which the chip idled while the engine
loop was NOT waiting for work: the complement of the union of device
operations (as ``idle_share.*`` counts it), between the engine thread's
first and last ``skytpu:`` span, less the instants a ``skytpu:wait_work``
span covers, over ``trace.py``'s window. Never above the cell's
``idle_share``. The run log splits the same idle time by the engine
thread's innermost cause (``loop_account.py``). None where the program
has no such span."""
from perfbench import loop_account

LAYER = 'device'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['qwen2-7b.chat', 'glm-4.7-flash.longctx', 'ouro-2.6b.reason',
         'solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    return loop_account.host_idle_share(run)
