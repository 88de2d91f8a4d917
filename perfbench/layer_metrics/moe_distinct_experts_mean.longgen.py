"""Mean number of distinct HELD experts an expert layer read in a decode
step of the window (``moe_distinct_experts_mean``'s counters, for this
cell). Of 40 held; with B live rows about 40 x (1 - (39/40)^B)."""
from perfbench import solar_window

LAYER = 'model + kernels'
UNIT = 'experts'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return solar_window.distinct_mean(run)
