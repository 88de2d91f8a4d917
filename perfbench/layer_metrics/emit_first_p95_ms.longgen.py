"""95th percentile of the ``emit_first`` stage of the ``longgen`` cell's
time to first token (the engine's readback of the first token to the
handler's write of it), as its chat namesake reads it. None where no
request has passed the stage."""
from perfbench import solar_window

LAYER = 'entry points'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return solar_window.ttft_stage_p95(run, 'emit_first')
