"""95th percentile of the ``first_token_lag`` stage of the ``longgen``
cell's time to first token (the return of the last chunk's dispatch to
the engine's readback of the token it sampled: the async pipeline), as
its chat namesake reads it. None where no request has passed the
stage."""
from perfbench import solar_window

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return solar_window.ttft_stage_p95(run, 'first_token_lag')
