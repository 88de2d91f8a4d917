"""95th percentile of the ``prefill`` stage of the ``longgen`` cell's
time to first token (a request's first chunk dispatched to its last:
up to 8 chunks of 256 tokens, one a decode call once a quarter of the
slots decode), as its chat namesake reads it. None where no request has
passed the stage."""
from perfbench import solar_window

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return solar_window.ttft_stage_p95(run, 'prefill')
