"""Median time from ``engine.add_request`` to slot assignment in the
``longgen`` cell, as the server's own rolling histogram has it at the
window's end (its chat namesake's number). None where the program does
not report it."""
LAYER = 'scheduler'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    return run['records']['metrics_end'].get('queue_wait_ms_median')
