"""Median time from submit to slot assignment, as the server's own
rolling histogram has it at the window's end."""
LAYER = 'scheduler'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'program_counter'


def read(run):
    return run['records']['metrics_end']['queue_wait_ms_median']
