"""Median host time a step spends in ``next(batches)`` and the
host-to-device hand-over, timed by the runner around its own calls."""
import statistics

LAYER = 'input pipeline'
UNIT = 'ms'
MOVES = 'train_tok_s'
CELLS = ['qwen2.5-1.5b.train']
SOURCE = 'host_clock'


def read(run):
    waits = run['data_wait_s']
    return statistics.median(waits) * 1e3 if waits else None
