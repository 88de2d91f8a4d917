"""Mean device time of one ring merge of the reasoning cell: over the
executions of the ``merge_ring_into_pool`` program in the traced part
(``XLA Modules``), which writes a decode call's ring rows (192 cache
layers x 16 heads a token) into the two pools after every call. None
where the trace holds no such execution."""
import statistics

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['ouro-2.6b.reason']
SOURCE = 'device_trace'


def read(run):
    merges = run['trace'].programs.get('merge_ring_into_pool', [])
    if not merges:
        return None
    return statistics.fmean(e.duration_s for e in merges) * 1e3
