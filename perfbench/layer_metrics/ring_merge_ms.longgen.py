"""Mean device time of one ring merge of the ``longgen`` cell: over the
executions of the ``merge_ring_into_pool`` program in the traced part,
which writes a decode call's ring rows (1 cache layer x 8 heads a
token; the recurrent state has no ring) into the two pools. None where
the trace holds no such execution."""
import statistics

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    merges = run['trace'].programs.get('merge_ring_into_pool', [])
    if not merges:
        return None
    return statistics.fmean(e.duration_s for e in merges) * 1e3
