"""Mean device time of one prefill chunk of the ``longgen`` cell: over
the executions of the ``prefill`` program in the traced part, whatever
each chunk held (``prefill_chunk_ms``'s reduction, for this cell). A
chunk runs the chunked delta rule in 3 layers and reads every held
expert of 4."""
import statistics

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    chunks = run['trace'].programs.get('prefill', [])
    if not chunks:
        return None
    return statistics.fmean(e.duration_s for e in chunks) * 1e3
