"""Mean device time of one prefill chunk of the looped cell: over the
executions of the ``prefill`` program in the traced part, whatever each
chunk held (``prefill_chunk_ms``'s reduction, for this cell). A chunk is
at most 2 prompts x 256 tokens through all 4 passes: its stacked rows
are 403 MB a prompt."""
import statistics

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['ouro-2.6b.reason']
SOURCE = 'device_trace'


def read(run):
    chunks = run['trace'].programs.get('prefill', [])
    if not chunks:
        return None
    return statistics.fmean(e.duration_s for e in chunks) * 1e3
