"""Mean device time of one prefill chunk: over the executions of the
``prefill`` program in the traced part (``XLA Modules``), whatever each
chunk held. The mix is one fixed schedule, so the chunks of a window are
the same chunks in every run. None where the trace holds no such
execution."""
import statistics

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'tpot_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'device_trace'


def read(run):
    chunks = run['trace'].programs.get('prefill', [])
    if not chunks:
        return None
    return statistics.fmean(e.duration_s for e in chunks) * 1e3
