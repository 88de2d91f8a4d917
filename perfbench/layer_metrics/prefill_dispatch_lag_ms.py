"""Median time a prefill chunk waits on the device's queue: over the prefill
executions of the traced part, the device start of the execution (``XLA
Modules``) less the end of the ``skytpu:prefill_chunk`` annotation that
dispatched it, both on the trace's one clock (``host_plane.py`` pairs them
in dispatch order). None where the trace holds no such annotation, or the
pairing is void."""
from perfbench import host_plane

LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'device_trace'


def read(run):
    data = host_plane.load(run.get('trace_dir'))
    if data is None:
        return None
    return host_plane.dispatch_lag_ms(data, 'prefill_chunk', 'prefill')
