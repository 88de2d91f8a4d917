"""Device time of the selective scan in one prefill chunk: the time the
trace holds under the ``mamba_scan`` scope of ``prefill`` (the Pallas
scan kernel of the 26 Mamba layers) over the ``prefill`` executions
traced. It sits inside ``mamba_prefill_mxu_share``'s scope and is the
part of it no matmul FLOP counts. None where the program has no such
scope (the parent's)."""
from perfbench import scopes

LAYER = 'model + kernels'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['jamba2-3b.docreason']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'prefill', 'mamba_scan')
    chunks = run['trace'].programs.get('prefill', []) if busy else None
    if not chunks:
        return None
    return 1e3 * busy / len(chunks)
