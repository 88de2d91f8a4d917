"""The prefill program against the MXU: the prompt tokens of the traced
chunks x the model's matmul FLOPs a token (``roofline_ouro``: 4 passes
over the layers' matrices and the head; attention over the context left
out) over the bf16 peak, as a share of the ``prefill`` program's device
time in the trace. The tokens are the engine's count, the one behind
``skytpu_prefill_tokens_total``, read from the ``skytpu:admit_upload``
annotation of each chunk in the trace, so tokens and time are of the
same chunks (but for one at either end of the trace). Padding rows (a
chunk is 64 or 256 wide, whatever it holds) are work the program does
and the need does not count: the share says what is left to win. None
where the trace has no such annotation (the parent's)."""
from perfbench import host_plane, roofline_ouro

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'ttft_p95_ms'
CELLS = ['ouro-2.6b.reason']
SOURCE = 'device_trace'


def read(run):
    busy = sum(e.duration_s
               for e in run['trace'].programs.get('prefill', []))
    data = host_plane.load(run.get('trace_dir')) if busy else None
    if data is None:
        return None
    tokens = sum(int(dict(key).get('tokens', 0)) for _, _, key in
                 host_plane.annotations(data, 'admit_upload'))
    if not tokens:
        return None
    ctx = run['ctx']
    flops = tokens * roofline_ouro.flops_per_token(ctx.config['model'])
    return 100.0 * flops / ctx.peak['bf16_flops_per_s'] / busy
