"""The KDA mixers of the prefill program against the MXU: the prompt
tokens of the traced chunks (the ``tokens`` key of each
``skytpu:admit_upload`` annotation) x the mixers' matmul FLOPs a token
(``roofline_solar.kda_prefill_flops_per_token``: 2 a matrix parameter of
the 3 mixers, since the scope holds the projections, and the chunked
delta rule's 12.06 MFLOP a layer) over the bf16 peak, as a share of the
device time the trace holds under the ``kda_mix`` scope of ``prefill``.
Padding rows, the delta rule's triangular inverse (float32 products at
the highest precision; its other products take the default one) and
its elementwise decays are work the program does and the need does not
count: the share says what is left to win. None where the
program has no such scope (the parent's)."""
from perfbench import host_plane, roofline_solar, scopes

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'ttft_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'prefill', 'kda_mix')
    data = host_plane.load(run.get('trace_dir')) if busy else None
    if data is None:
        return None
    tokens = sum(int(dict(key).get('tokens', 0)) for _, _, key in
                 host_plane.annotations(data, 'admit_upload'))
    if not tokens:
        return None
    ctx = run['ctx']
    flops = tokens * roofline_solar.kda_prefill_flops_per_token(
        ctx.config['model'])
    return 100.0 * flops / ctx.peak['bf16_flops_per_s'] / busy
