"""The Mamba mixers of the prefill program against the MXU: the prompt
tokens of the traced chunks (the ``tokens`` key of each
``skytpu:admit_upload`` annotation) x the mixers' matmul FLOPs a token
(``roofline_jamba.mamba_prefill_flops_per_token``: 26 x 82.25 MFLOP, 2
a parameter of W_in, W_x, W_dt and W_out) over the bf16 peak, as a
share of the device time the trace holds under the ``mamba_mix`` scope
of ``prefill``. That scope holds the selective scan too (elementwise,
not counted: ``mamba_scan_ms`` shows its time), the conv and the
padding rows: the share says what is left to win. None where the
program has no such scope (the parent's)."""
from perfbench import host_plane, roofline_jamba, scopes

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'ttft_p95_ms'
CELLS = ['jamba2-3b.docreason']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'prefill', 'mamba_mix')
    data = host_plane.load(run.get('trace_dir')) if busy else None
    if data is None:
        return None
    tokens = sum(int(dict(key).get('tokens', 0)) for _, _, key in
                 host_plane.annotations(data, 'admit_upload'))
    if not tokens:
        return None
    ctx = run['ctx']
    flops = tokens * roofline_jamba.mamba_prefill_flops_per_token(
        ctx.config['model'])
    return 100.0 * flops / ctx.peak['bf16_flops_per_s'] / busy
