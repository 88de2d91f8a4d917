"""The attention blocks of the prefill program against the MXU: the
query-key pairs under the causal mask that the traced chunks needed, at
the expanded form's 20,480 FLOPs a pair a layer, over the bf16 peak, as
a share of the device time under the ``mla_attn`` scope of ``prefill``.
The pairs are the engine's count, the one behind
``skytpu_prefill_attn_pairs_total``, read from the ``skytpu:admit_upload``
annotation of each chunk in the trace, so pairs and time are of the same
chunks (but for one at either end of the trace). The absorbed form the
program takes spends 2.1 times these FLOPs a pair, and the scope holds
the projections too: the share says what is left to win. None where the
trace has no such annotation or scope."""
from perfbench import host_plane, roofline_glm, scopes

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'ttft_p95_ms'
CELLS = ['glm-4.7-flash.longctx']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'prefill', 'mla_attn')
    data = host_plane.load(run.get('trace_dir')) if busy else None
    if data is None:
        return None
    pairs = sum(int(dict(key).get('pairs', 0)) for _, _, key in
                host_plane.annotations(data, 'admit_upload'))
    if not pairs:
        return None
    ctx, model = run['ctx'], run['ctx'].config['model']
    flops = pairs * roofline_glm.prefill_pair_flops(model) * model['n_layers']
    return 100.0 * flops / ctx.peak['bf16_flops_per_s'] / busy
