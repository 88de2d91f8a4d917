"""The attention blocks of the decode program against their roofline: the
larger of (their matrices + the live tokens' latent cache rows) over the
memory bandwidth and the absorbed form's FLOPs over the bf16 peak, for
the traced steps, as a share of the device time the trace holds under
the ``mla_attn`` scope of ``decode_steps``. An attention that copies the
padded batch's pages before it reads them reads low here."""
from perfbench import moe_window, roofline_glm, scopes

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['glm-4.7-flash.longctx']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'decode_steps', 'mla_attn')
    means = moe_window.window_means(run)
    steps = moe_window.traced_steps(run)
    if not busy or means is None or not steps:
        return None
    ctx, model = run['ctx'], run['ctx'].config['model']
    need_s = max(
        roofline_glm.mla_decode_bytes(model, means[1])
        / ctx.peak['hbm_bytes_per_s'],
        roofline_glm.mla_decode_flops(model, means[1], means[2])
        / ctx.peak['bf16_flops_per_s'])
    return 100.0 * steps * need_s / busy
