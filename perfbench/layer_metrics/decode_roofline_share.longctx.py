"""The least time the chip could take for a decode step of this model,
as a share of ``decode_step_ms.longctx``: the bytes it must stream over
the memory bandwidth. Bytes from shapes (``roofline_glm.py``): every
weight outside the routed experts, the experts that had a live token
(the window's mean distinct experts a layer step, counted on the
device), and the latent cache rows of the batch's live tokens (the mean
of ``kv_pool_tokens_used`` over the window's samples: page-granular)."""
from perfbench import moe_window, roofline_glm, trace

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['glm-4.7-flash.longctx']
SOURCE = 'device_trace'


def read(run):
    step_ms = trace.per_step_ms(run['trace'], 'decode_steps')
    means = moe_window.window_means(run)
    if step_ms is None or means is None:
        return None
    ctx = run['ctx']
    need = roofline_glm.decode_step_bytes(ctx.config['model'], means[0],
                                          means[1])
    return 100.0 * need / ctx.peak['hbm_bytes_per_s'] / (step_ms / 1e3)
