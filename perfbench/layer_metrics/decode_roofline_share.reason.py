"""The least time the chip could take for a decode step of the looped
model, as a share of ``decode_step_ms.reason``: the bytes it must stream
over the memory bandwidth. Bytes from shapes (``roofline_ouro.py``): the
48 layers' matrices once a pass, 4 passes, the output head, and the
cache rows of the batch's live tokens (the mean of
``kv_pool_tokens_used`` over the window's samples: page-granular, so a
little high) at 1,572,864 B a token."""
from perfbench import pool_window, roofline_ouro

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['ouro-2.6b.reason']
SOURCE = 'device_trace'


def read(run):
    step_ms = pool_window.decode_step_ms(run)
    live = pool_window.live_tokens_mean(run)
    if step_ms is None or live is None:
        return None
    ctx = run['ctx']
    need = roofline_ouro.decode_step_bytes(ctx.config['model'], live)
    return 100.0 * need / ctx.peak['hbm_bytes_per_s'] / (step_ms / 1e3)
