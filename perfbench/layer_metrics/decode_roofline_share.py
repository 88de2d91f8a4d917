"""The least time the chip could take for a decode step (the bytes it
must stream, over the memory bandwidth) as a share of ``decode_step_ms``.
Bytes from shapes (``roofline.py``): every weight leaf the step reads, as
stored, plus the stored K/V of the batch's live tokens (the mean of
``kv_pool_tokens_used`` over the window's samples: page-granular)."""
import statistics

from perfbench import roofline, trace

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'device_trace'


def read(run):
    step_ms = trace.per_step_ms(run['trace'], 'decode_steps')
    live = [s['kv_pool_tokens_used'] for s in run['records']['samples']
            if 'error' not in s]
    if step_ms is None or not live:
        return None
    ctx = run['ctx']
    need = roofline.decode_step_bytes(ctx.config['model'],
                                      statistics.fmean(live))
    return 100.0 * need / ctx.peak['hbm_bytes_per_s'] / (step_ms / 1e3)
