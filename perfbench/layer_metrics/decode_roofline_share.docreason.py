"""The least time the chip could take for a decode step of the
``docreason`` cell, as a share of ``decode_step_ms.docreason``: the
bytes it must move over the memory bandwidth. Bytes from shapes
(``roofline_jamba.py``): 6.06 GB of stored weights (the tied embedding
read as the head), each live row's 26 x (327,680 B of state + 30,720 B
of conv tail) read AND written, the live tokens' 1,024 B of cache rows
(the mean of ``kv_pool_tokens_used`` over the window's samples:
page-granular). Live rows are the window's mean, from the engine's
counters. None where the program has no such counters."""
from perfbench import pool_window, roofline_jamba, solar_window

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['jamba2-3b.docreason']
SOURCE = 'device_trace'


def read(run):
    step_ms = pool_window.decode_step_ms(run)
    rows = solar_window.live_rows_mean(run)
    tokens = pool_window.live_tokens_mean(run)
    if not step_ms or rows is None or tokens is None:
        return None
    need = roofline_jamba.decode_step_bytes(run['ctx'].config['model'],
                                            rows, tokens)
    return 100.0 * need / run['ctx'].peak['hbm_bytes_per_s'] / (
        step_ms / 1e3)
