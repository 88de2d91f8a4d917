"""The least time the chip could take for a decode step of the
``longgen`` cell, as a share of ``decode_step_ms.longgen``: the bytes it
must move over the memory bandwidth. Bytes from shapes
(``roofline_solar.py``): 1.191 GB of weights outside the routed experts,
0.201 GB of head, the distinct held experts a layer step x 31.46 MB x 4
layers, each live row's 12,582,912 B of recurrent state read AND
written, the live tokens' 4,096 B of cache rows (the mean of
``kv_pool_tokens_used`` over the window's samples: page-granular). Live
rows and distinct experts are the window's means, from the engine's
counters. None where the program has no such counters."""
from perfbench import pool_window, roofline_solar, solar_window

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    step_ms = pool_window.decode_step_ms(run)
    need = solar_window.step_need_bytes(run, roofline_solar)
    if not step_ms or need is None:
        return None
    return 100.0 * need / run['ctx'].peak['hbm_bytes_per_s'] / (
        step_ms / 1e3)
