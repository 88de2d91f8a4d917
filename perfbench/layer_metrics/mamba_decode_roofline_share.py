"""The Mamba mixers of the decode program against their byte roofline:
the traced steps x (26 x 82.7 MB of stored mixer weights + the mean live
rows x 26 x 2 x 358,400 B of state and conv tail read and written) over
the memory bandwidth, as a share of the device time the trace holds
under the ``mamba_mix`` scope of ``decode_steps`` (projections, conv,
the three norms, the one-token scan in its kernel, gate, W_out: the
scope holds the projections, so the need counts their bytes). A
program that reads or writes the state of slots that carry no request
reads low here. None where the program has no such scope (the
parent's)."""
from perfbench import pool_window, roofline_jamba, scopes, solar_window

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['jamba2-3b.docreason']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'decode_steps', 'mamba_mix')
    rows = solar_window.live_rows_mean(run) if busy else None
    steps = pool_window.traced_steps(run) if busy else None
    if rows is None or not steps:
        return None
    ctx = run['ctx']
    need = roofline_jamba.mamba_decode_bytes(ctx.config['model'], rows)
    return 100.0 * steps * need / ctx.peak['hbm_bytes_per_s'] / busy
