"""The KDA mixers of the decode program against their byte roofline:
the traced steps x (3 x 275.5 MB of KDA matrices + the mean live rows x
25.2 MB of recurrent state read and written) over the memory bandwidth,
as a share of the device time the trace holds under the ``kda_mix``
scope of ``decode_steps`` (projections, convolution, gates, the
one-step delta rule, output norm and gate, W_o: the scope holds the
projections, so the need counts their bytes). A program that reads or
writes the state of slots that carry no request, or reads it more than
once a step, reads low here. None where the program has no such scope
(the parent's)."""
from perfbench import pool_window, roofline_solar, scopes, solar_window

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'decode_steps', 'kda_mix')
    rows = solar_window.live_rows_mean(run) if busy else None
    steps = pool_window.traced_steps(run) if busy else None
    if rows is None or not steps:
        return None
    ctx = run['ctx']
    need = roofline_solar.kda_decode_bytes(ctx.config['model'], rows)
    return 100.0 * steps * need / ctx.peak['hbm_bytes_per_s'] / busy
