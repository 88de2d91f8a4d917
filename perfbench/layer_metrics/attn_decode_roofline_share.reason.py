"""The attention blocks of the decode program against their roofline:
(4 passes x 48 layers of q/k/v/o matrices + the live tokens' cache rows
at 1,572,864 B a token) over the memory bandwidth, for the traced steps,
as a share of the device time the trace holds under the ``gqa_attn``
scope of ``decode_steps`` (projections, RoPE, the paged kernel, the ring
merge, the output projection and its norm). The scope's projections are
counted in the need, so the share is the paged kernel's with its
surroundings: a kernel that reads pages it does not need, or a merge
that bounces partials through memory, reads low here. None where the
program has no such scope (the parent's)."""
from perfbench import pool_window, roofline_ouro, scopes

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['ouro-2.6b.reason']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'decode_steps', 'gqa_attn')
    live = pool_window.live_tokens_mean(run)
    steps = pool_window.traced_steps(run)
    if not busy or live is None or not steps:
        return None
    ctx = run['ctx']
    need = roofline_ouro.attn_decode_bytes(ctx.config['model'], live)
    return 100.0 * steps * need / ctx.peak['hbm_bytes_per_s'] / busy
