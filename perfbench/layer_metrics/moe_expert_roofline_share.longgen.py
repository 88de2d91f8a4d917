"""The held routed experts of the decode program against their byte
roofline: the weights of the held experts that had a live token (mean
distinct experts a layer step of the TRACED calls, from their
``skytpu:moe_readback`` annotations, x 31,457,280 B x 4 layers x the
traced steps) over the memory bandwidth, as a share of the device time
the trace holds under the ``moe_experts`` scope of ``decode_steps``."""
from perfbench import moe_window, pool_window, roofline_solar, scopes

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['solar-open2-250b.longgen']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'decode_steps', 'moe_experts')
    distinct = moe_window.traced_distinct_mean(run) if busy else None
    steps = pool_window.traced_steps(run) if busy else None
    if not distinct or not steps:
        return None
    ctx = run['ctx']
    need = roofline_solar.expert_bytes_read(ctx.config['model'], distinct)
    return 100.0 * steps * need / ctx.peak['hbm_bytes_per_s'] / busy
