"""The routed experts of the decode program against their byte roofline:
the weights of the experts that had a live token (mean distinct experts a
layer step of the TRACED calls, from their ``skytpu:moe_readback``
annotations, x one expert's bytes x the expert layers x the traced steps)
over the memory bandwidth, as a share of the device time the trace holds
under the ``moe_experts`` scope of ``decode_steps``. A layer that reads
experts nobody was routed to reads low here."""
from perfbench import moe_window, roofline_glm, scopes

LAYER = 'model + kernels'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['glm-4.7-flash.longctx']
SOURCE = 'device_trace'


def read(run):
    busy = scopes.of_run(run, 'decode_steps', 'moe_experts')
    distinct = moe_window.traced_distinct_mean(run) if busy else None
    steps = moe_window.traced_steps(run)
    if not distinct or not steps:
        return None
    ctx = run['ctx']
    need = roofline_glm.expert_bytes_read(ctx.config['model'], distinct)
    return 100.0 * steps * need / ctx.peak['hbm_bytes_per_s'] / busy
