"""What the readers of an expert-model cell share: the window's means
from the engine's cumulative counters (``engine_loop`` of
``/metrics?format=json``, differenced between the window's two ends) and
the decode steps the traced part holds. Every function returns None
where the program has no such counter (the parent's), and never raises.
"""
import statistics


def counter_delta(run, key):
    rec = run['records']
    a = rec['metrics_start'].get('engine_loop') or {}
    b = rec['metrics_end'].get('engine_loop') or {}
    if key not in a or key not in b:
        return None
    return b[key] - a[key]


def window_means(run):
    """(mean distinct experts an expert-layer step read, mean live
    tokens in the pool: page-granular, mean live rows a decode step)."""
    live = [s['kv_pool_tokens_used']
            for s in run['records'].get('samples', []) if 'error' not in s]
    layer_steps = counter_delta(run, 'moe_layer_steps_total')
    distinct = counter_delta(run, 'moe_distinct_experts_total')
    steps = counter_delta(run, 'decode_substeps_total')
    rows = counter_delta(run, 'decode_live_rows_total')
    if not live or not layer_steps or not steps or distinct is None \
            or rows is None:
        return None
    return distinct / layer_steps, statistics.fmean(live), rows / steps


def traced_distinct_mean(run):
    """Mean distinct experts an expert-layer step read, over the decode
    calls whose readback the trace holds (``skytpu:moe_readback``: within
    a call or two of the calls it holds executing)."""
    from perfbench import host_plane
    data = host_plane.load(run.get('trace_dir'))
    if data is None:
        return None
    seen = [dict(key) for _, _, key in
            host_plane.annotations(data, 'moe_readback')]
    steps = sum(int(k.get('layer_steps', 0)) for k in seen)
    if not steps:
        return None
    return sum(int(k.get('distinct', 0)) for k in seen) / steps


def traced_steps(run):
    """Fused decode steps of the ``decode_steps`` executions traced."""
    return sum(e.inner_loops
               for e in run['trace'].programs.get('decode_steps', []))
