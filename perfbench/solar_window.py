"""What the readers of the ``longgen`` cell share: the window's means
from the engine's cumulative counters (``engine_loop`` of
``/metrics?format=json``, differenced between the window's two ends).
Every function returns None where the program has no such counter (the
parent's), and never raises."""
from perfbench import moe_window, pool_window

counter_delta = moe_window.counter_delta


def live_rows_mean(run):
    """Mean batch rows that carried a request in a decode step."""
    steps = counter_delta(run, 'decode_substeps_total')
    rows = counter_delta(run, 'decode_live_rows_total')
    if not steps or rows is None:
        return None
    return rows / steps


def distinct_mean(run):
    """Mean distinct HELD experts an expert-layer step read."""
    steps = counter_delta(run, 'moe_layer_steps_total')
    distinct = counter_delta(run, 'moe_distinct_experts_total')
    if not steps or distinct is None:
        return None
    return distinct / steps


def step_need_bytes(run, roofline):
    """The bytes a decode step of the window had to move on the mean
    (``roofline_solar.decode_step_bytes``), or None."""
    rows, distinct = live_rows_mean(run), distinct_mean(run)
    tokens = pool_window.live_tokens_mean(run)
    if rows is None or distinct is None or tokens is None:
        return None
    return roofline.decode_step_bytes(run['ctx'].config['model'], distinct,
                                      rows, tokens)


def ttft_stage_p95(run, stage):
    """95th percentile, ms, of one stage of the time to first token over
    the server's rolling window at the window's end (``ttft_stages`` of
    ``/metrics?format=json``); None where no request passed the stage."""
    block = run['records']['metrics_end'].get('ttft_stages', {}).get(stage)
    return block['p95'] if block and block['n'] else None
