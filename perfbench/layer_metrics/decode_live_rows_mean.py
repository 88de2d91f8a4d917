"""Mean number of batch rows that carried a request in a decode step of the
window: live rows summed over the fused steps of every enqueued decode
call, over those steps (the two cumulative counters of ``engine_loop`` in
``/metrics?format=json``, differenced between the window's two ends). The
decode program runs ``max_batch`` padded rows whatever is live. None where
the program has no such counters or no decode call was enqueued."""
LAYER = 'engine step'
UNIT = 'rows'
MOVES = 'tpot_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'program_counter'


def read(run):
    rec = run['records']
    a = rec['metrics_start'].get('engine_loop')
    b = rec['metrics_end'].get('engine_loop')
    if not a or not b:
        return None
    steps = b['decode_substeps_total'] - a['decode_substeps_total']
    rows = b['decode_live_rows_total'] - a['decode_live_rows_total']
    return rows / steps if steps > 0 else None
