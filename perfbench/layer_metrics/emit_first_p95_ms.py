"""95th percentile of the ``emit_first`` stage of the time to first token (the
engine's readback of the first token to the return of the handler's flush of
its SSE line: the rest of the step, event routing, the outbox, the handler
thread's wake-up, write and flush), over the server's rolling window at the
window's end: the ``ttft_stages`` block of ``/metrics?format=json``. None
where the program has no such block, or no request has passed the stage.
"""
LAYER = 'entry points'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'program_counter'


def read(run):
    block = run['records']['metrics_end'].get('ttft_stages', {}).get(
        'emit_first')
    return block['p95'] if block and block['n'] else None
