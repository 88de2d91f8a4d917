"""95th percentile of the ``first_token_lag`` stage of the time to first token
(the return of the last chunk's dispatch to the engine's readback of the
token it sampled: the async pipeline), over the server's rolling window at
the window's end: the ``ttft_stages`` block of ``/metrics?format=json``.
None where the program has no such block, or no request has passed the
stage.
"""
LAYER = 'engine step'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'program_counter'


def read(run):
    block = run['records']['metrics_end'].get('ttft_stages', {}).get(
        'first_token_lag')
    return block['p95'] if block and block['n'] else None
