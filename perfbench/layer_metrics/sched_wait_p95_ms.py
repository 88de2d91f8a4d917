"""95th percentile of the ``sched_wait`` stage of the time to first token
(``RequestScheduler.submit`` to ``engine.add_request`` in ``fill_engine``:
the wait in the tier queues for the engine loop to come round), over the
server's rolling window at the window's end: the ``ttft_stages`` block of
``/metrics?format=json``. None where the program has no such block, or no
request has passed the stage.
"""
LAYER = 'scheduler'
UNIT = 'ms'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'program_counter'


def read(run):
    block = run['records']['metrics_end'].get('ttft_stages', {}).get(
        'sched_wait')
    return block['p95'] if block and block['n'] else None
