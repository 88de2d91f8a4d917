"""Share of the window in which the engine loop held the engine lock (fill +
step, its blocking readback included): the difference of the cumulative
``lock_held_seconds_total`` between the scrapes at the window's two ends,
over the difference of the monotonic clock both were read on
(``engine_loop`` of ``/metrics?format=json``). A hold is counted when it
ends, so the share cannot pass 100. While the lock is held no handler can
submit to, cancel in or read from the engine. None where the program has
no such block."""
LAYER = 'entry points'
UNIT = '%'
MOVES = 'ttft_p95_ms'
CELLS = ['qwen2-7b.chat']
SOURCE = 'program_counter'


def read(run):
    rec = run['records']
    a = rec['metrics_start'].get('engine_loop')
    b = rec['metrics_end'].get('engine_loop')
    if not a or not b or b['clock_s'] <= a['clock_s']:
        return None
    held = b['lock_held_seconds_total'] - a['lock_held_seconds_total']
    return 100.0 * held / (b['clock_s'] - a['clock_s'])
