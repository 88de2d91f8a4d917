"""Share of the window's prefilled prompt tokens that went in pieces wider
than the chunk (a prompt prefilled alone whose tail holds a whole wide
piece): the difference of ``prefill_wide_tokens_total`` over that of
``prefill_tokens_total`` between the window's two scrapes (``engine_loop``
of ``/metrics?format=json``). None where the program counts no wide pieces
(the parent of the change that added them), or prefilled nothing in the
window."""
from perfbench import moe_window

LAYER = 'engine step'
UNIT = '%'
MOVES = 'ttft_p95_ms'
CELLS = ['glm-4.7-flash.longctx', 'solar-open2-250b.longgen']
SOURCE = 'program_counter'


def read(run):
    wide = moe_window.counter_delta(run, 'prefill_wide_tokens_total')
    tokens = moe_window.counter_delta(run, 'prefill_tokens_total')
    if wide is None or not tokens:
        return None
    return 100.0 * wide / tokens
