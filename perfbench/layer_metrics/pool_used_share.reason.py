"""The most of the KV pool the window used: the largest
``kv_pool_tokens_used`` among the window's ``/metrics`` samples (which
``loadgen.py`` takes every 0.25 s of a traced run) over
``kv_pool_token_capacity`` at the window's end. At 1.5 MiB a token the
pool is ~40 pages of 128 tokens: near 100 % admission backs off and
requests are preempted, and ``ttft_p95_ms`` is the pool's and not the
chip's."""
from perfbench import pool_window

LAYER = 'pool'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['ouro-2.6b.reason']
SOURCE = 'program_counter'


def read(run):
    used = pool_window.tokens_used(run)
    cap = (run['records'].get('metrics_end') or {}).get(
        'kv_pool_token_capacity')
    if not used or not cap:
        return None
    return 100.0 * max(used) / cap
