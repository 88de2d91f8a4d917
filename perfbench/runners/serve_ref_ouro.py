"""``serve_ref.py`` for a dense looped model (Ouro-2.6B): its set-up,
window, reduction and scoring as they are, with this cell's two limits
and an engine line that says what a cached token is.

The limits, each with its reason. Read on the chip over 17 seeds x 370
served tokens of 4 requests (contexts 53-699 + up to 96 served; PERF.md,
Findings, PR 35): the right program's mean deficit 0.041-0.184 and worst
0.51-1.23, with 41-74 % of served tokens the reference's argmax; scored
against the reference on a tree rounded to int8 (8 of those seeds),
mean 0.437-0.875 and worst 1.57-2.59 (9-30 % the argmax).

- Why the deficits are not smaller: the model is bf16 as published, and
  its residual stream is bf16. A pass adds 96 unit-norm branch outputs
  to a stream whose norm grows to ~10, each add rounded to 8 bits of
  mantissa, through 4 passes of the same weights; the two best of 49,152
  random-weight logits lie ~0.2 apart. There is no routing here:
  ``serve_ref.py`` read deficits of this size as expert swaps.
- ``MEAN_DEFICIT`` 0.30 on the mean over all scored tokens: between the
  largest right reading (0.184) and the smallest int8 reading (0.437),
  1.6 x and 1.5 x away. This is the limit a lower precision or a wrong
  mathematics breaks: each of the five variants moves every position
  (``perfbench/tests/test_ouro_files.py`` shows each failing at a small
  size). ``serve_ref.py``'s 0.27 would separate the same readings; its
  6.0 separates nothing, below.
- ``WORST_DEFICIT`` 3.0 on the worst served token: above every right
  reading (1.23) and every int8 reading (2.59: the worst token does not
  tell a precision), below a token no better than a random one (the
  best of 49,152 unit logits is ~4.2 above a typical one; 6.0 would let
  it pass): a broken cache row or page, a wrong cache layer.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# A copy of the module of this runner's own: what is set on it below
# reaches no other cell.
serve_ref = _load(os.path.join(HERE, 'serve_ref.py'), 'perfbench_serve_ref')
serve = serve_ref.serve
# What sweep_ref.py drives, under the names sweep.py calls.
run_window, reduce_window = serve.run_window, serve.reduce_window
deficits = serve_ref.deficits

WORST_DEFICIT = 3.0
MEAN_DEFICIT = 0.30

within_limits = functools.partial(serve_ref.within_limits,
                                  worst=WORST_DEFICIT, mean=MEAN_DEFICIT)


def setup(ctx):
    cfg, params, srv, watch = _setup(ctx)
    eng, pool = srv.engine, srv.engine.kv_pool_stats()
    ctx.log('engine: ' + json.dumps({
        'params': cfg.num_params,
        'param_bytes': eng._param_bytes,
        'cache_layers': cfg.n_cache_layers,
        'kv_token_bytes': pool['kv_token_bytes'],
        'page_bytes': pool['kv_token_bytes'] * eng.page,
        'pool_pages': eng.alloc.n_pages,
        'pool_tokens': pool['pool_token_capacity'],
        'prefill_prompts_max': eng._prefill_n_max}))
    return cfg, params, srv, watch


# ``serve_ref.run`` and ``score_served`` read these names from their
# module when they are called: the set-up with the line above, the
# decision and the limits its score line prints.
_setup, serve_ref.setup = serve_ref.setup, setup
serve_ref.within_limits = within_limits
serve_ref.WORST_DEFICIT, serve_ref.MEAN_DEFICIT = WORST_DEFICIT, MEAN_DEFICIT
run = serve_ref.run
