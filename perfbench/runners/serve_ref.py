"""The serving runner for a configuration that comes with a plain
reference (``perfbench/reference/``): ``serve.py``'s server, warm-up,
window and reduction as they are, its own set-up (the configuration's
weight maker, named by the mix) and its own ``correct``.

``correct`` scores what the timed path served: after the window,
requests of the mix spanning its lengths are served greedy over HTTP,
together, and every served token is scored teacher-forced against the
benchmark's copy of the reference (float32, "highest" precision, a loop
over layers and experts) on the same weights: its logit deficit against
the reference's best token there.

The limits, each with its reason. Read on the chip over 8 seeds x 343 served
tokens of 4 requests (contexts 680-8192; PERF.md, Findings, PR 30): the
right program's mean deficit 0.108-0.170 and worst 2.13-3.70, with
69-78 % of served tokens the reference's argmax; scored against the
reference on a tree rounded to int8, mean 0.394 and worst 2.90; with the
1.8 left out, mean 0.999.

- Why the deficits are not rounding-sized: with 64 experts the 4th and
  5th biased scores of a token lie ~0.01 apart, and bf16 activations move
  a score by a few thousandths, so in most tokens some layer swaps one of
  the four experts against the float32 reference. A swap moves that
  position's logits by ~0.2, and the logits of random weights over
  154,880 tokens have their two best ~0.2 apart.
- ``MEAN_DEFICIT`` 0.27 on the mean over all scored tokens: the swaps
  average out over ~340 tokens (0.137 +- 0.02 from seed to seed), so the
  limit lies between the largest right reading (0.170) and the int8
  reading (0.394) with a third of the way to go on either side. This is
  the limit a lower precision or a wrong mathematics breaks: each of the
  five variants moves every position
  (``perfbench/tests/test_glm_files.py`` shows each failing at a small
  size).
- ``WORST_DEFICIT`` 6.0 on the worst served token: that token is a swap
  position (up to 3.70), and int8 reads no worse there (2.90), so this
  limit separates nothing rounding-sized. It catches a served token worse
  than a random one (the best of 154,880 unit logits is ~4.5 above a
  typical one): a broken cache row or page, not a precision.
"""
from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np

from perfbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


serve = _load(os.path.join(HERE, 'serve.py'), 'perfbench_serve')
# What sweep_ref.py drives, under the names sweep.py calls.
run_window, reduce_window = serve.run_window, serve.reduce_window

SCORE_REQUESTS = 4        # the shortest, the longest and two between
SCORE_OUTPUT_CAP = 96     # tokens scored a request
Q_BLOCK = 1024            # reference attention, query rows at a time
PAD_TO = 1024             # reference sequence lengths: few shapes
WORST_DEFICIT = 6.0
MEAN_DEFICIT = 0.27


def setup(ctx):
    """Weights, server, warm programs: everything before the window."""
    from skypilot_tpu.models.configs import ModelConfig
    from skypilot_tpu.telemetry import device as device_lib
    watch = device_lib.get_compile_watch()
    cfg = ModelConfig(**ctx.config['model'])
    maker = _load(os.path.join(ctx.root, ctx.mix['weights'] + '.py'),
                  'perfbench_' + ctx.mix['weights'])
    t = time.time()
    params = maker.make_tree(cfg, ctx.seed)
    ctx.log(f'weights: bf16 tree on the device in {time.time() - t:.1f}s')
    srv = serve.start_server(ctx, cfg, params, watch)
    eng = srv.engine
    ctx.log('engine: ' + json.dumps({
        'decode_impl': eng.decode_impl, 'page': eng.page,
        'chunk': eng.chunk, 'kv_cache_dtype': eng.kv_cache_dtype,
        'pool': eng.kv_pool_stats(),
        'bytes_by_device': eng._bytes_by_device,
        'memory': device_lib.device_memory()}))
    return cfg, params, srv, watch


def score_requests(mix, seed):
    """``SCORE_REQUESTS`` requests of the mix spanning its prompt
    lengths: of 32 scheduled, the shortest, the longest and those at the
    thirds between."""
    reqs = sorted(traffic.schedule(mix, seed + 1, 32, rate_per_s=1.0),
                  key=lambda r: r.prompt_tokens)
    picks = np.linspace(0, len(reqs) - 1, SCORE_REQUESTS).round().astype(int)
    return [reqs[i] for i in picks]


def serve_greedy(port, prompts, new_tokens):
    """POST the prompts together, greedy; their tokens in order."""
    out = [None] * len(prompts)

    def one(i):
        body = json.dumps({'prompt': prompts[i], 'temperature': 0.0,
                           'max_new_tokens': new_tokens[i]}).encode()
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate', body,
            {'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=300) as resp:
            out[i] = json.load(resp)['tokens']

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def deficits(reference, params, model, prompt, tokens):
    """Each served token's reference logit short of the reference's best
    at its position, teacher-forced; and whether every logit is finite."""
    import jax
    n, m = len(prompt), len(tokens)
    seq = np.zeros(-(-(n + m) // PAD_TO) * PAD_TO, np.int32)
    seq[:n + m] = prompt + tokens           # causal: the padding is unseen
    rows = np.arange(n - 1, n - 1 + m)      # position i predicts i + 1
    logits = np.asarray(reference.forward(
        params, seq, model, q_block=Q_BLOCK, rows=rows, wrap=jax.jit)[0])
    picked = logits[np.arange(m), np.asarray(tokens)]
    return logits.max(-1) - picked, bool(np.isfinite(logits).all())


def within_limits(deficit, finite=True, *, worst=WORST_DEFICIT,
                  mean=MEAN_DEFICIT) -> bool:
    """The decision: every logit finite, and the served tokens' deficits
    inside both limits."""
    return bool(finite and deficit.max() <= worst
                and deficit.mean() <= mean)


def score_served(ctx, srv, cfg, params, seed):
    reference = _load(os.path.join(ctx.root, 'reference',
                                   ctx.mix['reference'] + '.py'),
                      'perfbench_reference')
    reqs = score_requests(ctx.mix, seed)
    prompts = [traffic.prompt_ids(r, cfg.vocab_size) for r in reqs]
    new = [min(r.output_tokens, SCORE_OUTPUT_CAP) for r in reqs]
    served = serve_greedy(srv.port, prompts, new)
    counts_ok = all(t is not None and len(t) == k
                    for t, k in zip(served, new))
    if not counts_ok:
        ctx.log(f'score: served counts {[t and len(t) for t in served]} '
                f'of {new}')
        return False
    # The pool's bytes go to the reference: the server is done.
    engine = srv.engine
    srv.stop()
    engine.cache = None
    every, finite = [], True
    for prompt, tokens in zip(prompts, served):
        t = time.time()
        d, ok = deficits(reference, params, ctx.config['model'], prompt,
                         tokens)
        every.append(d)
        finite = finite and ok
        ctx.log(f'score: prompt {len(prompt)} + {len(tokens)} tokens: '
                f'worst deficit {d.max():.4f}, mean {d.mean():.4f}, '
                f'{int((d <= 0).sum())} are the reference\'s argmax, '
                f'{time.time() - t:.1f}s')
    every = np.concatenate(every)
    worst, mean = float(every.max()), float(every.mean())
    ctx.log(f'score: {every.size} served tokens over contexts '
            f'{min(map(len, prompts))}-{max(map(len, prompts))}: worst '
            f'deficit {worst:.4f} (limit {WORST_DEFICIT}), mean '
            f'{mean:.5f} (limit {MEAN_DEFICIT}), finite {finite}')
    return within_limits(every, finite)


def run(ctx):
    cfg, params, srv, watch = setup(ctx)
    trace_dir = os.path.join(ctx.workdir, 'trace') if ctx.trace else None
    try:
        rec = serve.run_window(ctx, srv, watch, seed=ctx.seed,
                               seconds=ctx.seconds, sample=ctx.trace,
                               trace_dir=trace_dir)
        ctx.log(f'compiles inside the window: {rec["compiles_in_window"]}, '
                f'by the end of the drain {rec["compiles_by_drain"]} '
                f'(process total {watch.count}); program keys first met '
                f'after the warm-up: {rec["unwarmed"]}')
        ctx.log('step phases (host time around dispatches, whole process): '
                + json.dumps(srv.engine._prof.phase_stats()['phases']))
        out = serve.reduce_window(rec, ctx.log)
        scored = score_served(ctx, srv, cfg, params, ctx.seed)
    finally:
        srv.stop()
    out['correct'] = (scored and out['wrong_token_count'] == 0
                      and out['attempted'] > 0)
    out['setup_s'] = rec['t0'] - ctx.t_start
    out['records'] = rec
    out['trace_dir'] = trace_dir
    return out
