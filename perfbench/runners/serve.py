"""The serving runner: one process holds the chip. It makes the weights,
starts ``ModelServer`` on a ready engine, warms every program shape the
mix can reach, lets the load generator (a child that never imports JAX)
drive one window over HTTP, and checks the answers.

The single seam into the program is ``serve.server.build_engine``: the
server has no argument for a ready engine, so that module attribute is
replaced for the load (``hand_over``).
"""
from __future__ import annotations

import contextlib
import json
import math
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

from perfbench import stats, trace, traffic, weights

GRACE_S = 30.0            # a request not finished this long after the
                          # window's end has failed
SCORE_REQUESTS = 8
SCORE_PROMPT_CAP = 512
# Served token's logit against the XLA reference's best, per
# sqrt(layers): chip_smoke.py's int8-KV tolerance (both sides hold the
# same int8 weights; the served side also rounds K/V rows to int8).
LOGIT_TOL_PER_SQRT_LAYER = 1 / 16
TRACE_SECONDS = 3.0       # the traced part of a --trace 1 window: its end


# ------------------------------------------------------------------ seam
@contextlib.contextmanager
def hand_over(make_engine):
    """Have ``ModelServer._load_engine`` take ``make_engine``'s engine."""
    from skypilot_tpu.serve import server as server_mod
    if not hasattr(server_mod, 'build_engine'):
        raise RuntimeError(
            'skypilot_tpu.serve.server has no build_engine any more: the '
            "benchmark's one seam into the program is gone (PERF.md, "
            '"The seam")')
    original = server_mod.build_engine
    server_mod.build_engine = lambda *a, **kw: make_engine(**kw)
    try:
        yield
    finally:
        server_mod.build_engine = original


# --------------------------------------------------------------- warm-up
def warm_programs(engine, mix, warm, watch, log) -> None:
    """Run every program shape the mix can reach before the server comes
    up, through the engine's public calls. The engine keys its programs
    by buckets of (prompts in a chunk batch, pages, chunk width) and
    (decode horizon, pages); which context lengths start a new bucket is
    found here by watching the compile count, not assumed. A context of a
    given length costs little: all prompts share the prefix of one long
    prompt that is prefilled first, so the prefix cache supplies it.
    Fixed seed: every run warms the same shapes with the same tokens."""
    rng = np.random.default_rng(0)
    vocab, chunk, page = engine.cfg.vocab_size, engine.chunk, engine.page
    lo, hi = mix['prompt_tokens']['min'], mix['prompt_tokens']['max']
    out_hi = mix['output_tokens']['max']
    base = rng.integers(0, vocab, hi + out_hi + page).tolist()

    def wave(n, cut, tail, new=2, horizon=8):
        before = watch.count
        for _ in range(n):
            engine.add_request(
                base[:cut] + rng.integers(0, vocab, tail).tolist(),
                max_new_tokens=new)
        engine.run_to_completion(horizon=horizon)
        return watch.count > before

    wave(1, hi, 0)                       # the walk: fills the prefix cache
    # Decode first, while every (horizon, pages) program is still new:
    # contexts a page apart over every length a request can have while it
    # decodes; the other horizons only where the first one met a new
    # program (a horizon is shorter than a page, so from a page's start
    # every horizon ends in the same page). Run after the prefill waves,
    # whose own few decode steps compile the first horizon's programs, it
    # skipped the others there: horizon 32 at 16 pages then compiled for
    # 14.2 s inside a window (my chip run, PR 25).
    t = time.time()
    hs = list(warm['horizons'])
    for ctx in range(lo // page * page, hi + out_hi + page, page):
        if wave(1, max(ctx - 8, 0), 8, new=hs[0] + 1, horizon=hs[0]):
            for h in hs[1:]:
                wave(1, max(ctx - 8, 0), 8, new=h + 1, horizon=h)
    log(f'warm-up decode: horizons {hs}, {time.time() - t:.1f}s, '
        f'compiles {watch.count}')
    # Prefill: a tail of one full piece and one short one meets both
    # chunk widths at the context ``cut``; the last entry but one is the
    # longest prompt itself, the last the shortest. The batch sizes past
    # the second run only where the second met a new program.
    t = time.time()
    tail = chunk + chunk // 4
    cuts = [(c, tail) for c in range(0, hi - tail + 1, chunk)]
    last = (hi - 1) // chunk * chunk
    cuts += [(last, hi - last), (0, min(lo, page))]
    ns = sorted(warm['concurrency'])
    for c, tl in cuts:
        wave(ns[0], c, tl)
    new_at = [(c, tl) for c, tl in cuts if wave(ns[1], c, tl)]
    for n in ns[2:]:
        for c, tl in new_at:
            wave(n, c, tl)
    log(f'warm-up prefill: {len(cuts)} contexts, new programs at '
        f'{[c + tl for c, tl in new_at]}, {time.time() - t:.1f}s, '
        f'compiles {watch.count}')


# ------------------------------------------------------------ the server
def free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def get_json(url: str, timeout: float = 30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)


def start_server(ctx, cfg, params, watch):
    """A ``ModelServer`` on an engine built from ``params`` with the
    keyword arguments and the warm-up request ``build_engine`` uses."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.serve.server import ModelServer
    dep = ctx.config['deployment']
    extra = {}
    if dep.get('tp', 1) * dep.get('dp', 1) > 1:
        from skypilot_tpu.parallel import mesh as mesh_lib
        extra['mesh'] = mesh_lib.serving_mesh(dep['tp'], dep.get('dp', 1))

    def make_engine(*, max_batch, max_seq, **_):
        engine = PagedInferenceEngine(
            cfg, params=params, max_batch=max_batch, max_seq=max_seq,
            quantize=dep['quantize'], **extra)
        engine.add_request([1, 2, 3], max_new_tokens=2)
        engine.run_to_completion(horizon=4)
        warm_programs(engine, ctx.mix, ctx.mix['warmup'], watch, ctx.log)
        return engine

    srv = ModelServer(cfg.name, max_batch=dep['max_batch'],
                      max_seq=dep['max_seq'], port=free_port(),
                      quantize=dep['quantize'], kv_cache='paged',
                      tp=dep.get('tp', 1), dp=dep.get('dp', 1))
    with hand_over(make_engine):
        srv.start(block=False)
        while not srv._ready.wait(1.0):
            if srv._error is not None:
                raise RuntimeError(f'server failed to load: {srv._error}')
    return srv


# ------------------------------------------------------------ the window
def run_window(ctx, srv, watch, *, seed, seconds, rate_per_s=None,
               sample=False, trace_dir=None):
    """One window of the mix through the child; returns its records with
    what this process saw at the window's two ends."""
    base = f'http://127.0.0.1:{srv.port}'
    out_path = os.path.join(ctx.workdir, 'loadgen_records.json')
    spec_path = os.path.join(ctx.workdir, 'loadgen_spec.json')
    with open(spec_path, 'w', encoding='utf-8') as f:
        json.dump({'host': '127.0.0.1', 'port': srv.port, 'mix': ctx.mix,
                   'seed': seed, 'seconds': seconds,
                   'rate_per_s': rate_per_s, 'grace_s': GRACE_S,
                   'vocab_size': srv.engine.cfg.vocab_size,
                   'warmup_requests': 2,
                   'sample_period_s': 0.25 if sample else 0,
                   'out': out_path}, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(ctx.root, 'loadgen.py'), spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        def event(name):
            line = child.stdout.readline()
            ev = json.loads(line) if line.strip() else {'event': 'eof'}
            if ev['event'] != name:
                raise RuntimeError(f'load generator said {ev}, not {name}')
            return ev

        event('ready')
        before = get_json(base + '/metrics?format=json')
        compiles_start = watch.count
        first_calls = len(srv.engine._prof.phase_stats().get('compiles', []))
        child.stdin.write('go\n')
        child.stdin.flush()
        t0 = event('start')['t0']
        t_hi = t0 + seconds
        traced = None
        if trace_dir is not None:
            length = min(TRACE_SECONDS, seconds / 2)
            time.sleep(max(0.0, t_hi - length - 1.0 - time.time()))
            trace.start(trace_dir)
            traced = [time.time(), None]
        time.sleep(max(0.0, t_hi - time.time()))
        at_end = get_json(base + '/metrics?format=json')
        compiles_end = watch.count
        if traced is not None:
            import jax
            traced[1] = time.time()
            jax.profiler.stop_trace()
        event('done')
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(out_path, encoding='utf-8') as f:
        rec = json.load(f)
    # A program key met for the first time after the warm-up is a hole in
    # the warm-up grid: named here, whether or not its compile ended
    # inside the window.
    unwarmed = srv.engine._prof.phase_stats().get('compiles', [])[first_calls:]
    rec.update(metrics_start=before, metrics_end=at_end,
               compiles_in_window=compiles_end - compiles_start,
               compiles_by_drain=watch.count - compiles_start,
               unwarmed=unwarmed, traced=traced)
    return rec


def reduce_window(rec, log):
    """Records -> the numbers the metrics are made of. A request that
    failed, was refused or did not finish in time enters each tail as
    +inf: worse than any measured value."""
    reqs = rec['requests']
    ttft, tpot, failed, wrong_count = [], [], 0, 0
    for r in reqs:
        ok = r['error'] is None and r['first'] is not None
        if ok and r['tokens'] != r['output_tokens']:
            wrong_count += 1
        if not ok:
            failed += 1
            ttft.append(math.inf)
            tpot.append(math.inf)
            continue
        ttft.append((r['first'] - r['due']) * 1e3)
        if r['tokens'] > 1:
            tpot.append((r['last'] - r['first']) / (r['tokens'] - 1) * 1e3)
    late = [(r['sent'] - r['due']) * 1e3 for r in reqs]
    log(f'generator lateness ms: median {np.median(late):.2f} '
        f'max {max(late):.2f} over {len(reqs)} sends')
    log(stats.describe('ttft_ms', ttft, 95))
    log(stats.describe('tpot_ms', tpot, 95))
    first_error = next((r['error'] for r in reqs if r['error']), None)
    if first_error:
        log(f'first error: {first_error}')
    tokens_in = sum(r['tokens_in_window'] for r in reqs)
    return {
        'attempted': len(reqs), 'failed': failed,
        'wrong_token_count': wrong_count,
        'ttft_p95_ms': stats.percentile(ttft, 95),
        'tpot_p95_ms': stats.percentile(tpot, 95),
        'ttft_p50_ms': stats.percentile(ttft, 50),
        'tpot_p50_ms': stats.percentile(tpot, 50),
        'out_tok_s': tokens_in / rec['seconds'],
        'ttft_ms': ttft, 'tpot_ms': tpot,
    }


# --------------------------------------------------------------- correct
def score_served(ctx, srv, cfg, params, seed):
    """8 seeded requests of the mix, served alone and greedy over HTTP,
    then scored as ``chip_smoke.py`` scores: each served token's logit
    under the XLA reference (``llama.forward``, teacher-forced, the same
    int8 tree) against the reference's best there."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import llama
    mix = ctx.mix
    reqs = traffic.schedule(mix, seed + 1, SCORE_REQUESTS,
                            rate_per_s=1.0)[:SCORE_REQUESTS]
    base = f'http://127.0.0.1:{srv.port}'
    served = []
    for r in reqs:
        prompt = traffic.prompt_ids(r, cfg.vocab_size)[:SCORE_PROMPT_CAP]
        body = json.dumps({'prompt': prompt, 'temperature': 0.0,
                           'max_new_tokens': r.output_tokens}).encode()
        req = urllib.request.Request(
            base + '/generate', body, {'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=120) as resp:
            served.append((prompt, json.load(resp)['tokens']))
    seq = SCORE_PROMPT_CAP + mix['output_tokens']['max']

    @jax.jit
    def score(params, tokens, chosen):
        logits, _ = llama.forward(params, tokens[None], cfg,
                                  attn_impl='xla')
        logits = logits[0].astype(jnp.float32)
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)
        return (logits.max(-1) - picked[:, 0],
                jnp.all(jnp.isfinite(logits)))

    worst, agree, positions, finite = 0.0, 0, 0, True
    for prompt, tokens in served:
        n, m = len(prompt), len(tokens)
        padded = np.zeros(seq, np.int32)
        padded[:n + m] = prompt + tokens
        chosen = np.zeros(seq, np.int32)
        chosen[n - 1:n - 1 + m] = tokens       # position i predicts i + 1
        deficit, ok = jax.device_get(
            score(params, jnp.asarray(padded), jnp.asarray(chosen)))
        deficit = deficit[n - 1:n - 1 + m]
        worst = max(worst, float(deficit.max()))
        agree += int((deficit <= 0).sum())
        positions += m
        finite = finite and bool(ok)
    tol = LOGIT_TOL_PER_SQRT_LAYER * math.sqrt(cfg.n_layers)
    counts_ok = all(len(t) == r.output_tokens
                    for (_, t), r in zip(served, reqs))
    ctx.log(f'score: {agree}/{positions} served tokens are the '
            f"reference's argmax, worst deficit {worst:.4f} logits "
            f'(tolerance {tol:.4f}), finite {finite}, counts {counts_ok}')
    return finite and counts_ok and worst <= tol


# ------------------------------------------------------------------- run
def setup(ctx):
    """Weights, server, warm programs: everything before the window."""
    from skypilot_tpu.models.configs import ModelConfig
    from skypilot_tpu.telemetry import device as device_lib
    watch = device_lib.get_compile_watch()
    cfg = ModelConfig(**ctx.config['model'])
    t = time.time()
    params = weights.make_int8_tree(cfg, ctx.seed)
    ctx.log(f'weights: int8 tree on the device in {time.time() - t:.1f}s')
    srv = start_server(ctx, cfg, params, watch)
    eng = srv.engine
    ctx.log('engine: ' + json.dumps({
        'decode_impl': eng.decode_impl, 'page': eng.page,
        'chunk': eng.chunk, 'kv_cache_dtype': eng.kv_cache_dtype,
        'pool': eng.kv_pool_stats(),
        'bytes_by_device': eng._bytes_by_device,
        'memory': device_lib.device_memory()}))
    return cfg, params, srv, watch


def run(ctx):
    cfg, params, srv, watch = setup(ctx)
    trace_dir = os.path.join(ctx.workdir, 'trace') if ctx.trace else None
    try:
        rec = run_window(ctx, srv, watch, seed=ctx.seed,
                         seconds=ctx.seconds, sample=ctx.trace,
                         trace_dir=trace_dir)
        ctx.log(f'compiles inside the window: {rec["compiles_in_window"]}, '
                f'by the end of the drain {rec["compiles_by_drain"]} '
                f'(process total {watch.count}); program keys first met '
                f'after the warm-up: {rec["unwarmed"]}')
        ctx.log('step phases (host time around dispatches, whole process): '
                + json.dumps(srv.engine._prof.phase_stats()['phases']))
        out = reduce_window(rec, ctx.log)
        scored = score_served(ctx, srv, cfg, params, ctx.seed)
    finally:
        srv.stop()
    out['correct'] = (scored and out['wrong_token_count'] == 0
                      and out['attempted'] > 0)
    out['setup_s'] = rec['t0'] - ctx.t_start
    out['records'] = rec
    out['trace_dir'] = trace_dir
    return out
