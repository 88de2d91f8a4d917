"""The oracle for greedy tokens: the plain forward pass, not an engine.

Two programs that round the same bf16 values in another order may pick
different tokens where the reference's top-2 logits lie closer than the
rounding (PR 22 measured margins of 0.0076 and 0.0097 logits on the
seed-0 ``tiny`` model), so a comparison of tokens across two DIFFERENT
programs (sharded against unsharded, k=8 against k=1, speculative
against vanilla, int4 KV against bf16 KV) flips from run to run. Such
programs are held to this oracle instead: every token a run emitted,
teacher-forced through ``llama.forward`` (``chip_smoke.py``'s scorer),
must lie within a tolerance of the reference's best logit at its
position. Tokens are compared exactly only where both sides are the
SAME program fed the same state (checkpoint -> recover, export ->
ingest, a replayed op log).

The tolerances are ``chip_smoke.py``'s, per sqrt(layer), by what one
side rounds that the reference does not; a wrong mask, scale, page or
position moves logits by O(1), many times any of them.
"""
import functools
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402  (stdlib-only at import; never touches jax)

# chip_smoke's kinds, plus int4 KV rows: scale = row absmax/7, 127/7 = 18
# times as coarse as an int8 row's share of 'int8_kv' (1/32), beside the
# same bf16 rounding (1/32).
TOL_PER_SQRT_LAYER = dict(chip_smoke.LOGIT_TOL_PER_SQRT_LAYER,
                          int4_kv=(1 + 127 / 7) / 32)

KV_KIND = {'bf16': 'bf16', 'int8': 'int8_kv', 'int4': 'int4_kv'}


def tolerance(cfg, kind: str) -> float:
    return TOL_PER_SQRT_LAYER[kind] * math.sqrt(cfg.n_layers)


@functools.lru_cache(maxsize=None)
def _scorer(cfg, seq: int):
    return chip_smoke._scorer(cfg, seq)         # one jit per (cfg, seq)


def score(cfg, params, prompt, output):
    """(deficit, margin) per emitted token, from the plain forward of
    ``params`` (a quantized tree scores through its own dequantizing
    matmuls: the reference holds the weights the engine holds)."""
    seq = 64
    while seq < len(prompt) + len(output):
        seq *= 2
    deficit, margin, finite = _scorer(cfg, seq)(params, prompt, output)
    assert finite, 'the reference forward produced a non-finite logit'
    return np.asarray(deficit), np.asarray(margin)


def assert_agrees(cfg, params, prompt, output, kind: str = 'bf16',
                  what: str = ''):
    """Every token of ``output`` is the reference's choice after
    ``prompt`` + the tokens before it, or within ``kind``'s tolerance of
    it in logits."""
    assert len(output) > 0, f'{what}: no token emitted'
    deficit, margin = score(cfg, params, list(prompt), list(output))
    tol = tolerance(cfg, kind)
    worst = int(np.argmax(deficit))
    assert deficit[worst] <= tol, (
        f'{what or "run"}: token {worst} ({output[worst]}) lies '
        f'{deficit[worst]:.4f} logits below the reference choice '
        f'(tolerance {tol:.4f} for {kind}; reference top-2 margin there '
        f'{margin[worst]:.4f}); output {list(output)}')


def assert_all_agree(cfg, params, prompts, outs, what: str = '',
                     kind: str = 'bf16', n_new=None):
    """``assert_agrees`` for each prompt's output (each ``n_new`` tokens
    long, where given)."""
    assert len(prompts) == len(outs)
    for prompt, out in zip(prompts, outs):
        if n_new is not None:
            assert len(out) == n_new, (what, out)
        assert_agrees(cfg, params, prompt, out, kind, what)


@functools.lru_cache(maxsize=None)
def _seed0(model: str):
    import jax

    from skypilot_tpu.models import configs, llama
    cfg = configs.get_config(model)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def assert_server_agrees(prompt, output, what: str = '',
                         model: str = 'tiny'):
    """``assert_agrees`` for what a ``ModelServer(model)`` streamed: it
    serves the seed-0 weights an engine makes when handed none. For a
    stream that migrated, resumed or was recomputed on another replica
    (a prefill of prompt + prefix where the uninterrupted run decoded:
    another program)."""
    cfg, params = _seed0(model)
    assert_agrees(cfg, params, prompt, output, what=what)


def greedy(engine, prompts, n_new, horizon: int = 4, **request_kw):
    """Outputs of ``prompts`` run to completion on ``engine``."""
    rids = [engine.add_request(list(p), max_new_tokens=n_new, **request_kw)
            for p in prompts]
    done = engine.run_to_completion(horizon=horizon)
    return [done[r].output for r in rids]
