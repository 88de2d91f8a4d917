"""One generator for every serving traffic mix: a file of parameters in,
a schedule of requests out. No JAX here: the load generator's process
imports this.

Every seed gets the SAME schedule: the lengths and the gaps between
arrivals are the stratified quantiles of the file's distributions (request
i of n takes quantile (i + 0.5) / n), dealt once into a fixed order (the
file's ``order_seed``). The run's seed draws the token ids, and nothing
that changes how much work arrives when: the tails of a serving cell turn
on which requests meet in a burst, far more than on the system's own noise.
Dealt in a freshly shuffled order per seed, the chat cell's 95th
percentiles differed by 30-44 % between seeds and by 2 % between two runs
of one seed; with one fixed cycle and only the window's start drawn from
the seed, still by 10 % against 1-4 % (my chip runs, PR 25).
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, NamedTuple

import numpy as np


class Request(NamedTuple):
    due_s: float          # from the window's start
    prompt_tokens: int
    output_tokens: int
    token_seed: int       # the prompt's ids are drawn from this


def _lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """n stratified draws of a clipped lognormal."""
    if spec['dist'] != 'lognormal':
        raise ValueError(f'unknown length distribution {spec["dist"]!r}')
    normal = NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.array([normal.inv_cdf(float(p)) for p in q])
    raw = spec['median'] * np.exp(spec['sigma'] * z)
    return np.clip(np.rint(raw), spec['min'], spec['max']).astype(np.int64)


def _gaps(rate_per_s: float, n: int) -> np.ndarray:
    """n stratified exponential gaps, scaled so they sum to n / rate:
    Poisson-like arrivals with exactly n requests due in n / rate s."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate_per_s) / gaps.sum()


def schedule(mix: Dict[str, Any], seed: int, seconds: float,
             rate_per_s: float = None) -> List[Request]:
    """The requests due inside a window of ``seconds``, in due order."""
    rate = float(rate_per_s if rate_per_s is not None
                 else mix['rate_per_s'])
    n = max(1, math.floor(rate * seconds))
    order = np.random.default_rng(int(mix.get('order_seed', 0)))
    prompts = order.permutation(_lengths(mix['prompt_tokens'], n))
    outputs = order.permutation(_lengths(mix['output_tokens'], n))
    gaps = order.permutation(_gaps(rate, n))
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    # The first request is due half a gap in, the last half a gap before
    # the end: every one of the n lies inside the window.
    due = np.cumsum(gaps) - gaps / 2
    seeds = rng.integers(0, 2**31 - 1, n)
    return [Request(float(due[i]), int(prompts[i]), int(outputs[i]),
                    int(seeds[i])) for i in range(n)]


def prompt_ids(req: Request, vocab_size: int) -> List[int]:
    rng = np.random.default_rng(req.token_seed)
    return rng.integers(0, vocab_size, req.prompt_tokens).tolist()
