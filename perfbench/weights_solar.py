"""Solar-Open2-250B's serving weights, one chip's share, made on the
device from the seed: the tree ``llama.init_params`` gives for a
configuration of two mixer kinds (``models/kda.py``: a stack of gated
GQA layers, a stack of KDA layers, each with its router, its HELD experts
and its shared expert), in bf16 as published, 6.62 GB. ``init_params``
itself draws every stacked leaf whole in float32 first (a KDA stack's 40
held experts x 3 layers are 2.5 GB that way); here each leaf is drawn in
one jitted call of its own and a stacked leaf one layer at a time inside
it, so the transient is one layer's random bits (0.8 GB for 40 experts).

The tree is ``models/kda.py``'s ``leaf_plan`` (every leaf's shape and
fan-in) drawn as its ``init_params`` draws it (``assumed`` in the
configuration file): normals scaled by the fan-in, norms at one, the
decay's vectors by ``kda.draw_vector``, the router in float32; but its
selection-only bias normal at ``ROUTER_BIAS_STD`` (small: a trained
router's correction bias BALANCES the experts' load, a random one skews
it. At 0.1, as ``init_params`` draws it, the 40 held experts took 15.1 %
of the assignments on the one seed read; at 0.01 11.7 %, nearer the 12.5
% of a balanced router, and it still changes the chosen experts of about
half the tokens, so a program that leaves it out is scored wrong).

**The tree is drawn from ``WEIGHTS_SEED``, the same for every run; a
run's ``--seed`` draws its token ids** (``traffic.py``). This chip holds
40 of each layer's 320 experts, and which experts a random router
favours is a property of the drawn weights: with a tree a seed, a seed's
held experts took 11.7 %-15.1 % of the assignments, a decode step's
expert reads moved with it, and the median TPOT of five runs ranged over
3 % (``tpot_p95_ms`` spread 2.8 % against the 1.25 % a cell is admitted
at; my chip runs, PR 37). A trained router's load is balanced and does
not change from run to run; a random one's is made not to here. What a
seed still varies: every prompt's tokens, so every routing decision.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

from perfbench import weights

ROUTER_BIAS_STD = 0.01
WEIGHTS_SEED = 0


@functools.partial(jax.jit, static_argnames=('shape', 'fan', 'dtype',
                                             'stacked'))
def _matrix(key, *, shape, fan, dtype, stacked):
    """Normals scaled by ``fan`` ** -0.5; a stacked leaf a layer at a
    time (the transient is one layer's random bits)."""
    def one(k, sub):
        return (jax.random.normal(k, sub, jnp.float32) * fan ** -0.5
                ).astype(dtype)

    if stacked:
        return jax.lax.map(lambda k: one(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    return one(key, shape)


def make_tree(cfg, seed: int, weights_seed: int = WEIGHTS_SEED):
    """``init_params(cfg)``'s structure, shapes and dtypes, drawn leaf
    by leaf on the default device from ``weights_seed``; the run's
    ``seed`` is not read (the docstring above says why)."""
    del seed
    from skypilot_tpu.models import kda
    key = weights.seed_key(weights_seed)
    top = {'embed': ((cfg.vocab_size, cfg.dim), cfg.dim),
           'unembed': ((cfg.dim, cfg.vocab_size), cfg.dim),
           'final_norm': cfg.dim}

    def build(plan, layers, path):
        """``plan``: leaf -> (shape, fan-in) | a norm's width | the name
        of a vector's draw; ``layers``: the stack's depth (None: not
        stacked)."""
        lead = () if layers is None else (layers,)
        out = {}
        for name, spec in sorted(plan.items()):
            # crc32: a stable number of the leaf's path (hash() is salted)
            k = jax.random.fold_in(
                key, zlib.crc32((path + name).encode()) & 0x7fffffff)
            if isinstance(spec, dict):
                out[name] = build(spec, layers, path + name + '/')
                continue
            if isinstance(spec, int):
                leaf = jnp.ones(lead + (spec,), jnp.float32)
            elif spec == 'router_bias':
                leaf = ROUTER_BIAS_STD * jax.random.normal(
                    k, lead + kda.vector_shape(spec, cfg), jnp.float32)
            elif isinstance(spec, str):
                leaf = kda.draw_vector(
                    spec, k, lead + kda.vector_shape(spec, cfg))
            else:
                shape, fan = spec
                leaf = _matrix(
                    k, shape=lead + tuple(shape), fan=fan,
                    dtype=jnp.dtype(jnp.float32 if name == 'router'
                                    else cfg.dtype),
                    stacked=layers is not None)
            out[name] = jax.block_until_ready(leaf)
        return out

    depth = kda.stack_depths(cfg)
    tree = build(top, None, '')
    for stack, plan in kda.leaf_plan(cfg).items():
        tree[stack] = build(plan, depth[stack], stack + '/')
    return tree
