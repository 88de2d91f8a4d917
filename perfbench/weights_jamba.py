"""AI21-Jamba2-3B's serving weights, the whole model, made on the device
from a seed: the tree ``llama.init_params`` gives for a configuration of
Mamba and GQA layers (``models/mamba.py``: a stack of GQA layers and a
stack of Mamba layers, each layer with its dense FFN; one tied
embedding), in bf16 as published, 6.06 GB. ``init_params`` itself draws
every stacked leaf whole in float32 first (the 26 Mamba layers' FFN
matrices are 2.2 GB each that way); here each leaf is drawn in one jitted
call of its own and a stacked leaf one layer at a time inside it, so the
transient is one layer's random bits.

The tree is ``models/mamba.py``'s ``leaf_plan`` (every leaf's shape and
fan-in) drawn as its ``init_params`` draws it (``assumed`` in the
configuration file): normals scaled by the fan-in in bf16, norms at one,
the scan's vectors by ``mamba.draw_vector`` (A_log = log(1..16) along the
state, D one, softplus(dt_bias) log-uniform in [0.001, 0.1), the conv
bias uniform in +-1/2) in float32.

**The tree is drawn from ``WEIGHTS_SEED``, the same for every run; a
run's ``--seed`` draws its token ids** (``traffic.py``), as the other
cells' trees with a reference are: the comparison that decides
``correct`` reads the same tree whatever the seed, and a run's step
times do not move with it.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from perfbench import weights
from perfbench.weights_solar import _matrix

WEIGHTS_SEED = 0


def make_tree(cfg, seed: int, weights_seed: int = WEIGHTS_SEED):
    """``init_params(cfg)``'s structure, shapes and dtypes, drawn leaf
    by leaf on the default device from ``weights_seed``; the run's
    ``seed`` is not read (the docstring above says why)."""
    del seed
    from skypilot_tpu.models import mamba
    key = weights.seed_key(weights_seed)
    top = {'embed': ((cfg.vocab_size, cfg.dim), cfg.dim),
           'final_norm': cfg.dim}
    if not cfg.tie_embeddings:
        top['unembed'] = ((cfg.dim, cfg.vocab_size), cfg.dim)

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
            elif isinstance(spec, str):
                leaf = mamba.draw_vector(
                    spec, k, lead + mamba.vector_shape(spec, cfg),
                    cfg.mamba_d_conv)
            else:
                shape, fan = spec
                leaf = _matrix(k, shape=lead + tuple(shape), fan=fan,
                               dtype=jnp.dtype(cfg.dtype),
                               stacked=layers is not None)
            out[name] = jax.block_until_ready(leaf)
        return out

    depth = mamba.stack_depths(cfg)
    tree = build(top, None, '')
    for stack, plan in mamba.leaf_plan(cfg).items():
        tree[stack] = build(plan, depth[stack], stack + '/')
    return tree
