"""Ouro-2.6B's serving weights made on the device from the seed: the tree
``llama.init_params`` gives for a looped configuration (sandwich norms,
the exit gate), in bf16 as published, 5.34 GB. ``init_params`` itself
draws every stacked leaf whole in float32 first (the 48-layer ``w_gate``
is 2.2 GB that way, beside the tree so far); here each leaf is drawn in
one jitted call of its own and a stacked leaf one layer at a time inside
it, so the transient is one layer's random bits.

Values as ``init_params`` (``assumed`` in the configuration file):
normals scaled by the fan-in, every norm at one, the gate's weight drawn
like any matrix and its bias zero.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

from perfbench import weights

# leaf -> the axes of its per-layer shape that a matmul contracts,
# counted from the end (so that the layer axis does not matter). ``w`` is
# the exit gate's Linear(dim -> 1), held as a vector.
FAN_IN_AXES = {
    'embed': (-1,), 'unembed': (-2,), 'w': (-1,),
    'wq': (-3,), 'wk': (-3,), 'wv': (-3,), 'wo': (-3, -2),
    'w_gate': (-2,), 'w_up': (-2,), 'w_down': (-2,),
}


def fan_in(name: str, shape) -> int:
    if name not in FAN_IN_AXES:
        raise ValueError(f'perfbench/weights_ouro.py does not know leaf '
                         f'{name!r}')
    return math.prod(shape[a] for a in FAN_IN_AXES[name])


@functools.partial(jax.jit, static_argnames=('name', 'shape', 'dtype',
                                             'stacked'))
def _make_leaf(key, *, name, shape, dtype, stacked):
    if name.endswith('norm'):
        return jnp.ones(shape, dtype)
    if name == 'b':
        return jnp.zeros(shape, dtype)
    scale = fan_in(name, shape) ** -0.5

    def one(k, sub):
        return (jax.random.normal(k, sub, jnp.float32) * scale
                ).astype(dtype)

    if stacked:
        return jax.lax.map(lambda k: one(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    return one(key, shape)


def make_tree(cfg, seed: int):
    """``init_params(cfg)``'s structure, shapes and dtypes, drawn leaf
    by leaf on the default device."""
    from skypilot_tpu.models import llama
    shapes = jax.eval_shape(functools.partial(llama.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    key = weights.seed_key(seed)

    def build(tree, stacked, path):
        out = {}
        for name, leaf in sorted(tree.items()):
            # crc32: a stable number of the leaf's path (hash() is salted)
            k = jax.random.fold_in(
                key, zlib.crc32((path + name).encode()) & 0x7fffffff)
            if isinstance(leaf, dict):
                out[name] = build(leaf, stacked or name == 'layers',
                                  path + name + '/')
            else:
                out[name] = _make_leaf(
                    k, name=name, shape=tuple(leaf.shape),
                    dtype=jnp.dtype(leaf.dtype), stacked=stacked)
                jax.block_until_ready(out[name])
        return out

    return build(shapes, False, '')
