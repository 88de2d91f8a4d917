"""Serving weights made on the device from the seed, int8 as served.

The server's own ways to a 7B tree are a checkpoint on disk (minutes, and
more host memory than the one-chip machine has) or ``init_params`` of the
whole bf16 tree (15.2 GB: does not fit beside the first int8 leaf). Here
each leaf of ``jax.eval_shape(llama.init_params)`` is drawn and quantized
in one jitted call of its own; a stacked layer leaf is drawn one layer at
a time inside that call, so the transient is one layer's random bits and
the peak is the int8 tree so far plus a few hundred MB.

The values are ``init_params``'s (normal, fan-in scaled, bf16) except the
q/k/v biases, which are drawn at 0.02 instead of zero so that a bias
path that is wrong shows in ``correct``.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import llama, quantization

BIAS_SCALE = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key from any whole-number seed (the driver's pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(4)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl='rbg')


def _fan_in(name: str, shape) -> int:
    if name in quantization.REDUCE_AXES:
        return math.prod(shape[a] for a in quantization.REDUCE_AXES[name])
    if name == 'embed':
        return shape[-1]
    raise ValueError(f'perfbench/weights.py does not know leaf {name!r}')


@functools.partial(jax.jit, static_argnames=('name', 'shape', 'dtype',
                                             'stacked', 'norm_fill'))
def _make_leaf(key, *, name, shape, dtype, stacked, norm_fill):
    if name.endswith('norm'):
        return jnp.full(shape, norm_fill, dtype)
    if name in ('bq', 'bk', 'bv'):
        return BIAS_SCALE * jax.random.normal(key, shape, dtype)
    scale = _fan_in(name, shape) ** -0.5
    quantized = name in quantization.REDUCE_AXES

    def one(k, sub):
        w = (jax.random.normal(k, sub, jnp.float32) * scale).astype(dtype)
        if not quantized:
            return w
        # The program's own quantizer, on a one-layer stack so that its
        # contracting axes (counted with the layer axis) still apply.
        if stacked:
            q = quantization.quantize_params({'layers': {name: w[None]}})
            return jax.tree.map(lambda a: a[0], q['layers'][name])
        return quantization.quantize_params({name: w})[name]

    if stacked:
        return jax.lax.map(lambda k: one(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    return one(key, shape)


def make_int8_tree(cfg, seed: int):
    """The tree ``quantize_params(init_params(...))`` would give, made
    leaf by leaf on the default device."""
    shapes = jax.eval_shape(functools.partial(llama.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    key = seed_key(seed)
    norm_fill = 0.0 if cfg.norm_plus_one else 1.0

    def build(tree, stacked, path):
        out = {}
        for name, leaf in sorted(tree.items()):
            # crc32: a stable number of the leaf's path (hash() is salted)
            k = jax.random.fold_in(
                key, zlib.crc32((path + name).encode()) & 0x7fffffff)
            if isinstance(leaf, dict):
                if name != 'layers':
                    raise ValueError('perfbench/weights.py does not know '
                                     f'subtree {name!r}')
                out[name] = build(leaf, True, name + '/')
            else:
                out[name] = _make_leaf(
                    k, name=name, shape=tuple(leaf.shape),
                    dtype=jnp.dtype(leaf.dtype), stacked=stacked,
                    norm_fill=norm_fill)
                jax.block_until_ready(out[name])
        return out

    return build(shapes, False, '')

