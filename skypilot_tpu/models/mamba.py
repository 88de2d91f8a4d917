"""Mamba-1 selective state-space layers beside GQA layers in one model
(``ModelConfig.mixer_pattern`` of 'mamba' and 'gqa'; Jamba's hybrid
layout, arXiv:2403.19887), every layer's FFN a dense SwiGLU.
ai21labs/AI21-Jamba2-3B's block (HF ``JambaMambaMixer``); the plain
reference of the same mathematics is ``models/reference/jamba.py``.

A Mamba layer caches no rows. A sequence's whole past is one float32
state of ``d_state`` x ``I`` (I = ``mamba_expand`` x dim; kept channels
last, on the lanes) and the last ``d_conv - 1`` inputs of a causal
depthwise conv (``cfg.state_spec``). With ``h`` the normed layer input:

    [x; z]    = h W_in                               no bias
    x~        = SiLU(b_conv + causal depthwise conv(x))
    [d; B; C] = x~ W_x                               d: dt_rank wide
    d, B, C   = RMSNorm(d), RMSNorm(B), RMSNorm(C)   Jamba's three norms
    dt        = softplus(d W_dt + b_dt)              [I]
    S_t       = exp(dt_t A) * S_{t-1} + B_t (dt_t x~_t)^T,  A = -exp(A_log)
    y_t       = C_t^T S_t + D * x~_t
    out       = (y * SiLU(z)) W_out                  no bias

The scan itself is ``ops/mamba.py``; projections, convolution, norms and
gate are this file's ``mixer``, shared by every form.

The parameter tree keeps ``llama``'s top level (a tied model has no
``unembed``) and holds a stack a kind: ``layers`` (the 'gqa' layers) and
``mamba_layers``, each layer with its norms and dense FFN.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import kda
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.ops import mamba as mamba_ops

Params = Dict[str, Any]

# name of the stack that holds the layers of each kind
STACKS = {kind: kda.ALL_STACKS[kind] for kind in ('gqa', 'mamba')}


# --------------------------------------------------------------------------
# The tree, as data
# --------------------------------------------------------------------------
def leaf_plan(cfg: ModelConfig) -> Dict[str, Any]:
    """stack -> every leaf's per-layer shape with its fan-in (a matrix),
    its width (a norm, ones), or a name (``'A_log'``, ``'dt_bias'``,
    ``'conv_bias'``, ``'ones'``): a float32 vector with a draw of its
    own. ``init_params`` and the benchmark's on-device weight maker
    build from it."""
    d, f = cfg.dim, cfg.ffn_dim
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    I, N, R = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    taps = cfg.mamba_d_conv
    ffn = {'ffn_norm': d, 'w_gate': ((d, f), d), 'w_up': ((d, f), d),
           'w_down': ((f, d), f)}
    gqa = {'attn_norm': d, 'wq': ((d, h, hd), d), 'wk': ((d, hkv, hd), d),
           'wv': ((d, hkv, hd), d), 'wo': ((h, hd, d), h * hd)}
    mix = {
        'in_proj': ((d, 2 * I), d),             # [x; z]
        # one tap row a position: the last row multiplies the current token
        'conv': ((taps, I), taps),
        'x_proj': ((I, R + 2 * N), I),          # [dt; B; C]
        'dt_norm': R, 'b_norm': N, 'c_norm': N,
        'dt_proj': ((R, I), R), 'dt_bias': 'dt_bias',
        'A_log': 'A_log', 'D': 'ones',
        'out_proj': ((I, d), I),
    }
    if cfg.mamba_conv_bias:
        mix['conv_bias'] = 'conv_bias'
    return {'layers': dict(gqa, **ffn),
            'mamba_layers': dict({'attn_norm': d, 'mamba': mix}, **ffn)}


def vector_shape(kind: str, cfg: ModelConfig) -> Tuple[int, ...]:
    """A named vector leaf's per-layer shape: ``A_log`` [N, I] (the
    state's layout), the others [I]."""
    if kind == 'A_log':
        return (cfg.mamba_d_state, cfg.mamba_inner)
    return (cfg.mamba_inner,)


def draw_vector(kind: str, key: jax.Array, shape, taps: int) -> jax.Array:
    """A float32 vector leaf's draw (``shape`` with any leading axes),
    as Mamba's init: ``A_log`` = log(1..N) along the state, every
    channel alike; softplus(``dt_bias``) log-uniform in
    ``kda.DT_RANGE``; ``D`` one; the conv bias uniform in
    +-1/sqrt(taps) (a conv's own default)."""
    if kind == 'A_log':
        n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape)
    if kind == 'dt_bias':
        return kda.draw_vector('dt_bias', key, shape)
    if kind == 'conv_bias':
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return jnp.ones(shape, jnp.float32)


def stack_depths(cfg: ModelConfig) -> Dict[str, int]:
    kinds = cfg.layer_kinds
    return {STACKS[k]: kinds.count(k) for k in STACKS}


def _count(tree, cfg) -> int:
    if isinstance(tree, dict):
        return sum(_count(v, cfg) for v in tree.values())
    if isinstance(tree, str):
        return math.prod(vector_shape(tree, cfg))
    if isinstance(tree, int):
        return tree
    return math.prod(tree[0])


def num_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameters the model holds (norms included; a tied embedding
    once). Every one is active: the FFNs are dense."""
    del active_only
    plan, depth = leaf_plan(cfg), stack_depths(cfg)
    total = sum(_count(plan[s], cfg) * depth[s] for s in plan)
    heads = 1 if cfg.tie_embeddings else 2
    return total + heads * cfg.vocab_size * cfg.dim + cfg.dim


def mixer_params(cfg: ModelConfig) -> int:
    """One Mamba mixer's parameters."""
    return _count(leaf_plan(cfg)['mamba_layers']['mamba'], cfg)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Fan-in scaled normals in the model's dtype, norms at one, the
    scan's vectors in float32 as ``draw_vector`` draws them."""
    from skypilot_tpu.models.llama import _dense_init
    keys = iter(jax.random.split(rng, 64))
    depth = stack_depths(cfg)

    def build(tree, L):
        out = {}
        for name, spec in tree.items():
            if isinstance(spec, dict):
                out[name] = build(spec, L)
            elif isinstance(spec, str):
                out[name] = draw_vector(spec, next(keys),
                                        (L,) + vector_shape(spec, cfg),
                                        cfg.mamba_d_conv)
            elif isinstance(spec, int):
                out[name] = jnp.ones((L, spec), jnp.float32)
            else:
                shape, fan = spec
                out[name] = _dense_init(next(keys), (L,) + shape,
                                        cfg.dtype, fan)
        return out

    plan = leaf_plan(cfg)
    params = {
        'embed': _dense_init(next(keys), (cfg.vocab_size, cfg.dim),
                             cfg.dtype, cfg.dim),
        'final_norm': jnp.ones((cfg.dim,), jnp.float32),
        **{s: build(plan[s], depth[s]) for s in plan},
    }
    if not cfg.tie_embeddings:
        params['unembed'] = _dense_init(next(keys),
                                        (cfg.dim, cfg.vocab_size),
                                        cfg.dtype, cfg.dim)
    return params


def param_logical_axes(cfg: ModelConfig) -> Params:
    """The structure of ``init_params(cfg)`` with logical-axis tuples as
    leaves (no mesh is served yet: ``engine.refuse_unsupported``)."""
    ffn = {'ffn_norm': ('layers', 'norm'),
           'w_gate': ('layers', 'embed', 'mlp'),
           'w_up': ('layers', 'embed', 'mlp'),
           'w_down': ('layers', 'mlp', 'embed')}
    gqa = {'attn_norm': ('layers', 'norm'),
           'wq': ('layers', 'embed', 'heads', 'head_dim'),
           'wk': ('layers', 'embed', 'kv_heads', 'head_dim'),
           'wv': ('layers', 'embed', 'kv_heads', 'head_dim'),
           'wo': ('layers', 'heads', 'head_dim', 'embed')}
    vec = ('layers', None)
    mix = {'in_proj': ('layers', 'embed', None), 'conv': ('layers', None,
                                                          None),
           'x_proj': ('layers', None, None), 'dt_norm': vec,
           'b_norm': vec, 'c_norm': vec, 'dt_proj': ('layers', None, None),
           'dt_bias': vec, 'A_log': ('layers', None, None), 'D': vec,
           'out_proj': ('layers', None, 'embed')}
    if cfg.mamba_conv_bias:
        mix['conv_bias'] = vec
    axes = {'embed': ('vocab_in', 'embed'), 'final_norm': ('norm',),
            'layers': dict(gqa, **ffn),
            'mamba_layers': dict({'attn_norm': ('layers', 'norm'),
                                  'mamba': mix}, **ffn)}
    if not cfg.tie_embeddings:
        axes['unembed'] = ('embed', 'vocab')
    return axes


# --------------------------------------------------------------------------
# The mixer
# --------------------------------------------------------------------------
def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mixer(p: Params, h: jax.Array, cfg: ModelConfig, rec=None,
          live: Optional[jax.Array] = None, step_fn=None):
    """h [b,s,d] normed -> (y [b,s,d], the state after the run). ``rec``
    = (S [b, 1, N, I] float32, conv tail [b, taps-1, I]) the state before
    it, None = a sequence's start (zeros). ``live`` [b,s] or [b,1] bool
    (None = all) marks the rows that carry a token, a prefix of the run:
    the others move neither S nor the tail. ``step_fn`` (None:
    ``ops.mamba.step``) is the one-token scan, ``(S, dt, u, B, C, A) ->
    (y, S)``: a caller that keeps the state elsewhere (the decode
    program's kernel over the stacked state) passes its own and an S it
    does not read."""
    b, s, _ = h.shape
    I, N, R = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    f32 = jnp.float32
    if rec is None:
        rec = (jnp.zeros((b, 1, N, I), f32),
               jnp.zeros((b, cfg.mamba_d_conv - 1, I), h.dtype))
    S, tail = rec
    xz = jnp.einsum('bsd,dn->bsn', h, p['in_proj'])
    x, z = jnp.split(xz, 2, axis=-1)
    xc, seen = kda.short_conv(p['conv'], tail, x, p.get('conv_bias'))
    dbc = jnp.einsum('bsi,in->bsn', xc.astype(h.dtype), p['x_proj'],
                     preferred_element_type=f32)
    d, B, C = jnp.split(dbc, [R, R + N], axis=-1)
    eps = cfg.norm_eps
    d, B, C = (_rms(d, p['dt_norm'], eps), _rms(B, p['b_norm'], eps),
               _rms(C, p['c_norm'], eps))
    dt = jax.nn.softplus(jnp.einsum(
        'bsr,ri->bsi', d.astype(h.dtype), p['dt_proj'],
        preferred_element_type=f32) + p['dt_bias'])
    if live is None:
        n_live = jnp.full((b,), s, jnp.int32)
    else:
        live = jnp.broadcast_to(live, (b, s))
        dt = jnp.where(live[..., None], dt, 0.0)
        n_live = jnp.sum(live, axis=1, dtype=jnp.int32)
    tail = kda.next_tail(seen, n_live, cfg.mamba_d_conv, tail.dtype)
    u = dt * xc
    A = -jnp.exp(p['A_log'])
    if s == 1:
        y, S = (step_fn or mamba_ops.step)(
            None if S is None else S[:, 0], dt[:, 0], u[:, 0], B[:, 0],
            C[:, 0], A)
        y = y[:, None]
    else:
        with jax.named_scope('mamba_scan'):
            y, S = mamba_ops.scan(S[:, 0], dt, u, B, C, A)
    S = None if S is None else S[:, None]
    y = (y + p['D'] * xc) * jax.nn.silu(z.astype(f32))
    return jnp.einsum('bsi,id->bsd', y.astype(h.dtype), p['out_proj']), \
        (S, tail)
