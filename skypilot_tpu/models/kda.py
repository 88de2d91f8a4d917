"""Layers of different kinds in one model: a period pattern
(``ModelConfig.mixer_pattern``) of gated NoPE grouped-query attention
('gqa': ``llama._layer_core``'s GQA branch) and Kimi Delta Attention
('kda': this file; arXiv:2510.26692), every layer's FFN routed + shared
experts (``latent_moe``'s router and dropless experts) of which the
program may hold a range (``n_held_experts`` from ``first_held_expert``).
upstage/Solar-Open2-250B's block (``solar_open2``); the plain reference
of the same mathematics is ``models/reference/solar_open2.py``.

A KDA layer caches no rows. A sequence's whole past is a state of
``kda_heads`` float32 matrices ``[head_dim, head_dim]`` and the last
``kda_conv - 1`` inputs of a short causal convolution
(``cfg.state_spec``): with ``h`` the normed layer input, per head,

    q~, k~, v = SiLU(conv(W_q h)), SiLU(conv(W_k h)), SiLU(conv(W_v h))
    q = q~ / |q~| / sqrt(head_dim),  k = k~ / |k~|
    g = -exp(A_log) softplus(W_a_up W_a_down h + dt_bias)   log decay, a channel
    beta = 2 sigmoid(W_beta h)                              in (0, 2)
    S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T ;  o = S_t^T q
    y = W_o [RMSNorm(o) * sigmoid(W_g_up W_g_down h + b_g)]

The recurrence itself is ``ops/kda.py``: one step a token (decode) or
chunked (any run of tokens); projections, convolution, gates, output
norm and gate are this file's ``mixer`` and shared by both.

The parameter tree keeps ``llama``'s top level and holds a stack a kind:
``layers`` (the 'gqa' layers, layer-major) and ``kda_layers``, each with
its own router, held experts and shared expert.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.ops import kda as kda_ops

Params = Dict[str, Any]

# name of the stack that holds the layers of each kind
STACKS = {'gqa': 'layers', 'kda': 'kda_layers'}
QK_NORM_EPS = 1e-6
# The decay's draws (fla's KimiDeltaAttention): A uniform in [1, 16),
# softplus(dt_bias) log-uniform in [0.001, 0.1).
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


# --------------------------------------------------------------------------
# The tree, as data
# --------------------------------------------------------------------------
def _ffn_shapes(d: int, f: int):
    return {'w_gate': ((d, f), d), 'w_up': ((d, f), d),
            'w_down': ((f, d), f)}


def leaf_plan(cfg: ModelConfig) -> Dict[str, Any]:
    """stack -> every leaf's per-layer shape with its fan-in (a matrix),
    its width (a norm, ones), or a name (``'router_bias'``, ``'A_log'``,
    ``'dt_bias'``, ``'zeros'``): a vector with a draw of its own.
    ``init_params`` and the benchmark's on-device weight maker build
    from it."""
    d, E, held = cfg.dim, cfg.n_routed_experts, cfg.held_experts
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    H, dk, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    f_s = cfg.moe_ffn_dim * cfg.n_shared_experts
    ffn = {
        'ffn_norm': d, 'router': ((d, E), d), 'router_bias': 'router_bias',
        'experts': {k: ((held,) + shape, fan) for k, (shape, fan)
                    in _ffn_shapes(d, cfg.moe_ffn_dim).items()},
        'shared': _ffn_shapes(d, f_s),
    }
    gqa = {'attn_norm': d, 'wq': ((d, h, hd), d), 'wk': ((d, hkv, hd), d),
           'wv': ((d, hkv, hd), d), 'wo': ((h, hd, d), h * hd)}
    if cfg.attn_gate:
        gqa['w_attn_gate'] = ((d, h, hd), d)
    kda = {
        'wq': ((d, H, dk), d), 'wk': ((d, H, dk), d), 'wv': ((d, H, dk), d),
        # one tap row a position: the last row multiplies the current token
        'conv': ((cfg.kda_conv, 3 * H * dk), cfg.kda_conv),
        'a_down': ((d, r), d), 'a_up': ((r, H, dk), r),
        'A_log': 'A_log', 'dt_bias': 'dt_bias',
        'w_beta': ((d, H), d),
        'g_down': ((d, r), d), 'g_up': ((r, H, dk), r), 'g_bias': 'zeros',
        'o_norm': dk, 'wo': ((H, dk, d), H * dk),
    }
    return {'layers': dict(gqa, **ffn),
            'kda_layers': dict({'attn_norm': d, 'kda': kda}, **ffn)}


def vector_shape(kind: str, cfg: ModelConfig) -> Tuple[int, ...]:
    H, dk = cfg.kda_heads, cfg.kda_head_dim
    return {'router_bias': (cfg.n_routed_experts,), 'A_log': (H,),
            'dt_bias': (H, dk), 'zeros': (H, dk)}[kind]


def draw_vector(kind: str, key: jax.Array, shape) -> jax.Array:
    """A float32 vector leaf's draw (``shape`` with any leading axes)."""
    from skypilot_tpu.models.latent_moe import ROUTER_BIAS_STD
    if kind == 'router_bias':
        return jax.random.normal(key, shape, jnp.float32) * ROUTER_BIAS_STD
    if kind == 'A_log':
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          *A_RANGE))
    if kind == 'dt_bias':
        lo, hi = (math.log(x) for x in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    return jnp.zeros(shape, jnp.float32)


def stack_depths(cfg: ModelConfig) -> Dict[str, int]:
    kinds = cfg.layer_kinds
    return {STACKS[k]: kinds.count(k) for k in STACKS}


def num_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameters this program holds (norms included; the held experts,
    the vocabulary slice), or with ``active_only`` those a token is
    multiplied by HERE: of a layer's routed experts the held share of
    its ``n_experts_per_token``, on average."""
    def count(tree):
        if isinstance(tree, dict):
            return sum(count(v) for v in tree.values())
        if isinstance(tree, str):
            return math.prod(vector_shape(tree, cfg))
        if isinstance(tree, int):
            return tree
        return math.prod(tree[0])

    plan, depth = leaf_plan(cfg), stack_depths(cfg)
    total = sum(count(plan[s]) * depth[s] for s in plan)
    if active_only:
        expert = count(plan['layers']['experts']) // cfg.held_experts
        here = (cfg.n_experts_per_token * cfg.held_experts
                // cfg.n_routed_experts)
        total += cfg.n_layers * expert * (here - cfg.held_experts)
    return total + 2 * cfg.vocab_size * cfg.dim + cfg.dim


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Fan-in scaled normals, norms at one, router and the decay's
    vectors in float32."""
    from skypilot_tpu.models.llama import _dense_init
    keys = iter(jax.random.split(rng, 128))
    depth = stack_depths(cfg)

    def build(tree, L):
        out = {}
        for name, spec in tree.items():
            if isinstance(spec, dict):
                out[name] = build(spec, L)
            elif isinstance(spec, str):
                out[name] = draw_vector(spec, next(keys),
                                        (L,) + vector_shape(spec, cfg))
            elif isinstance(spec, int):
                out[name] = jnp.ones((L, spec), jnp.float32)
            else:
                shape, fan = spec
                dtype = jnp.float32 if name == 'router' else cfg.dtype
                out[name] = _dense_init(next(keys), (L,) + shape, dtype,
                                        fan)
        return out

    plan = leaf_plan(cfg)
    return {
        'embed': _dense_init(next(keys), (cfg.vocab_size, cfg.dim),
                             cfg.dtype, cfg.dim),
        'unembed': _dense_init(next(keys), (cfg.dim, cfg.vocab_size),
                               cfg.dtype, cfg.dim),
        'final_norm': jnp.ones((cfg.dim,), jnp.float32),
        **{s: build(plan[s], depth[s]) for s in plan},
    }


def param_logical_axes(cfg: ModelConfig) -> Params:
    """``init_params``' structure with logical-axis tuples as leaves
    (no mesh is served yet: ``engine.refuse_unsupported``)."""
    ffn = {'w_gate': ('layers', 'embed', 'mlp'),
           'w_up': ('layers', 'embed', 'mlp'),
           'w_down': ('layers', 'mlp', 'embed')}
    routed = {
        'ffn_norm': ('layers', 'norm'), 'router': ('layers', 'embed', None),
        'router_bias': ('layers', None), 'shared': dict(ffn),
        'experts': {'w_gate': ('layers', 'expert', 'embed', 'mlp'),
                    'w_up': ('layers', 'expert', 'embed', 'mlp'),
                    'w_down': ('layers', 'expert', 'mlp', 'embed')}}
    proj = ('layers', 'embed', 'heads', 'head_dim')
    gqa = {'attn_norm': ('layers', 'norm'), 'wq': proj,
           'wk': ('layers', 'embed', 'kv_heads', 'head_dim'),
           'wv': ('layers', 'embed', 'kv_heads', 'head_dim'),
           'wo': ('layers', 'heads', 'head_dim', 'embed')}
    if cfg.attn_gate:
        gqa['w_attn_gate'] = proj
    low = ('layers', None, 'heads', 'head_dim')
    kda = {'wq': proj, 'wk': proj, 'wv': proj, 'conv': ('layers', None, None),
           'a_down': ('layers', 'embed', None), 'a_up': low,
           'A_log': ('layers', 'heads'),
           'dt_bias': ('layers', 'heads', 'head_dim'),
           'w_beta': ('layers', 'embed', 'heads'),
           'g_down': ('layers', 'embed', None), 'g_up': low,
           'g_bias': ('layers', 'heads', 'head_dim'),
           'o_norm': ('layers', 'norm'),
           'wo': ('layers', 'heads', 'head_dim', 'embed')}
    return {
        'embed': ('vocab_in', 'embed'), 'unembed': ('embed', 'vocab'),
        'final_norm': ('norm',),
        'layers': dict(gqa, **routed),
        'kda_layers': dict({'attn_norm': ('layers', 'norm'), 'kda': kda},
                           **routed),
    }


# --------------------------------------------------------------------------
# The layers, period by period
# --------------------------------------------------------------------------
def scan_periods(body, carry, params: Params, cfg: ModelConfig):
    """``lax.scan`` over the periods of ``cfg.mixer_pattern``; inside a
    period its layers in order: a run of layers of one kind is one
    ``lax.scan`` over the run (one traced body a kind, however many
    layers), a single layer is traced in line. ``body(carry, (layer,
    li))`` gets ``li`` = the layer's index AMONG ITS KIND (its row of
    that kind's stacked cache or state). The stacks are scan inputs at
    both levels, [periods, layers a period, ...]. A stack's ``experts``
    are not scanned (``llama.scan_layers``):
    the layer gets the whole stack and ``expert_layer``, its row of it.
    Returns (carry, {kind: that kind's per-layer outputs, layer-major})."""
    pattern = cfg.mixer_pattern
    n_periods = cfg.n_layers // len(pattern)
    per = {kind: pattern.count(kind) for kind in STACKS}
    runs = []                   # (kind, first among the period's, length)
    for i, kind in enumerate(pattern):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, pattern[:i].count(kind), 1])
    held = {kind: params[name]['experts'] for kind, name in STACKS.items()}
    scanned = {kind: jax.tree.map(
        lambda a, n=per[kind]: a.reshape((n_periods, n) + a.shape[1:]),
        {k: v for k, v in params[name].items() if k != 'experts'})
        for kind, name in STACKS.items()}

    def period(carry, xs):
        stacks, p = xs
        ys = {kind: [] for kind in STACKS}
        for kind, first, length in runs:
            base = p * per[kind] + first

            def one(carry, layer_j, kind=kind, base=base):
                layer, j = layer_j
                li = base + j
                return body(carry, (dict(layer, expert_layer=li,
                                         experts=held[kind]), li))

            run = jax.tree.map(lambda a: a[first:first + length],
                               stacks[kind])
            if length == 1:
                carry, y = one(carry, (jax.tree.map(lambda a: a[0], run), 0))
                y = jax.tree.map(lambda a: a[None], y)
            else:
                carry, y = lax.scan(one, carry, (run, jnp.arange(length)))
            ys[kind].append(y)
        return carry, {kind: jax.tree.map(
            lambda *a: jnp.concatenate(a), *v) for kind, v in ys.items()}

    carry, ys = lax.scan(period, carry, (scanned, jnp.arange(n_periods)))
    return carry, jax.tree.map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), ys)


# --------------------------------------------------------------------------
# The mixer
# --------------------------------------------------------------------------
def gates(p: Params, h: jax.Array, cfg: ModelConfig):
    """h [b,s,d] normed -> (g [b,s,H,dk] log decay <= 0, beta [b,s,H] in
    (0, 2), output gate [b,s,H,dk] in (0, 1)); float32."""
    f32 = jnp.float32
    a = jnp.einsum('bsr,rhk->bshk', jnp.einsum('bsd,dr->bsr', h, p['a_down']),
                   p['a_up'], preferred_element_type=f32)
    g = -jnp.exp(p['A_log'])[:, None] * jax.nn.softplus(a + p['dt_bias'])
    beta = 2.0 * jax.nn.sigmoid(jnp.einsum(
        'bsd,dh->bsh', h, p['w_beta'], preferred_element_type=f32))
    out = jnp.einsum('bsr,rhk->bshk',
                     jnp.einsum('bsd,dr->bsr', h, p['g_down']), p['g_up'],
                     preferred_element_type=f32)
    return g, beta, jax.nn.sigmoid(out + p['g_bias'])


def short_conv(w: jax.Array, tail: jax.Array, x: jax.Array):
    """Causal depthwise convolution over time and SiLU. w [taps, c]
    (the last row multiplies the current token); tail [b, taps-1, c] the
    inputs before x; x [b,s,c]. Returns (SiLU(conv) [b,s,c] float32, the
    inputs [b, taps-1+s, c] the next tail is cut from)."""
    taps, s = w.shape[0], x.shape[1]
    seen = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(seen[:, j:j + s].astype(jnp.float32) * wf[j]
            for j in range(taps))
    return jax.nn.silu(y), seen


def l2_normalise(x: jax.Array) -> jax.Array:
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + QK_NORM_EPS)


def output(p: Params, o: jax.Array, gate: jax.Array, cfg: ModelConfig,
           dtype) -> jax.Array:
    """o [b,s,H,dv] float32 -> W_o [RMSNorm_head(o) * gate] [b,s,d]."""
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
        * p['o_norm']
    return jnp.einsum('bshv,hvd->bsd', (o * gate).astype(dtype), p['wo'])


def mixer(p: Params, h: jax.Array, cfg: ModelConfig, rec=None,
          live: Optional[jax.Array] = None, step_fn=None):
    """h [b,s,d] normed -> (y [b,s,d], the state after the run). ``rec``
    = (S [b,H,dk,dv] float32, conv tail [b, taps-1, 3*H*dk]) the state
    before it, None = a sequence's start (zeros). ``live`` [b,s] or
    [b,1] bool (None = all) marks the rows that carry a token, a prefix
    of the run: the others move neither S nor the tail. ``step_fn``
    (None: ``ops.kda.recurrent_step``) is the one-token recurrence,
    ``(S, q, k, v, g, beta) -> (o, S)``: a caller that keeps the state
    elsewhere (the decode program's kernel over the stacked state)
    passes its own and an S it does not read."""
    b, s, _ = h.shape
    H, dk = cfg.kda_heads, cfg.kda_head_dim
    if rec is None:
        rec = (jnp.zeros((b, H, dk, dk), jnp.float32),
               jnp.zeros((b, cfg.kda_conv - 1, 3 * H * dk), h.dtype))
    S, tail = rec
    step_fn = step_fn or kda_ops.recurrent_step
    x = jnp.concatenate(
        [jnp.einsum('bsd,dhk->bshk', h, p[w]).reshape(b, s, H * dk)
         for w in ('wq', 'wk', 'wv')], axis=-1)
    qkv, seen = short_conv(p['conv'], tail, x)
    q, k, v = (a.reshape(b, s, H, dk) for a in jnp.split(qkv, 3, axis=-1))
    q, k = l2_normalise(q) * dk ** -0.5, l2_normalise(k)
    g, beta, gate = gates(p, h, cfg)
    if live is None:
        n_live = jnp.full((b,), s, jnp.int32)
    else:
        live = jnp.broadcast_to(live, (b, s))
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
        n_live = jnp.sum(live, axis=1, dtype=jnp.int32)
    # The tail after the run: the last taps-1 inputs up to the last live
    # row (the old tail itself where no row is live).
    rows = n_live[:, None] + jnp.arange(cfg.kda_conv - 1)[None, :]
    tail = jnp.take_along_axis(seen, rows[..., None], axis=1).astype(
        tail.dtype)
    if s == 1:
        o, S = step_fn(S, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        o = o[:, None]
    else:
        o, S = kda_ops.chunked(S, q, k, v, g, beta)
    return output(p, o, gate, cfg, h.dtype), (S, tail)
