"""Latent attention (MLA) with dropless routed + shared experts behind
leading dense layers: the layer kinds ``ModelConfig`` names
``attn_kind='latent'`` and ``ffn_kind='routed_shared'`` (GLM-4.7-Flash's
``glm4_moe_lite``; the DeepSeek-V2/V3 block). The plain reference of the
same mathematics is ``models/reference/glm4_moe_lite.py``.

The parameter tree keeps ``llama``'s top level (``embed``, ``unembed``,
``final_norm``) and holds two layer stacks, each scanned as one compiled
block: ``dense_layers`` (the first ``n_dense_layers``, dense SwiGLU) and
``layers`` (the rest, routed + shared experts). A layer's cache row is
one normed latent of ``kv_lora_rank`` values and one roped key part of
``qk_rope_head_dim`` (``cfg.kv_spec``), shared by every head.

*Attention.* ``attn_fn`` decides the form (``ops/latent_attention.py``):
None is the expanded causal form over the sequence itself (a full
forward); the paged programs pass the absorbed form over their cached
rows. *Experts.* No capacity and no dropped assignment: assignments are
sorted by expert and multiplied group by group
(``ops/grouped_matmul.py``), so a token's result does not depend on its
batch companions, and rows that are not ``live`` route nowhere and read
no expert.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.ops import grouped_matmul as gmm_lib
from skypilot_tpu.ops import latent_attention

Params = Dict[str, Any]

# The selection-only correction bias is drawn at this standard
# deviation: sigmoid scores of fan-in scaled logits spread by ~0.2, so
# leaving the bias out changes the chosen experts of most tokens.
ROUTER_BIAS_STD = 0.1


def _attn_shapes(cfg: ModelConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape, fan-in) of one layer's attention projections."""
    d, h = cfg.dim, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        'wq_a': ((d, cfg.q_lora_rank), d),
        'wq_b': ((cfg.q_lora_rank, h, qk), cfg.q_lora_rank),
        'wkv_a': ((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
        'wkv_b': ((cfg.kv_lora_rank, h,
                   cfg.qk_nope_head_dim + cfg.v_head_dim),
                  cfg.kv_lora_rank),
        'wo': ((h, cfg.v_head_dim, d), h * cfg.v_head_dim),
    }


def _ffn_shapes(d: int, f: int):
    return {'w_gate': ((d, f), d), 'w_up': ((d, f), d),
            'w_down': ((f, d), f)}


def _norm_shapes(cfg: ModelConfig):
    return {'attn_norm': cfg.dim, 'ffn_norm': cfg.dim,
            'q_norm': cfg.q_lora_rank, 'kv_norm': cfg.kv_lora_rank}


def leaf_plan(cfg: ModelConfig) -> Dict[str, Any]:
    """The tree's structure as data: every leaf's per-layer shape with
    its fan-in (a matrix), its width (a norm, ones), or ``'bias'`` (the
    router's correction bias). ``init_params`` and the benchmark's
    on-device weight maker both build from it."""
    d, E = cfg.dim, cfg.n_routed_experts
    f_s = cfg.moe_ffn_dim * cfg.n_shared_experts
    attn = dict(_attn_shapes(cfg), **_norm_shapes(cfg))
    experts = {k: ((E,) + shape, fan)
               for k, (shape, fan) in _ffn_shapes(d, cfg.moe_ffn_dim).items()}
    return {
        'dense_layers': dict(attn, **_ffn_shapes(d, cfg.ffn_dim)),
        'layers': dict(attn, router=((d, E), d), router_bias='bias',
                       experts=experts, shared=_ffn_shapes(d, f_s)),
    }


def stack_depths(cfg: ModelConfig) -> Dict[str, int]:
    return {'dense_layers': cfg.n_dense_layers,
            'layers': cfg.n_layers - cfg.n_dense_layers}


def num_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameters of the whole model (norms included), or with
    ``active_only`` those a token is multiplied by: ``n_experts_per_token``
    of a layer's routed experts."""
    def count(tree):
        if isinstance(tree, dict):
            return sum(count(v) for v in tree.values())
        if tree == 'bias':
            return cfg.n_routed_experts
        if isinstance(tree, int):
            return tree
        n = 1
        for dim in tree[0]:
            n *= dim
        return n

    plan, depth = leaf_plan(cfg), stack_depths(cfg)
    total = sum(count(plan[s]) * depth[s] for s in plan)
    if active_only:
        total -= (count(plan['layers']['experts']) * depth['layers']
                  * (cfg.n_routed_experts - cfg.n_experts_per_token)
                  // cfg.n_routed_experts)
    return total + 2 * cfg.vocab_size * cfg.dim + cfg.dim


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Fan-in scaled normals, norms at one, router in float32."""
    from skypilot_tpu.models.llama import _dense_init
    keys = iter(jax.random.split(rng, 64))
    depth = stack_depths(cfg)

    def build(tree, L):
        out = {}
        for name, spec in tree.items():
            if isinstance(spec, dict):
                out[name] = build(spec, L)
            elif spec == 'bias':
                out[name] = (jax.random.normal(
                    next(keys), (L, cfg.n_routed_experts), jnp.float32)
                    * ROUTER_BIAS_STD)
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
        'dense_layers': build(plan['dense_layers'], depth['dense_layers']),
        'layers': build(plan['layers'], depth['layers']),
    }


def param_logical_axes(cfg: ModelConfig) -> Params:
    """``init_params``' structure with logical-axis tuples as leaves:
    heads and FFN widths over tp, experts over the expert axis, the
    low-rank bottlenecks replicated."""
    del cfg
    attn = {
        'attn_norm': ('layers', 'norm'), 'ffn_norm': ('layers', 'norm'),
        'q_norm': ('layers', 'norm'), 'kv_norm': ('layers', 'norm'),
        'wq_a': ('layers', 'embed', None),
        'wq_b': ('layers', None, 'heads', 'head_dim'),
        'wkv_a': ('layers', 'embed', None),
        'wkv_b': ('layers', None, 'heads', 'head_dim'),
        'wo': ('layers', 'heads', 'head_dim', 'embed'),
    }
    ffn = {'w_gate': ('layers', 'embed', 'mlp'),
           'w_up': ('layers', 'embed', 'mlp'),
           'w_down': ('layers', 'mlp', 'embed')}
    experts = {'w_gate': ('layers', 'expert', 'embed', 'mlp'),
               'w_up': ('layers', 'expert', 'embed', 'mlp'),
               'w_down': ('layers', 'expert', 'mlp', 'embed')}
    return {
        'embed': ('vocab_in', 'embed'), 'unembed': ('embed', 'vocab'),
        'final_norm': ('norm',),
        'dense_layers': dict(attn, **ffn),
        'layers': dict(attn, router=('layers', 'embed', None),
                       router_bias=('layers', None), experts=experts,
                       shared=dict(ffn)),
    }


def layer_stacks(params: Params, cfg: ModelConfig) -> List[Tuple[Any, int]]:
    """[(stacked layer params, index of the stack's first layer)] in
    layer order; a stack of no layer is left out."""
    return [(params[name], first) for name, first, n in (
        ('dense_layers', 0, cfg.n_dense_layers),
        ('layers', cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers))
        if n > 0]


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
def _attention(layer: Params, h: jax.Array, cfg: ModelConfig,
               positions: jax.Array, attn_fn):
    """``h`` [b,s,d] normed hidden states -> (attention output [b,s,d],
    the new cache rows (c_kv [b,s,1,r], k_rope [b,s,1,dr]))."""
    from skypilot_tpu.models.llama import rms_norm, rope
    dn, dr, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                 cfg.kv_lora_rank)
    scale = (dn + dr) ** -0.5
    c_q = rms_norm(jnp.einsum('bsd,dq->bsq', h, layer['wq_a']),
                   layer['q_norm'], cfg.norm_eps)
    q = jnp.einsum('bsq,qhk->bshk', c_q, layer['wq_b'])
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], positions,
                                       cfg.rope_theta)
    kv = jnp.einsum('bsd,dk->bsk', h, layer['wkv_a'])
    c_kv = rms_norm(kv[..., :r], layer['kv_norm'], cfg.norm_eps)
    k_rope = rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    w_k, w_v = layer['wkv_b'][..., :dn], layer['wkv_b'][..., dn:]
    if attn_fn is None:
        out = latent_attention.expanded_causal_attention(
            q_nope, q_rope, jnp.einsum('bsr,rhk->bshk', c_kv, w_k),
            k_rope, jnp.einsum('bsr,rhv->bshv', c_kv, w_v), scale=scale)
    else:
        q_lat = jnp.einsum('bshk,rhk->bshr', q_nope, w_k)
        out_lat = attn_fn(q_lat, q_rope, c_kv, k_rope, scale)
        out = jnp.einsum('bshr,rhv->bshv', out_lat.astype(h.dtype), w_v)
    proj = jnp.einsum('bshv,hvd->bsd', out, layer['wo'])
    return proj, (c_kv[:, :, None], k_rope[:, :, None])


# --------------------------------------------------------------------------
# Experts
# --------------------------------------------------------------------------
def route(layer: Params, x: jax.Array, cfg: ModelConfig):
    """x [T,d] -> (chosen experts [T,k] int32, their weights [T,k] f32).
    Scores are sigmoids in float32; the correction bias moves the
    choice only; the chosen scores are renormalised to sum to 1 and
    scaled by ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(jnp.einsum(
        'td,de->te', x.astype(jnp.float32), layer['router'],
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + layer['router_bias'],
                          cfg.n_experts_per_token)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), w


def routed_experts(experts: Params, expert_layer, x: jax.Array,
                   chosen: jax.Array, w: jax.Array,
                   live: Optional[jax.Array], cfg: ModelConfig):
    """sum_k w[t,k] SwiGLU_{chosen[t,k]}(x[t]) for the live rows of x
    [T,d], zeros for the others; and how many distinct experts were
    read (int32 scalar). ``experts`` is the whole stack [layers, E, ...]
    and ``expert_layer`` this layer's row of it: the kernel takes the
    stack as it lies, the layer's groups offset into it, and no layer's
    experts are ever sliced out as a copy (``llama.scan_layers``).

    Told which experts it holds (``cfg.held_experts`` of the
    ``n_routed_experts`` routed over, from ``first_held_expert``), the
    layer computes the chosen experts that lie in its range: an
    assignment to an absent expert owns no group and adds nothing, as a
    dead row's does. The count is then [2]: distinct experts read, and
    the live assignments that were held."""
    T, d = x.shape
    k, E = cfg.n_experts_per_token, cfg.held_experts
    n_stacked = experts['w_gate'].shape[0]
    experts = {name: leaf.reshape((n_stacked * E,) + leaf.shape[2:])
               for name, leaf in experts.items()}
    flat = chosen.reshape(T * k)
    partial = not cfg.holds_every_expert
    if partial:                         # into the held range, or E
        flat = flat - cfg.first_held_expert
        flat = jnp.where((flat >= 0) & (flat < E), flat, E)
    if live is not None:                # E: owns no group, sorts last
        flat = jnp.where(jnp.repeat(live, k), flat, E)
    # A counting sort by expert (a TPU sort of 32k keys takes 20 s to
    # compile and is slow to run): an assignment's row is its expert's
    # start plus its rank among the expert's assignments.
    mine = flat[:, None] == jnp.arange(E + 1)[None, :]        # [A, E+1]
    before = jnp.cumsum(mine, axis=0, dtype=jnp.int32) - mine
    rank = jnp.sum(jnp.where(mine, before, 0), axis=1)
    counts = jnp.sum(mine, axis=0, dtype=jnp.int32)           # [E+1]
    dest = (jnp.cumsum(counts) - counts)[flat] + rank         # [A]
    layer_sizes = counts[:E]
    sizes = lax.dynamic_update_slice(
        jnp.zeros((n_stacked * E,), jnp.int32), layer_sizes,
        (expert_layer * E,))
    source = jnp.zeros((T * k,), jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32), unique_indices=True)
    rows = x[source // k]                           # [T*k, d] by expert
    pad = -(T * k) % gmm_lib.row_tile(T * k)
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    gate = gmm_lib.grouped_matmul(rows, experts['w_gate'], sizes,
                                  out_dtype=x.dtype)
    up = gmm_lib.grouped_matmul(rows, experts['w_up'], sizes,
                                out_dtype=x.dtype)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    down = gmm_lib.grouped_matmul(h, experts['w_down'], sizes,
                                  out_dtype=jnp.float32)
    # Back to assignment order; rows of no group are selected away (they
    # hold whatever the buffer held, NaN included).
    keep = (flat < E)[:, None]
    down = jnp.where(keep, down[dest] * w.reshape(T * k, 1), 0.0)
    y = down.reshape(T, k, d).sum(1).astype(x.dtype)
    distinct = jnp.sum(layer_sizes > 0).astype(jnp.int32)
    if partial:
        return y, jnp.stack([distinct, jnp.sum(layer_sizes)])
    return y, distinct


def _moe_ffn(layer: Params, h: jax.Array, cfg: ModelConfig,
             live: Optional[jax.Array]):
    from skypilot_tpu.models.llama import _ffn
    b, s, d = h.shape
    flat = h.reshape(b * s, d)
    with jax.named_scope('moe_router'):
        chosen, w = route(layer, flat, cfg)
    with jax.named_scope('moe_experts'):
        y, distinct = routed_experts(
            layer['experts'], layer['expert_layer'], flat, chosen, w,
            None if live is None else
            jnp.broadcast_to(live, (b, s)).reshape(b * s), cfg)
    with jax.named_scope('moe_shared'):
        shared = _ffn(layer['shared'], h, cfg)
    return y.reshape(b, s, d) + shared, distinct


def layer_core(layer: Params, x: jax.Array, cfg: ModelConfig,
               positions: jax.Array, attn_fn,
               live: Optional[jax.Array] = None):
    """One layer of either stack (a routed layer has ``experts``).
    ``live`` ([b,s] or [b,1] bool, None = all) marks the rows that carry
    a token. Returns ``llama._layer_core``'s triple: (x, the new cache
    rows, and in the place of an auxiliary loss, which this router does
    not have, the distinct experts the layer read as int32)."""
    from skypilot_tpu.models.llama import _ffn, rms_norm
    with jax.named_scope('mla_attn'):
        proj, new_rows = _attention(
            layer, rms_norm(x, layer['attn_norm'], cfg.norm_eps), cfg,
            positions, attn_fn)
    x = x + proj
    h = rms_norm(x, layer['ffn_norm'], cfg.norm_eps)
    if 'experts' in layer:
        ffn_out, distinct = _moe_ffn(layer, h, cfg, live)
    else:
        with jax.named_scope('dense_ffn'):
            ffn_out = _ffn(layer, h, cfg)
        distinct = jnp.zeros((), jnp.int32)
    return x + ffn_out, new_rows, distinct
