"""Llama-family decoder-only transformer, TPU-first.

Replaces the reference's *recipe* approach (``llm/llama-3/llama3.yaml``
launches vLLM; ``examples/tpu/v6e/`` launches HF+PyTorch/XLA) with an in-tree
engine designed for XLA:

- Pure-functional: params are a pytree; every entry has a parallel tuple of
  logical axis names (``param_logical_axes``) mapped to mesh axes by
  ``skypilot_tpu.parallel.mesh`` rules — FSDP/TP/SP/EP are sharding rules,
  not code paths.
- ``lax.scan`` over stacked layer params: one compiled block regardless of
  depth (fast compiles, constant-size HLO), with optional per-layer
  rematerialization (``jax.checkpoint``) for training.
- bf16 activations/params, fp32 attention logits + softmax, fp32 norms —
  the standard TPU numerics recipe.
- GQA + RoPE + SwiGLU; MoE FFN is delegated to ``models.moe`` when
  ``cfg.is_moe`` (Mixtral-class, expert-parallel over the mesh).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.ops.attention import attention, cached_attention

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# Initialization
# --------------------------------------------------------------------------
def _dense_init(key, shape, dtype, fan_in):
    scale = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize parameters. Layer params are stacked on a leading
    ``layers`` axis for lax.scan."""
    if cfg.latent:
        from skypilot_tpu.models import latent_moe
        return latent_moe.init_params(rng, cfg)
    if cfg.mixer_pattern:
        from skypilot_tpu.models import kda
        return kda.init_params(rng, cfg)
    d, hd = cfg.dim, cfg.head_dim
    n_h, n_kv, f, L = cfg.n_heads, cfg.n_kv_heads, cfg.ffn_dim, cfg.n_layers
    keys = jax.random.split(rng, 8)

    def stack_init(key, shape, fan_in):
        ks = jax.random.split(key, L)
        return jnp.stack([_dense_init(k, shape, cfg.dtype, fan_in)
                          for k in ks])

    params: Params = {
        'embed': _dense_init(keys[0], (cfg.vocab_size, d), cfg.dtype, d),
        'final_norm': (jnp.zeros((d,), jnp.float32) if cfg.norm_plus_one
                       else jnp.ones((d,), jnp.float32)),
        'layers': {
            'attn_norm': (jnp.zeros((L, d), jnp.float32)
                          if cfg.norm_plus_one
                          else jnp.ones((L, d), jnp.float32)),
            'ffn_norm': (jnp.zeros((L, d), jnp.float32)
                         if cfg.norm_plus_one
                         else jnp.ones((L, d), jnp.float32)),
            'wq': stack_init(keys[2], (d, n_h, hd), d),
            'wk': stack_init(keys[3], (d, n_kv, hd), d),
            'wv': stack_init(keys[4], (d, n_kv, hd), d),
            'wo': stack_init(keys[5], (n_h, hd, d), n_h * hd),
        },
    }
    if cfg.post_norms:                  # sandwich: a norm on each branch's output
        params['layers'].update({
            name: jnp.zeros_like(params['layers']['attn_norm'])
            if cfg.norm_plus_one
            else jnp.ones_like(params['layers']['attn_norm'])
            for name in ('attn_post_norm', 'ffn_post_norm')})
    if cfg.exit_gate:                   # Linear(dim -> 1) after every pass
        params['exit_gate'] = {
            'w': _dense_init(jax.random.fold_in(keys[7], 1), (d,),
                             cfg.dtype, d),
            'b': jnp.zeros((1,), jnp.float32),
        }
    if cfg.qkv_bias:                    # Qwen2-family attention biases
        params['layers'].update({
            'bq': jnp.zeros((L, n_h, hd), jnp.float32),
            'bk': jnp.zeros((L, n_kv, hd), jnp.float32),
            'bv': jnp.zeros((L, n_kv, hd), jnp.float32),
        })
    if not cfg.tie_embeddings:
        params['unembed'] = _dense_init(keys[1], (d, cfg.vocab_size),
                                        cfg.dtype, d)
    if cfg.is_moe:
        from skypilot_tpu.models import moe
        params['layers'].update(moe.init_moe_params(keys[6], cfg))
    else:
        k1, k2, k3 = jax.random.split(keys[6], 3)
        params['layers'].update({
            'w_gate': stack_init(k1, (d, f), d),
            'w_up': stack_init(k2, (d, f), d),
            'w_down': stack_init(k3, (f, d), f),
        })
    if cfg.lora_enabled:
        from skypilot_tpu.models import lora
        params['layers']['lora'] = lora.init_lora_layers(keys[7], cfg)
    return params


def param_logical_axes(cfg: ModelConfig) -> Params:
    """Same structure as ``init_params``, with logical-axis tuples as leaves.

    The leading scan axis is 'layers' (never sharded)."""
    if cfg.latent:
        from skypilot_tpu.models import latent_moe
        return latent_moe.param_logical_axes(cfg)
    if cfg.mixer_pattern:
        from skypilot_tpu.models import kda
        return kda.param_logical_axes(cfg)
    axes: Params = {
        'embed': ('vocab_in', 'embed'),
        'final_norm': ('norm',),
        'layers': {
            'attn_norm': ('layers', 'norm'),
            'ffn_norm': ('layers', 'norm'),
            'wq': ('layers', 'embed', 'heads', 'head_dim'),
            'wk': ('layers', 'embed', 'kv_heads', 'head_dim'),
            'wv': ('layers', 'embed', 'kv_heads', 'head_dim'),
            'wo': ('layers', 'heads', 'head_dim', 'embed'),
        },
    }
    if cfg.post_norms:
        axes['layers'].update({
            'attn_post_norm': ('layers', 'norm'),
            'ffn_post_norm': ('layers', 'norm'),
        })
    if cfg.exit_gate:
        axes['exit_gate'] = {'w': ('norm',), 'b': (None,)}
    if cfg.qkv_bias:
        axes['layers'].update({
            'bq': ('layers', 'heads', 'head_dim'),
            'bk': ('layers', 'kv_heads', 'head_dim'),
            'bv': ('layers', 'kv_heads', 'head_dim'),
        })
    if not cfg.tie_embeddings:
        axes['unembed'] = ('embed', 'vocab')
    if cfg.is_moe:
        from skypilot_tpu.models import moe
        axes['layers'].update(moe.moe_logical_axes(cfg))
    else:
        axes['layers'].update({
            'w_gate': ('layers', 'embed', 'mlp'),
            'w_up': ('layers', 'embed', 'mlp'),
            'w_down': ('layers', 'mlp', 'embed'),
        })
    if cfg.lora_enabled:
        from skypilot_tpu.models import lora
        axes['layers']['lora'] = lora.lora_logical_axes(cfg)
    return axes


# --------------------------------------------------------------------------
# KV cache
# --------------------------------------------------------------------------
class KVCache(NamedTuple):
    """The contiguous cache of ``forward(cache=...)``, the plain cached
    forward pass (no engine holds one: serving decodes through the
    paged pool, ``inference/paged.py``). k/v: [layers, batch, max_seq,
    kv_heads, head_dim]; length: [batch] valid entries per sequence.

    int8 mode (``create(..., quantized=True)``): k/v are int8 with
    per-(layer, row, position, head) fp32 absmax/127 scales, written
    through :func:`quantize_kv_rows` and contracted in int8
    (``cached_attention``); int4 packs two codes a byte."""
    k: jax.Array
    v: jax.Array
    length: jax.Array
    k_scale: Optional[jax.Array] = None    # [L, b, S, hkv, 1] fp32
    v_scale: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def packed(self) -> bool:
        """int4 mode: k/v hold two nibble codes per byte (uint8,
        head_dim halved); scales ride the int8 layout unchanged."""
        return self.k.dtype == jnp.uint8

    @classmethod
    def create(cls, cfg: ModelConfig, batch: int, max_seq: int,
               quantized: bool = False,
               kv_dtype: Optional[str] = None) -> 'KVCache':
        if kv_dtype is None:
            kv_dtype = 'int8' if quantized else 'bf16'
        shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        length = jnp.zeros((batch,), jnp.int32)
        if kv_dtype == 'int4':
            if cfg.head_dim % 2:
                raise ValueError('int4 KV needs an even head_dim')
            pshape = shape[:-1] + (cfg.head_dim // 2,)
            sshape = shape[:-1] + (1,)
            return cls(k=jnp.zeros(pshape, jnp.uint8),
                       v=jnp.zeros(pshape, jnp.uint8),
                       length=length,
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))
        if kv_dtype == 'int8' or quantized:
            sshape = shape[:-1] + (1,)
            return cls(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       length=length,
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))
        return cls(k=jnp.zeros(shape, cfg.dtype),
                   v=jnp.zeros(shape, cfg.dtype),
                   length=length)


def cache_logical_axes(quantized: bool = False) -> KVCache:
    kv = ('layers', 'batch', None, 'kv_heads', 'head_dim')
    if quantized:
        # fp32 scales ride the same layout; their unit head_dim is
        # replicated by the divisibility-aware spec mapping.
        return KVCache(k=kv, v=kv, length=('batch',),
                       k_scale=kv, v_scale=kv)
    return KVCache(k=kv, v=kv, length=('batch',))


def quantize_kv_rows(rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., d] bf16 rows -> (int8 rows, [..., 1] fp32 scales)."""
    rf = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(rf), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(rf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_kv_rows4(rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., d] bf16 rows -> (packed uint8 [..., d//2] nibble rows,
    [..., 1] fp32 scales). Same absmax discipline as int8 at 4-bit
    range (absmax/7, clip +-7); packing rides
    :func:`quantization.pack_int4` along the HEAD_DIM axis so every
    token row stays self-contained — single-row appends (decode ring
    merges, spec commits) never straddle a byte boundary the way a
    page-axis packing would."""
    from skypilot_tpu.models import quantization
    rf = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(rf), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(rf / scale), -7, 7).astype(jnp.int8)
    return quantization.pack_int4(q, axis=-1), scale


def merge_rows_into_cache(cache: KVCache, k_rows: jax.Array,
                          v_rows: jax.Array, starts: jax.Array,
                          new_length: jax.Array) -> KVCache:
    """Scatter new [L, b, n, hkv, d] KV rows into the cache at each
    batch row's ``starts`` offset, quantizing on the way in when the
    cache is int8."""

    def write(c, n, start):            # c [L,S,h,d] <- n [L,n,h,d] @ start
        return lax.dynamic_update_slice(c, n, (0, start, 0, 0))

    def scatter(c, rows):
        return jax.vmap(write, in_axes=(1, 1, 0), out_axes=1)(
            c, rows.astype(c.dtype), starts)

    if cache.quantized:
        quant = quantize_kv_rows4 if cache.packed else quantize_kv_rows
        kq, ks = quant(k_rows)
        vq, vs = quant(v_rows)
        return KVCache(k=scatter(cache.k, kq), v=scatter(cache.v, vq),
                       length=new_length,
                       k_scale=scatter(cache.k_scale, ks),
                       v_scale=scatter(cache.v_scale, vs))
    return KVCache(k=scatter(cache.k, k_rows),
                   v=scatter(cache.v, v_rows), length=new_length)


# --------------------------------------------------------------------------
# Building blocks
# --------------------------------------------------------------------------
def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             plus_one: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    scale = (1.0 + w) if plus_one else w
    return (xf * lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _embed_tokens(params: Params, tokens: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    table = params['embed']
    if tokens.shape[1] > 1 and _in_multidevice_mesh():
        # Training/prefill under a mesh: a gather from the fsdp-sharded
        # table forces an involuntary full rematerialization in the SPMD
        # partitioner (gather output is embed-sharded, activations are
        # batch-sharded). A one-hot matmul partitions cleanly and rides
        # the MXU — the TPU-idiomatic embedding (MaxText's iota-embed).
        # Decode (s == 1) keeps the gather: a per-step one-hot would
        # stream the whole table instead of b rows.
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=table.dtype)
        x = jnp.einsum('bsv,vd->bsd', oh, table)
    else:
        x = table[tokens]
    if cfg.scale_embeddings:                  # Gemma: sqrt(dim) input scale
        x = (x.astype(jnp.float32) * cfg.dim ** 0.5).astype(x.dtype)
    return x


def _unembed_logits(params: Params, x: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:                    # Gemma: unembed = embed^T
        return jnp.einsum('bsd,vd->bsv', x, params['embed'],
                          preferred_element_type=jnp.float32)
    from skypilot_tpu.models.quantization import qeinsum
    return qeinsum('bsd,dv->bsv', x, params['unembed'],
                   out_dtype=jnp.float32)


# Constrained decoding masks to a large-negative, FINITE value: -inf
# would make a fully-masked row all-NaN under softmax and trip the
# nonfinite-token eviction guard on a healthy request, and masked
# positions must stay orderable under temperature scaling.
VOCAB_MASK_NEG = -1e9


def apply_vocab_mask(logits: jax.Array,
                     vocab_mask: Optional[jax.Array]) -> jax.Array:
    """Constrained-decoding vocab mask (True = token allowed) applied
    at a sampling point. ``vocab_mask`` is [b, vocab]; extra position
    axes of ``logits`` (the speculative [b, k+1, vocab] verify and the
    all-positions prefill) broadcast after the batch axis. None = no
    constraint (byte-identical logits)."""
    if vocab_mask is None:
        return logits
    while vocab_mask.ndim < logits.ndim:
        vocab_mask = vocab_mask[:, None]
    return jnp.where(vocab_mask, logits,
                     jnp.asarray(VOCAB_MASK_NEG, logits.dtype))


def filtered_logits(logits: jax.Array, temps: jax.Array,
                    topks: jax.Array, topps: jax.Array,
                    vocab_mask: Optional[jax.Array] = None) -> jax.Array:
    """Temperature-scaled, top-k/top-p-masked logits over the LAST axis:
    kept tokens carry their scaled value, filtered ones -inf, so
    ``jax.random.categorical`` over the result draws from exactly the
    engines' sampling distribution. ``temps``/``topks``/``topps``
    broadcast over ``logits.shape[:-1]`` — the single-position decode
    sampler ([b, vocab]) and the speculative multi-position verify
    ([b, k+1, vocab]) share this one implementation, which is what
    makes rejection-sampling acceptance distribution-preserving.

    Filter semantics (identical to the historical ``sample_tokens``):
    top-k <= 0 and top-p >= 1 disable their filters; nucleus keeps the
    smallest prefix of the sorted distribution whose mass reaches
    top_p (the top-1 token always survives). Rows with temp <= 0 are
    scaled by 1/1e-6 — callers take the greedy argmax for those rows
    instead of sampling. ``vocab_mask`` (constrained decoding) composes
    here, at the one shared sampling point, BEFORE temperature/top-k/
    top-p so the filters act on the constrained distribution."""
    logits = apply_vocab_mask(logits, vocab_mask)
    shape = logits.shape[:-1]
    temps = jnp.broadcast_to(temps, shape)[..., None]
    topks = jnp.broadcast_to(topks, shape)[..., None]
    topps = jnp.broadcast_to(topps, shape)[..., None]
    scaled = logits / jnp.maximum(temps, 1e-6)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    idx = jnp.clip(topks - 1, 0, logits.shape[-1] - 1)
    kth = jnp.take_along_axis(sorted_desc, idx, axis=-1)
    thr_k = jnp.where(topks > 0, kth, -jnp.inf)
    masked_sorted = jnp.where(sorted_desc >= thr_k, sorted_desc,
                              -jnp.inf)
    probs = jax.nn.softmax(masked_sorted.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < topps
    thr_p = jnp.min(jnp.where(keep, masked_sorted, jnp.inf), axis=-1,
                    keepdims=True)
    thr = jnp.maximum(thr_k, jnp.where(topps < 1.0,
                                       thr_p.astype(scaled.dtype),
                                       -jnp.inf))
    return jnp.where(scaled >= thr, scaled, -jnp.inf)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [b, s, h, d], positions: [b, s]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def _ambient_mesh():
    """The (abstract) mesh of the enclosing ``jax.set_mesh`` context, or
    None. ``get_abstract_mesh`` because this is read while tracing,
    where ``jax.sharding.get_mesh`` refuses to answer."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _in_mesh_context() -> bool:
    return _ambient_mesh() is not None


def _in_multidevice_mesh() -> bool:
    """True when the ambient mesh spans more than one device (the case
    where gather-vs-one-hot embedding choice matters)."""
    m = _ambient_mesh()
    return m is not None and m.size > 1


def _pp_mesh():
    """The ambient mesh iff its pp axis is > 1 (else None)."""
    env_mesh = _ambient_mesh()
    if env_mesh is None:
        return None
    return env_mesh if env_mesh.shape.get('pp', 1) > 1 else None


import threading as _threading

_manual_region = _threading.local()


def _shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Activation sharding constraint via logical axes; no-op outside a mesh
    context (pure single-device runs, CPU unit tests) and inside manual
    shard_map regions (the pipeline body — mixing with_sharding_constraint
    into a partially-manual region trips XLA internal checks)."""
    if getattr(_manual_region, 'active', False):
        return x
    if not _in_mesh_context():
        return x
    from skypilot_tpu.parallel.mesh import spec_for
    return lax.with_sharding_constraint(x, spec_for(logical_axes))


def _ffn(layer: Params, x: jax.Array, cfg: ModelConfig,
         mlora_idx: Optional[jax.Array] = None) -> jax.Array:
    from skypilot_tpu.models.quantization import qeinsum
    lo = layer.get('lora') if isinstance(layer, dict) else None
    ml = layer.get('mlora') if isinstance(layer, dict) else None
    if mlora_idx is None:
        ml = None
    gate = qeinsum('bsd,df->bsf', x, layer['w_gate'])
    up = qeinsum('bsd,df->bsf', x, layer['w_up'])
    if lo is not None:
        from skypilot_tpu.models import lora as lora_lib
        gate = gate + lora_lib.apply(lo, 'w_gate', x, cfg)
        up = up + lora_lib.apply(lo, 'w_up', x, cfg)
    if ml is not None:
        from skypilot_tpu.models import multilora
        gate = multilora.adjusted(ml, 'w_gate', x, gate, mlora_idx)
        up = multilora.adjusted(ml, 'w_up', x, up, mlora_idx)
    act = jax.nn.silu if cfg.activation == 'silu' else \
        functools.partial(jax.nn.gelu, approximate=True)
    h = act(gate.astype(jnp.float32)).astype(x.dtype) * up
    h = _shard(h, 'batch', 'seq', 'mlp')
    down = qeinsum('bsf,fd->bsd', h, layer['w_down'])
    if lo is not None:
        from skypilot_tpu.models import lora as lora_lib
        down = down + lora_lib.apply(lo, 'w_down', h, cfg)
    if ml is not None:
        down = multilora.adjusted(ml, 'w_down', h, down, mlora_idx)
    return down


def layer_stacks(params: Params, cfg: ModelConfig):
    """[(stacked layer params, index of the stack's first layer)]: the
    one ``layers`` stack, or a latent model's dense and routed stacks."""
    if cfg.latent:
        from skypilot_tpu.models import latent_moe
        return latent_moe.layer_stacks(params, cfg)
    return [(params['layers'], 0)]


def scan_layers(body, x: jax.Array, params: Params, cfg: ModelConfig):
    """``lax.scan`` of ``body(x, (layer, li))`` over every layer stack in
    turn, ``li`` the layer's index in the model (its row of a stacked
    cache); the stacks' per-layer outputs come back as one, layer-major.

    A stack's ``experts`` are not scanned: a layer sliced out of the scan
    input is a copy, and as a kernel's operand that copy is made in full
    (604 MB of expert weights a layer step on GLM-4.7-Flash: every expert
    read, whatever was routed; compiler, PR 30). The layer gets the whole
    stack with ``expert_layer``, its row in it, and the grouped matmul
    takes the row as a group offset.

    A model of several mixer kinds (``cfg.mixer_pattern``) is scanned
    period by period (``kda.scan_periods``): ``li`` is then the layer's
    index among its kind and the outputs come back as {kind: stacked}."""
    if cfg.mixer_pattern:
        from skypilot_tpu.models import kda
        return kda.scan_periods(body, x, params, cfg)
    outs = []
    for stack, first in layer_stacks(params, cfg):
        held = {k: stack[k] for k in ('experts',) if k in stack}
        scanned = ({k: v for k, v in stack.items() if k not in held}
                   if held else stack)
        idx = jnp.arange(jax.tree.leaves(scanned)[0].shape[0])

        def step(carry, layer_idx, held=held, first=first):
            layer, i = layer_idx
            if held:
                layer = dict(layer, expert_layer=i, **held)
            return body(carry, (layer, i + first if first else i))

        x, ys = lax.scan(step, x, (scanned, idx))
        outs.append(ys)
    if len(outs) == 1:
        return x, outs[0]
    return x, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs)


def run_loops(body, x: jax.Array, params: Params, cfg: ModelConfig,
              with_exit: bool = False):
    """Every pass of the model over its layers, and the final norm:
    ``scan_layers`` once and the norm after it, or for a looped model
    (``cfg.n_loops`` > 1) a ``lax.scan`` over the passes around the two,
    the SAME weights in every pass and each pass's normed output the
    next one's input. ``body`` gets ``li`` = pass * n_layers + layer,
    the row of a cache stacked over ``cfg.n_cache_layers``; the
    per-layer outputs come back stacked the same way.

    ``x`` may be a tuple (x, what the body carries beside it: a
    recurrent model's state); the norm is x's.

    Returns (normed x, per-cache-layer outputs, exit gate), the gate
    None unless ``with_exit``: each pass's sigmoid(x . w + b), [passes,
    batch, seq] float32, from the state the pass left."""

    def final_norm(x):
        if isinstance(x, tuple):
            return (final_norm(x[0]),) + x[1:]
        return rms_norm(x, params['final_norm'], cfg.norm_eps,
                        cfg.norm_plus_one)

    def gate(x):
        g = params['exit_gate']
        return jax.nn.sigmoid(
            jnp.einsum('bsd,d->bs', x.astype(jnp.float32),
                       g['w'].astype(jnp.float32)) + g['b'][0])

    def one_pass(x, t):
        def at_pass(carry, layer_idx):
            layer, li = layer_idx
            return body(carry, (layer, t * cfg.n_layers + li))

        x, ys = scan_layers(body if t is None else at_pass, x, params, cfg)
        x = final_norm(x)
        return x, (ys, gate(x) if with_exit else None)

    if cfg.n_loops == 1:        # no scan over one pass: the program it was
        x, (ys, lam) = one_pass(x, None)
        return x, ys, (lam[None] if with_exit else None)
    x, (ys, lam) = lax.scan(one_pass, x, jnp.arange(cfg.n_loops))
    ys = jax.tree.map(
        lambda a: a.reshape((cfg.n_cache_layers,) + a.shape[2:]), ys)
    return x, ys, lam


def exit_pdf(lam: jax.Array) -> jax.Array:
    """[passes, ...] exit-gate sigmoids -> the distribution over the pass
    a token leaves at: lam[t] times the share still running, the last
    pass taking what is left."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], axis=0)


def _layer_core(layer: Params, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, attn_fn,
                mlora_idx: Optional[jax.Array] = None,
                live: Optional[jax.Array] = None, rec=None):
    """One transformer layer, parameterized by the attention op so every
    path (training full-sequence, prefill/decode against a cache, the
    fused serving loop) shares ONE copy of the layer math. ``attn_fn``
    maps roped (q, k, v) to the attention output (a latent model's maps
    its absorbed queries and new cache rows: ``latent_moe.layer_core``,
    which reads ``live``, the rows that carry a token, as a routed FFN
    and a recurrent mixer do). A layer whose mixer keeps a state and no
    rows (``models/kda.py``) takes ``rec``, the state before these
    tokens (None: a sequence's start), and returns the state after them
    in the place of the new kv rows; its ``attn_fn`` (None: the XLA
    form) is the one-token recurrence, ``kda.mixer``'s ``step_fn``.

    ``mlora_idx`` ([b] int32, -1 = none) gathers per-row adapters from
    the ``layer['mlora']`` bank slice (multi-tenant serving); None (the
    default, and every training/eval path) leaves the math untouched.

    Returns (x, (k, v) new kv rows, moe aux loss)."""
    if cfg.latent:
        from skypilot_tpu.models import latent_moe
        return latent_moe.layer_core(layer, x, cfg, positions, attn_fn,
                                     live=live)
    from jax.ad_checkpoint import checkpoint_name
    h = rms_norm(x, layer['attn_norm'], cfg.norm_eps,
                  cfg.norm_plus_one)
    if 'kda' in layer:
        from skypilot_tpu.models import kda
        with jax.named_scope('kda_mix'):
            proj, new_rec = kda.mixer(layer['kda'], h, cfg, rec=rec,
                                      live=live, step_fn=attn_fn)
        return _routed_ffn_branch(layer, x + proj, cfg, live, new_rec)
    from skypilot_tpu.models.quantization import qeinsum
    lo = layer.get('lora') if isinstance(layer, dict) else None
    ml = layer.get('mlora') if isinstance(layer, dict) else None
    if mlora_idx is None:
        ml = None
    # The scopes name device time for the trace (perfbench/scopes.py);
    # they change no operation.
    with jax.named_scope('gqa_attn'):
        q = qeinsum('bsd,dhk->bshk', h, layer['wq'])
        k = qeinsum('bsd,dhk->bshk', h, layer['wk'])
        v = qeinsum('bsd,dhk->bshk', h, layer['wv'])
        if lo is not None:
            from skypilot_tpu.models import lora as lora_lib
            q = q + lora_lib.apply(lo, 'wq', h, cfg)
            k = k + lora_lib.apply(lo, 'wk', h, cfg)
            v = v + lora_lib.apply(lo, 'wv', h, cfg)
        if ml is not None:
            from skypilot_tpu.models import multilora
            q = multilora.adjusted(ml, 'wq', h, q, mlora_idx)
            k = multilora.adjusted(ml, 'wk', h, k, mlora_idx)
            v = multilora.adjusted(ml, 'wv', h, v, mlora_idx)
        if cfg.qkv_bias:
            q = q + layer['bq'].astype(q.dtype)
            k = k + layer['bk'].astype(k.dtype)
            v = v + layer['bv'].astype(v.dtype)
        q = _shard(q, 'batch', 'seq', 'heads', 'head_dim')
        rot = ((lambda a: rope(a, positions, cfg.rope_theta))
               if cfg.use_rope else (lambda a: a))
        q = checkpoint_name(rot(q), 'q_rope')
        k = checkpoint_name(rot(k), 'k_rope')
        v = checkpoint_name(v, 'v_proj')
        out = attn_fn(q, k, v)
        if cfg.attn_gate:       # o * sigmoid(W_gate h), a channel
            out = (out * jax.nn.sigmoid(qeinsum(
                'bsd,dhk->bshk', h, layer['w_attn_gate']).astype(
                    jnp.float32))).astype(out.dtype)
        # Named for selective remat (cfg.remat='attn'): saving the
        # attention output keeps the backward pass from re-running the
        # whole attention forward, at [b,s,h,d] bytes per layer.
        out = checkpoint_name(out, 'attn_out')
        out = _shard(out, 'batch', 'seq', 'heads', 'head_dim')
        proj = qeinsum('bshk,hkd->bsd', out, layer['wo'])
        if lo is not None:
            proj = proj + lora_lib.apply(lo, 'wo', out, cfg)
        if ml is not None:
            proj = multilora.adjusted(ml, 'wo', out, proj, mlora_idx)
        if cfg.post_norms:
            proj = rms_norm(proj, layer['attn_post_norm'], cfg.norm_eps,
                            cfg.norm_plus_one)
    x = x + proj
    if cfg.ffn_kind == 'routed_shared':
        return _routed_ffn_branch(layer, x, cfg, live, (k, v))
    h = rms_norm(x, layer['ffn_norm'], cfg.norm_eps,
                 cfg.norm_plus_one)
    if cfg.is_moe:
        from skypilot_tpu.models import moe
        ffn_out, aux = moe.moe_ffn(layer, h, cfg)
    else:
        with jax.named_scope('dense_ffn'):
            ffn_out = _ffn(layer, h, cfg, mlora_idx=mlora_idx)
        aux = jnp.zeros((), jnp.float32)
    if cfg.post_norms:
        ffn_out = rms_norm(ffn_out, layer['ffn_post_norm'], cfg.norm_eps,
                           cfg.norm_plus_one)
    x = x + ffn_out
    x = _shard(x, 'batch', 'seq', 'embed')
    return x, (k, v), aux


def _routed_ffn_branch(layer: Params, x: jax.Array, cfg: ModelConfig,
                       live: Optional[jax.Array], mixer_out):
    """The second half of a layer whose FFN is routed + shared experts
    (``latent_moe``'s): (x + FFN(norm(x)), ``mixer_out`` handed through,
    the layer's expert counters in the place of an auxiliary loss)."""
    from skypilot_tpu.models import latent_moe
    h = rms_norm(x, layer['ffn_norm'], cfg.norm_eps)
    ffn_out, counted = latent_moe._moe_ffn(layer, h, cfg, live)
    return x + ffn_out, mixer_out, counted


def _layer_fn(layer: Params, x: jax.Array, cfg: ModelConfig,
              positions: jax.Array,
              cache_kv, cache_len, attn_impl: str):
    if cfg.latent:
        attn_fn = None          # expanded causal form over the sequence
    elif cache_kv is None:
        def attn_fn(q, k, v):
            return attention(q, k, v, causal=True, impl=attn_impl)
    else:
        # Two-block attention: the cache is read-only here (forward
        # scatters the new rows once, after the layer scan) — a decode
        # step's cache traffic is one streaming read + an s-token write,
        # not a full rewrite through scan carries. int8 caches arrive as
        # a 4-tuple of (codes, codes, k_scale, v_scale) and are
        # contracted in int8 (see cached_attention).
        if len(cache_kv) == 4:
            ck, cv, sk, sv = cache_kv
        else:
            (ck, cv), sk, sv = cache_kv, None, None

        def attn_fn(q, k, v):
            return cached_attention(q, k, v, ck, cv, cache_len,
                                    k_scale=sk, v_scale=sv)

    x, new_kv, aux = _layer_core(layer, x, cfg, positions, attn_fn)
    return x, (None if cache_kv is None else new_kv), aux


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------
def forward(
    params: Params,
    tokens: jax.Array,                 # [b, s] int32
    cfg: ModelConfig,
    *,
    cache: Optional[KVCache] = None,
    attn_impl: str = 'auto',
    return_aux: bool = False,
    return_exit: bool = False,
):
    """Run the model. Without a cache: training/eval full-sequence causal
    attention; positions are [0..s). With a cache: prefill/decode — tokens
    are appended at each sequence's current length and the cache is updated.

    Cache-capacity contract: callers must never append past ``max_seq`` —
    ``lax.dynamic_update_slice`` clamps rather than errors inside jit, so an
    overflow silently corrupts the last cache slot.

    Returns (logits [b, s, vocab], new_cache or None), plus the mean MoE
    load-balancing aux loss when ``return_aux`` (0 for dense models),
    plus the exit distribution [passes, b, s] when ``return_exit`` (a
    model with ``exit_gate``; the logits are the last pass's either way).
    """
    if cfg.latent and cache is not None:
        raise NotImplementedError(
            'a latent-attention model has no contiguous KVCache: it '
            'decodes through the paged pool (inference/paged.py)')
    if cfg.n_loops > 1 and (cache is not None or _pp_mesh() is not None):
        raise NotImplementedError(
            f'{cfg.name} runs its layers {cfg.n_loops} times a token: the '
            'contiguous KVCache and the pipeline schedule hold one row '
            'and one stage a layer; it decodes through the paged pool '
            '(inference/paged.py)')
    if return_exit and not cfg.exit_gate:
        raise ValueError(f'{cfg.name} has no exit gate')
    if cfg.mixer_pattern and (cache is not None or _pp_mesh() is not None):
        raise NotImplementedError(
            f'{cfg.name} holds layers of kinds {cfg.mixer_pattern}: the '
            'contiguous KVCache and the pipeline schedule hold one kind '
            'of layer; it decodes through the paged pool and its '
            'per-slot state (inference/paged.py)')
    x = _embed_tokens(params, tokens, cfg)
    x = _shard(x, 'batch', 'seq', 'embed')
    b, s = tokens.shape

    if cache is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        cache_len = None
    else:
        positions = cache.length[:, None] + jnp.arange(s)[None, :]
        cache_len = cache.length

    layer_params = params['layers']

    def make_body(positions, cache_len):
        """Per-layer body closing over a SPECIFIC positions/cache_len —
        a factory so the pp-decode path can rebuild it inside the
        shard_map region (closed-over tracers don't cross that
        boundary)."""

        def body(carry, layer_and_cache):
            x = carry
            layer, layer_cache = layer_and_cache
            return _layer_fn(layer, x, cfg, positions, layer_cache,
                             cache_len, attn_impl)

        if cfg.remat == 'block':
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        elif cfg.remat == 'attn':
            # Selective remat: save roped q/k/v and the attention output
            # ([b,s,h,d] each — small next to the ffn intermediates), so
            # the backward pass never re-runs the attention forward;
            # everything else (norms, ffn) is recomputed. The MFU middle
            # ground between 'none' (OOM at ≥1B on one chip) and 'block'
            # (full re-forward).
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_only_these_names(
                    'q_rope', 'k_rope', 'v_proj', 'attn_out'))
        elif cfg.remat == 'dots':
            # Keep all matmul outputs, recompute elementwise only.
            # Highest memory — viable for small models / many-chip FSDP
            # shards.
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        return body

    body = make_body(positions, cache_len)
    normed = False

    if cache is None:
        pp_mesh = _pp_mesh()
        if pp_mesh is not None:
            # Pipeline-parallel layer stack (bubble-skipping GPipe over
            # the pp axis); each stage scans its local layers. MoE aux
            # flows through the schedule (``with_aux``).
            from skypilot_tpu.parallel.pipeline import pipeline_layers

            def stage_fn(stage_params, x_mb):
                # Positions rebuilt at microbatch shape (the closed-over
                # `positions` is full-batch; rows are identical without a
                # cache).
                mb_pos = jnp.broadcast_to(jnp.arange(s)[None, :],
                                          (x_mb.shape[0], s))

                def layer_body(carry, layer):
                    _manual_region.active = True
                    try:
                        out, _, aux = _layer_fn(layer, carry, cfg, mb_pos,
                                                None, None, attn_impl)
                    finally:
                        _manual_region.active = False
                    return out, aux
                if cfg.remat == 'block':
                    layer_body = jax.checkpoint(
                        layer_body,
                        policy=jax.checkpoint_policies.nothing_saveable)
                out, auxs = lax.scan(layer_body, x_mb, stage_params)
                return out, jnp.mean(auxs)

            x, aux_mean = pipeline_layers(layer_params, x, stage_fn,
                                          pp_mesh, with_aux=True)
            aux_layers = aux_mean[None]
        else:
            def scan_body(carry, layer_idx):
                out, _, aux = body(carry, (layer_idx[0], None))
                return out, aux

            x, aux_layers, lam = run_loops(scan_body, x, params, cfg,
                                           with_exit=return_exit)
            if cfg.mixer_pattern:       # {kind: per-layer} -> per-layer
                aux_layers = jnp.concatenate(
                    [a.reshape(a.shape[0], -1)[:, 0]
                     for a in jax.tree.leaves(aux_layers)])
            normed = True       # every pass ends in the final norm
        new_cache = None
    else:
        # The cache is a loop INVARIANT (closed over, indexed per layer),
        # not a scan input/output: routing it through xs/ys makes XLA
        # restack the entire [L, b, S, h, d] cache every call — for
        # decode that turns a ~MB token write into a ~GB cache rewrite.
        cache_k, cache_v = cache.k, cache.v
        k_scale, v_scale = cache.k_scale, cache.v_scale

        def local_scan(stack_params, ck_stack, cv_stack, ks_stack,
                       vs_stack, x0, scan_body_fn):
            """Scan a (possibly stage-local) layer stack against its
            cache stack; returns (x, (k_rows, v_rows), aux)."""
            n_local = jax.tree.leaves(stack_params)[0].shape[0]

            def scan_body(carry, layer_and_idx):
                layer, li = layer_and_idx
                ck = lax.dynamic_index_in_dim(ck_stack, li, axis=0,
                                              keepdims=False)
                cv = lax.dynamic_index_in_dim(cv_stack, li, axis=0,
                                              keepdims=False)
                if cache.quantized:
                    layer_cache = (
                        ck, cv,
                        lax.dynamic_index_in_dim(ks_stack, li, axis=0,
                                                 keepdims=False),
                        lax.dynamic_index_in_dim(vs_stack, li, axis=0,
                                                 keepdims=False))
                else:
                    layer_cache = (ck, cv)
                out, new_kv, aux = scan_body_fn(carry, (layer, layer_cache))
                return out, (new_kv, aux)

            x1, (kv_rows, auxs) = lax.scan(
                scan_body, x0, (stack_params, jnp.arange(n_local)))
            return x1, kv_rows, auxs

        pp_mesh = _pp_mesh()
        if pp_mesh is not None:
            # pp-sharded decode/prefill: each stage reads only its
            # local layer + cache shards; the token activation chains
            # through the stages (parallel/pipeline.py, round-3 gap
            # "decode ignores pp").
            from skypilot_tpu.parallel.pipeline import \
                pipeline_decode_layers
            caches = ((cache.k, cache.v, k_scale, v_scale)
                      if cache.quantized else (cache.k, cache.v))

            def stage_fn(stage_params, stage_caches, x_mb, extras):
                pos_x, clen_x = extras
                if cache.quantized:
                    ck_s, cv_s, ks_s, vs_s = stage_caches
                else:
                    (ck_s, cv_s), ks_s, vs_s = stage_caches, None, None
                _manual_region.active = True
                try:
                    x1, kv_rows, _ = local_scan(
                        stage_params, ck_s, cv_s, ks_s, vs_s, x_mb,
                        make_body(pos_x, clen_x))
                finally:
                    _manual_region.active = False
                return x1, kv_rows

            x, (k_rows, v_rows) = pipeline_decode_layers(
                layer_params, caches, x, stage_fn, pp_mesh,
                extras=(positions, cache_len))
            aux_layers = jnp.zeros((1,), jnp.float32)
        else:
            x, (k_rows, v_rows), aux_layers = local_scan(
                layer_params, cache_k, cache_v, k_scale, v_scale, x,
                body)
        # One scatter of the new token rows across all layers.
        # k_rows: [L, b, s, kv_heads, d]; per batch row, write the
        # [L, s, kv_heads, d] block at that sequence's offset.

        new_cache = merge_rows_into_cache(cache, k_rows, v_rows,
                                          cache.length, cache.length + s)

    if not normed:
        x = rms_norm(x, params['final_norm'], cfg.norm_eps,
                     cfg.norm_plus_one)
    logits = _unembed_logits(params, x, cfg)
    logits = _shard(logits, 'batch', 'seq', 'vocab')
    out = (logits, new_cache)
    if return_aux:
        out += (jnp.mean(aux_layers),)
    if return_exit:
        out += (exit_pdf(lam),)
    return out


# Sentinel token emitted when a slot's logits row is non-finite
# (NaN/Inf — numerical blow-up, SDC, poisoned activations). Real token
# ids are >= 0, so the host readback can evict exactly the poisoned
# request while its co-batched neighbors continue untouched. The
# finiteness reduction runs ON DEVICE inside the already-compiled step
# and the sentinel rides the existing token readback: zero extra
# device->host transfers, zero new programs (jaxpr-audit-gated).
NONFINITE_TOKEN = -1


def mask_nonfinite_tokens(logits: jax.Array,
                          tokens: jax.Array) -> jax.Array:
    """Per-row finiteness guard at a sampling point: rows whose logits
    contain any NaN/Inf emit :data:`NONFINITE_TOKEN` instead of a
    sampled id (argmax over all-NaN logits returns 0 — a silently
    WRONG token that would stream to the client as real output)."""
    finite = jnp.all(jnp.isfinite(logits), axis=-1)
    return jnp.where(finite, tokens,
                     jnp.asarray(NONFINITE_TOKEN, tokens.dtype))


@functools.partial(jax.jit, static_argnames=('cfg',))
def greedy_logits(params: Params, tokens: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Convenience: jitted logits-only forward (no cache)."""
    logits, _ = forward(params, tokens, cfg)
    return logits
