"""Latent (MLA) attention in XLA, in its two forms.

*Expanded*: every cached latent row is projected up to per-head keys
and values, then plain causal attention: 2(d_nope + d_rope) + 2 d_v
FLOPs a query-key pair a head. What a full forward over a sequence
takes, since there every row is projected once.

*Absorbed*: the key half of the up-projection is folded into the query
(``q_lat = q_nope W_kvb^K``) and the value half is applied after the
weighted sum, so attention runs against the cached rows as they lie:
one shared "KV head" of ``r`` latent + ``d_rope`` rotary values under
all the query heads. 2(r + d_rope) + 2r FLOPs a pair a head, and no
per-head K or V of the context ever exists. What the paged programs
take: a prefill chunk would otherwise project its whole context up
again in every chunk (r x h x (d_nope + d_v) x 2 FLOPs a cached row a
chunk: at a 256-token chunk 1.75 times the pair FLOPs it saves, and a
[context, heads, d_nope + d_v] transient), and a decode step has one
query row against thousands of cached ones.

Same mathematics; ``tests/test_latent_moe.py`` holds them to each other.
Numerics as in ``ops/attention.py``: logits and softmax in float32,
probabilities cast to the values' dtype for the weighted sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = -1e30


def expanded_causal_attention(q_nope, q_rope, k_nope, k_rope, v, *,
                              scale: float) -> jax.Array:
    """q_nope [b,s,h,dn], q_rope [b,s,h,dr], k_nope [b,s,h,dn], k_rope
    [b,s,dr] (shared by the heads), v [b,s,h,dv] -> [b,s,h,dv]."""
    s = q_nope.shape[1]
    logits = (jnp.einsum('bqhd,bkhd->bhqk', q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum('bqhd,bkd->bhqk', q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(causal[None, None], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p.astype(v.dtype), v)


def _latent_logits(q_lat, q_rope, c, kr, scale):
    """[b,q,h,r] x [b,k,r] + [b,q,h,dr] x [b,k,dr] -> [b,h,q,k] f32."""
    return (jnp.einsum('bqhr,bkr->bhqk', q_lat, c,
                       preferred_element_type=jnp.float32)
            + jnp.einsum('bqhd,bkd->bhqk', q_rope, kr,
                         preferred_element_type=jnp.float32)) * scale


def absorbed_cached_attention(q_lat, q_rope, c_new, kr_new, c_ctx, kr_ctx,
                              cache_len, *, scale: float) -> jax.Array:
    """A chunk of ``s`` new rows against ``cache_len`` rows already
    cached plus itself, causally: one softmax over both blocks (the
    two-block form of ``cached_attention``). q_lat [b,s,h,r], q_rope
    [b,s,h,dr]; c_new [b,s,r], kr_new [b,s,dr]; c_ctx [b,S,r], kr_ctx
    [b,S,dr]; cache_len [b]. Returns the output in latent space
    [b,s,h,r]; the caller applies the value up-projection."""
    s, S = q_lat.shape[1], c_ctx.shape[1]
    lc = _latent_logits(q_lat, q_rope, c_ctx, kr_ctx, scale)
    ls = _latent_logits(q_lat, q_rope, c_new, kr_new, scale)
    pos = jnp.arange(S)[None, None, None, :]
    lc = jnp.where(pos < cache_len[:, None, None, None], lc, _NEG)
    causal = jnp.tril(jnp.ones((s, s), bool))
    ls = jnp.where(causal[None, None], ls, _NEG)
    m = jnp.maximum(jnp.max(lc, -1, keepdims=True),
                    jnp.max(ls, -1, keepdims=True))
    ec, es = jnp.exp(lc - m), jnp.exp(ls - m)
    denom = jnp.sum(ec, -1, keepdims=True) + jnp.sum(es, -1, keepdims=True)
    out = jnp.einsum('bhqk,bkr->bqhr', (ec / denom).astype(c_ctx.dtype),
                     c_ctx)
    return out + jnp.einsum('bhqk,bkr->bqhr',
                            (es / denom).astype(c_new.dtype), c_new)


def absorbed_ring_decode_attention(q_lat, q_rope, c_self, kr_self, c_ctx,
                                   kr_ctx, cache_len, ring_c, ring_kr,
                                   ring_len, *, scale: float) -> jax.Array:
    """One query row a slot over three blocks sharing one softmax: the
    cached rows (read-only for the whole fused horizon), the ring of
    rows the horizon's earlier steps made, and the current token (the
    three-block form of ``ring_decode_attention``). q_lat [b,1,h,r],
    q_rope [b,1,h,dr]; c_self [b,1,r], kr_self [b,1,dr]; c_ctx [b,S,r],
    kr_ctx [b,S,dr]; ring_c [b,H,r], ring_kr [b,H,dr]. -> [b,1,h,r]."""
    lc = _latent_logits(q_lat, q_rope, c_ctx, kr_ctx, scale)
    lr = _latent_logits(q_lat, q_rope, ring_c, ring_kr, scale)
    lself = _latent_logits(q_lat, q_rope, c_self, kr_self, scale)
    pos = jnp.arange(c_ctx.shape[1])[None, None, None, :]
    lc = jnp.where(pos < cache_len[:, None, None, None], lc, _NEG)
    rpos = jnp.arange(ring_c.shape[1])[None, None, None, :]
    lr = jnp.where(rpos < ring_len, lr, _NEG)
    m = jnp.maximum(jnp.maximum(jnp.max(lc, -1, keepdims=True),
                                jnp.max(lr, -1, keepdims=True)), lself)
    ec, er, es = jnp.exp(lc - m), jnp.exp(lr - m), jnp.exp(lself - m)
    denom = (jnp.sum(ec, -1, keepdims=True)
             + jnp.sum(er, -1, keepdims=True) + es)
    out = jnp.einsum('bhqk,bkr->bqhr', (ec / denom).astype(c_ctx.dtype),
                     c_ctx)
    out = out + jnp.einsum('bhqk,bkr->bqhr',
                           (er / denom).astype(ring_c.dtype), ring_c)
    return out + jnp.einsum('bhqk,bkr->bqhr',
                            (es / denom).astype(c_self.dtype), c_self)
