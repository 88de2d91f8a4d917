"""Bytes and FLOPs of GLM-4.7-Flash's layers from the configuration's
shapes only: the least a step must move or compute. ``model`` is the
configuration file's ``model`` object. Kept with the benchmark, beside
``roofline.py`` (dense GQA counts), so that no change to the program
moves the yardstick. Weights and the latent cache are bf16: 2 bytes.
"""
from __future__ import annotations

from typing import Any, Dict

BYTES = 2


def mla_params(m: Dict[str, Any]) -> int:
    """One layer's attention matrices: q_a, q_b, kv_a, kv_b, o."""
    d, h = m['dim'], m['n_heads']
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    return (d * m['q_lora_rank'] + m['q_lora_rank'] * h * qk
            + d * (m['kv_lora_rank'] + m['qk_rope_head_dim'])
            + m['kv_lora_rank'] * h * (m['qk_nope_head_dim']
                                       + m['v_head_dim'])
            + h * m['v_head_dim'] * d)


def expert_params(m: Dict[str, Any]) -> int:
    """One routed expert: gate, up, down."""
    return 3 * m['dim'] * m['moe_ffn_dim']


def expert_layers(m: Dict[str, Any]) -> int:
    return m['n_layers'] - m['n_dense_layers']


def expert_layer_fixed_params(m: Dict[str, Any]) -> int:
    """What an expert layer holds outside its routed experts: attention,
    the shared experts, the router with its bias."""
    return (mla_params(m) + m['n_shared_experts'] * expert_params(m)
            + m['dim'] * m['n_routed_experts'] + m['n_routed_experts'])


def dense_layer_params(m: Dict[str, Any]) -> int:
    return mla_params(m) + 3 * m['dim'] * m['ffn_dim']


def total_params(m: Dict[str, Any]) -> int:
    """Matrices of the whole cut (norms left out), embedding and head."""
    return (m['n_dense_layers'] * dense_layer_params(m)
            + expert_layers(m) * (expert_layer_fixed_params(m)
                                  + m['n_routed_experts'] * expert_params(m))
            + 2 * m['vocab_size'] * m['dim'])


def kv_token_bytes(m: Dict[str, Any]) -> int:
    """One cached token over all layers: the latent row and the rope row."""
    return m['n_layers'] * (m['kv_lora_rank'] + m['qk_rope_head_dim']) * BYTES


def decode_fixed_bytes(m: Dict[str, Any]) -> int:
    """Weights every decode step reads whatever is routed: all layers
    outside the routed experts, and the output head (the embedding is
    gathered, one row a sequence)."""
    return BYTES * (m['n_dense_layers'] * dense_layer_params(m)
                    + expert_layers(m) * expert_layer_fixed_params(m)
                    + m['vocab_size'] * m['dim'])


def expert_bytes_read(m: Dict[str, Any], distinct_per_layer: float) -> float:
    """Expert weights a decode step must read: the experts that had a
    live token, in each expert layer."""
    return BYTES * expert_params(m) * distinct_per_layer * expert_layers(m)


def decode_step_bytes(m: Dict[str, Any], distinct_per_layer: float,
                      live_tokens: float) -> float:
    return (decode_fixed_bytes(m) + expert_bytes_read(m, distinct_per_layer)
            + live_tokens * kv_token_bytes(m))


def mla_decode_bytes(m: Dict[str, Any], live_tokens: float) -> float:
    """What the attention blocks of one decode step must read: their
    matrices and the live tokens' cache rows."""
    return (BYTES * m['n_layers'] * mla_params(m)
            + live_tokens * kv_token_bytes(m))


def mla_decode_flops(m: Dict[str, Any], live_tokens: float,
                     live_rows: float) -> float:
    """Absorbed form: a query row against a cached row costs 2(r + rope)
    for the score and 2r for the output in latent space, a head; the
    projections 2 x their parameters a row."""
    r, rope = m['kv_lora_rank'], m['qk_rope_head_dim']
    pair = m['n_heads'] * (2 * (r + rope) + 2 * r)
    return m['n_layers'] * (pair * live_tokens
                            + 2 * mla_params(m) * live_rows)


def prefill_pair_flops(m: Dict[str, Any]) -> int:
    """Expanded form, the least the mathematics needs for a query-key
    pair under the causal mask, in one layer: 2(nope + rope) for the
    score and 2 v for the output, a head (20,480 as published)."""
    return m['n_heads'] * (2 * (m['qk_nope_head_dim'] + m['qk_rope_head_dim'])
                           + 2 * m['v_head_dim'])
