"""Bytes and FLOPs of Ouro-2.6B's steps from the configuration's shapes
only: the least a step must move or compute. ``model`` is the
configuration file's ``model`` object. Kept with the benchmark, beside
``roofline.py`` (one pass over dense GQA layers), so that no change to
the program moves the yardstick. Weights and cache are bf16: 2 bytes.

A looped model HOLDS its layers once and WORKS them ``n_loops`` times a
token: parameters count the layers once; a decode step streams their
matrices once a pass (5 GB does not stay in a chip's 128 MiB of fast
memory from one pass to the next), and a token's FLOPs and cache rows
count every pass.
"""
from __future__ import annotations

from typing import Any, Dict

BYTES = 2


def _head_dim(m: Dict[str, Any]) -> int:
    return m.get('head_dim_override') or m['dim'] // m['n_heads']


def attn_params(m: Dict[str, Any]) -> int:
    """One layer's attention matrices: q, k, v, o."""
    hd = _head_dim(m)
    return m['dim'] * hd * (2 * m['n_heads'] + 2 * m['n_kv_heads'])


def layer_matrix_params(m: Dict[str, Any]) -> int:
    """One layer's matrices: attention and the gated FFN."""
    return attn_params(m) + 3 * m['dim'] * m['ffn_dim']


def norm_params(m: Dict[str, Any]) -> int:
    """The four norms of a sandwich layer."""
    return 4 * m['dim']


def total_params(m: Dict[str, Any]) -> int:
    """Everything the tree holds: the layers once, embedding and head,
    the final norm, the exit gate's weight and bias."""
    return (m['n_layers'] * (layer_matrix_params(m) + norm_params(m))
            + 2 * m['vocab_size'] * m['dim'] + m['dim'] + m['dim'] + 1)


def cache_layers(m: Dict[str, Any]) -> int:
    return m['n_layers'] * m['n_loops']


def kv_token_bytes(m: Dict[str, Any]) -> int:
    """One cached token: K and V rows of every (pass, layer)."""
    return cache_layers(m) * m['n_kv_heads'] * 2 * _head_dim(m) * BYTES


def decode_weight_bytes(m: Dict[str, Any]) -> int:
    """Weights one decode step reads: the layers' matrices once a pass,
    and the output head (the embedding is gathered, one row a
    sequence)."""
    return BYTES * (m['n_loops'] * m['n_layers'] * layer_matrix_params(m)
                    + m['vocab_size'] * m['dim'])


def decode_step_bytes(m: Dict[str, Any], live_tokens: float) -> float:
    return decode_weight_bytes(m) + live_tokens * kv_token_bytes(m)


def attn_decode_bytes(m: Dict[str, Any], live_tokens: float) -> float:
    """What the attention blocks of one decode step must read: their
    matrices once a pass, and the live tokens' cache rows."""
    return (BYTES * m['n_loops'] * m['n_layers'] * attn_params(m)
            + live_tokens * kv_token_bytes(m))


def flops_per_token(m: Dict[str, Any]) -> int:
    """Matmul FLOPs of one token through every pass and the head: 2 a
    parameter worked (attention over the context is left out: at this
    mix's contexts it is under 4 % of the matrices')."""
    return 2 * (m['n_loops'] * m['n_layers'] * layer_matrix_params(m)
                + m['vocab_size'] * m['dim'])
