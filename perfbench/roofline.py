"""Bytes and FLOPs from shapes: the least a step must move or compute.

Kept with the benchmark so that no later change to the program's own cost
model (``analysis/costmodel.py``) moves the yardstick. ``model`` is the
configuration file's ``model`` object (``ModelConfig``'s field names).
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(model: Dict[str, Any]):
    d, L = model['dim'], model['n_layers']
    hd = model.get('head_dim_override') or d // model['n_heads']
    q, kv = model['n_heads'] * hd, model['n_kv_heads'] * hd
    return d, L, hd, q, kv, model['ffn_dim'], model['vocab_size']


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters every token is multiplied by: the layers' seven
    matrices and the unembedding (tied or not). The embedding lookup is a
    gather and the norms and biases are not matmuls."""
    d, L, _, q, kv, f, v = _dims(model)
    return L * (d * q + 2 * d * kv + q * d + 3 * d * f) + d * v


def decode_weight_bytes(model: Dict[str, Any], weight_bytes: float = 1.0,
                        scale_bytes: int = 2) -> int:
    """Stored bytes of every weight leaf one decode step reads: int8
    codes of the matmul leaves plus their per-output-channel scales, the
    fp32 norms and q/k/v biases. The embedding table is gathered (one row
    a sequence), not streamed."""
    d, L, _, q, kv, f, v = _dims(model)
    codes = matmul_params(model) * weight_bytes
    channels = L * (q + 2 * kv + d + 2 * f + d) + v
    norms = (2 * L + 1) * d * 4
    biases = L * (q + 2 * kv) * 4 if model.get('qkv_bias') else 0
    return int(codes + channels * scale_bytes + norms + biases)


def kv_token_bytes(model: Dict[str, Any], kv_bytes: float = 1.0,
                   scale_bytes: int = 4) -> int:
    """Stored bytes of one token's keys and values over all layers: an
    int8 row and one fp32 scale for each (layer, k or v, kv head)."""
    _, L, hd, _, _, _, _ = _dims(model)
    return int(L * 2 * model['n_kv_heads'] * (hd * kv_bytes + scale_bytes))


def decode_step_bytes(model: Dict[str, Any], live_tokens: float) -> float:
    """What one decode step must stream: the weights once, and the
    stored K/V of every live token of the batch."""
    return decode_weight_bytes(model) + live_tokens * kv_token_bytes(model)


def attention_flops(model: Dict[str, Any], new_tokens: float,
                    context_before: float) -> float:
    """Causal attention FLOPs (QK^T and PV) of ``new_tokens`` appended to
    ``context_before`` tokens: each new token attends to what came before
    it and to itself."""
    _, L, hd, q, _, _, _ = _dims(model)
    pairs = new_tokens * context_before + new_tokens * (new_tokens + 1) / 2
    return 4.0 * L * q * pairs


def prefill_flops(model: Dict[str, Any], new_tokens: float,
                  context_before: float) -> float:
    """Forward FLOPs of a prompt piece: 2 x matmul parameters a token
    (the unembedding too: an upper bound on need, since only a prompt's
    last token needs logits) + causal attention."""
    return (2.0 * matmul_params(model) * new_tokens
            + attention_flops(model, new_tokens, context_before))


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Forward + backward FLOPs a token of a packed ``seq``-token
    sequence needs: 6 x matmul parameters + 3 x the forward's causal
    attention. Recomputation (``remat``) is not counted."""
    return (6.0 * matmul_params(model)
            + 3.0 * attention_flops(model, seq, 0) / seq)
