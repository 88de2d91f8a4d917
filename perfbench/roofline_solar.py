"""Bytes and FLOPs of Solar-Open2-250B's steps, one chip's share, from
the configuration's shapes only: the least a step must move or compute.
``model`` is the configuration file's ``model`` object. Kept with the
benchmark, beside ``roofline.py``, so that no change to the program moves
the yardstick. Weights and KV cache are bf16 (2 bytes), the router and
the recurrent state float32 (4).

A layer is a mixer (gated GQA, or KDA: Kimi Delta Attention) and a
routed FFN: a router over ALL ``n_routed_experts``, the ``n_held_experts``
this chip holds, one shared expert. A decode step reads every matrix
outside the routed experts once, of the held experts those a live row
was routed to, the live rows' recurrent state (and writes it back), and
the live tokens' rows of the one layer kind that caches any.
"""
from __future__ import annotations

from typing import Any, Dict

BYTES = 2
STATE_BYTES = 4
SUB = 64          # tokens a WY transform covers (ops/kda.py)


def kinds(m: Dict[str, Any]):
    pattern = list(m['mixer_pattern'])
    return pattern * (m['n_layers'] // len(pattern))


def gqa_layers(m) -> int:
    return kinds(m).count('gqa')


def kda_layers(m) -> int:
    return kinds(m).count('kda')


def gqa_mixer_params(m) -> int:
    """q, the output gate and o over the heads; k and v over the KV
    heads."""
    hd = m['head_dim_override']
    return m['dim'] * hd * (3 * m['n_heads'] + 2 * m['n_kv_heads'])


def kda_matrix_params(m) -> int:
    """q, k, v, o; the two low-rank gates; beta; the convolution."""
    H, dk, r, d = (m['kda_heads'], m['kda_head_dim'], m['kda_gate_rank'],
                   m['dim'])
    return (4 * d * H * dk + 2 * (d * r + r * H * dk) + d * H
            + m['kda_conv'] * 3 * H * dk)


def kda_mixer_params(m) -> int:
    """The matrices and the vectors: dt_bias and the gate's bias a
    channel, A_log a head, the output norm."""
    H, dk = m['kda_heads'], m['kda_head_dim']
    return kda_matrix_params(m) + 2 * H * dk + H + dk


def expert_params(m) -> int:
    return 3 * m['dim'] * m['moe_ffn_dim']


def held_experts(m) -> int:
    held = m.get('n_held_experts')
    return m['n_routed_experts'] if held is None else held


def layer_ffn_params(m) -> int:
    """Router and its bias, the shared expert, the held experts, the
    layer's two norms."""
    return (m['dim'] * m['n_routed_experts'] + m['n_routed_experts']
            + m['n_shared_experts'] * expert_params(m)
            + held_experts(m) * expert_params(m) + 2 * m['dim'])


def total_params(m) -> int:
    return (gqa_layers(m) * gqa_mixer_params(m)
            + kda_layers(m) * kda_mixer_params(m)
            + m['n_layers'] * layer_ffn_params(m)
            + 2 * m['vocab_size'] * m['dim'] + m['dim'])


def kv_token_bytes(m) -> int:
    """One cached token: K and V rows of the layers that cache rows."""
    return (gqa_layers(m) * m['n_kv_heads'] * 2 * m['head_dim_override']
            * BYTES)


def state_slot_bytes(m) -> int:
    """One sequence's recurrent state over the KDA layers (the
    convolution's tail, 442,368 B at these shapes, is left out of the
    roofline: 3.5 % of it)."""
    return (kda_layers(m) * m['kda_heads'] * m['kda_head_dim'] ** 2
            * STATE_BYTES)


def kda_weight_bytes(m) -> int:
    return kda_layers(m) * kda_mixer_params(m) * BYTES


def fixed_weight_bytes(m) -> int:
    """Weights a decode step reads whatever was routed: the mixers, the
    shared experts, the routers (float32)."""
    return (BYTES * (gqa_layers(m) * gqa_mixer_params(m)
                     + kda_layers(m) * kda_mixer_params(m)
                     + m['n_layers'] * m['n_shared_experts']
                     * expert_params(m))
            + 4 * m['n_layers'] * m['dim'] * m['n_routed_experts'])


def head_bytes(m) -> int:
    return m['vocab_size'] * m['dim'] * BYTES


def expert_bytes_read(m, distinct_per_layer: float) -> float:
    """Held experts' weights a decode step must read: those that had a
    live row, in each layer."""
    return BYTES * expert_params(m) * distinct_per_layer * m['n_layers']


def decode_step_bytes(m, distinct_per_layer: float, live_rows: float,
                      live_tokens: float) -> float:
    return (fixed_weight_bytes(m) + head_bytes(m)
            + expert_bytes_read(m, distinct_per_layer)
            + live_rows * 2 * state_slot_bytes(m)
            + live_tokens * kv_token_bytes(m))


def kda_decode_bytes(m, live_rows: float) -> float:
    """What the KDA mixers of one decode step must move: their matrices,
    and each live row's state read and written."""
    return kda_weight_bytes(m) + live_rows * 2 * state_slot_bytes(m)


def delta_rule_flops_per_token(m) -> int:
    """Matmul FLOPs of the chunked form for one token of one KDA layer,
    all heads: per head the two decayed score products against the
    sub-chunk (2 x 2 SUB dk), the forward substitution's row (2 SUB^2 /
    SUB x SUB), the transform applied to keys and values (2 SUB (dk +
    dv)), the state's three products (3 x 2 dk dv) and the scores on the
    pseudo-values (2 SUB dv). The pairwise decays inside a block are
    elementwise work and are not counted."""
    dk = dv = m['kda_head_dim']
    per_head = (2 * 2 * SUB * dk + 2 * SUB * SUB + 2 * SUB * (dk + dv)
                + 3 * 2 * dk * dv + 2 * SUB * dv)
    return m['kda_heads'] * per_head


def kda_prefill_flops_per_token(m) -> int:
    """One token through every KDA mixer: 2 a matrix parameter, and the
    chunked delta rule."""
    return kda_layers(m) * (2 * kda_matrix_params(m)
                            + delta_rule_flops_per_token(m))
