"""Bytes and FLOPs of AI21-Jamba2-3B's steps, from the configuration's
shapes only: the least a step must move or compute. ``model`` is the
configuration file's ``model`` object. Kept with the benchmark, beside
``roofline.py``, so that no change to the program moves the yardstick.
Matrices and the KV cache are bf16 (2 bytes); norms, the scan's vectors
(A_log, D, dt_bias, the conv bias) and the recurrent state float32 (4);
the conv tail bf16.

A layer is a mixer (NoPE GQA, or Mamba-1) and a dense SwiGLU FFN. A
decode step reads every stored weight once (the tied embedding as the
head), the live rows' recurrent state and conv tail (and writes both
back), and the live tokens' rows of the layers that cache any.
"""
from __future__ import annotations

from typing import Any, Dict

BYTES = 2
F32 = 4


def kinds(m: Dict[str, Any]):
    pattern = list(m['mixer_pattern'])
    return pattern * (m['n_layers'] // len(pattern))


def gqa_layers(m) -> int:
    return kinds(m).count('gqa')


def mamba_layers(m) -> int:
    return kinds(m).count('mamba')


def inner(m) -> int:
    return m['mamba_expand'] * m['dim']


def head_dim(m) -> int:
    return m.get('head_dim_override') or m['dim'] // m['n_heads']


def mamba_matrix_params(m) -> int:
    """W_in [d, 2I], W_x [I, R + 2N], W_dt [R, I], W_out [I, d]: what a
    token is multiplied by."""
    d, I = m['dim'], inner(m)
    R, N = m['mamba_dt_rank'], m['mamba_d_state']
    return d * 2 * I + I * (R + 2 * N) + R * I + I * d


def mamba_vector_params(m) -> int:
    """The float32 leaves: the conv bias, dt's bias, A_log [N, I], D, and
    the three norms of dt, B and C."""
    I, R, N = inner(m), m['mamba_dt_rank'], m['mamba_d_state']
    return 3 * I + N * I + R + 2 * N


def mamba_conv_params(m) -> int:
    return m['mamba_d_conv'] * inner(m)


def mamba_mixer_params(m) -> int:
    return (mamba_matrix_params(m) + mamba_conv_params(m)
            + mamba_vector_params(m))


def mamba_mixer_bytes(m) -> int:
    """One Mamba mixer as stored: matrices and conv taps bf16, vectors
    float32."""
    return (BYTES * (mamba_matrix_params(m) + mamba_conv_params(m))
            + F32 * mamba_vector_params(m))


def gqa_mixer_params(m) -> int:
    """q and o over the heads; k and v over the KV heads."""
    return m['dim'] * head_dim(m) * (2 * m['n_heads'] + 2 * m['n_kv_heads'])


def ffn_params(m) -> int:
    return 3 * m['dim'] * m['ffn_dim']


def total_params(m) -> int:
    """Every layer's mixer, FFN and two norms; the tied embedding once;
    the final norm."""
    heads = 1 if m['tie_embeddings'] else 2
    return (gqa_layers(m) * gqa_mixer_params(m)
            + mamba_layers(m) * mamba_mixer_params(m)
            + m['n_layers'] * (ffn_params(m) + 2 * m['dim'])
            + heads * m['vocab_size'] * m['dim'] + m['dim'])


def weight_bytes(m) -> int:
    """The whole stored tree: bf16 matrices, float32 norms and vectors."""
    heads = 1 if m['tie_embeddings'] else 2
    return (BYTES * (gqa_layers(m) * gqa_mixer_params(m)
                     + m['n_layers'] * ffn_params(m)
                     + heads * m['vocab_size'] * m['dim'])
            + mamba_layers(m) * mamba_mixer_bytes(m)
            + F32 * (2 * m['n_layers'] + 1) * m['dim'])


def kv_token_bytes(m) -> int:
    """One cached token: K and V rows of the layers that cache rows."""
    return gqa_layers(m) * m['n_kv_heads'] * 2 * head_dim(m) * BYTES


def state_bytes(m) -> int:
    """One sequence's scan state in one Mamba layer: [N, I] float32."""
    return m['mamba_d_state'] * inner(m) * F32


def tail_bytes(m) -> int:
    """One sequence's conv tail in one Mamba layer: d_conv - 1 inputs."""
    return (m['mamba_d_conv'] - 1) * inner(m) * BYTES


def state_slot_bytes(m) -> int:
    return mamba_layers(m) * (state_bytes(m) + tail_bytes(m))


def decode_step_bytes(m, live_rows: float, live_tokens: float) -> float:
    """Every stored weight, each live row's state and tail read AND
    written in every Mamba layer, the live tokens' cache rows."""
    return (weight_bytes(m) + live_rows * 2 * state_slot_bytes(m)
            + live_tokens * kv_token_bytes(m))


def mamba_decode_bytes(m, live_rows: float) -> float:
    """What the Mamba mixers of one decode step must move: their stored
    weights, and each live row's state and tail read and written."""
    return mamba_layers(m) * (mamba_mixer_bytes(m) + live_rows * 2 * (
        state_bytes(m) + tail_bytes(m)))


def mamba_prefill_flops_per_token(m) -> int:
    """One token through every Mamba mixer: 2 a matrix parameter. The
    scan (elementwise, ~6 N I a token) and the conv are not counted:
    ``mamba_scan_ms`` shows the scan's time beside it."""
    return mamba_layers(m) * 2 * mamba_matrix_params(m)
