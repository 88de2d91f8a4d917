"""Model configurations for the in-tree model layer.

The reference ships *recipes* that launch external frameworks
(``llm/llama-3/llama3.yaml``, ``llm/mixtral/``); we ship the engines in-tree
(SURVEY.md §2.3), so model configs are first-class here.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp


class KVSpec(NamedTuple):
    """The cache rows of one token of one layer: ``heads`` rows of
    ``k_dim`` values in the K pool and as many of ``v_dim`` in the V
    pool. GQA: (n_kv_heads, head_dim, head_dim). Latent attention: one
    shared row, the normed latent in the K pool and the roped key part
    in the V pool (no per-head K or V is ever stored)."""
    heads: int
    k_dim: int
    v_dim: int

    @property
    def row_values(self) -> int:
        return self.heads * (self.k_dim + self.v_dim)


class StateSpec(NamedTuple):
    """The state one sequence keeps in one recurrent layer, whatever its
    length: ``heads`` matrices of ``k_dim`` x ``v_dim`` float32, and the
    last ``conv_rows`` inputs of the short convolution, ``conv_dim``
    channels each, in the model's dtype."""
    heads: int
    k_dim: int
    v_dim: int
    conv_rows: int
    conv_dim: int

    def slot_bytes(self, dtype_bytes: int) -> int:
        return (self.heads * self.k_dim * self.v_dim * 4
                + self.conv_rows * self.conv_dim * dtype_bytes)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A decoder-only transformer configuration. ``attn_kind`` and
    ``ffn_kind`` name what a layer is made of: grouped-query attention
    with a dense gated FFN (the Llama family, the default), or latent
    attention (MLA) with routed + shared experts behind
    ``n_dense_layers`` leading dense layers (``models/latent_moe.py``)."""
    name: str
    vocab_size: int
    dim: int                    # model/embedding width
    n_layers: int
    n_heads: int
    n_kv_heads: int             # < n_heads => grouped-query attention
    ffn_dim: int                # SwiGLU hidden width
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    # MoE fields (None => dense FFN)
    n_experts: Optional[int] = None
    n_experts_per_token: int = 2
    # Expert buffer size = tokens * k / E * this factor (GShard capacity;
    # tokens routed past a full expert are dropped to the residual path).
    moe_capacity_factor: float = 1.25
    # Remat policy for training: 'none' | 'block' (checkpoint each layer)
    remat: str = 'block'
    # Gemma-family knobs: tied input/output embeddings, GeGLU instead of
    # SwiGLU, and RMSNorm computing x * (1 + w) instead of x * w.
    tie_embeddings: bool = False
    activation: str = 'silu'            # 'silu' | 'gelu'
    norm_plus_one: bool = False
    # Gemma scales embeddings by sqrt(dim) at the input.
    scale_embeddings: bool = False
    # Explicit per-head width (HF configs may set head_dim != dim//n_heads,
    # e.g. Gemma-7B uses 256 with dim=3072, n_heads=16).
    head_dim_override: Optional[int] = None
    # Qwen2-family: biases on the q/k/v projections (attention only).
    qkv_bias: bool = False
    # LoRA fine-tuning (reference recipe parity: torchtune LoRA at
    # ``llm/llama-3_1-finetuning/lora.yaml``). rank > 0 adds low-rank
    # adapter leaves under ``params['layers']['lora']``; the trainer
    # freezes the base and trains only the adapters. ``lora_targets``
    # names the projections to adapt ('wq','wk','wv','wo' always legal;
    # 'w_gate','w_up','w_down' for dense-FFN models).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ('wq', 'wk', 'wv', 'wo')
    # Attention kind: 'gqa' | 'latent'. Latent (MLA) projects queries
    # through ``q_lora_rank`` and caches one shared row a token a layer:
    # ``kv_lora_rank`` latent values and ``qk_rope_head_dim`` rotary
    # ones; a head's query/key is ``qk_nope_head_dim`` + rope wide, its
    # value ``v_head_dim``.
    attn_kind: str = 'gqa'
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # FFN kind of the layers after the first ``n_dense_layers`` (those
    # are dense SwiGLU of width ``ffn_dim``): 'dense' | 'routed_shared'
    # = ``n_routed_experts`` dropless experts of width ``moe_ffn_dim``,
    # ``n_experts_per_token`` a token by sigmoid score (a selection-only
    # bias, weights renormalised and scaled by
    # ``routed_scaling_factor``), beside ``n_shared_experts`` experts
    # every token takes.
    ffn_kind: str = 'dense'
    n_dense_layers: int = 0
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    moe_ffn_dim: int = 0
    routed_scaling_factor: float = 1.0
    # A looped decoder: the SAME ``n_layers`` layers run ``n_loops``
    # times a token, the final norm after every pass and its output
    # entering the next. Each (pass, layer) keeps a cache of its own:
    # ``n_cache_layers`` rows a token. ``post_norms`` puts a second
    # RMSNorm on each branch's OUTPUT, before the residual add (sandwich
    # norms). ``exit_gate`` holds a Linear(dim -> 1) read after every
    # pass; its sigmoids give the distribution over passes to leave at
    # (``llama.forward(return_exit=True)``). ``early_exit_threshold`` 1
    # means every token takes every pass: the one value served.
    n_loops: int = 1
    post_norms: bool = False
    exit_gate: bool = False
    early_exit_threshold: float = 1.0
    # A period pattern of mixer kinds, repeated over the layers: () =
    # every layer is ``attn_kind``. 'gqa' caches rows a token
    # (``kv_spec``); 'kda' (Kimi Delta Attention, ``models/kda.py``)
    # keeps a fixed state a sequence (``state_spec``) and no rows:
    # ``kda_heads`` x ``kda_head_dim`` keys and values behind a causal
    # depthwise conv of ``kda_conv`` taps, low-rank (``kda_gate_rank``)
    # decay and output gates. ``use_rope`` False leaves the GQA layers
    # without rotary; ``attn_gate`` multiplies their attention output by
    # sigmoid(W_gate h) elementwise.
    mixer_pattern: tuple = ()
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_gate_rank: int = 0
    use_rope: bool = True
    attn_gate: bool = False
    # The routed experts this program holds of each layer's
    # ``n_routed_experts``: ``n_held_experts`` (None = all) from
    # ``first_held_expert`` on. The router keeps its width; assignments
    # to absent experts add nothing (one chip's share of an
    # expert-parallel layer, without its exchange).
    n_held_experts: Optional[int] = None
    first_held_expert: int = 0

    def __post_init__(self):
        # a list out of a JSON file: hashable, as a jit static must be
        object.__setattr__(self, 'mixer_pattern', tuple(self.mixer_pattern))
        if self.mixer_pattern:
            bad = set(self.mixer_pattern) - {'gqa', 'kda'}
            if bad or self.n_layers % len(self.mixer_pattern):
                raise ValueError(
                    f'{self.name}: mixer_pattern {self.mixer_pattern} must '
                    "hold 'gqa' / 'kda' and divide n_layers "
                    f'{self.n_layers}')
        held, first = self.held_experts, self.first_held_expert
        if not 0 <= first <= first + held <= max(self.n_routed_experts,
                                                 held):
            raise ValueError(
                f'{self.name}: held experts [{first}, {first + held}) lie '
                f'outside the {self.n_routed_experts} routed over')

    @property
    def layer_kinds(self) -> tuple:
        """The mixer kind of every layer, in layer order."""
        if not self.mixer_pattern:
            return (self.attn_kind,) * self.n_layers
        return self.mixer_pattern * (self.n_layers
                                     // len(self.mixer_pattern))

    @property
    def n_recurrent_layers(self) -> int:
        """Layers that keep a fixed state a sequence and no cache rows."""
        return self.layer_kinds.count('kda')

    @property
    def recurrent(self) -> bool:
        return self.n_recurrent_layers > 0

    @property
    def n_cache_layers(self) -> int:
        """Cache rows a token has: one per (pass, layer that caches
        rows)."""
        return (self.n_layers - self.n_recurrent_layers) * self.n_loops

    @property
    def state_spec(self) -> Optional[StateSpec]:
        """What one sequence's state in one recurrent layer is made of."""
        if not self.recurrent:
            return None
        return StateSpec(self.kda_heads, self.kda_head_dim,
                         self.kda_head_dim, self.kda_conv - 1,
                         3 * self.kda_heads * self.kda_head_dim)

    @property
    def held_experts(self) -> int:
        return (self.n_routed_experts if self.n_held_experts is None
                else self.n_held_experts)

    @property
    def holds_every_expert(self) -> bool:
        return self.held_experts == self.n_routed_experts

    @property
    def lora_enabled(self) -> bool:
        return self.lora_rank > 0

    @property
    def lora_scale(self) -> float:
        return self.lora_alpha / max(self.lora_rank, 1)

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.dim // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts is not None

    @property
    def latent(self) -> bool:
        return self.attn_kind == 'latent'

    @property
    def kv_spec(self) -> KVSpec:
        """What one cached token of one layer is made of."""
        if self.latent:
            return KVSpec(1, self.kv_lora_rank, self.qk_rope_head_dim)
        return KVSpec(self.n_kv_heads, self.head_dim, self.head_dim)

    def _dense_param_split(self):
        """(parameters of the layer stack, held once; all the others:
        embeddings, final norm, exit gate)."""
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        q_dim = self.n_heads * self.head_dim
        kv_dim = self.n_kv_heads * self.head_dim
        attn = d * q_dim + 2 * d * kv_dim + q_dim * d   # wq, wk, wv, wo
        ffn = 3 * d * f
        if self.is_moe:
            ffn *= self.n_experts
            ffn += d * self.n_experts           # router
        norms = 4 if self.post_norms else 2
        embeds = v * d if self.tie_embeddings else v * d * 2
        gate = d + 1 if self.exit_gate else 0
        return self.n_layers * (attn + ffn + norms * d), embeds + d + gate

    @property
    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        if self.latent:
            from skypilot_tpu.models import latent_moe
            return latent_moe.num_params(self)
        if self.mixer_pattern:
            from skypilot_tpu.models import kda
            return kda.num_params(self)
        return sum(self._dense_param_split())

    def flops_per_token(self, training: bool = False) -> float:
        """~2*N matmul FLOPs per token fwd (6*N with backward); a
        looped model's layers are held once and worked ``n_loops``
        times."""
        n = self.num_params
        if self.latent:
            from skypilot_tpu.models import latent_moe
            n = latent_moe.num_params(self, active_only=True)
        elif self.mixer_pattern:
            from skypilot_tpu.models import kda
            n = kda.num_params(self, active_only=True)
        elif self.n_loops > 1:
            n += (self.n_loops - 1) * self._dense_param_split()[0]
        if self.is_moe:
            # only active experts count
            d, f = self.dim, self.ffn_dim
            dense_ffn = 3 * d * f * self.n_layers
            n = n - dense_ffn * self.n_experts + dense_ffn * self.n_experts_per_token
        return (6.0 if training else 2.0) * n


# --- Presets ---------------------------------------------------------------
def _cfg(**kw) -> ModelConfig:
    return ModelConfig(**kw)


LLAMA3_8B = _cfg(name='llama3-8b', vocab_size=128256, dim=4096, n_layers=32,
                 n_heads=32, n_kv_heads=8, ffn_dim=14336)

LLAMA3_70B = _cfg(name='llama3-70b', vocab_size=128256, dim=8192, n_layers=80,
                  n_heads=64, n_kv_heads=8, ffn_dim=28672)

LLAMA2_7B = _cfg(name='llama2-7b', vocab_size=32000, dim=4096, n_layers=32,
                 n_heads=32, n_kv_heads=32, ffn_dim=11008, rope_theta=10000.0,
                 max_seq_len=4096)

# ~1.1B-param config that fits one 16GB v5e chip in bf16 with room for a KV
# cache — the single-chip flagship of __graft_entry__.entry().
LLAMA3_1B = _cfg(name='llama3-1b', vocab_size=128256, dim=2048, n_layers=16,
                 n_heads=32, n_kv_heads=8, ffn_dim=8192)

MIXTRAL_8X7B = _cfg(name='mixtral-8x7b', vocab_size=32000, dim=4096,
                    n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336,
                    rope_theta=1000000.0, n_experts=8, n_experts_per_token=2)

# Tiny configs for CPU-mesh tests.
TINY = _cfg(name='tiny', vocab_size=256, dim=64, n_layers=2, n_heads=4,
            n_kv_heads=2, ffn_dim=128, max_seq_len=128, remat='none')

TINY_MOE = _cfg(name='tiny-moe', vocab_size=256, dim=64, n_layers=2, n_heads=4,
                n_kv_heads=2, ffn_dim=128, max_seq_len=128, n_experts=4,
                n_experts_per_token=2, remat='none')

GEMMA_2B = _cfg(name='gemma-2b', vocab_size=256128, dim=2048, n_layers=18,
                n_heads=8, n_kv_heads=1, ffn_dim=16384,
                rope_theta=10000.0, tie_embeddings=True, activation='gelu',
                norm_plus_one=True, scale_embeddings=True)

GEMMA_7B = _cfg(name='gemma-7b', vocab_size=256128, dim=3072, n_layers=28,
                n_heads=16, n_kv_heads=16, ffn_dim=24576,
                rope_theta=10000.0, tie_embeddings=True, activation='gelu',
                norm_plus_one=True, scale_embeddings=True)

TINY_GEMMA = _cfg(name='tiny-gemma', vocab_size=256, dim=64, n_layers=2,
                  n_heads=4, n_kv_heads=1, ffn_dim=128, max_seq_len=128,
                  remat='none', tie_embeddings=True, activation='gelu',
                  norm_plus_one=True, scale_embeddings=True)

QWEN2_7B = _cfg(name='qwen2-7b', vocab_size=152064, dim=3584, n_layers=28,
                n_heads=28, n_kv_heads=4, ffn_dim=18944,
                rope_theta=1000000.0, qkv_bias=True, max_seq_len=32768)

TINY_QWEN = _cfg(name='tiny-qwen', vocab_size=256, dim=64, n_layers=2,
                 n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                 remat='none', qkv_bias=True)

# zai-org/GLM-4.7-Flash (``glm4_moe_lite``) as published; its
# multi-token-prediction block is a draft head outside the forward pass
# and is not held.
GLM_4_7_FLASH = _cfg(
    name='glm-4.7-flash', vocab_size=154880, dim=2048, n_layers=47,
    n_heads=20, n_kv_heads=20, ffn_dim=10240, max_seq_len=202752,
    rope_theta=1000000.0, norm_eps=1e-5, attn_kind='latent',
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, ffn_kind='routed_shared',
    n_dense_layers=1, n_routed_experts=64, n_experts_per_token=4,
    n_shared_experts=1, moe_ffn_dim=1536, routed_scaling_factor=1.8)

# Every size differs from its neighbour where a mix-up would hide:
# nope != rope != v, the dense width != the expert width.
TINY_GLM = _cfg(
    name='tiny-glm', vocab_size=256, dim=64, n_layers=3, n_heads=4,
    n_kv_heads=4, ffn_dim=160, max_seq_len=128, remat='none',
    attn_kind='latent', q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=16, ffn_kind='routed_shared', n_dense_layers=1,
    n_routed_experts=8, n_experts_per_token=2, n_shared_experts=1,
    moe_ffn_dim=96, routed_scaling_factor=1.8)

# ByteDance/Ouro-2.6B (``ouro``) as published: plain multi-head
# attention, 48 layers run 4 times a token.
OURO_2_6B = _cfg(
    name='ouro-2.6b', vocab_size=49152, dim=2048, n_layers=48, n_heads=16,
    n_kv_heads=16, ffn_dim=5632, max_seq_len=65536, rope_theta=1000000.0,
    norm_eps=1e-6, n_loops=4, post_norms=True, exit_gate=True)

# Sizes that differ where a mix-up would hide: loops != layers, heads !=
# kv heads, dim != heads x head_dim.
TINY_OURO = _cfg(
    name='tiny-ouro', vocab_size=256, dim=64, n_layers=2, n_heads=4,
    n_kv_heads=2, ffn_dim=160, max_seq_len=128, remat='none',
    head_dim_override=24, n_loops=3, post_norms=True, exit_gate=True)

# upstage/Solar-Open2-250B (``solar_open2``) as ONE CHIP'S SHARE of an
# 8-way expert-parallel stage: one whole period [GQA, KDA, KDA, KDA] of
# the published 12, experts 0-39 of each layer's 320 (routed over all
# 320, 8 a token), 1/8 of the 196,608-row vocabulary; every width as
# published (perfbench/configs/solar-open2-250b.json has the arithmetic).
SOLAR_OPEN2_250B = _cfg(
    name='solar-open2-250b', vocab_size=24576, dim=4096, n_layers=4,
    n_heads=64, n_kv_heads=8, ffn_dim=10240, max_seq_len=1048576,
    rope_theta=10000.0, norm_eps=1e-5, head_dim_override=128,
    mixer_pattern=('gqa', 'kda', 'kda', 'kda'), kda_heads=64,
    kda_head_dim=128, kda_conv=4, kda_gate_rank=128, use_rope=False,
    attn_gate=True, ffn_kind='routed_shared', n_routed_experts=320,
    n_experts_per_token=8, n_shared_experts=1, moe_ffn_dim=1280,
    routed_scaling_factor=1.0, n_held_experts=40, first_held_expert=0)

# Two periods; heads != KV heads, KDA heads != heads, its head width !=
# the GQA one, the gate rank != either, 4 of 16 experts held from 4 on.
TINY_SOLAR = _cfg(
    name='tiny-solar', vocab_size=256, dim=64, n_layers=8, n_heads=4,
    n_kv_heads=2, ffn_dim=160, max_seq_len=256, remat='none',
    head_dim_override=24, mixer_pattern=('gqa', 'kda', 'kda', 'kda'),
    kda_heads=3, kda_head_dim=16, kda_conv=4, kda_gate_rank=8,
    use_rope=False, attn_gate=True, ffn_kind='routed_shared',
    n_routed_experts=16, n_experts_per_token=2, n_shared_experts=1,
    moe_ffn_dim=48, routed_scaling_factor=1.0, n_held_experts=4,
    first_held_expert=4)

PRESETS = {c.name: c for c in [
    LLAMA3_8B, LLAMA3_70B, LLAMA2_7B, LLAMA3_1B, MIXTRAL_8X7B,
    GEMMA_2B, GEMMA_7B, QWEN2_7B, TINY, TINY_MOE, TINY_GEMMA,
    TINY_QWEN, GLM_4_7_FLASH, TINY_GLM, OURO_2_6B, TINY_OURO,
    SOLAR_OPEN2_250B, TINY_SOLAR]}


def get_config(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise ValueError(f'Unknown model {name!r}. Known: {sorted(PRESETS)}')
    return PRESETS[name]
