"""HF-format checkpoint import/export for the in-tree engines.

The reference never loads weights itself — its recipes point external
engines at HF checkpoints (vLLM `--model` in ``llm/llama-3/llama3.yaml:109``,
JetStream converting Llama-2-7B in ``examples/tpu/v6e/README.md:119``).
Since our engines are in-tree (SURVEY.md §2.3), the weight import is too:
this module maps a HuggingFace checkpoint directory
(``config.json`` + ``*.safetensors`` [+ index]) onto the stacked-layer
param pytree used by ``models/llama.py``.

Layout notes:
- HF stores per-layer weights under ``model.layers.{i}.*`` as
  ``[out, in]`` Linear matrices; we stack all layers on a leading
  ``layers`` axis (for ``lax.scan``) and keep matrices input-major
  (``[in, out]``), so every projection is transposed on import.
- Our RoPE uses the split-half ("rotate_half") convention, identical to
  HF Llama/Gemma/Mixtral — no head permutation is needed.
- Norm weights stay float32; matmul weights cast to ``cfg.dtype``.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterator, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.utils.host import host_sync

Params = Dict[str, Any]

_ARCH_FAMILY = {
    'LlamaForCausalLM': 'llama',
    'MistralForCausalLM': 'llama',
    'GemmaForCausalLM': 'gemma',
    'MixtralForCausalLM': 'mixtral',
    'Qwen2ForCausalLM': 'qwen2',
    'OuroForCausalLM': 'ouro',
    'SolarOpen2ForCausalLM': 'solar_open2',
    'JambaForCausalLM': 'jamba',
}


def config_from_hf(hf: Dict[str, Any],
                   name: Optional[str] = None,
                   dtype: Any = jnp.bfloat16) -> ModelConfig:
    """Build a ModelConfig from an HF ``config.json`` dict."""
    archs = hf.get('architectures') or []
    family = next((_ARCH_FAMILY[a] for a in archs if a in _ARCH_FAMILY),
                  None)
    if family is None:
        raise ValueError(
            f'Unsupported architectures {archs!r}; supported: '
            f'{sorted(_ARCH_FAMILY)}')
    dim = hf['hidden_size']
    n_heads = hf['num_attention_heads']
    head_dim = hf.get('head_dim')
    kw: Dict[str, Any] = dict(
        name=name or hf.get('model_type', family),
        vocab_size=hf['vocab_size'],
        dim=dim,
        n_layers=hf['num_hidden_layers'],
        n_heads=n_heads,
        n_kv_heads=hf.get('num_key_value_heads', n_heads),
        ffn_dim=hf['intermediate_size'],
        max_seq_len=hf.get('max_position_embeddings', 8192),
        rope_theta=float(hf.get('rope_theta', 10000.0)),
        norm_eps=float(hf.get('rms_norm_eps', 1e-5)),
        dtype=dtype,
        tie_embeddings=bool(hf.get('tie_word_embeddings', False)),
    )
    if head_dim is not None and head_dim != dim // n_heads:
        kw['head_dim_override'] = head_dim
    if family == 'gemma':
        kw.update(tie_embeddings=True, activation='gelu',
                  norm_plus_one=True, scale_embeddings=True)
    if family == 'qwen2':
        kw.update(qkv_bias=True)
    if family == 'mixtral':
        kw.update(n_experts=hf['num_local_experts'],
                  n_experts_per_token=hf.get('num_experts_per_tok', 2))
    if family == 'ouro':
        # A looped decoder: sandwich norms and the exit gate come with
        # the family, the passes with ``total_ut_steps``.
        kw.update(n_loops=hf['total_ut_steps'], post_norms=True,
                  exit_gate=True, early_exit_threshold=float(
                      hf.get('early_exit_threshold', 1.0)))
    if family == 'solar_open2':
        kw.update(_solar_open2_fields(hf))
    if family == 'jamba':
        kw.update(_jamba_fields(hf))
    return ModelConfig(**kw)


def _jamba_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``model_type: jamba``: attention at the layers ``i`` with ``i %
    attn_layer_period == attn_layer_offset`` (``JambaConfig``'s rule),
    Mamba-1 at the others; no positional encoding. A dense FFN in every
    layer is the one built (``num_experts`` 1)."""
    period, offset = hf['attn_layer_period'], hf['attn_layer_offset']
    if hf['num_hidden_layers'] % period or not 0 <= offset < period:
        raise ValueError(f'attention every {period} layers from {offset} '
                         f'does not repeat over '
                         f'{hf["num_hidden_layers"]} layers')
    if hf.get('num_experts', 1) != 1 or hf.get('mamba_proj_bias'):
        raise ValueError('jamba with routed experts or biased Mamba '
                         'projections is not built')
    return dict(
        mixer_pattern=tuple('gqa' if i == offset else 'mamba'
                            for i in range(period)),
        use_rope=False, mamba_d_state=hf['mamba_d_state'],
        mamba_d_conv=hf['mamba_d_conv'], mamba_expand=hf['mamba_expand'],
        mamba_dt_rank=hf['mamba_dt_rank'],
        mamba_conv_bias=bool(hf.get('mamba_conv_bias', True)),
        mamba_proj_bias=False)


def _solar_open2_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """``model_type: solar_open2``: gated NoPE GQA layers at
    ``gqa_layers``, Kimi Delta Attention (``linear_attn_config``) at the
    others, every FFN routed + shared experts. Not in ``config.json``
    and set by the family's convention: the low-rank gates' rank (the
    linear head width). ``n_held_experts`` / ``first_held_expert`` are
    this repo's keys for one chip's share of an expert-parallel layer."""
    n, gqa = hf['num_hidden_layers'], set(hf['gqa_layers'])
    period = hf['gqa_interval'] + 1
    pattern = tuple('gqa' if i in gqa else 'kda' for i in range(period))
    if n % period or [i for i in range(n)
                      if (pattern[i % period] == 'gqa') != (i in gqa)]:
        raise ValueError(f'gqa_layers {sorted(gqa)} do not repeat with '
                         f'period {period} over {n} layers')
    if hf.get('first_k_dense_replace', 0) or hf.get('kda_use_full_proj'):
        raise ValueError('solar_open2 with leading dense layers or '
                         'full-rank KDA gate projections is not built')
    lin = hf['linear_attn_config']
    return dict(
        mixer_pattern=pattern, kda_heads=lin['num_heads'],
        kda_head_dim=lin['head_dim'],
        kda_conv=lin['short_conv_kernel_size'],
        kda_gate_rank=lin['head_dim'],
        use_rope=bool(hf.get('use_rope', True)),
        attn_gate=bool(hf.get('use_gqa_gate', False)),
        ffn_kind='routed_shared', n_routed_experts=hf['n_routed_experts'],
        n_experts_per_token=hf['num_experts_per_tok'],
        n_shared_experts=hf['n_shared_experts'],
        moe_ffn_dim=hf['moe_intermediate_size'],
        routed_scaling_factor=float(hf.get('routed_scaling_factor', 1.0)),
        n_held_experts=hf.get('n_held_experts'),
        first_held_expert=hf.get('first_held_expert', 0))


def _refuse_tensors(cfg: ModelConfig) -> None:
    if cfg.mixer_pattern:
        raise NotImplementedError(
            f'{cfg.name}: the checkpoint tensor names of a model with '
            f'layers of kinds {cfg.mixer_pattern} are not mapped (no '
            'published checkpoint is at hand to hold a mapping to); its '
            'config.json is read and written, its weights are made from '
            'a seed (llama.init_params)')


_EXIT_GATE_KEYS = ('model.early_exit_gate.weight',
                   'model.early_exit_gate.bias')


def _read_hf_config(path: str) -> Dict[str, Any]:
    with open(os.path.join(path, 'config.json'), encoding='utf-8') as f:
        return json.load(f)


def _safetensor_files(path: str) -> list:
    index = os.path.join(path, 'model.safetensors.index.json')
    if os.path.exists(index):
        with open(index, encoding='utf-8') as f:
            weight_map = json.load(f)['weight_map']
        return sorted({os.path.join(path, v) for v in weight_map.values()})
    single = os.path.join(path, 'model.safetensors')
    if os.path.exists(single):
        return [single]
    files = sorted(f for f in os.listdir(path) if f.endswith('.safetensors'))
    if not files:
        raise FileNotFoundError(f'No .safetensors files under {path}')
    return [os.path.join(path, f) for f in files]


def load_workers() -> int:
    """Checkpoint-load parallelism (threads reading safetensors
    shards). ``SKYTPU_LOAD_WORKERS`` overrides; default
    min(8, cpu count). 1 disables threading entirely. The bench
    records this so load-time trajectories stay attributable."""
    env = os.environ.get('SKYTPU_LOAD_WORKERS')
    if env:
        return max(1, int(env))
    return min(8, os.cpu_count() or 1)


def _iter_tensors(path: str) -> Iterator[Tuple[str, np.ndarray]]:
    from safetensors import safe_open
    for fname in _safetensor_files(path):
        with safe_open(fname, framework='np') as f:
            for key in f.keys():
                yield key, f.get_tensor(key)


def _for_each_tensor(path: str, process) -> None:
    """Apply ``process(key, tensor)`` to every tensor in the checkpoint,
    reading shards with a thread pool of :func:`load_workers` threads.
    Each worker holds its own ``safe_open`` handles and AT MOST ONE
    decoded tensor at a time, so peak extra host memory is bounded by
    ``workers x largest tensor`` — not the checkpoint size. safetensors
    reads release the GIL for the file I/O + memcpy, so ``ckpt_load_s``
    scales with workers until the disk saturates. ``process`` must be
    thread-safe for DISTINCT keys (each key is processed exactly once)."""
    from concurrent.futures import ThreadPoolExecutor

    from safetensors import safe_open
    workers = load_workers()
    files = _safetensor_files(path)
    per_file: list = []
    for fname in files:
        with safe_open(fname, framework='np') as f:
            per_file.append((fname, list(f.keys())))
    pairs = [(fname, key) for fname, keys in per_file for key in keys]
    if workers <= 1 or len(pairs) <= 1:
        for key, w in _iter_tensors(path):
            process(key, w)
        return

    def run_shard(shard) -> None:
        import contextlib
        with contextlib.ExitStack() as stack:
            handles = {}
            for fname, key in shard:
                f = handles.get(fname)
                if f is None:
                    f = stack.enter_context(
                        safe_open(fname, framework='np'))
                    handles[fname] = f
                process(key, f.get_tensor(key))

    shards = [pairs[i::workers] for i in range(workers)]
    with ThreadPoolExecutor(max_workers=workers) as ex:
        # list() re-raises the first worker exception.
        list(ex.map(run_shard, [s for s in shards if s]))


def _hf_key_map(cfg: ModelConfig) -> Dict[str, Tuple[str, ...]]:
    """HF tensor suffix (within ``model.layers.{i}.``) -> our leaf path.
    The transform per suffix is applied in ``load_hf_params``."""
    m = {
        'input_layernorm.weight': ('layers', 'attn_norm'),
        'post_attention_layernorm.weight': ('layers', 'ffn_norm'),
        'self_attn.q_proj.weight': ('layers', 'wq'),
        'self_attn.k_proj.weight': ('layers', 'wk'),
        'self_attn.v_proj.weight': ('layers', 'wv'),
        'self_attn.o_proj.weight': ('layers', 'wo'),
    }
    if cfg.post_norms:
        m.update({
            'input_layernorm_2.weight': ('layers', 'attn_post_norm'),
            'post_attention_layernorm_2.weight': ('layers',
                                                  'ffn_post_norm'),
        })
    if cfg.qkv_bias:
        m.update({
            'self_attn.q_proj.bias': ('layers', 'bq'),
            'self_attn.k_proj.bias': ('layers', 'bk'),
            'self_attn.v_proj.bias': ('layers', 'bv'),
        })
    if cfg.is_moe:
        m['block_sparse_moe.gate.weight'] = ('layers', 'router')
        for e in range(cfg.n_experts):
            m[f'block_sparse_moe.experts.{e}.w1.weight'] = (
                'layers', 'moe_gate', e)
            m[f'block_sparse_moe.experts.{e}.w3.weight'] = (
                'layers', 'moe_up', e)
            m[f'block_sparse_moe.experts.{e}.w2.weight'] = (
                'layers', 'moe_down', e)
    else:
        m['mlp.gate_proj.weight'] = ('layers', 'w_gate')
        m['mlp.up_proj.weight'] = ('layers', 'w_up')
        m['mlp.down_proj.weight'] = ('layers', 'w_down')
    return m


def _transform(leaf: Tuple[str, ...], w: np.ndarray,
               cfg: ModelConfig) -> np.ndarray:
    """HF [out, in] Linear -> our input-major layout (+ head reshapes)."""
    name = leaf[1]
    hd = cfg.head_dim
    if name.endswith('norm'):
        return w.astype(np.float32)
    if name == 'bq':
        return w.reshape(cfg.n_heads, hd).astype(np.float32)
    if name in ('bk', 'bv'):
        return w.reshape(cfg.n_kv_heads, hd).astype(np.float32)
    if name == 'wq':
        return w.T.reshape(cfg.dim, cfg.n_heads, hd)
    if name in ('wk', 'wv'):
        return w.T.reshape(cfg.dim, cfg.n_kv_heads, hd)
    if name == 'wo':
        return w.T.reshape(cfg.n_heads, hd, cfg.dim)
    if name == 'router':
        return w.T                      # [E, d] -> [d, E]
    # All FFN projections (dense + expert): [out, in] -> [in, out].
    return w.T


def load_hf_params(path: str, cfg: ModelConfig,
                   quantize: Optional[str] = None) -> Params:
    """Load an HF checkpoint directory into the stacked-layer pytree.

    Layer tensors are accumulated into preallocated numpy buffers
    ([n_layers, ...]) so peak host memory stays ~1× checkpoint size, then
    cast to ``cfg.dtype`` (norms stay fp32) as jax arrays.

    ``quantize='int8'`` quantizes the matmul weights ON THE HOST before
    any device transfer: only int8 codes + scales ever reach the chip, so
    a 7B checkpoint costs ~7 GB of HBM and host-to-device traffic
    instead of ~14 GB bf16 followed by an on-device quantization pass. (An fp32
    upcast of the stacked 7B MLP leaf alone is ~5.8 GB — quantizing
    on-device after a bf16 load cannot fit a 16 GB v5e.)
    """
    if quantize is not None and quantize not in ('int8', 'int4'):
        # Validate BEFORE streaming gigabytes of tensors.
        raise ValueError(f'unknown quantize mode {quantize!r}')
    _refuse_tensors(cfg)
    key_map = _hf_key_map(cfg)
    L = cfg.n_layers
    stacked: Dict[str, np.ndarray] = {}     # our layer-leaf name -> buffer
    expert_bufs: Dict[str, np.ndarray] = {}
    top: Dict[str, np.ndarray] = {}
    seen = set()
    # Tensors stream in from a thread pool (_for_each_tensor; bounded
    # memory — each worker decodes one tensor at a time). Buffer ROW
    # writes are disjoint per key; only the shared-dict mutations
    # (buffer allocation, the seen set) need the lock.
    import threading
    alloc_lock = threading.Lock()

    def process(key: str, w: np.ndarray) -> None:
        if key == 'model.embed_tokens.weight':
            with alloc_lock:
                top['embed'] = w
                seen.add(key)
            return
        if key == 'model.norm.weight':
            w = w.astype(np.float32)
            with alloc_lock:
                top['final_norm'] = w
                seen.add(key)
            return
        if key == 'lm_head.weight':
            if not cfg.tie_embeddings:
                with alloc_lock:
                    top['unembed'] = w.T
                    seen.add(key)
            return
        if key in _EXIT_GATE_KEYS:
            if cfg.exit_gate:
                with alloc_lock:
                    top[key] = w
                    seen.add(key)
            return
        if not key.startswith('model.layers.'):
            return
        rest = key[len('model.layers.'):]
        idx_str, suffix = rest.split('.', 1)
        i = int(idx_str)
        leaf = key_map.get(suffix)
        if leaf is None:
            return
        w = _transform(leaf, w, cfg)
        name = leaf[1]
        if len(leaf) == 3:                   # per-expert tensor
            e = leaf[2]
            with alloc_lock:
                buf = expert_bufs.setdefault(
                    name,
                    np.zeros((L, cfg.n_experts) + w.shape, w.dtype))
                seen.add(key)
            buf[i, e] = w
        else:
            with alloc_lock:
                buf = stacked.setdefault(
                    name, np.zeros((L,) + w.shape, w.dtype))
                seen.add(key)
            buf[i] = w

    _for_each_tensor(path, process)

    # Completeness: every expected tensor must have been seen, per layer —
    # a missing layer tensor would otherwise silently load as zeros.
    expected = {'model.embed_tokens.weight', 'model.norm.weight'}
    if not cfg.tie_embeddings:
        expected.add('lm_head.weight')
    if cfg.exit_gate:
        expected.update(_EXIT_GATE_KEYS)
    for i in range(L):
        for suffix in key_map:
            expected.add(f'model.layers.{i}.{suffix}')
    missing = sorted(expected - seen)
    if missing:
        raise ValueError(
            f'Checkpoint at {path} is missing {len(missing)} tensors, '
            f'first: {missing[:6]}')

    from skypilot_tpu.models import quantization

    def cast(name: str, a: np.ndarray) -> Any:
        if name.endswith('norm') or name in ('bq', 'bk', 'bv'):
            return jnp.asarray(a, jnp.float32)
        if quantize is not None and name in quantization.REDUCE_AXES:
            # int4 packs the dense leaves; MoE expert leaves stay int8
            # even in int4 mode (quantization module docstring).
            int4 = (quantize == 'int4'
                    and name in quantization.INT4_LEAVES)
            return _host_quantize(a, quantization.REDUCE_AXES[name],
                                  cfg.dtype, int4=int4)
        # Cast on host (numpy handles ml_dtypes) so only ONE device
        # buffer per leaf is ever live, not fp16+bf16 copies.
        return jnp.asarray(np.asarray(a, cfg.dtype))

    params: Params = {
        'embed': cast('embed', top['embed']),
        'final_norm': cast('final_norm', top['final_norm']),
        'layers': {},
    }
    # Each host buffer is dropped as its leaf lands, so peak host
    # memory falls as the quantized copies grow.
    for bufs in (stacked, expert_bufs):
        for k in list(bufs):
            params['layers'][k] = cast(k, bufs.pop(k))
    if not cfg.tie_embeddings:
        params['unembed'] = cast('unembed', top['unembed'])
    if cfg.exit_gate:                   # Linear(dim -> 1): [1, d] and [1]
        w, b = (top[k] for k in _EXIT_GATE_KEYS)
        params['exit_gate'] = {
            'w': jnp.asarray(np.asarray(w.reshape(-1), cfg.dtype)),
            'b': jnp.asarray(b.reshape(1), jnp.float32)}
    return params


def _host_quantize(a: np.ndarray, reduce_axes, scale_dtype,
                   int4: bool = False):
    """Numpy twin of ``quantization._quantize_array`` (same rounded-scale
    contract; ``int4=True`` mirrors ``_quantize_array4`` — packed codes
    + per-channel/group scales): quantizes on the host so only codes +
    scales hit the device. Stacked layer leaves quantize one
    layer-slice per worker thread — the fp32 transient stays at
    ``load_workers()``/L of the leaf (a 7B MLP leaf upcast whole is
    ~5.8 GB), with reduce axes always excluding axis 0."""
    from skypilot_tpu.models.quantization import (QuantizedWeight,
                                                  QuantizedWeight4)
    cls = QuantizedWeight4 if int4 else QuantizedWeight

    if a.ndim >= 3 and 0 not in reduce_axes:
        from concurrent.futures import ThreadPoolExecutor
        sub_axes = tuple(ax - 1 for ax in reduce_axes)

        def one_layer(i):
            return _host_quantize_slice(a[i], sub_axes, scale_dtype,
                                        int4=int4)

        # Layers are independent and numpy releases the GIL on arrays
        # this size: one thread took ~3.5 minutes over an 8B checkpoint
        # (the replica's cold start), load_workers() threads a fraction.
        with ThreadPoolExecutor(max_workers=load_workers()) as ex:
            codes, scales = zip(*ex.map(one_layer, range(a.shape[0])))
        return cls(jnp.asarray(np.stack(codes)),
                   jnp.asarray(np.stack(scales)))
    q, scale = _host_quantize_slice(a, reduce_axes, scale_dtype,
                                    int4=int4)
    return cls(jnp.asarray(q), jnp.asarray(scale))


def _host_quantize_slice(a: np.ndarray, reduce_axes, scale_dtype,
                         int4: bool = False):
    """Round-scale-first quantize of one array (fp32 transient = this
    slice only). int8: codes in [-127, 127]. int4: codes in [-7, 7]
    packed two-per-byte along the last reduce axis (group-wise scales
    under SKYTPU_INT4_GROUP), the exact on-device layout."""
    from skypilot_tpu.models import quantization
    af = np.asarray(a, np.float32)
    if int4:
        ax = reduce_axes[-1] % af.ndim
        group = quantization.int4_group_size()
        if group:
            m = af.shape[ax]
            if m % group or group % 2:
                raise ValueError(
                    f'SKYTPU_INT4_GROUP={group} must be even and '
                    f'divide the packed axis (size {m})')
            split = af.shape[:ax] + (m // group, group) + af.shape[ax + 1:]
            ag = af.reshape(split)
            red = tuple(x if x % af.ndim < ax else x % af.ndim + 1
                        for x in reduce_axes[:-1]) + (ax + 1,)
            absmax = np.max(np.abs(ag), axis=red, keepdims=True)
            scale = (np.maximum(absmax, 1e-8) / 7.0).astype(scale_dtype)
            q = np.clip(np.rint(ag / scale.astype(np.float32)), -7,
                        7).astype(np.int8).reshape(af.shape)
            sshape = tuple(1 if x in [r % af.ndim for r in reduce_axes]
                           else d for x, d in enumerate(af.shape))
            sshape = sshape[:ax] + (m // group,) + sshape[ax + 1:]
            scale = scale.reshape(sshape)
        else:
            absmax = np.max(np.abs(af), axis=reduce_axes, keepdims=True)
            scale = (np.maximum(absmax, 1e-8) / 7.0).astype(scale_dtype)
            q = np.clip(np.rint(af / scale.astype(np.float32)), -7,
                        7).astype(np.int8)
        return quantization.pack_int4(q, axis=ax), scale
    absmax = np.max(np.abs(af), axis=reduce_axes, keepdims=True)
    scale = (np.maximum(absmax, 1e-8) / 127.0).astype(scale_dtype)
    q = np.clip(np.rint(af / scale.astype(np.float32)), -127,
                127).astype(np.int8)
    return q, scale


def load_checkpoint(path: str,
                    dtype: Any = jnp.bfloat16,
                    name: Optional[str] = None,
                    quantize: Optional[str] = None,
                    use_cache: bool = True
                    ) -> Tuple[ModelConfig, Params]:
    """One-call import: HF dir -> (ModelConfig, params).

    With ``quantize='int8'`` (or ``'int4'``) the quantized tree is
    cached next to the checkpoint (``.int8_cache.bin`` /
    ``.int4_cache.bin`` + ``.meta.json`` manifest): the first load pays
    the full fp16-read + host-quantize pass; reruns mmap the smaller
    quantized tree (packed int4 codes ride as raw uint8) and device_put
    leaves in parallel. Best-effort — a read-only checkpoint dir just
    skips the cache."""
    cfg = config_from_hf(_read_hf_config(path), name=name, dtype=dtype)
    quantized = quantize in ('int8', 'int4')
    cache_file = os.path.join(path, f'.{quantize}_cache.bin')
    fingerprint = _cache_fingerprint(path, dtype)
    if quantized and use_cache and os.path.exists(cache_file):
        try:
            if _read_cache_meta(cache_file) == fingerprint:
                return cfg, _load_int8_cache(cache_file, cfg)
            print(f'[weights] {quantize} cache stale (checkpoint or '
                  'dtype changed); requantizing', flush=True)
        except Exception as e:  # pylint: disable=broad-except
            print(f'[weights] {quantize} cache unreadable ({e}); '
                  'reloading', flush=True)
    params = load_hf_params(path, cfg, quantize=quantize)
    if quantized and use_cache:
        try:
            _save_int8_cache(cache_file, params, fingerprint)
        except OSError as e:
            print(f'[weights] {quantize} cache not written: {e}',
                  flush=True)
    return cfg, params


def _cache_fingerprint(path: str, dtype: Any) -> Dict[str, Any]:
    """Validity key for the int8 cache: requested dtype + the size/mtime
    of every safetensors shard (a re-exported checkpoint or a different
    compute dtype must invalidate)."""
    files = [(os.path.basename(f), os.path.getsize(f),
              int(os.path.getmtime(f)))
             for f in _safetensor_files(path)]
    return {'dtype': str(jnp.dtype(dtype)), 'files': files}


def _read_cache_meta(cache_file: str) -> Optional[Dict[str, Any]]:
    """The saved fingerprint (for staleness checks)."""
    meta = _read_cache_manifest(cache_file)
    if meta is None:
        return None
    fp = meta['fingerprint']
    fp['files'] = [tuple(e) for e in fp.get('files', [])]
    return fp


def _read_cache_manifest(cache_file: str) -> Optional[Dict[str, Any]]:
    meta_file = cache_file + '.meta.json'
    if not os.path.exists(meta_file):
        return None
    with open(meta_file, encoding='utf-8') as f:
        return json.load(f)


def _flatten_leaves(params: Params, prefix: str = ''):
    from skypilot_tpu.models.quantization import (QuantizedWeight,
                                                  QuantizedWeight4)
    for k, v in params.items():
        if isinstance(v, dict):
            yield from _flatten_leaves(v, f'{prefix}{k}/')
        elif isinstance(v, QuantizedWeight):
            yield f'{prefix}{k}.int8', v.int8
            yield f'{prefix}{k}.scale', v.scale
        elif isinstance(v, QuantizedWeight4):
            yield f'{prefix}{k}.int4', v.packed
            yield f'{prefix}{k}.scale', v.scale
        else:
            yield f'{prefix}{k}', v


def _save_int8_cache(cache_file: str, params: Params,
                     fingerprint: Dict[str, Any]) -> None:
    """Flat binary + JSON manifest: each leaf's raw little-endian
    buffer at a 128-byte-aligned offset. The loader np.memmaps the file
    and hands zero-copy views straight to ``jax.device_put`` — the
    round-4 npz (zip-container) cache decompressed through a single
    thread at ~0.25 GB/s (27.9 s for the 7B int8 tree, which is
    replica scale-up latency). bf16 arrays ride as uint16 with a
    ``view`` tag (numpy has no native bf16). The meta file is written
    LAST so a crashed save never yields a valid-looking cache."""
    align = 128
    manifest = []
    entries = []
    off = 0
    for name, leaf in _flatten_leaves(params):
        a = np.ascontiguousarray(host_sync(leaf))
        view = None
        if a.dtype == jnp.bfloat16:
            a = a.view(np.uint16)
            view = 'bfloat16'
        off = (off + align - 1) // align * align
        manifest.append({'name': name, 'dtype': str(a.dtype),
                         'view': view, 'shape': list(a.shape),
                         'offset': off, 'nbytes': int(a.nbytes)})
        entries.append((off, a))
        off += a.nbytes
    tmp = cache_file + '.tmp'
    with open(tmp, 'wb') as f:
        for o, a in entries:
            f.seek(o)
            a.tofile(f)
    os.replace(tmp, cache_file)
    meta_tmp = cache_file + '.meta.json.tmp'
    with open(meta_tmp, 'w', encoding='utf-8') as f:
        json.dump({'version': 2, 'fingerprint': fingerprint,
                   'manifest': manifest}, f)
    os.replace(meta_tmp, cache_file + '.meta.json')
    # Drop the round-4 zip-container cache (superseded; multi-GB).
    legacy = cache_file[:-len('.bin')] + '.npz'
    for f in (legacy, legacy + '.meta.json'):
        try:
            os.remove(f)
        except OSError:
            pass


def _load_int8_cache(cache_file: str, cfg: ModelConfig) -> Params:
    """Loads int8 AND int4 quantized-tree caches (the leaf class is
    recovered from the ``.int8`` / ``.int4`` name suffix)."""
    from concurrent.futures import ThreadPoolExecutor

    from skypilot_tpu.models.quantization import (QuantizedWeight,
                                                  QuantizedWeight4)
    meta = _read_cache_manifest(cache_file)
    mm = np.memmap(cache_file, dtype=np.uint8, mode='r')

    def fetch(entry):
        raw = mm[entry['offset']:entry['offset'] + entry['nbytes']]
        a = raw.view(np.dtype(entry['dtype'])).reshape(entry['shape'])
        if entry['view'] == 'bfloat16':
            a = a.view(jnp.bfloat16)
        return entry['name'], jnp.asarray(a)

    # Parallel device puts: each leaf streams disk -> page cache ->
    # device independently; the load_workers() pool overlaps the host
    # read with the transfer (the serialized per-leaf put was the
    # other half of the 27.9 s).
    with ThreadPoolExecutor(max_workers=load_workers()) as ex:
        flat = dict(ex.map(fetch, meta['manifest']))
    params: Params = {}
    pending: Dict[str, Dict[str, Any]] = {}
    for name, arr in flat.items():
        parts = name.split('/')
        node = params
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        leaf = parts[-1]
        if leaf.endswith(('.int8', '.int4', '.scale')):
            base, field = leaf.rsplit('.', 1)
            slot = pending.setdefault(f'{"/".join(parts[:-1])}/{base}',
                                      {'node': node, 'base': base})
            slot[field] = arr
        else:
            node[leaf] = arr
    for slot in pending.values():
        if 'int4' in slot:
            slot['node'][slot['base']] = QuantizedWeight4(
                packed=slot['int4'], scale=slot['scale'])
        else:
            slot['node'][slot['base']] = QuantizedWeight(
                int8=slot['int8'], scale=slot['scale'])
    return params


# ---------------------------------------------------------------- export
def hf_config_dict(cfg: ModelConfig,
                   torch_dtype: str = 'float32') -> Dict[str, Any]:
    """The HF ``config.json`` dict for a ModelConfig — single source for
    the export path and the synthetic-checkpoint generator (must stay
    the exact inverse of ``config_from_hf``)."""
    arch = {'llama': 'LlamaForCausalLM', 'gemma': 'GemmaForCausalLM',
            'mixtral': 'MixtralForCausalLM',
            'qwen2': 'Qwen2ForCausalLM', 'ouro': 'OuroForCausalLM',
            'solar_open2': 'SolarOpen2ForCausalLM',
            'jamba': 'JambaForCausalLM'}
    family = ('jamba' if 'mamba' in cfg.mixer_pattern else
              'solar_open2' if cfg.mixer_pattern else
              'ouro' if cfg.n_loops > 1 else
              'mixtral' if cfg.is_moe else
              'gemma' if cfg.norm_plus_one else
              'qwen2' if cfg.qkv_bias else 'llama')
    hf_cfg: Dict[str, Any] = {
        'architectures': [arch[family]],
        'model_type': family,
        'hidden_size': cfg.dim,
        'intermediate_size': cfg.ffn_dim,
        'num_hidden_layers': cfg.n_layers,
        'num_attention_heads': cfg.n_heads,
        'num_key_value_heads': cfg.n_kv_heads,
        'head_dim': cfg.head_dim,
        'vocab_size': cfg.vocab_size,
        'max_position_embeddings': cfg.max_seq_len,
        'rope_theta': cfg.rope_theta,
        'rms_norm_eps': cfg.norm_eps,
        'tie_word_embeddings': cfg.tie_embeddings,
        'torch_dtype': torch_dtype,
    }
    if cfg.is_moe:
        hf_cfg.update(num_local_experts=cfg.n_experts,
                      num_experts_per_tok=cfg.n_experts_per_token)
    if family == 'gemma':
        hf_cfg['hidden_act'] = 'gelu_pytorch_tanh'
    if family == 'ouro':
        hf_cfg.update(total_ut_steps=cfg.n_loops,
                      early_exit_threshold=cfg.early_exit_threshold)
    if family == 'solar_open2':
        period = len(cfg.mixer_pattern)
        hf_cfg.update(
            gqa_interval=period - 1,
            gqa_layers=[i for i, k in enumerate(cfg.layer_kinds)
                        if k == 'gqa'],
            linear_attn_config={
                'short_conv_kernel_size': cfg.kda_conv,
                'head_dim': cfg.kda_head_dim, 'num_heads': cfg.kda_heads,
                'num_kv_heads': None},
            use_rope=cfg.use_rope, use_gqa_gate=cfg.attn_gate,
            kda_use_full_proj=False, kda_allow_neg_eigval=True,
            first_k_dense_replace=0,
            n_routed_experts=cfg.n_routed_experts,
            num_experts_per_tok=cfg.n_experts_per_token,
            n_shared_experts=cfg.n_shared_experts,
            moe_intermediate_size=cfg.moe_ffn_dim, norm_topk_prob=True,
            routed_scaling_factor=cfg.routed_scaling_factor,
            n_held_experts=cfg.n_held_experts,
            first_held_expert=cfg.first_held_expert)
    if family == 'jamba':
        if cfg.mixer_pattern.count('gqa') != 1:
            raise ValueError(f'{cfg.name}: jamba names one attention '
                             'layer a period')
        hf_cfg.update(
            attn_layer_period=len(cfg.mixer_pattern),
            attn_layer_offset=cfg.mixer_pattern.index('gqa'),
            num_experts=1, num_experts_per_tok=1,
            mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
            mamba_expand=cfg.mamba_expand, mamba_dt_rank=cfg.mamba_dt_rank,
            mamba_conv_bias=cfg.mamba_conv_bias,
            mamba_proj_bias=cfg.mamba_proj_bias)
    return hf_cfg


def save_hf_checkpoint(path: str, cfg: ModelConfig, params: Params) -> None:
    """Inverse of ``load_hf_params``: write ``config.json`` +
    ``model.safetensors`` in HF layout (used by tests and for handing
    trained weights back to HF-ecosystem tools)."""
    from safetensors.numpy import save_file
    _refuse_tensors(cfg)
    os.makedirs(path, exist_ok=True)
    hd = cfg.head_dim
    out: Dict[str, np.ndarray] = {}

    def np_(a) -> np.ndarray:
        # Must be C-contiguous: the host copy of a TPU-backed jax array
        # can carry non-C strides (np.array keeps order='K'), and
        # safetensors serializes the raw buffer while assuming C order —
        # silently scrambling strided input.
        return np.ascontiguousarray(
            host_sync(jnp.asarray(a, jnp.float32)), dtype=np.float32)

    out['model.embed_tokens.weight'] = np_(params['embed'])
    out['model.norm.weight'] = np_(params['final_norm'])
    if not cfg.tie_embeddings:
        out['lm_head.weight'] = np_(params['unembed']).T
    if cfg.exit_gate:
        out[_EXIT_GATE_KEYS[0]] = np_(params['exit_gate']['w'])[None]
        out[_EXIT_GATE_KEYS[1]] = np_(params['exit_gate']['b'])
    lp = params['layers']
    for i in range(cfg.n_layers):
        p = f'model.layers.{i}.'
        out[p + 'input_layernorm.weight'] = np_(lp['attn_norm'][i])
        out[p + 'post_attention_layernorm.weight'] = np_(lp['ffn_norm'][i])
        if cfg.post_norms:
            out[p + 'input_layernorm_2.weight'] = np_(
                lp['attn_post_norm'][i])
            out[p + 'post_attention_layernorm_2.weight'] = np_(
                lp['ffn_post_norm'][i])
        out[p + 'self_attn.q_proj.weight'] = (
            np_(lp['wq'][i]).reshape(cfg.dim, cfg.n_heads * hd).T)
        out[p + 'self_attn.k_proj.weight'] = (
            np_(lp['wk'][i]).reshape(cfg.dim, cfg.n_kv_heads * hd).T)
        out[p + 'self_attn.v_proj.weight'] = (
            np_(lp['wv'][i]).reshape(cfg.dim, cfg.n_kv_heads * hd).T)
        out[p + 'self_attn.o_proj.weight'] = (
            np_(lp['wo'][i]).reshape(cfg.n_heads * hd, cfg.dim).T)
        if cfg.qkv_bias:
            out[p + 'self_attn.q_proj.bias'] = (
                np_(lp['bq'][i]).reshape(cfg.n_heads * hd))
            out[p + 'self_attn.k_proj.bias'] = (
                np_(lp['bk'][i]).reshape(cfg.n_kv_heads * hd))
            out[p + 'self_attn.v_proj.bias'] = (
                np_(lp['bv'][i]).reshape(cfg.n_kv_heads * hd))
        if cfg.is_moe:
            out[p + 'block_sparse_moe.gate.weight'] = np_(lp['router'][i]).T
            for e in range(cfg.n_experts):
                ep = p + f'block_sparse_moe.experts.{e}.'
                out[ep + 'w1.weight'] = np_(lp['moe_gate'][i, e]).T
                out[ep + 'w3.weight'] = np_(lp['moe_up'][i, e]).T
                out[ep + 'w2.weight'] = np_(lp['moe_down'][i, e]).T
        else:
            out[p + 'mlp.gate_proj.weight'] = np_(lp['w_gate'][i]).T
            out[p + 'mlp.up_proj.weight'] = np_(lp['w_up'][i]).T
            out[p + 'mlp.down_proj.weight'] = np_(lp['w_down'][i]).T
    # Transposed views are not C-contiguous; safetensors assumes C order.
    out = {k: np.ascontiguousarray(v) for k, v in out.items()}
    save_file(out, os.path.join(path, 'model.safetensors'))

    with open(os.path.join(path, 'config.json'), 'w',
              encoding='utf-8') as f:
        json.dump(hf_config_dict(cfg, torch_dtype='float32'), f, indent=2)
