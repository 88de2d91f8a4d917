"""Continuous-batching inference engine: the host-side request lifecycle.

The reference serves models by launching external engines (vLLM/SGLang/
JetStream recipes under ``llm/``); this is the in-tree TPU engine those
recipes become. This module holds what does not depend on how the cache
is stored: the request record, the queue and slot table, finish/cancel
bookkeeping, KV handoff validation, parameter preparation, sampling and
the per-token byte accounting. The cache (a shared page pool), its
compiled programs and the step loop are ``inference/paged.py``'s
``PagedInferenceEngine``, the one engine.

- **Continuous batching**: a fixed decode batch of ``max_batch`` slots;
  finished slots are immediately refilled from the queue — the decode
  step shape never changes.
- **Prefill/decode split**: prompts prefill in chunks interleaved with
  decode horizons; decode advances all active slots together.
- **Sampling**: greedy / temperature / top-k / top-p (nucleus), jitted
  with the decode step; per-request stop sequences checked host-side.
- **Sharding**: with a mesh, params shard by their logical axes (tp for
  serving) and the pool's kv heads over tp.

Requests whose prompt+max_new_tokens exceed ``max_seq`` are rejected, and
decode stops at capacity.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu import telemetry
from skypilot_tpu.models import llama
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.telemetry import clock
from skypilot_tpu.telemetry import tracing
from skypilot_tpu.utils.host import device_upload


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    # Stop sequences (token-id lists): decode finishes when the output
    # ends with any of them; the matched suffix is trimmed from
    # ``output``. NOTE a multi-token stop may partially stream before it
    # matches — non-streaming callers always see the trimmed output.
    stop: Optional[List[List[int]]] = None
    stop_hit: bool = False
    # Admission priority hint (lower = more urgent; the serve
    # scheduler maps SLO tiers to these). Orders queue pops — FIFO
    # within a priority class — so an engine-internal requeue
    # (paged preemption backoff) cannot park a latency-tier request
    # behind newly queued throughput work.
    priority: int = 0
    # Disaggregated prefill: a held request runs admission + prefill
    # and samples its first token, then TAKES NO DECODE STEPS (every
    # decode-phase ready mask skips it) until the serve layer exports
    # its KV to a decode worker — or releases the hold on handoff
    # failure (colocated fallback). Keeps a prefill worker's chips on
    # prefill instead of racing the handoff with local decode.
    hold: bool = False
    # Multi-tenant serving: which bank adapter this request decodes
    # with (None = base model, byte-identical to an adapter-less
    # engine), which tenant submitted it (telemetry label only), and an
    # optional grammar constraint ('json' | token-id list | [vocab]
    # bool mask) compiled into a vocab logit mask at admission.
    adapter: Optional[str] = None
    tenant: Optional[str] = None
    grammar: Any = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # NaN blast-radius isolation: the device emitted the non-finite
    # sentinel for this request's logits row — it was evicted with a
    # retryable error instead of streaming argmax-of-NaN garbage.
    nan_evicted: bool = False
    # Paged-engine early slot recycle: output tokens covered by
    # ENQUEUED device calls, and whether the slot was freed before the
    # request's tail tokens surfaced through the async pipeline.
    _enq_out: int = 0
    _early_freed: bool = False
    # Adapter-bank pin state: the bank slot this request gathers
    # (-1 = none), the compiled [vocab] bool mask (host numpy) its
    # grammar produced, and whether its registry pin was released
    # (every exit path releases exactly once).
    _adapter_slot: int = -1
    _vocab_mask: Optional[Any] = None
    _adapter_released: bool = False
    # Per-request lifecycle trace (telemetry.tracing.RequestTrace;
    # None when engine telemetry is off).
    trace: Optional[Any] = None

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return (self.first_token_time - self.submit_time) * 1e3


def _bucket_len(n: int, minimum: int = 64) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _ring_row_bytes(cfg, batch: int, mesh=None) -> int:
    """Bytes of ONE horizon-step's ring rows (k+v across all layers) —
    the ring stays in model dtype regardless of cache quantization.
    With a mesh, PER-DEVICE bytes: the ring's kv-head dim shards over
    tp like the cache it merges into (batch sharding is NOT credited —
    the paged ring rides a replicated batch, so dividing by dp would
    under-reserve)."""
    return (cfg.n_cache_layers * batch * cfg.kv_spec.row_values *
            jnp.dtype(cfg.dtype).itemsize
            ) // kv_shard_degree(cfg, mesh)


_RING_BYTES_CAP = int(1e9)


# Re-exported for engine-side callers; defined in kv_transfer so the
# serve layer can catch it without importing this jax-heavy module.
from skypilot_tpu.inference.kv_transfer import HandoffCapacityError  # noqa: E402,F401 pylint: disable=wrong-import-position


def resolve_kv_cache_dtype(kv_cache_dtype: Optional[str],
                           quantize: Optional[str]) -> str:
    """Effective KV storage dtype ('bf16' | 'int8' | 'int4') from the
    engine flag. ``None``/``'auto'`` follows the WEIGHT quantization
    mode (int8 weights => int8 KV, int4 weights => int4 KV — with
    weights already 4-bit the KV stream is the dominant decode HBM
    traffic, so auto matches its width); an explicit value decouples
    them in either direction — int8/int4 KV over bf16 weights shrinks
    the dominant decode HBM stream (and grows pool token capacity) on
    its own, and bf16 KV over quantized weights is the ablation/debug
    spelling."""
    if kv_cache_dtype in (None, 'auto'):
        return {'int8': 'int8', 'int4': 'int4'}.get(quantize, 'bf16')
    if kv_cache_dtype not in ('bf16', 'int8', 'int4'):
        raise ValueError(
            f'unknown kv_cache_dtype {kv_cache_dtype!r}; supported: '
            "'bf16', 'int8', 'int4' (None/'auto' follows the weight "
            'quantize mode)')
    return kv_cache_dtype


def kv_shard_degree(cfg, mesh=None) -> int:
    """How many ways the stored KV-head dimension actually splits over
    the mesh: the tp axis size when it divides ``n_kv_heads``, else 1 —
    mirroring ``mesh_lib.spec_for``'s divisibility fallback, which
    replicates KV heads for MQA/GQA models with ``n_kv_heads < tp``.
    THE divisor per-shard KV byte accounting rides; using the raw tp
    size would claim HBM savings the sharding rules never delivered."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    return mesh_lib.axis_shard_degree(
        mesh, mesh_lib.DEFAULT_RULES['kv_heads'], cfg.n_kv_heads)


def kv_token_bytes(cfg, quantized: bool, mesh=None) -> int:
    """Stored bytes of ONE cached token: k+v rows across all layers,
    per-row fp32 scales included for int8 caches. THE per-token cost
    every capacity decision rides — paged pool sizing, the prefill
    stacked-rows caps, preemption accounting, and the telemetry
    capacity gauges — so int8 KV's halved cost shows up everywhere at
    once instead of drifting per call site.

    ``mesh`` (optional) makes the cost PER-SHARD: the kv-head dim
    shards over tp, so one device stores ``1/tp`` of every token's
    rows. HBM-budget decisions (pool auto-sizing, prefill stack caps)
    must pass the mesh; token-capacity surfaces (pool stats, scheduler
    bounds) stay global — a token is a token regardless of how many
    chips hold its rows.

    ``quantized`` accepts the historical bool (True == int8) or a kv
    dtype string: int4 rows are PACKED — two nibble codes per byte
    (head_dim/2) plus the same fp32 row scale."""
    spec = cfg.kv_spec          # a latent model: one row, not k+v heads
    if quantized == 'int4':
        row_w = spec.k_dim // 2 + spec.v_dim // 2 + 8
    elif quantized and quantized != 'bf16':
        row_w = spec.k_dim + spec.v_dim + 8
    else:
        row_w = (spec.k_dim + spec.v_dim) * jnp.dtype(cfg.dtype).itemsize
    return (cfg.n_cache_layers * spec.heads * row_w
            ) // kv_shard_degree(cfg, mesh)


def refuse_unsupported(cfg, **asked) -> None:
    """What a model's layer kinds cannot yet be combined with is refused
    where the engine is built, with the reason, and never fails later or
    silently. ``asked`` holds what the caller was asked for:
    ``quantize``, ``kv_cache_dtype`` (resolved), ``speculate_k``,
    ``adapter_slots``, ``mesh``, ``decode_impl``."""
    if cfg.n_loops > 1:
        _refuse_looped(cfg, asked)
    if cfg.recurrent:
        _refuse_recurrent(cfg, asked)
    if not cfg.latent:
        return
    reasons = {
        'quantize': (asked.get('quantize') is not None,
                     'quantize_params knows the dense GQA leaves only; '
                     'the expert stacks need a grouped matmul that '
                     'dequantizes (ROADMAP.md)'),
        'kv_cache_dtype': (asked.get('kv_cache_dtype', 'bf16') != 'bf16',
                           'a per-row scale of the normed latent is '
                           'not implemented; the latent row is already '
                           '17.8x smaller than expanded K/V'),
        'speculate_k': (bool(asked.get('speculate_k')),
                        'paged_spec_verify attends through '
                        'cached_attention (GQA rows)'),
        'adapter_slots': (bool(asked.get('adapter_slots')),
                          'the LoRA bank targets wq/wk/wv/wo and the '
                          'dense FFN, which this model does not have'),
        'mesh': (asked.get('mesh') is not None,
                 'the one shared latent row cannot shard over tp, and '
                 'experts are not yet placed over a mesh'),
        'decode_impl': (asked.get('decode_impl') == 'cross_layer',
                        'the fused-merge kernel contracts K and V rows '
                        "of one width under per-head groups; 'pallas' "
                        'is the latent paged kernel, with the ring '
                        'merged in XLA'),
    }
    _refuse_first(cfg, f'attn_kind={cfg.attn_kind!r}, '
                  f'ffn_kind={cfg.ffn_kind!r}', reasons, asked)


def _refuse_first(cfg, what: str, reasons, asked) -> None:
    for name, (hit, why) in reasons.items():
        if hit:
            raise ValueError(
                f'{cfg.name} ({what}) cannot be combined with '
                f'{name}={asked[name]!r}: {why}')


def _refuse_looped(cfg, asked) -> None:
    """``refuse_unsupported`` for a model whose layers run ``n_loops``
    times a token, over ``n_cache_layers`` cache rows."""
    what = f'n_loops={cfg.n_loops}'
    if cfg.early_exit_threshold != 1:
        raise ValueError(
            f'{cfg.name} ({what}) cannot be served with '
            f'early_exit_threshold={cfg.early_exit_threshold!r}: every '
            'row of a step takes every pass; rows that leave the loop '
            'at different passes need a scheduler and a decode program '
            'that fill the cache layers they skip (ROADMAP.md)')
    _refuse_first(cfg, what, {
        'speculate_k': (bool(asked.get('speculate_k')),
                        'paged_spec_verify scans the layers once, over '
                        'n_layers cache rows'),
        'adapter_slots': (bool(asked.get('adapter_slots')),
                          'the bank is gathered once a layer; no test '
                          'holds it to a looped pass'),
        'mesh': (asked.get('mesh') is not None,
                 'no looped program has been compiled or held to its '
                 'reference over a mesh'),
        'decode_impl': (asked.get('decode_impl') == 'cross_layer',
                        'the fused-merge kernel has not been held to a '
                        "reference over pass * n_layers + layer; "
                        "'pallas' takes that row as its layer"),
    }, asked)


def _refuse_recurrent(cfg, asked) -> None:
    """``refuse_unsupported`` for a model some of whose layers keep a
    per-slot state in place of cache rows (``cfg.mixer_pattern``). (KV
    export / ingest and prefix snapshots are refused where they are
    asked for, ``PagedInferenceEngine._refuse_kv_transfer``; a prefix
    is neither matched nor registered: no state is kept at a page
    boundary.)"""
    if cfg.n_loops > 1:
        raise ValueError(
            f'{cfg.name} (mixer_pattern={cfg.mixer_pattern}) cannot be '
            f'combined with n_loops={cfg.n_loops}: a state a slot is '
            'kept per layer, not per (pass, layer)')
    if cfg.recurrent_kind == 'mamba':
        others = {
            'quantize': 'quantize_params knows the dense GQA leaves only: '
                        'not the Mamba mixer\'s projections, and no test '
                        'holds quantized weights beside the float32 '
                        'state to the reference',
            'adapter_slots': 'the LoRA bank targets wq/wk/wv/wo and the '
                             'dense FFN; the Mamba mixer has no such '
                             'projection and no bank beside a state is '
                             'held to the reference',
            'mesh': 'the per-slot state is not yet placed over a mesh'}
    else:
        others = {
            'quantize': 'quantize_params knows the dense GQA leaves only: '
                        'not the recurrent mixer\'s projections nor the '
                        'expert stacks',
            'adapter_slots': 'the LoRA bank targets wq/wk/wv/wo and the '
                             'dense FFN; the recurrent mixer and the '
                             'routed FFN have neither',
            'mesh': 'the per-slot state and the held experts are not yet '
                    'placed over a mesh (no all-to-all)'}
    _refuse_first(cfg, f'mixer_pattern={cfg.mixer_pattern}', {
        'quantize': (asked.get('quantize') is not None, others['quantize']),
        'kv_cache_dtype': (asked.get('kv_cache_dtype', 'bf16') != 'bf16',
                           'the recurrent state is float32 and is not '
                           'quantized, and no test holds quantized rows '
                           'beside it to the reference'),
        'speculate_k': (bool(asked.get('speculate_k')),
                        'a rejected draft would have to roll the '
                        'recurrent state back, and no state before the '
                        'drafts is kept'),
        'adapter_slots': (bool(asked.get('adapter_slots')),
                          others['adapter_slots']),
        'mesh': (asked.get('mesh') is not None, others['mesh']),
        'decode_impl': (asked.get('decode_impl') == 'cross_layer',
                        'the fused-merge kernel has not been held to a '
                        'reference on a model whose cache layers are '
                        'not its layers'),
    }, asked)


# Telemetry series every engine registers at construction (zeros from
# the first scrape): the decode step's KV read traffic.
KV_READ_METRIC = 'skytpu_kv_read_bytes_per_step'


def _ring_horizon_cap(cfg, batch: int, param_bytes: int,
                      mesh=None) -> int:
    """Longest sensible fused-decode horizon: the ring re-read must stay
    under ~15% of the weight stream AND the ring buffers under ~1 GB
    (at batch 48 on a 7B the 15% rule alone allowed a 1.6 GB ring that
    blew the HBM budget at runtime). The floor matters as much as the
    cap: horizons below ~32 pay per-call dispatch more often than a
    bigger ring costs in re-reads. Both were fitted where a dispatch
    cost ~100 ms; that cost is not measured on the current chip, and
    re-deriving the values is ROADMAP C8."""
    row = _ring_row_bytes(cfg, batch, mesh)
    return max(8, min(int(0.15 * param_bytes / row),
                      _RING_BYTES_CAP // row))


def prepare_params(cfg: ModelConfig, params, *, quantize=None, mesh=None,
                   donate_params: bool = False):
    """Param preparation for an engine: LoRA merge, init-if-absent,
    a recurrent mixer's serving layout, optional int8 / int4
    quantization, mesh sharding. Returns (cfg,
    params, effective_quantize) — cfg changes when a LoRA checkpoint is
    folded (lora_rank drops to 0).

    Ordering matters twice: LoRA adapters fold BEFORE quantization
    (folding into an int8 base is refused), and on a mesh the bf16 tree
    is sharded FIRST so a 7B-class checkpoint never has to fit
    (bf16 + int8) on one chip; single-device quantization frees each
    bf16 leaf as its int8 replacement lands when ``donate_params``."""
    from skypilot_tpu.models import kda
    from skypilot_tpu.models import lora as lora_lib
    from skypilot_tpu.models import quantization
    # A LoRA checkpoint serves as its merged model: fold the adapters
    # into the base once; decode then runs the plain weight path.
    # ``donate_params`` lets the fold reuse the base buffers (peak HBM
    # = |W| + one layer's delta instead of 2|W|).
    cfg, params = lora_lib.maybe_merge(cfg, params,
                                       donate=donate_params)
    if params is None:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    # A recurrent mixer's q/k/v as the one matrix its dot reads where it
    # lies (``kda.serving_layout``; a tree without that stack passes).
    # Undonated, the caller's leaves stay whole: the benchmark reads the
    # same tree into its reference after the engine is built.
    params = kda.serving_layout(params, donate=donate_params)
    if quantize is not None and quantize not in ('int8', 'int4'):
        raise ValueError(f'unknown quantize mode {quantize!r}; '
                         "supported: 'int8', 'int4'")
    premode = quantization.quantized_mode(params)
    prequantized = premode is not None
    if prequantized:
        # e.g. host-side quantization during checkpoint load
        # (weights.load_checkpoint(quantize='int8'|'int4')).
        quantize = premode
    if mesh is not None and not prequantized:
        bf16_sh = mesh_lib.tree_shardings(
            llama.param_logical_axes(cfg), mesh, shapes=params)
        params = jax.device_put(params, bf16_sh)
    if quantize is not None and not prequantized:
        # int8: the two biggest decode HBM streams each halve (weights
        # AND the auto-coupled int8 KV). int4: the weight stream halves
        # AGAIN — packed nibble codes cross HBM, dequant fused into
        # qeinsum; KV stays int8.
        params = quantization.quantize_params(params,
                                              donate=donate_params,
                                              mode=quantize)
    if mesh is not None and quantize is not None:
        # Canonicalize: quantized codes shard like their bf16 parents
        # (int4's packed axis is halved — the divisibility-aware
        # spec_for falls back to replication where a shard no longer
        # divides); per-channel/group scales follow the output axes and
        # replicate over the contracted dims.
        qaxes = quantization.quantize_logical_axes(
            llama.param_logical_axes(cfg), mode=quantize)
        # A checkpoint quantized on the host arrives whole on the first
        # device; donated, each leaf leaves it as its shards land.
        params = jax.device_put(
            params, mesh_lib.tree_shardings(qaxes, mesh, shapes=params),
            donate=donate_params)
    return cfg, params, quantize


class _EngineBase:
    """Host-side request lifecycle of the paged engine
    (``inference/paged.py``, the one subclass): queue, slot table,
    finish/cancel bookkeeping, KV handoff and gang entries. The subclass
    holds the cache, the compiled paths, ``step()`` and the lagged
    readback (``_process_one()``)."""

    def _init_telemetry(self, enabled: bool = True) -> None:
        """Engine telemetry: the step-phase profiler + per-request
        traces. ``enabled`` ANDs with the process-wide kill switch
        (``SKYTPU_TELEMETRY=0``). All measurement is host-side around
        dispatches — the jaxpr audit's ``telemetry`` preset proves
        telemetry-on adds zero d2h transfers and zero compiles."""
        from skypilot_tpu.telemetry import profiler as profiler_lib
        self.telemetry_enabled = bool(enabled) and telemetry.enabled()
        self._prof = (profiler_lib.StepProfiler(
            engine=type(self).__name__) if self.telemetry_enabled
            else profiler_lib.NullProfiler())
        # KV-round-two gauge, registered AT CONSTRUCTION so the series
        # sits on the very first scrape (zero) — the stable-schema
        # contract: dashboards never join against a series that
        # appears only after the first decode.
        self._kv_read_gauge = None
        self._kv_layout_gauges = None
        if self.telemetry_enabled:
            from skypilot_tpu.telemetry import registry as registry_lib
            reg = registry_lib.get_registry()
            self._kv_layout_gauges = (
                reg.gauge(profiler_lib.KV_CACHE_LAYERS_METRIC,
                          'Cache layers a token has: layers x passes'),
                reg.gauge(profiler_lib.KV_TOKEN_BYTES_METRIC,
                          'Stored bytes of one cached token over all '
                          'its cache layers'),
                reg.gauge(profiler_lib.RECURRENT_LAYERS_METRIC,
                          'Layers that keep a per-slot state in place of '
                          'cache rows'),
                reg.gauge(profiler_lib.RECURRENT_STATE_BYTES_METRIC,
                          "Bytes of one slot's recurrent state over all "
                          'those layers'),
                reg.gauge(profiler_lib.MOE_HELD_EXPERTS_METRIC,
                          "Routed experts of a layer this program holds "
                          '(all of them unless told a range)'))
            self._kv_read_gauge = reg.gauge(
                KV_READ_METRIC,
                'KV-cache bytes one decode substep streams from HBM '
                '(live context rows x per-token stored cost, per '
                'shard) — the bandwidth-wall numerator')

    def _note_kv_layout(self) -> None:
        """Set the two layout gauges, once ``cfg`` and the cache dtype
        are resolved."""
        if self._kv_layout_gauges is not None:
            layers, token_bytes = self._kv_layout_gauges[:2]
            layers.set(self.cfg.n_cache_layers)
            token_bytes.set(kv_token_bytes(self.cfg, self.kv_cache_dtype))

    def _note_state_layout(self) -> None:
        """Set the gauges of what the model keeps beside cache rows and
        of the experts it holds, once the state is sized."""
        if self._kv_layout_gauges is not None:
            layers, state_bytes, held = self._kv_layout_gauges[2:]
            layers.set(self.cfg.n_recurrent_layers)
            state_bytes.set(self._state_slot_bytes)
            held.set(self.cfg.held_experts)

    def _note_decode_step(self, live_tokens: int) -> None:
        """Per-dispatch attribution behind the KV-round-two gauge: the
        HBM bytes the step's attention reads stream (live context rows
        x the same per-token cost every capacity decision uses). Host
        arithmetic only; nothing here touches the device."""
        if self._kv_read_gauge is None:
            return
        per_tok = kv_token_bytes(self.cfg, self.kv_cache_dtype,
                                 mesh=getattr(self, 'mesh', None))
        self._kv_read_gauge.set(live_tokens * per_tok)

    # ------------------------------------------ cost-model boundary
    # Operand-class annotation at the decode program boundary: the
    # static cost model (analysis/costmodel.py) prices each dispatch
    # by attributing every jaxpr input to a byte stream — weights
    # (codes/scales split out for quantized trees), the KV pool, and
    # the per-call control tables (args[0]=params, args[1]=cache,
    # control after).
    def decode_operand_classes(self, args):
        from skypilot_tpu.analysis import costmodel
        return costmodel.classify_decode_args(args)

    def phase_stats(self) -> Dict[str, Any]:
        """Step-phase latency decomposition + first-compile events for
        THIS engine (the ``/debug`` surface)."""
        return self._prof.phase_stats()

    @property
    def profiler(self):
        """The step-phase profiler: the serve layer's engine loop times
        its own phases (lock wait, fill, event routing) through it, so
        one profiler holds the whole loop."""
        return self._prof

    def _trace_finish(self, req: 'Request', **meta: Any) -> None:
        """Complete a request's trace and publish it to the process
        ring buffer (the ``/debug/requests`` surface)."""
        if req.trace is None:
            return
        req.trace.end('decode')
        req.trace.finish(output_tokens=len(req.output), **meta)
        tracing.get_trace_buffer().add(req.trace)
        req.trace = None            # publish exactly once

    def _trace_sched(self, req: 'Request') -> None:
        """Queue -> slot transition: close the queue-wait span, open
        the prefill span (re-admissions re-open both — the spans
        repeat, preserving the real timeline)."""
        if req.trace is not None:
            req.trace.end('queue')
            req.trace.begin('prefill')

    def _trace_first_token(self, req: 'Request') -> None:
        """Prefill -> decode transition, at the readback of the token
        the last chunk sampled. The prefill span ends where that
        chunk's dispatch returned; what the async pipeline added until
        the host read the token is ``first_token_lag``."""
        trace = req.trace
        if trace is None:
            return
        dispatched = trace.last_end('prefill_chunk')
        trace.end('prefill', at=dispatched)
        if dispatched is not None:
            trace.add('first_token_lag', dispatched, clock.monotonic())
        trace.begin('decode')

    def _init_slots(self, max_batch: int) -> None:
        if not hasattr(self, '_prof'):       # engines call _init_telemetry
            self._init_telemetry(True)       # first; belt and braces
        self._slots: List[Optional[Request]] = [None] * max_batch
        # A deque, not queue.Queue: admission must be able to REQUEUE AT
        # THE HEAD (capacity backoff) without starving the request
        # behind later arrivals. Thread safety is the caller's job (the
        # serve layer serializes all engine calls under one lock).
        self._queue: 'collections.deque[Request]' = collections.deque()
        self._next_id = 0
        self._finished: Dict[int, Request] = {}
        self._slot_len = np.zeros(max_batch, np.int64)
        # Async dispatch pipeline (see step()): device calls whose
        # results have not been read back yet, oldest first. Each entry
        # is {'kind': 'prefill'|'decode', 'toks': device array, ...}.
        self._pending: 'collections.deque[dict]' = collections.deque()
        self._meta_dirty = True      # slot table changed since upload
        self._meta_dev: Optional[Tuple[Any, ...]] = None
        # Device-resident current token per slot: decode call N+1 is
        # fed call N's last-token COLUMN without a host round trip (the
        # async pipeline's data path). Prefill tokens scatter in via
        # _merge_tokens_drop.
        self._tok_dev = jnp.zeros((max_batch,), jnp.int32)
        # Multi-LoRA / grammar per-slot state: device adapter indices
        # ([b] int32, -1 = base) and vocab masks ([b, vocab] bool),
        # rebuilt with the slot-meta tuple. _vmask_any is STICKY: once
        # any grammar request is seen, decode programs keep receiving a
        # mask array (all-True for unconstrained rows) — flipping
        # None<->array changes the jit treedef, and one recompile per
        # program shape is the ceiling we accept.
        self._adp_dev: Optional[Any] = None
        self._vmask_dev: Optional[Any] = None
        self._vmask_any = False

    def _step_out_shardings(self, n_lead: int) -> Dict[str, Any]:
        """jit kwargs pinning a step program's CACHE output to the
        cache's own sharding tree (``_cache_sh``), preceded by
        ``n_lead`` unpinned outputs (tokens/commit counts — GSPMD
        infers those). This is the zero-resharding contract: every
        program that returns the cache emits it in exactly the layout
        the next program consumes it in, so chained steps never insert
        a resharding collective. Empty (no kwargs) for meshless
        engines — the single-chip path stays untouched."""
        sh = getattr(self, '_cache_sh', None)
        if sh is None:
            return {}
        out = sh if n_lead == 0 else (None,) * n_lead + (sh,)
        return {'out_shardings': out}

    def _slot_meta(self, ready: List[Optional[Request]]):
        """Device copies of the per-slot sampling params + active mask,
        rebuilt only when the slot table changed (``_meta_dirty``) —
        each host->device transfer costs a dispatch round trip, so the
        per-call rebuild the engines used to do defeated the async
        pipeline. Returns (temps, topks, topps, active, sample)."""
        if self._meta_dirty or self._meta_dev is None:
            temps = np.array([r.temperature if r else 0.0
                              for r in ready], np.float32)
            # ``jnp.asarray`` of a list converts on the device: eager ops.
            with self._prof.phase('eager', site='slot_meta'):
                self._meta_dev = (
                    jnp.asarray(temps),
                    jnp.asarray([r.top_k if r else 0 for r in ready],
                                np.int32),
                    jnp.asarray([r.top_p if r else 1.0 for r in ready],
                                np.float32),
                    jnp.asarray(np.array([r is not None for r in ready])),
                    bool((temps > 0).any()))
            if getattr(self, 'adapters', None) is not None:
                with self._prof.phase('upload'):
                    self._adp_dev = device_upload(np.array(
                        [r._adapter_slot if r is not None else -1
                         for r in ready], np.int32))
            if self._vmask_any:
                vm = np.ones((len(ready), self.cfg.vocab_size), bool)
                for i, r in enumerate(ready):
                    if r is not None and r._vocab_mask is not None:
                        vm[i] = r._vocab_mask
                with self._prof.phase('upload'):
                    self._vmask_dev = device_upload(vm)
            self._meta_dirty = False
        return self._meta_dev

    def _queue_pop(self) -> Optional[Request]:
        """Next request to admit: the FIRST queue entry of the most
        urgent (lowest) priority present — FIFO within a priority
        class, and requeue-at-front keeps its meaning for same-priority
        capacity backoff. O(n) scan; the serve scheduler keeps this
        queue at most a few entries deep (it holds its own backlog)."""
        if not self._queue:
            return None
        best_i = 0
        best_p = self._queue[0].priority
        if best_p > 0:              # a lower-priority head: scan for better
            for i, r in enumerate(self._queue):
                if r.priority < best_p:
                    best_i, best_p = i, r.priority
                    if best_p <= 0:
                        break
        if best_i == 0:
            return self._queue.popleft()
        req = self._queue[best_i]
        del self._queue[best_i]
        return req

    def _requeue_front(self, reqs: List[Request]) -> None:
        """Put not-yet-admitted requests back at the FRONT, preserving
        their original order (FIFO fairness under backpressure)."""
        self._queue.extendleft(reversed(reqs))

    # ------------------------------------------------------------- API
    def add_request(self, prompt: List[int], max_new_tokens: int = 128,
                    temperature: float = 0.0, top_k: int = 0,
                    top_p: float = 1.0, eos_id: Optional[int] = None,
                    stop: Optional[List[List[int]]] = None,
                    priority: int = 0, hold: bool = False,
                    adapter: Optional[str] = None,
                    tenant: Optional[str] = None,
                    grammar: Any = None) -> int:
        if not prompt:
            raise ValueError('empty prompt')
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f'top_p must be in (0, 1], got {top_p}')
        if stop:
            stop = [list(s) for s in stop if s]
        self._validate_request(prompt, max_new_tokens)
        registry = getattr(self, 'adapters', None)
        if adapter is not None and registry is None:
            raise ValueError(
                f'request names adapter {adapter!r} but the engine has '
                f'no adapter bank (adapter_slots=0)')
        vocab_mask = None
        if grammar is not None:
            from skypilot_tpu.inference import adapters as adapters_lib
            vocab_mask = adapters_lib.compile_grammar(
                grammar, self.cfg.vocab_size, eos_id)
        # Pin the adapter BEFORE building the request: a bank-full /
        # unknown-adapter error must reject at admission, not mid-step.
        adapter_slot = -1
        if adapter is not None:
            adapter_slot = registry.acquire(adapter)
        req = Request(request_id=self._next_id, prompt=list(prompt),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      top_k=top_k, top_p=top_p, eos_id=eos_id,
                      stop=stop or None, priority=int(priority),
                      hold=bool(hold), adapter=adapter, tenant=tenant,
                      grammar=grammar, submit_time=clock.now())
        req._adapter_slot = adapter_slot
        req._vocab_mask = vocab_mask
        if vocab_mask is not None:
            self._vmask_any = True
            self._meta_dirty = True
        if registry is not None:
            registry.note_request(adapter)
        if self.telemetry_enabled:
            req.trace = tracing.RequestTrace(req.request_id)
            req.trace.begin('queue', prompt_tokens=len(prompt),
                            max_new_tokens=max_new_tokens)
        self._next_id += 1
        self._queue.append(req)
        return req.request_id

    def adopt_trace_context(self, request_id: int,
                            trace_id: Optional[str] = None,
                            parent_span: Optional[str] = None,
                            submitted_at: Optional[float] = None
                            ) -> Optional[str]:
        """Join a queued/running request to a wire-supplied trace
        context (the LB's ``X-Skytpu-Trace`` hop header). Returns the
        request's effective 128-bit trace id — locally minted when no
        wire context arrived — or None when the request is unknown or
        telemetry is off. ``submitted_at`` (wall clock) is when the
        serve scheduler took the request: its wait there becomes the
        trace's ``sched_wait`` span. Caller holds the engine lock
        (same contract as ``add_request``)."""
        for req in list(self._queue) + [r for r in self._slots
                                        if r is not None]:
            if req.request_id == request_id:
                if req.trace is None:
                    return None
                req.trace.adopt_wire_context(trace_id, parent_span)
                if submitted_at is not None:
                    req.trace.prepend('sched_wait', submitted_at)
                return req.trace.trace_id
        return None

    def _validate_request(self, prompt: List[int],
                          max_new_tokens: int) -> None:
        if len(prompt) + max_new_tokens > self.max_seq:
            raise ValueError(
                f'prompt ({len(prompt)}) + max_new_tokens '
                f'({max_new_tokens}) exceeds engine max_seq '
                f'({self.max_seq})')

    def has_work(self) -> bool:
        return (len(self._queue) > 0
                or any(r is not None for r in self._slots))

    def has_runnable_work(self) -> bool:
        """``has_work`` minus parked state: False when everything live
        is a HELD slot awaiting a KV handoff — stepping then does
        nothing, so the serve loop sleeps until a wake (submit /
        release_hold / drain all set it) instead of spinning."""
        if self._queue or self._pending:
            return True
        if getattr(self, '_lagging', None):
            return True
        return any(r is not None and not r.hold for r in self._slots)

    def _decode_ready(self) -> List[Optional['Request']]:
        """Per-slot request list for decode-phase programs: None for
        empty slots, mid-prefill slots, and HELD slots (a prefill-role
        handoff candidate stops after its prefill-sampled first token
        — it must not race the handoff with local decode steps)."""
        return [None if (r is None or s in self._prefill_off or r.hold)
                else r for s, r in enumerate(self._slots)]

    def release_hold(self, request_id: int) -> bool:
        """Resume local decoding of a held request (handoff failed or
        no decode worker available — the colocated fallback). True when
        a hold was actually cleared."""
        for r in list(self._queue) + [r for r in self._slots
                                      if r is not None]:
            if r.request_id == request_id and r.hold:
                r.hold = False
                self._meta_dirty = True
                return True
        return False

    # Requests evicted because their logits row went non-finite (the
    # device-side NaN sentinel, llama.NONFINITE_TOKEN). The serve
    # layer watches the delta to escalate repeated hits to a
    # replica-level alarm.
    nan_evictions = 0

    def _evict_nonfinite(self, slot: int,
                         req: 'Request') -> Tuple[int, int, bool]:
        """The device emitted the NaN sentinel for this request: evict
        it (free its slot, finish its trace) WITHOUT recording it as
        finished — the serve scheduler turns the sentinel event into a
        retryable per-request error, so co-batched requests continue
        untouched while this one fails over. Returns the event tuple
        the caller appends in place of a token event."""
        req.nan_evicted = True
        req.finish_time = clock.now()
        self.nan_evictions += 1
        self._release_adapter(req)
        self._trace_finish(req, nan_evicted=True)
        if 0 <= slot < len(self._slots) and self._slots[slot] is req:
            self._free_slot(slot)
        return (req.request_id, llama.NONFINITE_TOKEN, True)

    def mesh_axes(self) -> Dict[str, int]:
        """{axis: size} of this engine's mesh (all 1s when meshless) —
        the stable-schema payload behind ``skytpu_mesh_shape{axis=}``,
        the JSON ``mesh`` block, and the LB's replica view."""
        from skypilot_tpu.parallel import mesh as mesh_lib
        return mesh_lib.mesh_axis_sizes(getattr(self, 'mesh', None))

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (the serve metrics surface)."""
        return len(self._queue)

    def _remaining_decode(self, req: 'Request') -> int:
        """Decode tokens this request may still emit (budget- and
        capacity-clamped)."""
        ctx = len(req.prompt) + len(req.output)
        return max(0, min(req.max_new_tokens - len(req.output),
                          self.max_seq - ctx))

    def remaining_work_tokens(self) -> int:
        """Estimated TOKENS of work ahead of a new arrival: every
        queued request's full prefill+decode budget plus every live
        slot's unprefilled prompt tail and remaining decode budget.
        An upper bound (eos/stop finish early) — the serve scheduler's
        Retry-After and the queue-depth LB policy both read it, where
        overestimating by the early-stop margin only makes backoff
        slightly conservative."""
        total = 0
        for r in self._queue:
            # Recompute context (prompt+output) + decode remainder
            # telescopes to prompt + max_new_tokens.
            total += len(r.prompt) + min(r.max_new_tokens,
                                         self.max_seq - len(r.prompt))
        for slot, r in enumerate(self._slots):
            if r is None:
                continue
            total += self._slot_remaining_prefill(slot)
            total += self._remaining_decode(r)
        return total

    # Fraction of the interleaved scheduler's token budget spent on
    # decode while prompts are mid-prefill (None = engine default).
    _DEFAULT_DECODE_PRIORITY = 0.5

    def _interleave_horizon(self) -> int:
        """Decode horizon to run between prefill chunk batches, from the
        ``decode_priority_ratio`` token budget (Sarathi-style
        piggybacking): one scheduler iteration spends ``n x chunk``
        prompt tokens on the chunk batch and ``active x h`` tokens on
        decode, so ``h = r/(1-r) * chunk * n / active`` splits the
        budget r:(1-r). r -> 0 drains prefill monolithically (decode
        starves); r -> 1 starves prefill instead. The caller still caps
        by its own horizon and the ring/capacity limits."""
        r = self.decode_priority_ratio
        if r is None:
            r = self._DEFAULT_DECODE_PRIORITY
        if r >= 1.0:
            return self._HORIZON_BUCKETS[-1]
        active = self.num_active - len(self._prefill_off)
        if active <= 0:
            return 1
        n = max(1, min(len(self._prefill_off), self._prefill_n_max))
        want = r / max(1.0 - r, 1e-3) * self.chunk * n / active
        return max(1, int(want))

    # Multi-step on-device decode: when set (k >= 1), every decode
    # enqueue fuses EXACTLY k steps in one jitted call — on-device
    # sampling included — so per-call dispatch, readback lag, and
    # sampling host-syncs amortize k x. None (default) keeps the
    # caller-driven adaptive horizon. Pinning wins over the interleave
    # / queue-pressure shrinks (the knob is an explicit throughput
    # trade) but never over the capacity/ring safety caps; the jit key
    # stays static at (k, sample, bucket). Composes with
    # ``speculate_k``: when both are set the two knobs fuse into
    # in-scan speculative verify (``_spec_step_fused`` — k verify
    # rounds per dispatch); with ``decode_steps_per_call`` unset or 1,
    # speculation runs one synchronous verify round per step.
    decode_steps_per_call: Optional[int] = None

    @staticmethod
    def _validate_decode_steps(decode_steps_per_call):
        if decode_steps_per_call is None:
            return None
        k = int(decode_steps_per_call)
        if k < 1:
            raise ValueError(
                f'decode_steps_per_call must be >= 1, got {k}')
        return k

    # Depth of the async dispatch pipeline: device calls kept in flight
    # before the host reads results back. Depth 2 overlaps the per-call
    # dispatch round trip with device compute: the next decode is
    # enqueued with DEVICE-resident tokens/cache from the previous call,
    # so the host sync rides one call behind and the device never idles.
    # (The depth was chosen where a dispatch cost 100-600 ms: not
    # measured on the current chip, ROADMAP C8.)
    _PIPELINE_DEPTH = 2

    def run_to_completion(self, horizon: int = 32) -> Dict[int, Request]:
        """Drive until queue + slots + in-flight calls drain. Returns
        finished requests."""
        while self.has_work() or self._pending:
            self.step(horizon)
        return dict(self._finished)

    def cancel(self, request_id: int) -> bool:
        """Abort a live request: drop it from the wait queue or free its
        decode slot so a disconnected client stops consuming capacity.
        Returns True if the request was still live (it is NOT recorded in
        the finished table). Safe no-op for finished/unknown ids."""
        dropped = [r for r in self._queue if r.request_id == request_id]
        self._queue = collections.deque(
            r for r in self._queue if r.request_id != request_id)
        if dropped:
            self._release_adapter(dropped[0])
            self._trace_finish(dropped[0], cancelled=True)
            return True
        for slot, req in enumerate(self._slots):
            if req is not None and req.request_id == request_id:
                req.finish_time = clock.now()
                self._release_adapter(req)
                self._trace_finish(req, cancelled=True)
                self._free_slot(slot)
                return True
        return False

    def export_inflight(self) -> List[Dict[str, Any]]:
        """Resubmittable snapshot of every live request (queued AND
        decoding), for the fault-tolerance layer: original prompt plus
        the tokens generated so far, so a surviving replica can
        continue from ``prompt + output`` (the prefix cache makes the
        recompute cheap) with the remaining decode budget. Greedy
        continuations are byte-identical to the uninterrupted run.
        Callers serialize engine access (the serve layer's engine
        lock), like every other host-side engine call."""
        out: List[Dict[str, Any]] = []
        live = list(self._queue) + [r for r in self._slots
                                    if r is not None]
        for req in live:
            if req.finish_time is not None:
                continue
            out.append({
                'request_id': req.request_id,
                'prompt': list(req.prompt),
                'output': list(req.output),
                'max_new_tokens': req.max_new_tokens,
                'remaining_new_tokens': max(
                    0, req.max_new_tokens - len(req.output)),
                'temperature': req.temperature,
                'top_k': req.top_k,
                'top_p': req.top_p,
                'eos_id': req.eos_id,
                'stop': ([list(s) for s in req.stop]
                         if req.stop else None),
                'priority': req.priority,
            })
        return out

    # ------------------------------------------------- disaggregation
    # KV handoff (disaggregated prefill/decode serving): a prefill
    # worker exports a live request's context rows in the cache's
    # STORED dtype (int8 codes+scales stay int8 — the wire codec never
    # dequantizes); a decode worker ingests them and resumes decoding
    # at the exact original bytes. The pool's gather and scatter are
    # the subclass's (_gather_kv_rows / _land_kv_rows).

    def export_kv_snapshot(self, request_id: int):
        """Resumable handoff snapshot of a live DECODING request:
        (snapshot dict, drained events). The async pipeline is drained
        first so the host view (output tokens, row counts) is complete
        and the device rows are final — the drained token events are
        RETURNED, not dropped; the caller must route them to its
        consumers exactly like ``step()`` events. Returns
        ``(None, events)`` when the request is not in a decodable slot
        (finished, cancelled, still mid-prefill, or only queued)."""
        events: List[Tuple[int, int, bool]] = []
        while self._pending:
            events.extend(self._process_one())
        slot = next((s for s, r in enumerate(self._slots)
                     if r is not None and r.request_id == request_id),
                    None)
        if slot is None or slot in getattr(self, '_prefill_off', {}):
            return None, events
        req = self._slots[slot]
        if not req.output:
            return None, events        # no first token yet
        n_rows = int(self._slot_len[slot])
        if n_rows != len(req.prompt) + len(req.output) - 1:
            # Row/token bookkeeping out of sync (should not happen in
            # the greedy serving path): refuse the handoff rather than
            # ship an inconsistent snapshot.
            return None, events
        k, v, ks, vs = self._gather_kv_rows(slot, n_rows)
        cfg = self.cfg
        snapshot = {
            'kv_cache_dtype': self.kv_cache_dtype,
            'n_rows': n_rows,
            'model': {'n_layers': cfg.n_layers,
                      'n_kv_heads': cfg.n_kv_heads,
                      'head_dim': cfg.head_dim},
            'prompt': list(req.prompt),
            'output': list(req.output),
            'max_new_tokens': req.max_new_tokens,
            'temperature': req.temperature,
            'top_k': req.top_k,
            'top_p': req.top_p,
            'eos_id': req.eos_id,
            'stop': ([list(s) for s in req.stop] if req.stop else None),
            'priority': req.priority,
            'k': k, 'v': v, 'k_scale': ks, 'v_scale': vs,
        }
        return snapshot, events

    def decoding_request_ids(self) -> List[int]:
        """Request ids currently seated in decode slots (the set
        ``export_kv_snapshot`` can snapshot). Callers serialize engine
        access like every other host-side engine call."""
        return [r.request_id for r in self._slots if r is not None]

    # ------------------------------------------------- gang lockstep
    # Multi-host gang serving (serve/gang.py): rank 0 records every
    # engine mutation (add/step/cancel/flush/warmup) to an op log and
    # nonzero ranks replay it verbatim, so every process executes the
    # same jitted steps in the same order on its mesh shard. These two
    # entries are the follower side of that contract.

    def drain_pipeline(self) -> List[Tuple[int, int, bool]]:
        """Flush the async dispatch pipeline completely; returns the
        drained events (callers route them exactly like ``step()``
        events). The gang ``flush`` op: rank 0 drains before a
        checkpoint/handoff export and followers mirror it, so every
        rank's pipeline depth — and therefore its subsequent event
        stream — stays aligned."""
        events: List[Tuple[int, int, bool]] = []
        while self._pending:
            events.extend(self._process_one())
        return events

    def follower_step(self, horizon: int = 1, *,
                      prepared: bool = False
                      ) -> List[Tuple[int, int, bool]]:
        """Gang-follower step entry: execute exactly the step rank 0
        recorded — the same proposer preparation, the same fused
        horizon — and return the step's events for finished-request
        digest verification (the caller reaps finished requests; no
        scheduler runs on followers)."""
        if prepared and getattr(self, 'speculate_k', 0):
            self.prepare_proposals()
        return self.step(horizon=horizon)

    def _validate_kv_entry(self, entry: Dict[str, Any],
                           n_rows: int) -> None:
        """Shared KV-payload validation for ingest/warmup: model
        shape, kv dtype (no transcoding) and row-array shapes. Raises
        ``ValueError`` (permanent refusal)."""
        cfg = self.cfg
        model = entry.get('model') or {}
        for key, want in (('n_layers', cfg.n_layers),
                          ('n_kv_heads', cfg.n_kv_heads),
                          ('head_dim', cfg.head_dim)):
            if int(model.get(key, -1)) != want:
                raise ValueError(
                    f'handoff model mismatch: {key}='
                    f'{model.get(key)} != engine {want}')
        if entry.get('kv_cache_dtype') != self.kv_cache_dtype:
            raise ValueError(
                'handoff kv_cache_dtype '
                f'{entry.get("kv_cache_dtype")!r} != engine '
                f'{self.kv_cache_dtype!r} (no wire transcoding: '
                'quantized KV must land in a same-dtype pool)')
        # int4 rows travel PACKED: two nibble codes per byte along
        # head_dim (uint8, head_dim/2) — exactly the resident layout.
        row_d = (cfg.head_dim // 2 if self.kv_cache_dtype == 'int4'
                 else cfg.head_dim)
        for arr, name in ((entry['k'], 'k'), (entry['v'], 'v')):
            shape = tuple(np.shape(arr))
            want_shape = (cfg.n_layers, n_rows, cfg.n_kv_heads, row_d)
            if shape != want_shape:
                raise ValueError(f'handoff {name} rows shape {shape} '
                                 f'!= {want_shape}')
        if self.kv_cache_dtype in ('int8', 'int4'):
            for arr, name in ((entry['k_scale'], 'k_scale'),
                              (entry['v_scale'], 'v_scale')):
                shape = tuple(np.shape(arr))
                if shape != (cfg.n_layers, n_rows, cfg.n_kv_heads):
                    raise ValueError(
                        f'handoff {name} shape {shape} != '
                        f'{(cfg.n_layers, n_rows, cfg.n_kv_heads)}')
            want_np = (np.uint8 if self.kv_cache_dtype == 'int4'
                       else np.int8)
            for arr, name in ((entry['k'], 'k'), (entry['v'], 'v')):
                if np.dtype(getattr(arr, 'dtype', None)) != want_np:
                    raise ValueError(
                        f'handoff {name} codes are '
                        f'{getattr(arr, "dtype", None)}, expected '
                        f'{np.dtype(want_np).name} (quantized KV '
                        'never widens on the wire)')

    def _validate_ingest(self, snap: Dict[str, Any]) -> None:
        """Shared ingest validation: model shape, kv dtype (no
        transcoding — int8 stays int8 end to end), row-count
        consistency, and the engine's own request limits. Raises
        ``ValueError`` (permanent refusal)."""
        prompt, output = snap['prompt'], snap['output']
        if not output:
            raise ValueError('handoff carries no generated token')
        n_rows = int(snap['n_rows'])
        if n_rows != len(prompt) + len(output) - 1:
            raise ValueError(
                f'handoff n_rows {n_rows} != context rows '
                f'{len(prompt) + len(output) - 1}')
        if len(output) >= int(snap['max_new_tokens']):
            raise ValueError('handoff request is already complete')
        self._validate_request(prompt, int(snap['max_new_tokens']))
        self._validate_kv_entry(snap, n_rows)

    def _ingest_request(self, snap: Dict[str, Any]) -> Request:
        """Recreate the engine Request a handoff snapshot describes
        (output prepopulated; finish checks then behave exactly as if
        the tokens had been generated here)."""
        req = Request(
            request_id=self._next_id, prompt=list(snap['prompt']),
            max_new_tokens=int(snap['max_new_tokens']),
            temperature=float(snap.get('temperature') or 0.0),
            top_k=int(snap.get('top_k') or 0),
            top_p=float(snap.get('top_p') or 1.0),
            eos_id=snap.get('eos_id'),
            stop=([list(s) for s in snap['stop']]
                  if snap.get('stop') else None),
            priority=int(snap.get('priority') or 0),
            output=list(snap['output']),
            submit_time=clock.now())
        # The first token happened on the prefill worker; set the
        # timestamp so per-token bookkeeping treats the slot as live.
        # The serve layer skips TTFT observation for handoff
        # continuations.
        req.first_token_time = req.submit_time
        req._enq_out = len(req.output)
        if self.telemetry_enabled:
            # A handoff continuation JOINS the fleet-wide trace the
            # prefill worker started (the /kv/ingest hop carries
            # X-Skytpu-Trace; the server parks it in snap['trace']).
            ctx = snap.get('trace') or {}
            req.trace = tracing.RequestTrace(
                self._next_id, trace_id=ctx.get('trace_id'),
                parent_span=ctx.get('parent_span'))
            req.trace.begin('decode', handoff=True,
                            context_tokens=len(req.prompt)
                            + len(req.output))
        self._next_id += 1
        return req

    def ingest_kv_snapshot(self, snap: Dict[str, Any]) -> int:
        """Land a handoff: validate, seat the request in a free slot
        with its KV rows written at the exact original bytes, and
        return the new request id. Raises ``ValueError`` for
        malformed/mismatched handoffs (permanent) and
        :class:`HandoffCapacityError` when no slot or KV capacity is
        free (retryable — the router picks another decode worker)."""
        self._validate_ingest(snap)
        slot = next((s for s in range(self.max_batch)
                     if self._slots[s] is None), None)
        if slot is None:
            raise HandoffCapacityError('no free decode slot')
        req = self._ingest_request(snap)
        self._land_kv_rows(slot, req, snap)
        ctx = req.prompt + req.output
        self._slots[slot] = req
        self._slot_len[slot] = int(snap['n_rows'])
        # Current token = the last generated one; decode resumes on
        # the very next horizon without a host round trip.
        with self._prof.phase('upload'):
            slot_d, tok_d = device_upload(
                (np.array([slot], np.int32),
                 np.array([ctx[-1]], np.int32)))
        with self._prof.phase('dispatch'):
            self._tok_dev = self._merge_tokens_drop(self._tok_dev, slot_d,
                                                    tok_d)
        self._meta_dirty = True
        return req.request_id

    def get_finished(self, request_id: int) -> Optional[Request]:
        return self._finished.get(request_id)

    def pop_finished(self, request_id: int) -> Optional[Request]:
        """Consume a finished request, evicting it from the finished
        table. Long-lived servers MUST use this (or evict otherwise):
        the table grows without bound under steady traffic."""
        return self._finished.pop(request_id, None)

    # -------------------------------------------------------- internals
    def _free_slot(self, slot: int) -> None:
        self._slots[slot] = None
        self._slot_len[slot] = 0
        self._meta_dirty = True      # async engines re-upload slot meta

    def _finish_req(self, slot: int, req, token: int) -> bool:
        """Request-scoped finish check, so that EARLY-RECYCLED
        tenancies (slot already freed or re-assigned, tail tokens still
        surfacing through the async pipeline) can finish their request
        without touching whoever holds the slot now — it is only freed
        when ``req`` still owns it."""
        # Stop sequences first: a stop completing exactly on the
        # max_new_tokens/max_seq boundary must still be trimmed.
        done = False
        if req.stop:
            for seq in req.stop:
                if (len(req.output) >= len(seq)
                        and req.output[-len(seq):] == seq):
                    del req.output[-len(seq):]
                    req.stop_hit = True
                    done = True
                    break
        done = (done or len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and token == req.eos_id)
                or len(req.prompt) + len(req.output) >= self.max_seq)
        if done:
            req.finish_time = clock.now()
            self._finished[req.request_id] = req
            self._release_adapter(req)
            self._trace_finish(req, stop_hit=req.stop_hit)
            if self._slots[slot] is req:
                self._free_slot(slot)
        return done

    def _release_adapter(self, req) -> None:
        """Drop this request's adapter-bank pin, exactly once per
        request lifetime (finish, cancel, and NaN eviction all call
        this; the flag makes overlapping exit paths safe)."""
        if req.adapter is None or req._adapter_released:
            return
        req._adapter_released = True
        registry = getattr(self, 'adapters', None)
        if registry is not None:
            registry.release(req.adapter)


def sample_tokens(logits: jax.Array, step_rng: jax.Array,
                  temps: jax.Array, topks: jax.Array,
                  topps: jax.Array,
                  vocab_mask: Optional[jax.Array] = None) -> jax.Array:
    """Per-slot next-token sampling of the fused decode: optional
    grammar vocab mask, then
    temperature scaling, then top-k and nucleus (top-p) filtering
    (``llama.filtered_logits`` — one descending sort of the scaled
    logits, also the distribution speculative verify rejection-samples
    against), then categorical draw. Rows with temp <= 0 take the
    greedy argmax; top-k <= 0 and top-p >= 1 disable their filters.
    The mask applies BEFORE the greedy argmax too — a constrained
    greedy request picks the best ALLOWED token."""
    logits = llama.apply_vocab_mask(logits, vocab_mask)
    next_greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    masked = llama.filtered_logits(logits, temps, topks, topps)
    sampled = jax.random.categorical(step_rng, masked).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, next_greedy)
