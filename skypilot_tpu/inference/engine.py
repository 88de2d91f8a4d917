"""Continuous-batching inference engine (JetStream-class serving core).

The reference serves models by launching external engines (vLLM/SGLang/
JetStream recipes under ``llm/``); this is the in-tree TPU engine those
recipes become. Design:

- **Slot-based continuous batching**: a fixed decode batch of ``max_batch``
  slots over one batched KV cache ([layers, slots, max_seq, kv_heads, d],
  per-slot lengths). Finished slots are immediately refilled from the queue
  — the decode step shape never changes, so XLA compiles exactly two
  programs (prefill per length-bucket, decode) and the MXU sees a fixed
  [slots, 1] batch every step.
- **Prefill/decode split**: prefill runs per-request at bucketed lengths
  (powers of two — bounded compile count), writes its KV rows into the
  slot; decode advances all active slots one token per step.
- **Sampling**: greedy / temperature / top-k / top-p (nucleus), jitted
  with the decode step; per-request stop sequences checked host-side.
- **Sharding**: with a mesh, params shard by their logical axes (tp for
  serving) and the KV cache by ``cache_logical_axes`` — batch over data
  axes, kv heads over tp.

The cache-capacity contract (llama.forward docstring) is enforced here:
requests whose prompt+max_new_tokens exceed ``max_seq`` are rejected, and
decode stops at capacity.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu import telemetry
from skypilot_tpu.inference.speculative import SpeculativeMixin
from skypilot_tpu.models import llama
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.telemetry import clock
from skypilot_tpu.telemetry import tracing
from skypilot_tpu.utils.host import device_upload, host_sync


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
    return (cfg.n_layers * batch * cfg.kv_spec.row_values *
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
    return (cfg.n_layers * spec.heads * row_w
            ) // kv_shard_degree(cfg, mesh)


def refuse_unsupported(cfg, **asked) -> None:
    """What a model's layer kinds cannot yet be combined with is refused
    where the engine is built, with the reason, and never fails later or
    silently. ``asked`` holds what the caller was asked for: ``engine``
    ('slot' | 'paged'), ``quantize``, ``kv_cache_dtype`` (resolved),
    ``speculate_k``, ``adapter_slots``, ``mesh``, ``decode_impl``."""
    if not cfg.latent:
        return
    reasons = {
        'engine': (asked.get('engine') == 'slot',
                   'the slot engine reserves [max_seq, kv_heads, '
                   "head_dim] rows a slot; a latent cache row has no "
                   "head axis: serve it with kv_cache='paged'"),
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
    for name, (hit, why) in reasons.items():
        if hit:
            raise ValueError(
                f'{cfg.name} (attn_kind={cfg.attn_kind!r}, '
                f'ffn_kind={cfg.ffn_kind!r}) cannot be combined with '
                f'{name}={asked[name]!r}: {why}')


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
    """Shared param preparation for the slot and paged engines:
    LoRA merge, init-if-absent, optional int8 quantization, mesh
    sharding. Returns (cfg, params, effective_quantize) — cfg changes
    when a LoRA checkpoint is folded (lora_rank drops to 0).

    Ordering matters twice: LoRA adapters fold BEFORE quantization
    (folding into an int8 base is refused), and on a mesh the bf16 tree
    is sharded FIRST so a 7B-class checkpoint never has to fit
    (bf16 + int8) on one chip; single-device quantization frees each
    bf16 leaf as its int8 replacement lands when ``donate_params``."""
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
    """Host-side request lifecycle shared by the slot engine (below) and
    the paged engine (``inference/paged.py``): queue, slot table,
    finish/cancel bookkeeping, the async step loop. Subclasses implement
    ``_admit()``, ``_enqueue_decode(horizon)`` and ``_process_one()``
    (the compiled paths + their lagged readback) and may override
    ``_free_slot``/``_validate_request``."""

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
        if self.telemetry_enabled:
            from skypilot_tpu.telemetry import registry as registry_lib
            reg = registry_lib.get_registry()
            self._kv_read_gauge = reg.gauge(
                KV_READ_METRIC,
                'KV-cache bytes one decode substep streams from HBM '
                '(live context rows x per-token stored cost, per '
                'shard) — the bandwidth-wall numerator')

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
    # the per-call control tables. Both engines share the calling
    # convention (args[0]=params, args[1]=cache, control after), so
    # the base annotation covers them.
    def decode_operand_classes(self, args):
        from skypilot_tpu.analysis import costmodel
        return costmodel.classify_decode_args(args)

    def kv_token_capacity(self) -> int:
        """Token rows the resident KV arrays physically hold (the
        divisor that turns pool avals into stored bytes/token — the
        cost model's telemetry-comparable KV unit). The slot cache
        reserves every row up front; the paged pool overrides with
        its page count."""
        return self.max_batch * self.max_seq

    def phase_stats(self) -> Dict[str, Any]:
        """Step-phase latency decomposition + first-compile events for
        THIS engine (the bench and ``/debug`` surface)."""
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
        self._inflight_steps = 0     # sum of horizons of pending decodes
        self._meta_dirty = True      # slot table changed since upload
        self._meta_dev: Optional[Tuple[Any, ...]] = None
        # Device-resident current token per slot: decode call N+1 is
        # fed call N's last-token COLUMN without a host round trip (the
        # async pipeline's data path). Prefill tokens scatter in via
        # _merge_tokens.
        self._tok_dev = jnp.zeros((max_batch,), jnp.int32)
        self._merge_tokens = jax.jit(
            lambda tok, slots, vals: tok.at[slots].set(vals))
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
            self._meta_dev = (
                jnp.asarray(temps),
                jnp.asarray([r.top_k if r else 0 for r in ready],
                            np.int32),
                jnp.asarray([r.top_p if r else 1.0 for r in ready],
                            np.float32),
                jnp.asarray(np.array([r is not None for r in ready])),
                bool((temps > 0).any()))
            if getattr(self, 'adapters', None) is not None:
                self._adp_dev = device_upload(np.array(
                    [r._adapter_slot if r is not None else -1
                     for r in ready], np.int32))
            if self._vmask_any:
                vm = np.ones((len(ready), self.cfg.vocab_size), bool)
                for i, r in enumerate(ready):
                    if r is not None and r._vocab_mask is not None:
                        vm[i] = r._vocab_mask
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

    # Pool-pressure recompute requeues. The slot engine reserves
    # max_seq rows per slot up front so it never preempts; the paged
    # engine overrides this with a live counter. One spelling so the
    # telemetry/bench surfaces read the same attribute off either.
    preemptions = 0

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

    def _slot_remaining_prefill(self, slot: int) -> int:
        """Prompt tokens this slot still has to prefill (0 once
        decodable). Chunked engines override with their cursor."""
        del slot
        return 0

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

    def _pinned_horizon(self, horizon: int) -> int:
        """The fused horizon ``step()`` should run: the pinned k when
        the multi-step knob is set, else the caller's horizon."""
        return self.decode_steps_per_call or horizon

    # Depth of the async dispatch pipeline: device calls kept in flight
    # before the host reads results back. Depth 2 overlaps the per-call
    # dispatch round trip with device compute: the next decode is
    # enqueued with DEVICE-resident tokens/cache from the previous call,
    # so the host sync rides one call behind and the device never idles.
    # (The depth was chosen where a dispatch cost 100-600 ms: not
    # measured on the current chip, ROADMAP C8.)
    _PIPELINE_DEPTH = 2

    def step(self, horizon: int = 1) -> List[Tuple[int, int, bool]]:
        """Admit waiting requests into free slots (prefill), enqueue up
        to ``horizon`` fused decode steps. Returns
        [(request_id, token, finished), ...] in emission order.

        Results lag enqueues by up to ``_PIPELINE_DEPTH`` calls — a
        request's tokens surface one or two step() calls after the
        device produced them; callers that need everything drained use
        run_to_completion()."""
        events: List[Tuple[int, int, bool]] = []
        # Make room in the pipeline (sync the oldest call) BEFORE
        # admitting: processing frees finished slots, so admission sees
        # the freshest slot table.
        with self._prof.phase('readback'):
            while len(self._pending) >= self._PIPELINE_DEPTH:
                events.extend(self._process_one())
        with self._prof.phase('admit'):
            events.extend(self._admit())
        with self._prof.phase('decode_enqueue'):
            enqueued = self._enqueue_decode(self._pinned_horizon(horizon))
        if not enqueued and self._pending:
            # Nothing to enqueue (no active slots, or capacity pinned
            # until in-flight calls land): drain one instead.
            with self._prof.phase('readback'):
                events.extend(self._process_one())
        return events

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
    # at the exact original bytes. Engine-specific gather/land live in
    # the subclasses (_gather_kv_rows / _land_kv_rows).

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

    def _gather_kv_rows(self, slot: int, n_rows: int):
        """Engine-specific: the slot's first ``n_rows`` context rows as
        host numpy (k, v, k_scale|None, v_scale|None), token-major
        [L, n, hkv, d] (scales [L, n, hkv])."""
        raise NotImplementedError

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

    # ---------------------------------------------- prefix checkpoint
    # Spot resilience: on a preemption warning the serve layer
    # checkpoints the engine's hottest prefix-cache page chains (plus
    # in-flight request snapshots) through the SKKV/SKPF wire codec,
    # and a replacement replica lands them via warm_prefix BEFORE it
    # enters LB rotation — post-recovery TTFT is near-warm instead of
    # cold. The slot engine has no prefix cache, so the base
    # implementations are honest no-ops; the paged engine overrides
    # both.

    def export_prefix_snapshots(self, max_entries: int = 8):
        """Hottest prefix-cache page chains as prefix entries
        (``kv_transfer.encode_prefix_chain`` input dicts), plus any
        events drained from the async pipeline (routed by the caller
        exactly like ``step()`` events). Base: no prefix cache —
        ``([], [])``."""
        del max_entries
        return [], []

    def warm_prefix(self, entry: Dict[str, Any]) -> int:
        """Land a prefix entry (or a request snapshot viewed as one)
        into the prefix cache WITHOUT seating a request; returns the
        number of KV rows landed. Base: no prefix cache — 0 rows (the
        warmup endpoint reports it; callers must not treat 0 as an
        error)."""
        del entry
        return 0

    def hot_prefix_digest(self, max_entries: int = 16):
        """Bounded (chain-hash, token-length, hits) digest of the
        hottest cached prefix chains, for the LB's prefix-affinity
        routing. Host-side state only — the probe path ships it on
        every /metrics scrape, so it must never touch the device.
        Base: no prefix cache — empty."""
        del max_entries
        return []

    def export_prefix_entry(self, hash_hex: str):
        """One digest-named hot chain as ``(entry_or_None, events)``
        — the proactive affinity-migration export. Base: no prefix
        cache — ``(None, [])``."""
        del hash_hex
        return None, []

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
        # timestamp so per-token bookkeeping (and the slot engine's
        # readback guard) treats the slot as live. The serve layer
        # skips TTFT observation for handoff continuations.
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
        slot_d, tok_d = device_upload(
            (np.array([slot], np.int32),
             np.array([ctx[-1]], np.int32)))
        self._tok_dev = self._merge_tokens_drop(self._tok_dev, slot_d,
                                                tok_d)
        self._meta_dirty = True
        return req.request_id

    def _land_kv_rows(self, slot: int, req: Request,
                      snap: Dict[str, Any]) -> None:
        """Engine-specific: write the snapshot's rows into this slot's
        cache storage (raises ``HandoffCapacityError`` on pool
        pressure)."""
        raise NotImplementedError

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

    def _maybe_finish(self, slot: int, token: int) -> bool:
        return self._finish_req(slot, self._slots[slot], token)

    def _finish_req(self, slot: int, req, token: int) -> bool:
        """Request-scoped finish check. Distinct from _maybe_finish so
        the paged engine's EARLY-RECYCLED tenancies (slot already freed
        or re-assigned, tail tokens still surfacing through the async
        pipeline) can finish their request without touching whoever
        holds the slot now — it is only freed when ``req`` still owns
        it."""
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


def _slot_spec_verify(params, big_cache, tokens, proposals, n_prop,
                      temps, topks, topps, active, rng, *, cfg,
                      attn_impl, kv_bucket, max_seq, k, sample,
                      mlora_idx=None, vocab_mask=None):
    """One speculative verify round over the slot cache — the traced
    body shared by the single-round jit (``_get_spec_verify``) and the
    fused in-scan rounds (``_get_spec_fused``): one forward over the
    k+1 positions [t0, d1..dk] per slot, device acceptance, and a
    MASKED sentinel scatter of the accepted rows. Returns
    ``(commit, n_commit, new_tok, new_cache)``."""
    from skypilot_tpu.inference import speculative
    b = tokens.shape[0]
    len0 = big_cache.length
    # Length-aware cache read, same policy as decode_horizon: slice
    # only when it at least halves the stream (the sliced prefix
    # materializes as a program temp).
    ck = big_cache.k[:, :, :kv_bucket]
    cv = big_cache.v[:, :, :kv_bucket]
    if big_cache.quantized:
        cache_kv = (ck, cv, big_cache.k_scale[:, :, :kv_bucket],
                    big_cache.v_scale[:, :, :kv_bucket])
    else:
        cache_kv = (ck, cv)
    seq = jnp.concatenate([tokens[:, None], proposals], axis=1)
    logits, rows = llama.prefill_rows(
        params, seq, jnp.full((b,), k + 1, jnp.int32), cfg,
        attn_impl=attn_impl,
        quantize_rows=('int4' if big_cache.packed
                       else big_cache.quantized),
        cache_kv=cache_kv, cache_len=len0, all_logits=True,
        mlora_idx=mlora_idx)
    # Grammar masks constrain verification too — the [n, k+1, vocab]
    # logits mask broadcasts over the k+1 verify positions, so a
    # proposal outside the grammar is rejected exactly like any other
    # mismatching draft.
    logits = llama.apply_vocab_mask(logits, vocab_mask)
    commit, n_commit = speculative.verify_tokens(
        logits, proposals, n_prop, rng, temps, topks, topps,
        sample=sample)
    n_commit = jnp.where(active, n_commit, 0)
    # Masked commit: rows past each slot's accepted count (and every
    # row of inactive slots) scatter to the max_seq sentinel and drop.
    pos = len0[:, None] + jnp.arange(k + 1)[None, :]
    pos = jnp.where(jnp.arange(k + 1)[None, :]
                    < n_commit[:, None], pos, max_seq)
    slots = jnp.arange(b)
    length = len0 + n_commit

    def scatter(c, r):
        return c.at[:, slots[:, None], pos].set(
            r.astype(c.dtype), mode='drop')

    if big_cache.quantized:
        kq, vq, ks, vs = rows
        new_cache = llama.KVCache(
            k=scatter(big_cache.k, kq),
            v=scatter(big_cache.v, vq), length=length,
            k_scale=scatter(big_cache.k_scale, ks),
            v_scale=scatter(big_cache.v_scale, vs))
    else:
        k_rows, v_rows = rows
        new_cache = llama.KVCache(
            k=scatter(big_cache.k, k_rows),
            v=scatter(big_cache.v, v_rows), length=length)
    # Next round's t0 = the last committed token per slot.
    nxt = jnp.take_along_axis(
        commit, jnp.maximum(n_commit - 1, 0)[:, None],
        axis=1)[:, 0]
    new_tok = jnp.where(active, nxt, tokens)
    return commit, n_commit, new_tok, new_cache


class InferenceEngine(SpeculativeMixin, _EngineBase):
    """Slot-cache engine core: callers drive ``step()``; the serve layer
    wraps it in an HTTP loop. Decode/prefill calls dispatch through the
    async pipeline (``_EngineBase.step``): results are read back one
    call behind the enqueue, so per-call dispatch latency overlaps
    device compute and short fused horizons stop paying a round trip
    each. ``speculate_k > 0`` switches decode to the speculative
    propose→verify→commit loop (``inference/speculative.py``): up to
    k+1 tokens per slot per weight-stream pass."""

    def __init__(self, cfg: ModelConfig, params: Optional[Any] = None,
                 *, max_batch: int = 8, max_seq: int = 1024,
                 mesh: Optional[Any] = None, rng_seed: int = 0,
                 attn_impl: str = 'auto',
                 quantize: Optional[str] = None,
                 kv_cache_dtype: Optional[str] = None,
                 donate_params: bool = False,
                 prefill_w8a8: bool = False,
                 prefill_chunk_tokens: Optional[int] = 256,
                 decode_priority_ratio: Optional[float] = None,
                 decode_steps_per_call: Optional[int] = None,
                 speculate_k: int = 0,
                 adapter_slots: int = 0,
                 adapter_dir: Optional[str] = None,
                 adapter_rank: int = 8,
                 adapter_targets: Optional[Any] = None,
                 telemetry: bool = True):
        self._init_telemetry(telemetry)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.mesh = mesh
        self.attn_impl = attn_impl
        # Multi-step on-device decode (see _EngineBase): pin every
        # decode call at exactly k fused steps.
        self.decode_steps_per_call = self._validate_decode_steps(
            decode_steps_per_call)
        # Opt-in: quantize prefill activations to int8 (2x MXU rate on
        # the compute-bound prefill; decode unaffected). Off by default
        # — W8A8 adds activation quantization noise to the KV rows.
        self.prefill_w8a8 = prefill_w8a8
        # Chunked prefill (on by default): prompts prefill in
        # ``prefill_chunk_tokens``-sized chunks interleaved with decode
        # horizons, bounding how long running requests stall behind a
        # long prompt (the monolithic admit measured 5.5 s median burst
        # TTFT — head-of-line blocking, BENCH_r05). 0/None falls back
        # to monolithic whole-prompt admission waves (bench baseline).
        # ``decode_priority_ratio`` splits the interleaved token budget
        # (see _EngineBase._interleave_horizon); None = 0.5.
        chunk = prefill_chunk_tokens or 0
        self.chunk = _bucket_len(chunk, minimum=8) if chunk else 0
        self.chunked = self.chunk > 0
        self.decode_priority_ratio = decode_priority_ratio
        self._rng = jax.random.PRNGKey(rng_seed)

        refuse_unsupported(cfg, engine='slot')
        cfg, self.params, quantize = prepare_params(
            cfg, params, quantize=quantize, mesh=mesh,
            donate_params=donate_params)
        self.cfg = cfg
        # Actual PER-DEVICE stored parameter bytes (int8 leaves count
        # 1B/elem; sharded leaves count their local shard) — sizes the
        # decode-horizon ring cap against the true per-chip weight
        # stream: under tp both the weight stream and the ring rows
        # split, so the cap stays put instead of drifting with mesh
        # shape.
        from skypilot_tpu.models import quantization
        self._param_bytes = quantization.per_device_bytes(self.params)

        # KV storage dtype is its OWN knob (decoupled from the weight
        # quantize mode; None follows it for backward compatibility):
        # the cache's quantized flag drives every downstream write site
        # (prefill scatter, chunk prefill, spec verify, decode merge)
        # and the fused-dequant attention reads.
        self.kv_cache_dtype = resolve_kv_cache_dtype(kv_cache_dtype,
                                                     quantize)
        create_cache = functools.partial(
            llama.KVCache.create, cfg, batch=max_batch, max_seq=max_seq,
            kv_dtype=self.kv_cache_dtype)
        # Pre-partitioned cache + pinned output shardings: the cache is
        # placed ONCE with its logical-axis shardings, and every
        # jitted step that returns it pins the SAME tree as its
        # out_shardings — each program's output layout IS the next
        # program's input layout (the pjit in/out_axis_resources-
        # matching discipline), so steady state never inserts a
        # resharding collective between steps. None (meshless) skips
        # the machinery entirely.
        self._cache_sh = None
        if mesh is None:
            self.cache = create_cache()
        else:
            # Each shard is born on its own device, never the whole
            # cache on the first (see the paged engine's pool).
            self._cache_sh = mesh_lib.tree_shardings(
                llama.cache_logical_axes(
                    quantized=self.kv_cache_dtype != 'bf16'),
                mesh, shapes=jax.eval_shape(create_cache))
            self.cache = jax.jit(create_cache,
                                 out_shardings=self._cache_sh)()

        # slot bookkeeping (host side); device cache.length is
        # authoritative for attention masking.
        self._init_slots(max_batch)
        # Multi-tenant adapter bank (adapter_slots > 0): installs the
        # stacked multi-LoRA bank into params['layers']['mlora'] BEFORE
        # the decode programs trace, so every program below carries the
        # batched gather matmul. adapter_slots=0 leaves the params tree
        # — and every traced program — byte-identical to before.
        self.adapters = None
        if adapter_slots > 0:
            from skypilot_tpu.inference import adapters as adapters_lib
            self.adapters = adapters_lib.AdapterRegistry(
                self, slots=adapter_slots, rank=adapter_rank,
                adapter_dir=adapter_dir, targets=adapter_targets)
        self._decode_fn = self._build_decode()
        self._prefill_fns: Dict[int, Any] = {}
        # Chunked-prefill scheduler state: slot -> prompt tokens
        # prefilled so far. A slot in this dict is assigned but not yet
        # decodable; the scheduling loop interleaves its remaining
        # chunks with decode horizons.
        self._prefill_off: Dict[int, int] = {}
        self._chunk_prefill_fns: Dict[Tuple, Any] = {}
        # Max mid-prefill slots per chunk batch (padded to a compiled
        # n bucket); the per-call stacked-rows budget shrinks it
        # further when the gathered-cache bucket is wide.
        self._prefill_n_max = self._PREFILL_N_BUCKETS[-1]
        # Fixed-shape first-token merge (completing chunk rows):
        # padding entries scatter to the out-of-range sentinel
        # max_batch and are dropped.
        self._merge_tokens_drop = jax.jit(
            lambda tok, slots, vals: tok.at[slots].set(vals,
                                                       mode='drop'))
        # KV handoff programs (disaggregated serving): export gathers
        # keyed by context bucket, ingest scatters keyed by row bucket.
        self._export_fns: Dict[int, Any] = {}
        self._ingest_fns: Dict[int, Any] = {}
        # Speculative decoding (0 = off): n-gram propose + batched
        # verify instead of the fused decode horizon.
        self._init_spec(speculate_k)

    @classmethod
    def from_pretrained(cls, path: str, *, dtype: Any = None,
                        **kwargs) -> 'InferenceEngine':
        """Build an engine from an HF checkpoint directory
        (``config.json`` + safetensors; see ``models/weights.py``).
        Pass ``quantize='int8'`` for int8 serving (weights AND KV
        cache)."""
        import jax.numpy as jnp
        from skypilot_tpu.models import weights
        # Quantize host-side during load: only int8 codes + scales ever
        # reach the device (a 7B bf16 tree would not leave room on a
        # 16 GB chip for the quantization pass).
        cfg, params = weights.load_checkpoint(
            path, dtype=dtype if dtype is not None else jnp.bfloat16,
            quantize=kwargs.get('quantize'))
        # The freshly loaded tree has no other owner: let quantization
        # free bf16 buffers in place if it ever runs on-device.
        kwargs.setdefault('donate_params', True)
        return cls(cfg, params, **kwargs)

    def kv_pool_stats(self) -> Dict[str, Any]:
        """KV capacity/pressure in TOKENS — the schema the telemetry
        gauges and bench share with the paged engine. The slot cache's
        capacity is the static ``max_batch x max_seq`` reservation;
        "used" counts live context rows, and preemptions are always 0
        (every admitted request owns its full reservation)."""
        cap = self.max_batch * self.max_seq
        used = int(self._slot_len.sum())
        return {
            'kv_cache_dtype': self.kv_cache_dtype,
            'pool_token_capacity': cap,
            'tokens_used': used,
            'tokens_free': cap - used,
            'preemptions': int(self.preemptions),
            'kv_token_bytes': kv_token_bytes(self.cfg,
                                             self.kv_cache_dtype),
            # Bytes ONE device stores per token (kv heads shard over
            # tp) — the per-shard HBM view; token counts above stay
            # GLOBAL (a token is a token however many chips hold it).
            'kv_token_bytes_per_shard': kv_token_bytes(
                self.cfg, self.kv_cache_dtype, mesh=self.mesh),
            'kv_shards': kv_shard_degree(self.cfg, self.mesh),
        }

    # -------------------------------------------------- KV handoff
    def _get_export(self, bucket: int):
        """Compiled context-row gather for one slot (handoff export):
        [L, bucket, hkv, d] rows (+ scales) straight off the slot
        cache, in the STORED dtype — int8 codes and fp32 scales come
        out exactly as resident, never dequantized."""
        if bucket in self._export_fns:
            return self._export_fns[bucket]
        quantized = self.cache.quantized

        @jax.jit
        def export(cache, slot):
            k = cache.k[:, slot, :bucket]
            v = cache.v[:, slot, :bucket]
            if quantized:
                return (k, v, cache.k_scale[:, slot, :bucket],
                        cache.v_scale[:, slot, :bucket])
            return k, v

        self._export_fns[bucket] = export
        return export

    def _gather_kv_rows(self, slot: int, n_rows: int):
        bucket = min(_bucket_len(max(1, n_rows)), self.max_seq)
        slot_d = device_upload(np.array(slot, np.int32))
        out = self._get_export(bucket)(self.cache, slot_d)
        # Sanctioned d2h: the handoff export IS a host readback by
        # design (the rows leave this process on the wire).
        host = host_sync(out)
        if self.cache.quantized:
            k, v, ks, vs = host
            return (k[:, :n_rows], v[:, :n_rows],
                    ks[:, :n_rows, :, 0], vs[:, :n_rows, :, 0])
        k, v = host
        return k[:, :n_rows], v[:, :n_rows], None, None

    def _get_ingest(self, nb: int):
        """Compiled handoff scatter: land [L, 1, nb, hkv, d] rows (+
        scales) into one slot's reservation at positions [0, valid),
        padding rows dropping at the max_seq sentinel."""
        if nb in self._ingest_fns:
            return self._ingest_fns[nb]
        quantized = self.cache.quantized
        max_seq = self.max_seq

        def _scatter(c, r, slots_arr, pos):
            return c.at[:, slots_arr[:, None], pos].set(
                r.astype(c.dtype), mode='drop')

        if quantized:
            @functools.partial(jax.jit, donate_argnums=(0,),
                               **self._step_out_shardings(0))
            def ingest(cache, kq, ks, vq, vs, slots_arr, valid):
                pos = jnp.arange(nb)[None, :]
                pos = jnp.where(pos < valid[:, None], pos, max_seq)
                length = cache.length.at[slots_arr].set(valid,
                                                        mode='drop')
                return llama.KVCache(
                    k=_scatter(cache.k, kq, slots_arr, pos),
                    v=_scatter(cache.v, vq, slots_arr, pos),
                    length=length,
                    k_scale=_scatter(cache.k_scale, ks, slots_arr, pos),
                    v_scale=_scatter(cache.v_scale, vs, slots_arr, pos))
        else:
            @functools.partial(jax.jit, donate_argnums=(0,),
                               **self._step_out_shardings(0))
            def ingest(cache, kr, vr, slots_arr, valid):
                pos = jnp.arange(nb)[None, :]
                pos = jnp.where(pos < valid[:, None], pos, max_seq)
                length = cache.length.at[slots_arr].set(valid,
                                                        mode='drop')
                return llama.KVCache(
                    k=_scatter(cache.k, kr, slots_arr, pos),
                    v=_scatter(cache.v, vr, slots_arr, pos),
                    length=length)

        self._ingest_fns[nb] = ingest
        return ingest

    def _land_kv_rows(self, slot: int, req: Request,
                      snap: Dict[str, Any]) -> None:
        cfg = self.cfg
        n_rows = int(snap['n_rows'])
        nb = min(_bucket_len(max(1, n_rows)), self.max_seq)

        def pad(arr, tail):
            out = np.zeros((cfg.n_layers, 1, nb, cfg.n_kv_heads)
                           + tail, dtype=arr.dtype)
            out[:, 0, :n_rows] = arr.reshape(
                (cfg.n_layers, n_rows, cfg.n_kv_heads) + tail)
            return out

        slots_arr = np.array([slot], np.int32)
        valid = np.array([n_rows], np.int32)
        ingest = self._get_ingest(nb)
        code_d = (cfg.head_dim // 2 if self.cache.packed
                  else cfg.head_dim)
        if self.cache.quantized:
            (kq, ks, vq, vs, slots_d, valid_d) = device_upload(
                (pad(snap['k'], (code_d,)),
                 pad(snap['k_scale'], (1,)),
                 pad(snap['v'], (code_d,)),
                 pad(snap['v_scale'], (1,)), slots_arr, valid))
            self.cache = ingest(self.cache, kq, ks, vq, vs, slots_d,
                                valid_d)
        else:
            kr, vr, slots_d, valid_d = device_upload(
                (pad(snap['k'], (cfg.head_dim,)),
                 pad(snap['v'], (cfg.head_dim,)), slots_arr, valid))
            self.cache = ingest(self.cache, kr, vr, slots_d, valid_d)

    # ------------------------------------------------------------------
    # Compiled steps
    # ------------------------------------------------------------------
    def _build_decode(self):
        """Multi-step decode: ``horizon`` steps fused into one program per
        host sync (llama.decode_horizon's ring-buffer loop). Fusing N
        steps amortizes the host round trip, the same trick a production
        engine uses to hide dispatch latency. ``sample`` is STATIC: the all-greedy program
        skips the top-k/temperature machinery entirely (a full-vocab sort
        per step otherwise)."""
        cfg = self.cfg

        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=('horizon', 'sample',
                                            'kv_bucket'),
                           **self._step_out_shardings(1))
        def decode_steps(params, cache, tokens, rng, temps, topks, topps,
                         active, adp, vmask, horizon, sample, kv_bucket):
            if sample:
                def sample_fn(logits, step_rng):
                    return sample_tokens(logits, step_rng, temps, topks,
                                         topps)
                rngs = jax.random.split(rng, horizon)
            else:
                sample_fn, rngs = None, None
            toks, cache = llama.decode_horizon(
                params, cache, tokens, cfg, horizon=horizon,
                sample_fn=sample_fn, rngs=rngs, kv_bucket=kv_bucket,
                mlora_idx=adp, vocab_mask=vmask)
            # inactive slots don't advance their cache length
            new_len = jnp.where(active, cache.length,
                                cache.length - horizon)
            cache = cache._replace(length=new_len)
            return toks, cache                        # [slots, horizon]

        return decode_steps

    def _get_prefill(self, bucket: int, n: int):
        """Batched prefill: n prompts (padded to one bucket) in one device
        call that computes KV, scatters it into the requested slots of the
        big cache, and returns the first sampled token per prompt. One host
        round trip per admit cycle instead of three per request.

        Rides ``llama.prefill_rows``: plain causal attention over the
        bucket (flash kernel on TPU — the old forward-with-scratch-cache
        path read a bucket of zero cache rows per layer and never hit
        flash), rows quantized inside the layer scan for int8 caches
        (halves the stacked-rows transient -> doubles the admission
        wave), and last-position-only unembed."""
        key = (bucket, n)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        cfg, attn_impl = self.cfg, self.attn_impl
        w8a8 = self.prefill_w8a8

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._step_out_shardings(1))
        def prefill(params, big_cache, tokens, true_lens, slots,
                    adp, vmask):
            """tokens [n, bucket]; true_lens [n]; slots [n] target rows."""
            last, rows = llama.prefill_rows(
                params, tokens, true_lens, cfg, attn_impl=attn_impl,
                quantize_rows=('int4' if big_cache.packed
                               else big_cache.quantized), w8a8=w8a8,
                mlora_idx=adp)
            last = llama.apply_vocab_mask(last, vmask)
            next_tokens = llama.mask_nonfinite_tokens(
                last, jnp.argmax(last, -1).astype(jnp.int32))
            # Scatter KV rows + lengths into the slot cache.
            length = big_cache.length.at[slots].set(true_lens)
            if big_cache.quantized:
                kq, vq, ks, vs = rows
                return next_tokens, llama.KVCache(
                    k=big_cache.k.at[:, slots, :bucket].set(kq),
                    v=big_cache.v.at[:, slots, :bucket].set(vq),
                    length=length,
                    k_scale=big_cache.k_scale.at[:, slots, :bucket].set(ks),
                    v_scale=big_cache.v_scale.at[:, slots, :bucket].set(vs))
            k_rows, v_rows = rows
            ck = big_cache.k.at[:, slots, :bucket].set(
                k_rows.astype(big_cache.k.dtype))
            cv = big_cache.v.at[:, slots, :bucket].set(
                v_rows.astype(big_cache.v.dtype))
            return next_tokens, llama.KVCache(k=ck, v=cv, length=length)

        self._prefill_fns[key] = prefill
        return prefill

    # ------------------------------------------------------------------
    _PREFILL_N_BUCKETS = (1, 2, 4, 8, 16, 32)

    # Under saturation, admissions batch into waves of at least this
    # many slots: a prefill call's cost is dominated by its fixed part
    # at small n (measured 7B: n=2 ~120 ms vs n=8 ~260 ms — 60 vs 32 ms
    # per request), so admitting every freed slot immediately spends
    # ~2x the device time on prefill for the same arrivals.
    _ADMIT_WAVE_MIN = 4

    def _admit(self) -> List[Tuple[int, int, bool]]:
        """Admission dispatch. Chunked (default): assign free slots
        immediately and run at most ONE prefill chunk batch before
        decode resumes — the scheduling loop (``step``) interleaves the
        remaining chunks with decode horizons. Monolithic
        (``prefill_chunk_tokens=0``): the historical whole-prompt
        admission wave. Both ALWAYS return [] — prefill results ride
        the async pipeline and their first-token events surface in
        ``_process_one`` up to ``_PIPELINE_DEPTH`` calls later."""
        if not self.chunked:
            return self._admit_monolithic()
        self._assign_slots()
        events = self._prefill_chunk_batch()
        # Burst exception (mirrors the paged engine): while the
        # DECODING population is under a quarter of the batch (cold
        # start / arrival burst), the one-chunk-per-step TPOT bound
        # protects almost nobody — run chunk batches back to back so
        # the first slots start decoding sooner.
        while (self._prefill_off
               and self.num_active - len(self._prefill_off)
               < self.max_batch // 4):
            events += self._prefill_chunk_batch()
        return events

    def _assign_slots(self) -> None:
        """Reserve free slots for queued requests with a zero prefill
        cursor; chunks stream in via _prefill_chunk_batch."""
        for slot in range(self.max_batch):
            if self._slots[slot] is not None:
                continue
            req = self._queue_pop()
            if req is None:
                return
            self._slots[slot] = req
            self._slot_len[slot] = 0
            self._prefill_off[slot] = 0
            self._trace_sched(req)

    def _free_slot(self, slot: int) -> None:
        self._prefill_off.pop(slot, None)      # cancel mid-prefill
        super()._free_slot(slot)

    def _slot_remaining_prefill(self, slot: int) -> int:
        off = self._prefill_off.get(slot)
        if off is None:
            return 0
        return max(0, len(self._slots[slot].prompt) - off)

    def _prefill_chunk_batch(self) -> List[Tuple[int, int, bool]]:
        """One fixed-size prefill chunk across up to a compiled
        n-bucket of mid-prefill slots, attending the slots' EXISTING
        cache rows (nonzero cache offset) and scattering the new rows
        at each slot's cursor. Completing rows sample their first token
        ON DEVICE (per-request params) and merge it into the device
        token vector before this returns, so they decode on the very
        next horizon; the first-token EVENT surfaces via _process_one.
        ALWAYS returns []."""
        pending = sorted(self._prefill_off)
        if not pending:
            return []
        # Per-DEVICE token cost: the stacked chunk transient shards
        # its kv-head dim over tp, so a tp=2 engine admits twice the
        # wave within the same per-chip scratch budget.
        scratch_tok = kv_token_bytes(self.cfg, self.kv_cache_dtype,
                                     mesh=self.mesh)

        def shapes(batch):
            # Chunk width: the full chunk, or a smaller bucket when
            # every pending piece is short (prompt tails) — bounded
            # compiled-program count, half/quarter the FLOPs.
            rest_max = max(len(self._slots[s].prompt)
                           - self._prefill_off[s] for s in batch)
            chunk_w = min(self.chunk,
                          _bucket_len(rest_max,
                                      minimum=min(64, self.chunk)))
            # Cache-read bucket: covers every batch row's cursor (rows
            # past each cursor are masked); 0 when no row has context
            # yet — that variant runs plain causal attention
            # (flash-eligible), exactly the monolithic first-chunk
            # math.
            start_max = int(max(self._slot_len[s] for s in batch))
            kv_bucket = (0 if start_max == 0
                         else min(_bucket_len(start_max), self.max_seq))
            return chunk_w, kv_bucket

        batch = pending[:self._prefill_n_max]
        chunk_w, kv_bucket = shapes(batch)
        # The chunk program's transient is the stacked [L, n, chunk_w]
        # new rows PLUS the gathered [L, n, kv_bucket] cache copy —
        # cap n to the same scratch budget as the monolithic wave.
        fit = int(0.75e9) // max(1, (chunk_w + kv_bucket) * scratch_tok)
        cap = 1
        for b in self._PREFILL_N_BUCKETS:
            if b <= fit:
                cap = b
        if len(batch) > cap:
            batch = batch[:cap]
            chunk_w, kv_bucket = shapes(batch)
        n = next(b for b in self._PREFILL_N_BUCKETS if b >= len(batch))

        tokens = np.zeros((n, chunk_w), np.int32)
        starts = np.zeros(n, np.int32)
        valid = np.zeros(n, np.int32)
        want = np.full(n, -1, np.int32)
        # Padding rows carry the out-of-range slot sentinel: their
        # writes (rows, lengths, token merge) all drop.
        slots_arr = np.full(n, self.max_batch, np.int32)
        temps = np.zeros(n, np.float32)
        topks = np.zeros(n, np.int32)
        topps = np.ones(n, np.float32)
        adp_h = (np.full(n, -1, np.int32)
                 if self.adapters is not None else None)
        vm_h = (np.ones((n, self.cfg.vocab_size), bool)
                if self._vmask_any else None)
        for i, slot in enumerate(batch):
            req = self._slots[slot]
            off = self._prefill_off[slot]
            piece = req.prompt[off:off + chunk_w]
            tokens[i, :len(piece)] = piece
            starts[i] = self._slot_len[slot]
            valid[i] = len(piece)
            if off + len(piece) == len(req.prompt):
                want[i] = len(piece) - 1
            slots_arr[i] = slot
            temps[i] = req.temperature
            topks[i] = req.top_k or 0
            topps[i] = req.top_p
            if adp_h is not None:
                adp_h[i] = req._adapter_slot
            if vm_h is not None and req._vocab_mask is not None:
                vm_h[i] = req._vocab_mask
        # Sampling variant only when a COMPLETING row needs it (the
        # full-vocab sort costs hundreds of ms on TPU; mid-prompt
        # chunks and greedy completions must not pay it).
        sample = any(self._slots[s].temperature > 0
                     for i, s in enumerate(batch) if want[i] >= 0)
        self._rng, prng = jax.random.split(self._rng)
        # ONE batched host->device transfer for every host-built
        # operand (each separate jnp.asarray is its own dispatch round
        # trip).
        extras = tuple(x for x in (adp_h, vm_h) if x is not None)
        uploaded = device_upload(
            (tokens, starts, valid, want, slots_arr, temps, topks,
             topps) + extras)
        (tokens_d, starts_d, valid_d, want_d, slots_d, temps_d,
         topks_d, topps_d) = uploaded[:8]
        rest = list(uploaded[8:])
        adp_d = rest.pop(0) if adp_h is not None else None
        vm_d = rest.pop(0) if vm_h is not None else None
        prefill = self._get_chunk_prefill(n, chunk_w, kv_bucket, sample)
        chunk_t0 = clock.monotonic()
        with self._prof.phase('prefill_chunk', prompts=n, width=chunk_w,
                              kv_bucket=kv_bucket), \
                self._prof.jit_key('chunk_prefill',
                                   (n, chunk_w, kv_bucket, sample)):
            first, self.cache = prefill(
                self.params, self.cache, tokens_d, starts_d, valid_d,
                want_d, slots_d, adp_d, vm_d, temps_d, topks_d,
                topps_d, prng)
        chunk_t1 = clock.monotonic()
        for i, slot in enumerate(batch):
            r = self._slots[slot]
            if r.trace is not None:
                r.trace.add('prefill_chunk', chunk_t0, chunk_t1,
                            offset=self._prefill_off[slot],
                            tokens=int(valid[i]))
        # Async: host bookkeeping advances NOW (device writes are
        # program-ordered); completing slots' sampled tokens merge into
        # the device token vector immediately so they decode on the
        # next horizon.
        done_rows: List[Tuple[int, int]] = []    # (row i, slot)
        for i, slot in enumerate(batch):
            self._slot_len[slot] += int(valid[i])
            self._prefill_off[slot] += int(valid[i])
            if want[i] < 0:
                continue                         # more chunks to go
            del self._prefill_off[slot]
            done_rows.append((i, slot))
        if done_rows:
            rows_p = np.zeros(n, np.int32)
            slots_p = np.full(n, self.max_batch, np.int32)
            for j, (i, slot) in enumerate(done_rows):
                rows_p[j], slots_p[j] = i, slot
            rows_d, sl_d = device_upload((rows_p, slots_p))
            self._tok_dev = self._merge_tokens_drop(
                self._tok_dev, sl_d, jnp.take(first, rows_d))
            self._meta_dirty = True              # slots become decodable
            self._pending.append({'kind': 'prefill', 'toks': first,
                                  'batch': [(slot, self._slots[slot], i)
                                            for i, slot in done_rows]})
        return []

    def _get_chunk_prefill(self, n: int, chunk_w: int, kv_bucket: int,
                           sample: bool):
        """Compiled chunk-prefill program: gather the batch slots' first
        ``kv_bucket`` cache rows (0 = no cache read — plain causal,
        flash-eligible), run the chunk through prefill_rows at each
        row's offset, scatter the new rows back at the cursors
        (mode='drop': positions past ``valid`` or ``max_seq`` and the
        padding sentinel slot all discard instead of clamp-corrupting
        the cache tail), and sample each completing row's next token."""
        key = (n, chunk_w, kv_bucket, sample)
        if key in self._chunk_prefill_fns:
            return self._chunk_prefill_fns[key]
        cfg, attn_impl = self.cfg, self.attn_impl
        w8a8 = self.prefill_w8a8
        max_seq = self.max_seq

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._step_out_shardings(1))
        def prefill(params, big_cache, tokens, starts, valid, want_idx,
                    slots, adp, vmask, temps, topks, topps, rng):
            if kv_bucket:
                ck = big_cache.k[:, slots, :kv_bucket]
                cv = big_cache.v[:, slots, :kv_bucket]
                if big_cache.quantized:
                    cache_kv = (ck, cv,
                                big_cache.k_scale[:, slots, :kv_bucket],
                                big_cache.v_scale[:, slots, :kv_bucket])
                else:
                    cache_kv = (ck, cv)
            else:
                cache_kv = None
            last_idx = jnp.clip(want_idx, 0, chunk_w - 1)
            last, rows = llama.prefill_rows(
                params, tokens, last_idx + 1, cfg, attn_impl=attn_impl,
                quantize_rows=('int4' if big_cache.packed
                               else big_cache.quantized), w8a8=w8a8,
                cache_kv=cache_kv,
                cache_len=starts if kv_bucket else None,
                mlora_idx=adp)
            # Completing rows' first sampled token honors the grammar.
            last = llama.apply_vocab_mask(last, vmask)
            if sample:
                first = sample_tokens(last, rng, temps, topks, topps)
            else:
                first = jnp.argmax(last, -1).astype(jnp.int32)
            # NaN guard on completing rows (llama.mask_nonfinite_tokens
            # — the host evicts the poisoned request at readback).
            first = llama.mask_nonfinite_tokens(last, first)
            pos = starts[:, None] + jnp.arange(chunk_w)[None, :]
            pos = jnp.where(jnp.arange(chunk_w)[None, :] < valid[:, None],
                            pos, max_seq)        # invalid rows drop
            length = big_cache.length.at[slots].set(starts + valid,
                                                    mode='drop')

            def scatter(c, r):
                return c.at[:, slots[:, None], pos].set(
                    r.astype(c.dtype), mode='drop')

            if big_cache.quantized:
                kq, vq, ks, vs = rows
                new_cache = llama.KVCache(
                    k=scatter(big_cache.k, kq),
                    v=scatter(big_cache.v, vq), length=length,
                    k_scale=scatter(big_cache.k_scale, ks),
                    v_scale=scatter(big_cache.v_scale, vs))
            else:
                k_rows, v_rows = rows
                new_cache = llama.KVCache(k=scatter(big_cache.k, k_rows),
                                          v=scatter(big_cache.v, v_rows),
                                          length=length)
            return first, new_cache

        self._chunk_prefill_fns[key] = prefill
        return prefill

    # ------------------------------------------------------- speculative
    def _get_spec_verify(self, sample: bool, kv_bucket: int):
        """Compiled speculative verify: one forward over the k+1
        positions [t0, d1..dk] per slot against the slots' existing
        cache rows (the nonzero-cache-offset prefill path), acceptance
        on device, and a MASKED scatter of the accepted rows — per-slot
        variable acceptance never changes a shape, so the jit key is
        exactly (k, sample, kv_bucket)."""
        key = (self.speculate_k, sample, kv_bucket)
        if key in self._spec_verify_fns:
            return self._spec_verify_fns[key]
        cfg, attn_impl = self.cfg, self.attn_impl
        k = self.speculate_k
        max_seq = self.max_seq

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._step_out_shardings(3))
        def verify(params, big_cache, tokens, proposals, n_prop, temps,
                   topks, topps, active, adp, vmask, rng):
            return _slot_spec_verify(
                params, big_cache, tokens, proposals, n_prop, temps,
                topks, topps, active, rng, cfg=cfg,
                attn_impl=attn_impl, kv_bucket=kv_bucket,
                max_seq=max_seq, k=k, sample=sample,
                mlora_idx=adp, vocab_mask=vmask)

        self._spec_verify_fns[key] = verify
        return verify

    def _get_spec_fused(self, sample: bool, kv_bucket: int,
                        rounds: int):
        """Compiled in-scan speculative rounds: ``rounds`` x (device
        n-gram propose → verify forward → masked commit) fused into ONE
        program via lax.scan. The verify body is exactly
        ``_slot_spec_verify`` (greedy byte-identity inherited), the
        proposer reads a gather-carried right-aligned history window,
        and the ``rem`` budget carry reproduces the host budget cap so
        commits never overshoot ``max_new_tokens`` or the sequence
        capacity. jit key: (k, sample, kv_bucket, rounds)."""
        key = ('fused', self.speculate_k, sample, kv_bucket, rounds)
        if key in self._spec_verify_fns:
            return self._spec_verify_fns[key]
        from skypilot_tpu.inference import speculative
        cfg, attn_impl = self.cfg, self.attn_impl
        k = self.speculate_k
        max_seq = self.max_seq
        max_ngram = self.spec_max_ngram
        H = self.spec_hist_window

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._step_out_shardings(4))
        def fused(params, big_cache, tokens, hist, rem, temps, topks,
                  topps, active, adp, vmask, rngs):
            def round_body(carry, rng):
                cache, tok, hist, rem = carry
                prop, n_prop = speculative.ngram_propose_device(
                    hist, k, max_ngram=max_ngram)
                # Budget carry: at most ``rem`` tokens may still commit
                # (n_commit <= n_prop + 1) — _spec_build_proposals's
                # cap, applied round by round on device.
                n_prop = jnp.minimum(n_prop, jnp.maximum(rem - 1, 0))
                act = active & (rem >= 1)
                commit, n_commit, new_tok, new_cache = \
                    _slot_spec_verify(
                        params, cache, tok, prop, n_prop, temps,
                        topks, topps, act, rng, cfg=cfg,
                        attn_impl=attn_impl, kv_bucket=kv_bucket,
                        max_seq=max_seq, k=k, sample=sample,
                        mlora_idx=adp, vocab_mask=vmask)
                # History carry: append the commit row and re-right-
                # align (shift left by n_commit; uncommitted positions
                # land past the window and are never gathered).
                combined = jnp.concatenate([hist, commit], axis=1)
                gidx = (jnp.arange(H, dtype=jnp.int32)[None, :]
                        + n_commit[:, None])
                new_hist = jnp.take_along_axis(combined, gidx, axis=1)
                return ((new_cache, new_tok, new_hist,
                         rem - n_commit),
                        (commit, n_commit, n_prop))

            (big_cache, tokens, hist, rem), stacked = jax.lax.scan(
                round_body, (big_cache, tokens, hist, rem), rngs)
            commits, n_commits, n_props = stacked
            return commits, n_commits, n_props, tokens, big_cache

        self._spec_verify_fns[key] = fused
        return fused

    def _spec_verify_call(self, ready, proposals, n_prop):
        temps_d, topks_d, topps_d, active_d, sample = \
            self._slot_meta(ready)
        k = self.speculate_k
        max_live = int(max(self._slot_len[s]
                           for s in range(self.max_batch)
                           if self._slots[s] is not None))
        kv_bucket = min(self.max_seq, _bucket_len(max_live + k + 1))
        if kv_bucket > self.max_seq // 2:
            kv_bucket = self.max_seq
        self._rng, rng = jax.random.split(self._rng)
        prop_d, n_prop_d = device_upload((proposals, n_prop))
        verify = self._get_spec_verify(sample, kv_bucket)
        with self._prof.jit_key('spec_verify',
                                (self.speculate_k, sample, kv_bucket)):
            commit, n_commit, self._tok_dev, self.cache = verify(
                self.params, self.cache, self._tok_dev, prop_d, n_prop_d,
                temps_d, topks_d, topps_d, active_d, self._adp_dev,
                self._vmask_dev, rng)
        return commit, n_commit

    def _spec_fused_call(self, ready, rounds):
        """Dispatch ``rounds`` fused propose→verify→commit rounds in one
        jitted call (``_spec_step_fused``). The kv bucket covers the
        worst-case growth ``rounds * (k + 1)`` so every in-scan round
        reads a long-enough cache slice."""
        temps_d, topks_d, topps_d, active_d, sample = \
            self._slot_meta(ready)
        k = self.speculate_k
        max_live = int(max(self._slot_len[s]
                           for s in range(self.max_batch)
                           if self._slots[s] is not None))
        kv_bucket = min(self.max_seq,
                        _bucket_len(max_live + rounds * (k + 1)))
        if kv_bucket > self.max_seq // 2:
            kv_bucket = self.max_seq
        hist, rem = self._spec_hist_state(ready)
        keys = jax.random.split(self._rng, rounds + 1)
        self._rng = keys[0]
        hist_d, rem_d = device_upload((hist, rem))
        fused = self._get_spec_fused(sample, kv_bucket, rounds)
        with self._prof.jit_key('spec_fused',
                                (self.speculate_k, sample, kv_bucket,
                                 rounds)):
            commits, n_commits, n_props, self._tok_dev, self.cache = \
                fused(self.params, self.cache, self._tok_dev, hist_d,
                      rem_d, temps_d, topks_d, topps_d, active_d,
                      self._adp_dev, self._vmask_dev, keys[1:])
        return commits, n_commits, n_props

    def step(self, horizon: int = 1) -> List[Tuple[int, int, bool]]:
        """Chunked scheduling loop: admit (one chunk batch max), then
        enqueue decode through the async pipeline. While prompts are
        mid-prefill the decode horizon is capped by the
        ``decode_priority_ratio`` token budget so the next chunk runs
        within a bounded number of decode steps; while the queue is
        non-empty a medium cap keeps freed slots noticed promptly.
        Monolithic mode keeps _EngineBase.step semantics unchanged.
        ``speculate_k > 0`` replaces the fused decode horizon with one
        synchronous propose→verify→commit round per step (admission —
        chunked or monolithic — is unchanged); adding
        ``decode_steps_per_call > 1`` fuses that many rounds into one
        dispatch instead (in-scan speculative verify)."""
        if not self.chunked and not self.speculate_k:
            return super().step(horizon)
        events: List[Tuple[int, int, bool]] = []
        with self._prof.phase('readback'):
            while len(self._pending) >= self._PIPELINE_DEPTH:
                events.extend(self._process_one())
        with self._prof.phase('admit'):
            events.extend(self._admit())
        if self.speculate_k:
            if (self.decode_steps_per_call or 0) > 1:
                events.extend(self._spec_step_fused())
            else:
                events.extend(self._spec_step())
            return events
        if self.decode_steps_per_call:
            # Multi-step pin: exactly k fused steps per call — the
            # dispatch-amortization knob wins over the interleave /
            # queue-pressure shrinks (capacity caps still apply in
            # _enqueue_decode).
            horizon = self.decode_steps_per_call
        elif self._prefill_off:
            horizon = min(horizon, self._interleave_horizon())
        elif self._queue:
            horizon = min(horizon, 32)
        with self._prof.phase('decode_enqueue'):
            enqueued = self._enqueue_decode(horizon)
        if not enqueued and self._pending:
            with self._prof.phase('readback'):
                events.extend(self._process_one())
        return events

    def _admit_monolithic(self) -> List[Tuple[int, int, bool]]:
        """Whole-prompt admission waves (``prefill_chunk_tokens=0`` —
        the pre-chunking baseline, kept for bench comparison)."""
        free = [s for s in range(self.max_batch) if self._slots[s] is None]
        wave_min = min(self._ADMIT_WAVE_MIN, self.max_batch)
        if (0 < len(free) < wave_min and len(free) < self.max_batch
                and len(self._queue) > len(free) + wave_min):
            # Saturated (queue outruns capacity) with slots still
            # decoding: hold admission until a fuller wave accumulates.
            # Freed slots arrive within ~a call horizon, so the TTFT
            # cost is bounded; when the queue is short (latency regime)
            # or every slot is free (nothing to wait for) admission is
            # immediate.
            return []
        batch: List[Tuple[int, Request]] = []
        for slot in free:
            req = self._queue_pop()
            if req is None:
                break
            batch.append((slot, req))
        if not batch:
            return []
        # Cap the wave: by the largest compiled bucket, AND by the
        # prefill stacked-rows transient — the batched prefill stacks
        # [L, n, bucket] KV rows across the layer scan, and at n=32 x
        # bucket=256 on a 7B the bf16 stack is 2 GB x2, which pushed the
        # compile past HBM with the slot cache + weights resident. int8
        # caches quantize the rows INSIDE the scan (prefill_rows), so
        # their stack is half the width and the wave twice as deep. The
        # overflow requeues at the FRONT (keeps FIFO) for the next step.
        bucket = min(_bucket_len(max(len(r.prompt) for _, r in batch)),
                     self.max_seq)
        scratch_tok = kv_token_bytes(self.cfg, self.kv_cache_dtype,
                                     mesh=self.mesh)
        fit = int(0.75e9) // max(1, bucket * scratch_tok)
        cap = 1
        for b in self._PREFILL_N_BUCKETS:     # largest PADDED n that fits
            if b <= fit:
                cap = b
        if len(batch) > cap:
            self._requeue_front([req for _, req in batch[cap:]])
            batch = batch[:cap]
            bucket = min(_bucket_len(max(len(r.prompt)
                                         for _, r in batch)),
                         self.max_seq)
        # Pad request count to a compiled bucket (extra rows re-prefill the
        # first request into its own slot — harmless duplicate writes).
        n = 1
        for b in self._PREFILL_N_BUCKETS:
            if b >= len(batch):
                n = b
                break
        else:
            n = self._PREFILL_N_BUCKETS[-1]
        prefill = self._get_prefill(bucket, n)

        tokens = np.zeros((n, bucket), np.int32)
        true_lens = np.zeros(n, np.int32)
        slots = np.zeros(n, np.int32)
        adp_h = (np.full(n, -1, np.int32)
                 if self.adapters is not None else None)
        vm_h = (np.ones((n, self.cfg.vocab_size), bool)
                if self._vmask_any else None)
        for i in range(n):
            slot, req = batch[min(i, len(batch) - 1)]
            tokens[i, :len(req.prompt)] = req.prompt
            true_lens[i] = len(req.prompt)
            slots[i] = slot
            if adp_h is not None:
                adp_h[i] = req._adapter_slot
            if vm_h is not None and req._vocab_mask is not None:
                vm_h[i] = req._vocab_mask
        adp_d = jnp.asarray(adp_h) if adp_h is not None else None
        vm_d = jnp.asarray(vm_h) if vm_h is not None else None
        # Queue -> slot happens here, before the dispatch, so that the
        # whole-prompt prefill is one ``prefill_chunk`` span inside the
        # ``prefill`` span, as a chunk is in chunked mode.
        for _, req in batch:
            self._trace_sched(req)
        chunk_t0 = clock.monotonic()
        with self._prof.phase('prefill_chunk', prompts=n, width=bucket), \
                self._prof.jit_key('prefill', (bucket, n)):
            next_tokens, self.cache = prefill(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(true_lens), jnp.asarray(slots),
                adp_d, vm_d)
        chunk_t1 = clock.monotonic()
        for _, req in batch:
            if req.trace is not None:
                req.trace.add('prefill_chunk', chunk_t0, chunk_t1,
                              offset=0, tokens=len(req.prompt))
        # Async: reserve the slots NOW (so the next admission wave and
        # _enqueue_decode see them taken) but defer the token readback —
        # the prefill result rides the pipeline and its events surface
        # in _process_one. The device token vector picks up the prefill
        # tokens without a host trip.
        slots_used = np.array([s for s, _ in batch], np.int32)
        self._tok_dev = self._merge_tokens(
            self._tok_dev, jnp.asarray(slots_used),
            next_tokens[:len(batch)])
        for slot, req in batch:
            self._slots[slot] = req
            self._slot_len[slot] = len(req.prompt)
        self._meta_dirty = True
        self._pending.append({'kind': 'prefill', 'toks': next_tokens,
                              'batch': [(slot, req, i) for i, (slot, req)
                                        in enumerate(batch)]})
        return []

    _HORIZON_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

    def _enqueue_decode(self, horizon: int = 1) -> bool:
        """Enqueue one fused-horizon decode call fed entirely by
        device-resident state (tokens from the previous call's last
        column, the chained cache). Returns False when nothing could be
        enqueued. The host reads the result back in _process_one, up to
        _PIPELINE_DEPTH calls later. Mid-prefill slots (chunked
        admission cursors still advancing) are masked inactive: their
        cache lengths are mid-prompt and their token-vector entries
        stale until the completing chunk merges the first token."""
        ready = self._decode_ready()
        active = np.array([r is not None for r in ready])
        if not active.any():
            return False
        # Cap the horizon by remaining KV capacity (+1 for the token
        # written during the step) — counting the steps already IN
        # FLIGHT, whose device-side lengths have advanced past the host
        # view. The max runs over EVERY occupied slot, mid-prefill ones
        # included: the horizon's ring merge writes (masked-off garbage)
        # rows at each slot's device length, and dynamic_update_slice
        # CLAMPS — a merge pushed past max_seq on a nearly-full
        # mid-prefill slot would slide back over its real prompt rows.
        max_live = int(max(self._slot_len[s]
                           for s in range(self.max_batch)
                           if self._slots[s] is not None))
        cap = int(self.max_seq - 1 - max_live - self._inflight_steps)
        if cap < 1:
            return False
        horizon = max(1, min(horizon, cap))
        # Each fused step re-reads the whole [L, b, horizon] ring of rows
        # produced this horizon; past ~15% of the weight-read traffic the
        # ring dominates the HBM budget and longer horizons backfire
        # (measured: 1B model, b=64 — horizon 128 halves throughput vs 64).
        # The ring rides MODEL dtype (it only quantizes on the merge into
        # an int8 cache), so its rows are costed at cfg.dtype — costing
        # them at the pool's int8 width (round-4 bug) both understated
        # the re-read traffic and allowed rings that blew the HBM budget.
        ring_cap = _ring_horizon_cap(self.cfg, self.max_batch,
                                     self._param_bytes, self.mesh)
        horizon = min(horizon, ring_cap)
        if self.decode_steps_per_call is None:
            for b in reversed(self._HORIZON_BUCKETS):
                if b <= horizon:
                    horizon = b
                    break
        # else: multi-step pin — run EXACTLY k (capacity-clamped above)
        # so the jit key stays (k, sample, kv_bucket) and the audit's
        # one-dispatch-per-k-tokens contract holds.

        temps_d, topks_d, topps_d, active_d, sample = \
            self._slot_meta(ready)
        # Length-aware KV reads: attention streams only the first
        # kv_bucket cache rows (decode is HBM-bound on this read). The
        # bucket must cover every live context through this horizon
        # (in-flight steps included); power-of-two-ish rounding bounds
        # compiled-program count.
        kv_bucket = min(self.max_seq,
                        _bucket_len(max_live + self._inflight_steps +
                                    horizon))
        self._rng, rng = jax.random.split(self._rng)
        # Per-substep attribution: one dispatch covers ``horizon``
        # decode substeps (the multi-step amortization the profiler's
        # per_substep_ms split makes visible).
        self._prof.note_substeps('decode_enqueue', horizon,
                                 live_rows=int(active.sum()))
        self._prof.tag(horizon=horizon, kv_bucket=kv_bucket)
        with self._prof.jit_key('decode', (horizon, sample, kv_bucket)):
            toks, self.cache = self._decode_fn(
                self.params, self.cache, self._tok_dev, rng,
                temps_d, topks_d, topps_d, active_d, self._adp_dev,
                self._vmask_dev, horizon, sample, kv_bucket)
        self._note_decode_step(
            int(sum(self._slot_len[s] + self._inflight_steps
                    for s in range(self.max_batch)
                    if ready[s] is not None)))
        self._tok_dev = toks[:, -1]
        self._inflight_steps += horizon
        self._pending.append({'kind': 'decode', 'toks': toks,
                              'horizon': horizon,
                              'snapshot': ready})
        return True

    def _process_one(self) -> List[Tuple[int, int, bool]]:
        """Sync the oldest in-flight call and turn it into events. A
        request that finished (or was cancelled) after the call was
        enqueued produced garbage rows on the device — skipped here;
        its cache rows sit past the corrected length and the slot's
        next prefill overwrites them."""
        entry = self._pending.popleft()
        # THE sanctioned device->host readback of the async pipeline:
        # everything else in the step loop must stay device-side (the
        # jaxpr audit gates on it).
        toks = host_sync(entry['toks'])
        events: List[Tuple[int, int, bool]] = []
        now = clock.now()
        if entry['kind'] == 'prefill':
            for slot, req, row in entry['batch']:
                if req.finish_time is not None:       # cancelled in flight
                    continue
                token = int(toks[row])
                if token < 0:
                    # Non-finite sentinel: the prompt blew up in
                    # prefill — evict just this request.
                    events.append(self._evict_nonfinite(slot, req))
                    continue
                req.first_token_time = now
                self._trace_first_token(req)
                req.output.append(token)
                finished = self._finish_req(slot, req, token)
                events.append((req.request_id, token, finished))
            return events
        self._inflight_steps -= entry['horizon']
        for slot, req in enumerate(entry['snapshot']):
            if req is None or req.finish_time is not None:
                continue
            if req.first_token_time is None:
                # Prefill result still queued behind this decode —
                # cannot happen (FIFO pipeline), but guard anyway.
                continue
            for i in range(entry['horizon']):
                token = int(toks[slot, i])
                if token < 0:
                    # Non-finite sentinel: this slot's logits row went
                    # NaN/Inf mid-horizon. Evict exactly this request
                    # (its remaining horizon tokens are garbage by
                    # construction); every other slot's tokens land
                    # normally.
                    events.append(self._evict_nonfinite(slot, req))
                    break
                req.output.append(token)
                self._slot_len[slot] += 1
                finished = self._maybe_finish(slot, token)
                events.append((req.request_id, token, finished))
                if finished:
                    break
        return events


def sample_tokens(logits: jax.Array, step_rng: jax.Array,
                  temps: jax.Array, topks: jax.Array,
                  topps: jax.Array,
                  vocab_mask: Optional[jax.Array] = None) -> jax.Array:
    """Per-slot next-token sampling, shared by the slot and paged
    engines' fused decode: optional grammar vocab mask, then
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
