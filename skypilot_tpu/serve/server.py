"""Replica-side model server: HTTP front end on the in-tree
PagedInferenceEngine (the piece the reference delegates to vLLM/JetStream
recipes — here it ships in-tree, SURVEY §7 step 8).

Endpoints:
- ``GET /readiness`` — 200 once the engine has compiled its first step
  (the serve readiness-probe target).
- ``POST /generate`` — ``{"prompt": [ids...], "max_new_tokens": N,
  "temperature": t, "top_k": k, "top_p": p, "stop": [...],
  "slo_tier": "latency"|"throughput"}`` →
  ``{"tokens": [...], "ttft_ms": ...}``. ``stop`` entries are strings
  (tokenized with the model tokenizer) or token-id lists; generation
  ends when the output ends with any entry, which is trimmed.
- ``GET /metrics`` — the process telemetry registry in Prometheus text
  exposition format (TTFT/TPOT/queue-wait histograms — aggregate AND
  per SLO tier, engine step-phase timings, speculation gauges,
  scheduler queue/shed series —
  ``skytpu_sched_queue_tokens{tier=...}``,
  ``skytpu_sched_shed_total{tier,reason}`` — and KV pool
  capacity/pressure).
  ``GET /metrics?format=json`` keeps the PR-3 stable-schema JSON gauge
  block for existing scrapers (every key always present, zeros never
  omitted; the scheduler adds a ``sched.tiers`` block with the same
  guarantee).
- ``GET /debug/requests`` — the bounded ring of completed request
  timelines (queue → prefill chunks → decode → spec rounds), newest
  first; ``?limit=N`` caps the count.

Every number comes from the single telemetry registry
(``skypilot_tpu.telemetry``) — the server keeps no private metrics
dicts; the rolling TTFT/TPOT/queue-wait median/p90 ride the registry
histograms' bounded windows (ONE windowed-quantile implementation).

Request flow (round 6): handler threads submit into the
:class:`skypilot_tpu.serve.scheduler.RequestScheduler` — the SLO-aware
admission core that owns per-tier bounded queues, the priority +
shortest-remaining-work admission order, load shedding (HTTP 429 with
a telemetry-derived ``Retry-After`` instead of silent queue growth)
and the per-request outboxes handlers stream from. One background
thread drives ``engine.step()`` continuously (the engine core is
synchronous); each iteration it tops the engine up from the scheduler
and routes the step's token events to the outboxes — the step never
blocks on a slow client. Run on every replica slice via the service
task's ``run`` command:
``python -m skypilot_tpu.serve.server --model llama3-1b``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import http.server
import json
import os
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Any, Dict, List, Optional

from skypilot_tpu import telemetry
from skypilot_tpu import tpu_logging
from skypilot_tpu.inference import kv_transfer
from skypilot_tpu.models.tokenizer import sanitize_text
from skypilot_tpu.serve import disagg as disagg_lib
from skypilot_tpu.serve import faults as faults_lib
from skypilot_tpu.serve import gang as gang_lib
from skypilot_tpu.serve import scheduler as scheduler_lib
from skypilot_tpu.serve import wire
from skypilot_tpu.telemetry import clock
from skypilot_tpu.telemetry import device as device_lib
from skypilot_tpu.telemetry import profiler as profiler_lib
from skypilot_tpu.telemetry import tracing

logger = tpu_logging.init_logger(__name__)


def build_engine(cfg_name: str, *, max_batch: int, max_seq: int,
                 model_path: Optional[str] = None,
                 quantize: Optional[str] = None,
                 kv_cache_dtype: Optional[str] = None,
                 page_size: Optional[int] = None,
                 decode_impl: Optional[str] = None,
                 prefill_w8a8: bool = False,
                 prefill_chunk_tokens: Optional[int] = None,
                 decode_priority_ratio: Optional[float] = None,
                 decode_steps_per_call: Optional[int] = None,
                 speculate_k: int = 0,
                 adapter_slots: int = 0,
                 adapter_dir: Optional[str] = None,
                 adapter_rank: int = 8,
                 tp: int = 1, dp: int = 1,
                 gang: Optional['gang_lib.GangSpec'] = None):
    """Construct AND warm one inference engine — the single engine
    recipe every gang rank shares. Followers must build a
    byte-identical engine to rank 0's (same config, same warmup
    request, so request-id counters, prefix-cache state, and compiled
    programs all align) — which is why this lives outside the
    ModelServer: rank 0's ``_load_engine`` and the rank-N follower
    entry both call exactly this."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    if gang is not None and gang.is_gang:
        # Multi-host data plane: on a pod-capable backend the gang
        # shares one jax.distributed program (the mesh then spans all
        # processes); on CPU (the tests) each rank keeps a full
        # model replica and lockstep is digest-verified by the gang
        # bus (the 'replicated' plane).
        import jax
        if jax.default_backend() == 'tpu' and gang.coordinator:
            from skypilot_tpu.parallel import mesh as mesh_lib
            mesh_lib.initialize_gang_distributed(
                gang.coordinator, gang.rank, gang.world,
                timeout_s=gang.join_timeout_s)
    extra = {}
    if tp * dp > 1:
        from skypilot_tpu.parallel import mesh as mesh_lib
        extra['mesh'] = mesh_lib.serving_mesh(tp, dp)
    if page_size is not None:
        extra['page_size'] = page_size
    if decode_impl is not None:
        extra['decode_impl'] = decode_impl
    if prefill_chunk_tokens is not None:
        extra['prefill_chunk_tokens'] = prefill_chunk_tokens
    if decode_priority_ratio is not None:
        extra['decode_priority_ratio'] = decode_priority_ratio
    if decode_steps_per_call is not None:
        extra['decode_steps_per_call'] = decode_steps_per_call
    if kv_cache_dtype is not None:
        extra['kv_cache_dtype'] = kv_cache_dtype
    extra['prefill_w8a8'] = prefill_w8a8
    extra['speculate_k'] = speculate_k
    if adapter_slots:
        # Multi-tenant LoRA bank: slots rows of rank-r factors live in
        # params (re-uploaded on load/evict, never recompiled).
        extra['adapter_slots'] = adapter_slots
        extra['adapter_dir'] = adapter_dir
        extra['adapter_rank'] = adapter_rank
    if model_path:
        engine = PagedInferenceEngine.from_pretrained(
            model_path, max_batch=max_batch, max_seq=max_seq,
            quantize=quantize, **extra)
    else:
        cfg = configs.get_config(cfg_name)
        engine = PagedInferenceEngine(cfg, max_batch=max_batch,
                                      max_seq=max_seq, quantize=quantize,
                                      **extra)
    # Warmup: compile prefill+decode before declaring readiness. Part
    # of the shared recipe — it advances the request-id counter and
    # registers prefix pages, so a follower that skipped it
    # would diverge on its very first replayed op.
    engine.add_request([1, 2, 3], max_new_tokens=2)
    engine.run_to_completion(horizon=4)
    return engine


class ModelServer:

    def __init__(self, cfg_name: str = 'tiny', *, max_batch: int = 8,
                 max_seq: int = 1024, port: int = 8081,
                 model_path: Optional[str] = None,
                 quantize: Optional[str] = None,
                 tp: Optional[int] = None,
                 dp: Optional[int] = None,
                 kv_cache: str = 'paged',
                 kv_cache_dtype: Optional[str] = None,
                 page_size: Optional[int] = None,
                 decode_impl: Optional[str] = None,
                 prefill_w8a8: bool = False,
                 prefill_chunk_tokens: Optional[int] = None,
                 decode_priority_ratio: Optional[float] = None,
                 decode_steps_per_call: Optional[int] = None,
                 speculate_k: int = 0,
                 adapter_slots: int = 0,
                 adapter_dir: Optional[str] = None,
                 adapter_rank: int = 8,
                 slo_tier_default: str = 'latency',
                 max_queue_tokens: Optional[int] = None,
                 latency_admit_frac: float = 0.7,
                 drain_deadline_s: float = 30.0,
                 fault_spec: Optional[Any] = None,
                 role: Optional[str] = None,
                 handoff_targets: Optional[List[str]] = None,
                 checkpoint_path: Optional[str] = None,
                 gang: Optional['gang_lib.GangSpec'] = None,
                 step_watchdog_s: Optional[float] = None,
                 watchdog_clock: Optional[Any] = None,
                 nan_alarm_threshold: Optional[int] = None):
        self.cfg_name = cfg_name
        self.model_path = model_path  # HF checkpoint dir (real weights)
        self.quantize = quantize      # 'int8' | 'int4' weights
        # Serving mesh shape: explicit args win, else the controller's
        # adaptive-TP placement env (SKYTPU_TP/SKYTPU_DP), else 1x1.
        # Resolved HERE (not at engine load) so the mesh gauges and the
        # JSON mesh block report the configured shape from the very
        # first scrape — the LB's replica view must not see a replica
        # flap from 1x1 to tp=2 mid-boot.
        from skypilot_tpu.parallel import mesh as mesh_lib
        self._mesh_spec = mesh_lib.serving_spec_from_env(tp=tp, dp=dp)
        self.tp = self._mesh_spec.tp
        self.dp = self._mesh_spec.dp
        # perfbench/runners/serve.py (a directory this tree may not
        # edit) still passes kv_cache='paged': accepted, selects nothing.
        if kv_cache != 'paged':
            raise ValueError(
                f'kv_cache={kv_cache!r}: the paged engine is the one '
                'engine; the keyword selects nothing')
        # KV storage dtype ('bf16' | 'int8'); None follows --quantize.
        # Decoupled: int8 KV over bf16 weights halves the dominant
        # decode HBM stream (and ~doubles pool capacity) on its own.
        self.kv_cache_dtype = kv_cache_dtype
        self.page_size = page_size    # paged granularity (None = auto)
        # Paged decode attention path ('gather' | 'pallas' |
        # 'cross_layer'); None = the engine's 'auto' pick. cross_layer
        # walks each slot's pages ONCE per step for all layers.
        self.decode_impl = decode_impl
        self.prefill_w8a8 = prefill_w8a8  # int8 activations on prefill
        # Chunked-prefill scheduler knobs (None = engine defaults):
        # chunk width and the decode share of the interleaved token
        # budget while prompts are mid-prefill.
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.decode_priority_ratio = decode_priority_ratio
        # Multi-step on-device decode: pin every decode call at
        # exactly k fused steps (dispatch/readback/sampling host work
        # amortizes k x). None = the loop's adaptive 8/32 horizon.
        self.decode_steps_per_call = decode_steps_per_call
        # Speculative decoding: n-gram/prompt-lookup proposer + batched
        # on-device verify (0 = off). Greedy outputs are identical to
        # vanilla decode; sampling keeps the output distribution.
        self.speculate_k = speculate_k or 0
        # Multi-tenant LoRA: bank capacity (0 = off), checkpoint dir
        # for on-demand load-by-name, and the bank's fixed rank.
        self.adapter_slots = adapter_slots
        self.adapter_dir = adapter_dir
        self.adapter_rank = adapter_rank
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.port = port
        self.engine = None            # set once loaded
        self.tokenizer = None         # set once loaded
        self._error: Optional[str] = None   # fatal engine failure
        self._ready = threading.Event()
        self._work = threading.Event()
        self._lock = threading.Lock()  # engine mutation
        # The SLO-aware admission/scheduling core: per-tier bounded
        # queues, priority + shortest-remaining-work admission, load
        # shedding (429 + Retry-After), per-request outbox streaming.
        # Constructed UP FRONT so its /metrics schema is stable from
        # the first scrape; the engine binds once loaded.
        self.sched = scheduler_lib.RequestScheduler(
            self._lock, default_tier=slo_tier_default,
            max_queue_tokens=max_queue_tokens,
            latency_admit_frac=latency_admit_frac,
            wake=self._work.set)
        # Telemetry: every counter/gauge/histogram lives in the process
        # registry (rendered at /metrics in Prometheus format and as
        # the stable-schema JSON at /metrics?format=json). The request
        # latency histograms keep a bounded window for exact rolling
        # median/p90 — the one windowed-quantile implementation shared
        # by TTFT, TPOT, and queue-wait (the serve autoscaler and
        # operators watch these to see the scheduler holding its
        # latency SLO; bounded so a long-lived replica's quantiles
        # reflect CURRENT traffic, not its lifetime).
        reg = telemetry.get_registry()
        self._reg = reg
        # Every XLA compile of the process, from before the engine
        # loads: /metrics?format=json reports the count, so a caller
        # can see that steady state over repeated shapes adds none.
        self._compiles = device_lib.get_compile_watch()
        self._m_served = reg.counter(
            'skytpu_requests_served_total',
            'Requests completed and returned to a client')
        self._m_aborted = reg.counter(
            'skytpu_requests_aborted_total',
            'Requests cancelled mid-stream (client disconnect)')
        self._h_ttft = reg.histogram(
            'skytpu_request_ttft_ms', 'Time to first token (ms)')
        self._h_tpot = reg.histogram(
            'skytpu_request_tpot_ms',
            'Mean time per output token after the first (ms)')
        self._h_queue_wait = reg.histogram(
            'skytpu_request_queue_wait_ms',
            'Time in the engine queue: add_request to slot '
            'assignment (ms); the wait before add_request is the '
            'sched_wait stage')
        # The time to first token as stages that add up (the spans of
        # tracing.TTFT_STAGES, folded in when a request is recorded as
        # finished) — registered here, zeros from the first scrape.
        self._h_ttft_stage = {
            stage: reg.histogram(
                'skytpu_request_ttft_stage_ms',
                'Time to first token by stage (ms): sched_wait + queue '
                '+ prefill + first_token_lag + emit_first = submit to '
                "the first token's flushed SSE line", stage=stage)
            for stage in tracing.TTFT_STAGES}
        self._h_sse_write = reg.histogram(
            'skytpu_request_sse_write_ms',
            "A streamed request's total time in SSE writes and flushes "
            '(ms), observed once at its finish')
        # The engine thread's own account of the engine lock, stamped
        # around its hold in _engine_loop: against wall time, the share
        # of it a handler cannot have the engine.
        self._m_lock_held = reg.counter(
            'skytpu_engine_lock_held_seconds_total',
            'Seconds the engine loop held the engine lock (fill + '
            'step, its blocking readback included)')
        self._m_lock_wait = reg.counter(
            'skytpu_engine_lock_wait_seconds_total',
            'Seconds the engine loop waited to acquire the engine lock')
        self._httpd: Optional[http.server.ThreadingHTTPServer] = None
        self._stopping = False
        self._engine_thread: Optional[threading.Thread] = None
        # Fault injection (serve/faults.py): resolved ONCE here from
        # the explicit spec or SKYTPU_FAULT_SPEC; None (the default)
        # keeps the hooks at a single attribute check — zero overhead
        # on the engine loop, nothing in the compute layer.
        self._faults = faults_lib.make_injector(fault_spec)
        # Robustness series (faults/migrations/drain/recovery/gray)
        # register up front so they render as zeros from first scrape.
        faults_lib.register_metrics()
        # Gray-failure defense (round 13). Wedge watchdog: a
        # clock-injectable per-step deadline on the engine loop. The
        # loop arms a monotonic stamp before entering the step region
        # and clears it after; a stamp older than ``step_watchdog_s``
        # means a step is WEDGED (stuck jitted call, dead accelerator,
        # deadlocked readback) while the HTTP front end still answers
        # — the classic gray failure. The watchdog thread then flips
        # /readiness to a degraded 503 (the manager's probe machinery
        # fails the replica over) and fails in-flight requests with
        # retryable errors (the LB's existing in-flight recovery
        # resubmits them to surviving replicas). ``watchdog_clock`` is
        # injectable so tests drive virtual time; 0 disables.
        self.step_watchdog_s = (
            float(step_watchdog_s) if step_watchdog_s is not None
            else float(os.environ.get('SKYTPU_STEP_WATCHDOG_S', '120')))
        self._wd_clock = watchdog_clock or time.monotonic
        self._wd_lock = threading.Lock()
        self._step_started: Optional[float] = None
        # Degraded (gray-failed but process-alive) state: set by the
        # watchdog and the NaN-storm alarm. Readiness reports 503
        # status='degraded'; new submits get a retryable 503.
        self._degraded: Optional[str] = None
        # NaN blast-radius escalation: single poisoned requests are
        # evicted per-request (the device sentinel), but this many
        # total hits mean the REPLICA is sick (bad HBM, corrupted
        # weights) — escalate to the replica-level degraded alarm.
        self.nan_alarm_threshold = (
            int(nan_alarm_threshold) if nan_alarm_threshold is not None
            else int(os.environ.get('SKYTPU_NAN_ALARM', '8')))
        self._nan_seen = 0
        self._nan_evict_pending = False    # latched nan_logits inject
        self._g_wd_age = reg.gauge(
            'skytpu_engine_step_watchdog_age_seconds',
            'Age of the engine step currently in flight (0 when the '
            'loop is between steps); sustained growth = wedged step')
        self._h_drain = reg.histogram(
            'skytpu_replica_drain_seconds',
            'Graceful-drain duration: drain start to idle (s)',
            buckets=telemetry.registry.DEFAULT_SECONDS_BUCKETS)
        # Graceful drain: all drain attributes are written under
        # _drain_lock (begin_drain is idempotent and may race the
        # monitor thread and /drain handlers).
        self.drain_deadline_s = float(drain_deadline_s)
        self._drain_lock = threading.Lock()
        self._drain_started: Optional[float] = None
        self._drain_deadline: Optional[float] = None
        self._drained = threading.Event()
        # Idempotent request keys: a bounded map of completed
        # request_key -> result, so a retried request (the LB's hedged
        # retry / a client replay after a mid-stream migration) gets
        # the SAME answer instead of a second execution.
        self._keys_lock = threading.Lock()
        self._completed_keys: 'collections.OrderedDict[str, Dict]' = \
            collections.OrderedDict()
        self._max_completed_keys = 512
        # Disaggregated serving (serve/disagg.py): this replica's phase
        # role (flag > SKYTPU_ROLE launch env > colocated) plus the
        # static handoff peers a prefill worker may stream finished
        # KV to when no router supplied an X-Handoff-Target header.
        # The disagg telemetry series register at construction so the
        # /metrics schema is stable from the first scrape — zeros on
        # every outcome/direction whether or not a handoff ever runs.
        self.role = disagg_lib.resolve_role(role)
        self.handoff_targets = disagg_lib.static_targets(handoff_targets)
        disagg_lib.register_metrics(self.role)
        # Multi-host gang serving (serve/gang.py): explicit spec wins,
        # else the SKYTPU_COORDINATOR/SKYTPU_RANK/SKYTPU_WORLD launch
        # env; world <= 1 (the default) keeps every hook a None check.
        # Rank 0 hosts the GangCoordinator on this same HTTP front end
        # (/gang/sync); nonzero ranks never construct a ModelServer at
        # all (main() dispatches them to a GangFollower). Gang series
        # register unconditionally so the /metrics schema is stable
        # from the first scrape on gang and non-gang replicas alike.
        gang_lib.register_metrics()
        self.gang = gang if gang is not None else \
            gang_lib.GangSpec.from_env()
        self._gang: Optional[gang_lib.GangCoordinator] = None
        self._gang_boot_blob: Optional[bytes] = None
        self._gang_drain_cid: Optional[int] = None
        if self.gang.is_gang:
            if not self.gang.is_leader:
                raise ValueError(
                    'ModelServer is the rank-0 gang process; run '
                    'nonzero ranks through the follower entry '
                    '(python -m skypilot_tpu.serve.server '
                    '--gang-rank N)')
            if self.role != 'colocated':
                logger.warning(
                    f'gang serving forces role=colocated (was '
                    f'{self.role}): disaggregated handoff in/out of a '
                    'gang would desync follower engine state')
                self.role = 'colocated'
            self._gang = gang_lib.GangCoordinator(self.gang)
            # Op-log hooks: every admission/cancel the scheduler
            # performs is recorded (under the engine lock) so
            # followers replay the identical engine call stream.
            self.sched.on_admit = self._gang_record_admit
            self.sched.on_cancel = self._gang_record_cancel
        # Spot resilience: prefix-cache checkpoint/warmup. On a
        # preemption warning the controller POSTs /checkpoint (the
        # response is the SKCK container of hot prefix chains +
        # in-flight request snapshots) and lands it into the
        # replacement via /kv/warmup BEFORE it enters rotation. With a
        # local checkpoint_path (flag > SKYTPU_KV_CHECKPOINT_PATH
        # env), the server additionally persists a checkpoint when a
        # drain begins and warms itself from the file at boot — the
        # standalone / bench restart path. The warmup histogram is
        # registered at construction (stable schema); this process
        # observes it only for boot-from-file warmups — HTTP warmups
        # are observed end-to-end by the controller-side manager.
        self.checkpoint_path = (checkpoint_path
                                or os.environ.get(
                                    'SKYTPU_KV_CHECKPOINT_PATH')
                                or None)
        self._h_warmup = reg.histogram(
            'skytpu_prefix_warmup_seconds',
            'Prefix-cache warmup of a recovered replica: checkpoint '
            'POST to landed (s)',
            buckets=telemetry.registry.DEFAULT_SECONDS_BUCKETS)
        reg.counter(
            'skytpu_spot_preemptions_total',
            'Spot replica preemptions observed (advance warnings and '
            'hard cluster losses)')
        self._m_handoff = {o: disagg_lib.handoff_counter(o)
                           for o in disagg_lib.HANDOFF_OUTCOMES}
        self._m_kv_bytes = {d: disagg_lib.transfer_bytes_counter(d)
                            for d in disagg_lib.KV_TRANSFER_DIRECTIONS}
        self._h_kv_transfer = disagg_lib.transfer_seconds()

    # ------------------------------------------------------------- engine
    def _load_engine(self) -> None:
        from skypilot_tpu.models.tokenizer import load_tokenizer
        # The shared gang recipe: real weights come from an HF
        # checkpoint dir (config.json + safetensors [+ tokenizer.json])
        # — the reference serves such checkpoints through
        # vLLM/JetStream (llm/llama-3/llama3.yaml:109). The (tp, dp)
        # mesh keeps the zero-resharding contract the paged-tp
        # jaxpr-audit preset gates.
        engine = build_engine(
            self.cfg_name, max_batch=self.max_batch,
            max_seq=self.max_seq, model_path=self.model_path,
            quantize=self.quantize,
            kv_cache_dtype=self.kv_cache_dtype,
            page_size=self.page_size, decode_impl=self.decode_impl,
            prefill_w8a8=self.prefill_w8a8,
            prefill_chunk_tokens=self.prefill_chunk_tokens,
            decode_priority_ratio=self.decode_priority_ratio,
            decode_steps_per_call=self.decode_steps_per_call,
            speculate_k=self.speculate_k,
            adapter_slots=self.adapter_slots,
            adapter_dir=self.adapter_dir,
            adapter_rank=self.adapter_rank,
            tp=self.tp, dp=self.dp,
            gang=self.gang if self.gang.is_gang else None)
        if self.model_path:
            self.cfg_name = engine.cfg.name
        self.tokenizer = load_tokenizer(
            self.model_path, model_vocab_size=engine.cfg.vocab_size)
        self.engine = engine
        self.sched.bind_engine(engine)
        # Prefix-cache warm boot: land a local checkpoint file (written
        # by a prior drain/preemption) BEFORE readiness — the replica
        # never serves cold when warm state exists on disk. A gang
        # leader DEFERS the landing until the barrier completes and
        # routes it through the op log, so followers land the identical
        # entries in the identical order (a warm leader over cold
        # followers would diverge on prefix-cache hits).
        if self.checkpoint_path and os.path.exists(self.checkpoint_path):
            t0 = time.monotonic()
            try:
                with open(self.checkpoint_path, 'rb') as f:
                    blob = f.read()
                if self._gang is not None:
                    self._gang_boot_blob = blob
                else:
                    res = self.warm_from_checkpoint(blob)
                    self._h_warmup.observe(time.monotonic() - t0)
                    logger.info(
                        f'Warm boot from {self.checkpoint_path}: '
                        f'{res["warmed_rows"]} row(s) across '
                        f'{res["entries"]} entr(ies) in '
                        f'{time.monotonic() - t0:.2f}s')
            except Exception as e:  # pylint: disable=broad-except
                logger.warning(
                    f'Warm boot from {self.checkpoint_path} failed '
                    f'({type(e).__name__}: {e}); serving cold')
        self._ready.set()
        device = device_lib.device_identity()
        logger.info(f'Engine ready: model={self.cfg_name} '
                    f'max_batch={self.max_batch} max_seq={self.max_seq} '
                    f'on {device["device_count"]} x '
                    f'{device["device_kind"]} ({device["platform"]}), '
                    f'decode_impl={engine.decode_impl}')

    def _engine_loop(self) -> None:
        try:
            self._load_engine()
        except Exception as e:  # pylint: disable=broad-except
            self._fatal(e)
            return
        if self._stopping:
            # stop() raced the load: drop the just-loaded engine instead
            # of resurrecting the reference stop() exists to release.
            self.engine = None
            self._ready.clear()
            return

        while not self._stopping:
            try:
                # Parked for lack of work: the loop account's one cause
                # that says the chip idles because nothing arrived.
                prof = self.engine.profiler
                with prof.phase('wait_work'):
                    self._work.wait()
                if self._stopping:
                    break
                if (self._gang is not None
                        and self._gang_boot_blob is not None
                        and self._gang.all_joined):
                    # Deferred gang warm boot: the barrier is complete,
                    # so the warmup op now reaches every rank in log
                    # order (warm_from_checkpoint appends it).
                    blob, self._gang_boot_blob = \
                        self._gang_boot_blob, None
                    try:
                        self.warm_from_checkpoint(blob)
                    except Exception as e:  # pylint: disable=broad-except
                        logger.warning(
                            f'gang warm boot failed '
                            f'({type(e).__name__}: {e}); serving cold')
                if self._faults is not None:
                    # Deterministic fault injection at the point the
                    # loop touches the hardware: a stall sleeps inside
                    # the loop (slow replica), a crash raises into the
                    # _fatal path (dead replica) — exactly the paths a
                    # real failure exercises.
                    rule = self._faults.fire('engine_step')
                    if rule is not None:
                        if rule.kind == 'engine_stall':
                            time.sleep(rule.delay_s)
                        elif rule.kind == 'replica_crash':
                            raise faults_lib.InjectedFault(
                                'injected replica_crash '
                                f'(engine_step #{self._faults.site_count("engine_step")})')
                        elif rule.kind == 'wedged_step':
                            # The gray failure a crash is not: the loop
                            # hangs INSIDE a step forever while the
                            # HTTP front end keeps answering. Arm the
                            # watchdog stamp exactly as a real step
                            # would, then never progress — detection
                            # and containment are the watchdog's job.
                            logger.warning(
                                'injected wedged_step: engine loop '
                                'hanging inside the step region')
                            self._wd_arm()
                            while (not self._stopping
                                   and self._degraded is None):
                                # This loop IS the injected hang (not
                                # a retry loop — nothing to back off).
                                time.sleep(0.01)  # graftcheck: disable=GC112
                            return     # a wedged step never returns
                        elif rule.kind == 'nan_logits':
                            # Evict one live decoding request exactly
                            # as the device-side non-finite sentinel
                            # would (deterministic stand-in for real
                            # NaN logits — the device reduction itself
                            # is unit-tested with poisoned params).
                            # LATCHED: if the loop iteration the rule
                            # lands on has no live request yet, the
                            # eviction applies to the NEXT one — the
                            # injection is deterministic under any
                            # arrival timing.
                            self._nan_evict_pending = True
                if self.speculate_k and self.engine is not None:
                    # Host-only n-gram matching for the next verify
                    # round, BEFORE taking the engine lock — handler
                    # threads must never queue behind proposer CPU
                    # work (graftcheck GC108 pins this discipline).
                    # Stale results (a slot turned over meanwhile) are
                    # revalidated and recomputed inside step().
                    self.engine.prepare_proposals()
                with self._engine_lock_timed(prof):
                    # Top the engine up from the scheduler's tier
                    # queues (priority + SRW order, tier budget
                    # split), then step. The scheduler holds the
                    # backlog; the engine queue stays empty, so
                    # admission ORDER is decided here every step, not
                    # at submit time.
                    with prof.phase('fill_engine'):
                        self.sched.fill_engine(self.engine)
                    # has_runnable_work: a prefill worker whose only
                    # live slots are HELD (awaiting their KV handoff)
                    # parks here instead of spinning — release_hold /
                    # submit / drain all set the wake event.
                    if self.engine.has_runnable_work():
                        prof.note_turn()
                        # Adaptive fused horizon: long fused calls
                        # maximize throughput at saturation (dispatch
                        # is pipelined away, but per-call host work
                        # isn't), short ones keep streaming latency
                        # low when the batch is nearly idle.
                        sat = max(2, self.engine.max_batch // 2)
                        # The multi-step knob pins the fused horizon
                        # (the engine would override anyway — keeping
                        # the recorded gang op h consistent with what
                        # actually runs).
                        h = self.decode_steps_per_call or (
                            32 if self.engine.num_active >= sat else 8)
                        if self._gang is not None:
                            # Record the step BEFORE running it (op
                            # order == execution order; the engine
                            # lock serializes both) so followers run
                            # the identical fused horizon.
                            self._gang.append_op(
                                {'k': 'step', 'h': h,
                                 'prepared': bool(self.speculate_k)})
                        # Wedge watchdog window: the stamp covers
                        # exactly the device-step region — the part a
                        # stuck jitted call or dead accelerator wedges.
                        self._wd_arm()
                        try:
                            events = self.engine.step(horizon=h)
                        finally:
                            self._wd_clear()
                        if self._nan_evict_pending:
                            events = self._inject_nan_evict(events)
                        if self._gang is not None and events:
                            # Finished-request digests feed the
                            # cross-rank byte-identity check; must run
                            # before on_events pops the finished
                            # Request objects.
                            self._gang.digest.update(self.engine,
                                                     events)
                    else:
                        events = []
                        if not self.sched.backlog:
                            self._work.clear()
                            if self.sched.backlog:
                                # A submit raced the clear (its wake
                                # landed between the check and clear):
                                # re-arm or the request strands until
                                # the next arrival.
                                self._work.set()
                # Outbox routing runs OUTSIDE the lock: puts are
                # lock-free and a slow SSE consumer can never hold the
                # engine step hostage.
                with prof.phase('route_events'):
                    self.sched.on_events(self.engine, events)
                # NaN blast-radius escalation: isolated poisoned
                # requests are evicted per-request above, but repeated
                # hits mean the REPLICA is sick (bad HBM, corrupted
                # weights, SDC) — escalate to the replica-level
                # degraded alarm so the manager replaces it.
                eng = self.engine
                if eng is not None \
                        and eng.nan_evictions > self._nan_seen:
                    self._nan_seen = eng.nan_evictions
                    if (self.nan_alarm_threshold > 0
                            and self._nan_seen
                            >= self.nan_alarm_threshold
                            and self._degraded is None):
                        self._gray_degrade(
                            'nan_logits',
                            f'{self._nan_seen} non-finite-logits '
                            'evictions (replica-level NaN storm)',
                            count=False)
                        return
            except Exception as e:  # pylint: disable=broad-except
                self._fatal(e)
                return
        # Clean stop: wake every waiter the way _fatal does — an
        # in-flight handler blocked on its outbox would otherwise hang
        # its client forever. The error sentinel is set BEFORE waking
        # (exactly like _fatal) so woken handlers report the stop.
        if self._error is None:
            self._error = 'server stopped'
        self.sched.fail_all(self._error)

    @contextlib.contextmanager
    def _engine_lock_timed(self, prof):
        """The engine thread's hold of the engine lock: the wait for it
        is the profiler's ``lock_wait`` phase, and wait and hold go on
        the two cumulative counters (two clock reads a loop turn)."""
        t_want = clock.monotonic()
        with prof.phase('lock_wait'):
            self._lock.acquire()
        t_have = clock.monotonic()
        try:
            yield
        finally:
            self._lock.release()
            self._m_lock_wait.inc(t_have - t_want)
            self._m_lock_held.inc(clock.monotonic() - t_have)

    def _fatal(self, e: Exception) -> None:
        """Engine died: drop readiness (the serve probe then pulls this
        replica out of rotation) and fail every queued and in-flight
        request so handler threads return errors instead of blocking
        forever. On a gang leader this also fails the whole gang —
        every follower's next sync gets the error and self-terminates
        (one dead rank, dead gang; never a half-alive replica)."""
        logger.exception(f'Engine loop died: {type(e).__name__}: {e}')
        self._error = f'{type(e).__name__}: {e}'
        if self._gang is not None:
            self._gang.fail(self._error)
        self._ready.clear()
        self.sched.fail_all(self._error)

    # ------------------------------------------------- gray-failure defense
    def _wd_arm(self) -> None:
        with self._wd_lock:
            self._step_started = self._wd_clock()

    def _wd_clear(self) -> None:
        with self._wd_lock:
            self._step_started = None

    def watchdog_age_s(self) -> float:
        """Age of the engine step currently in flight (0 between
        steps) — the ``skytpu_engine_step_watchdog_age_seconds``
        gauge, on the injectable watchdog clock."""
        with self._wd_lock:
            if self._step_started is None:
                return 0.0
            return max(0.0, self._wd_clock() - self._step_started)

    def watchdog_check(self) -> bool:
        """One watchdog evaluation (the monitor thread's body; tests
        call it directly on a virtual clock): a step older than
        ``step_watchdog_s`` flips the replica to the degraded state.
        Returns True when the watchdog fired."""
        if self.step_watchdog_s <= 0 or self._degraded is not None:
            return False
        age = self.watchdog_age_s()
        if age <= self.step_watchdog_s:
            return False
        self._gray_degrade(
            'wedged_step',
            f'engine step stuck for {age:.1f}s '
            f'(deadline {self.step_watchdog_s:.1f}s)')
        return True

    def _gray_degrade(self, kind: str, detail: str,
                      count: bool = True) -> None:
        """Containment for a replica-level gray failure: mark the
        replica degraded (readiness flips to a 503 the manager's probe
        escalation acts on), stop admitting, and fail every queued and
        in-flight request with a retryable error — the LB's in-flight
        recovery resubmits the streams to surviving replicas. The
        process stays up (a wedged accelerator does not kill HTTP),
        which is exactly why the state is 'degraded', not 'failed'."""
        if count:
            faults_lib.gray_failure_counter(kind).inc()
        self._degraded = f'{kind}: {detail}'
        logger.warning(f'replica degraded ({self._degraded}); failing '
                       'in-flight work over')
        if self._error is None:
            self._error = f'degraded ({kind}): {detail}'
        self._ready.clear()
        self.sched.fail_all(
            f'replica degraded ({kind}); retry on another replica')

    def _watchdog_loop(self) -> None:
        import random as random_mod
        rng = random_mod.Random()
        period = min(5.0, max(0.05, self.step_watchdog_s / 4.0))
        while not self._stopping and self._degraded is None:
            try:
                self.watchdog_check()
            except Exception:  # pylint: disable=broad-except
                logger.exception('watchdog check error')
            # Jittered poll (graftcheck GC112: no fixed-sleep loops).
            time.sleep(period * (0.5 + rng.random()))

    def _inject_nan_evict(self, events):
        """Injected ``nan_logits`` (engine lock held): cancel one live
        decoding request and prepend the non-finite sentinel event —
        the scheduler then fails exactly that outbox retryably, the
        same containment a real device-side sentinel drives. Stays
        latched until a live request exists (deterministic under any
        arrival timing)."""
        rids = self.engine.decoding_request_ids()
        if not rids:
            return events
        self._nan_evict_pending = False
        rid = rids[0]
        if self._gang is not None:
            # Keep the op log consistent: followers must drop the
            # same slot at the same log position.
            self._gang.append_op({'k': 'cancel', 'rid': rid})
            self._gang.digest.drop(rid)
        self.engine.cancel(rid)
        self.engine.nan_evictions += 1
        logger.warning(f'injected nan_logits: evicting request {rid}')
        return [(rid, -1, True)] + list(events)

    # --------------------------------------------------------------- gang
    def _gang_record_admit(self, rid: int, sr) -> None:
        """Scheduler admission hook (engine lock held): log the exact
        ``add_request`` call for follower replay."""
        s = sr.sampling
        self._gang.append_op({
            'k': 'add', 'rid': rid, 'prompt': list(sr.prompt),
            'max_new_tokens': sr.max_new_tokens,
            'priority': scheduler_lib.TIERS.index(sr.tier),
            'temperature': s.get('temperature', 0.0),
            'top_k': s.get('top_k', 0), 'top_p': s.get('top_p', 1.0),
            'eos_id': s.get('eos_id'), 'stop': s.get('stop'),
            # Multi-tenant LoRA: followers must decode with the same
            # bank row (and the same logit mask) or their digests
            # diverge on the first adapter token.
            'adapter': s.get('adapter'), 'tenant': s.get('tenant'),
            'grammar': s.get('grammar'),
            # Fleet trace id: follower ranks attribute their lockstep
            # replay of this request to the same trace.
            'trace_id': (sr.trace_ctx or {}).get('trace_id')})

    def _gang_record_cancel(self, rid: int) -> None:
        self._gang.append_op({'k': 'cancel', 'rid': rid})
        self._gang.digest.drop(rid)

    def _gang_monitor(self) -> None:
        """Leader-side gang health loop: join-deadline and follower
        heartbeat enforcement. Any gang failure routes through
        ``_fatal`` — the whole replica leaves rotation at once and the
        LB's in-flight recovery resubmits to a surviving replica."""
        import random as random_mod
        rng = random_mod.Random()
        while not self._stopping and self._error is None:
            try:
                self._gang.check()
            except gang_lib.GangFailure as e:
                self._gang.count_failure(e.cause)
                self._gang.fail(str(e))
                self._fatal(e)
                return
            except Exception:  # pylint: disable=broad-except
                logger.exception('gang monitor error')
            # Jittered poll (graftcheck GC112: no fixed-sleep loops).
            time.sleep(self.gang.heartbeat_s * (0.5 + rng.random()))

    def gang_status(self) -> Dict[str, Any]:
        """The /gang/status payload (also the health-accounting block
        the controller ships to the LB): stable keys whether or not
        this replica is a gang."""
        if self._gang is None:
            return {'gang_id': self.gang.gang_id, 'world': 1,
                    'barrier': True, 'join_seconds': None, 'ops': 0,
                    'failed': self._error, 'members': {}}
        return self._gang.status()

    def submit(self, prompt, max_new_tokens: int, temperature: float,
               top_k: int, eos_id: Optional[int], top_p: float = 1.0,
               stop=None, tier: Optional[str] = None,
               adapter: Optional[str] = None,
               tenant: Optional[str] = None,
               grammar: Optional[Any] = None,
               handoff_target: Optional[str] = None,
               trace_ctx: Optional[Dict[str, Any]] = None
               ) -> Dict[str, Any]:
        """Blocking submit (non-streaming handlers): admission-control
        through the scheduler, then drain the outbox to completion.
        Raises ``scheduler.ShedError`` (→ HTTP 429) when the tier's
        queue bound would be exceeded. On a prefill-role replica with a
        ``handoff_target``, the request hands off to the decode worker
        after prefill and the continuation is collected from its
        stream (falling back to local decode on any failure)."""
        if self._error is not None:
            raise RuntimeError(f'engine failed: {self._error}')
        sr = self.sched.submit(
            prompt, max_new_tokens=max_new_tokens, tier=tier,
            trace_ctx=trace_ctx,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, stop=stop,
            adapter=adapter, tenant=tenant, grammar=grammar,
            hold=handoff_target is not None)
        pre = None
        if handoff_target is not None:
            pre = sr.outbox.get(timeout=300)
            if pre[0] is not None and not pre[1]:
                result = self._collect_handoff(
                    sr, handoff_target, prompt,
                    dict(temperature=temperature, top_k=top_k,
                         top_p=top_p, eos_id=eos_id, stop=stop,
                         adapter=adapter, tenant=tenant,
                         grammar=grammar))
                if result is not None:
                    return result
                self._m_handoff['fallback_local'].inc()
                self.release_hold(sr)
        while True:
            token, finished = (pre if pre is not None
                               else sr.outbox.get())
            pre = None
            if token is None or finished:
                break
        if sr.outbox.error is not None or sr.result is None:
            raise RuntimeError(
                f'engine failed: {sr.outbox.error or self._error}')
        req = sr.result
        self._record_finished(req, sr)
        hit_eos = (req.eos_id is not None and req.output
                   and req.output[-1] == req.eos_id)
        return {
            'request_id': sr.request_id,
            'tokens': req.output,
            'ttft_ms': req.ttft_ms,
            'finish_reason': ('stop' if (req.stop_hit or hit_eos)
                              else 'length'),
            'prompt_tokens': len(req.prompt),
        }

    def submit_stream(self, prompt, max_new_tokens: int, temperature: float,
                      top_k: int, eos_id: Optional[int],
                      top_p: float = 1.0, stop=None,
                      tier: Optional[str] = None,
                      adapter: Optional[str] = None,
                      tenant: Optional[str] = None,
                      grammar: Optional[Any] = None,
                      hold: bool = False,
                      trace_ctx: Optional[Dict[str, Any]] = None):
        """Register a streaming request; returns its ScheduledRequest
        (``sr.outbox`` streams ``(token, finished)`` tuples). Callers
        must call ``finish_stream(sr)`` when done. Raises
        ``scheduler.ShedError`` (→ HTTP 429) on admission refusal.
        ``hold``: stop after the prefill-sampled first token (the
        disaggregated-handoff window; see ``release_hold``)."""
        if self._error is not None:
            raise RuntimeError(f'engine failed: {self._error}')
        return self.sched.submit(
            prompt, max_new_tokens=max_new_tokens, tier=tier,
            trace_ctx=trace_ctx,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, stop=stop,
            adapter=adapter, tenant=tenant, grammar=grammar, hold=hold)

    def release_hold(self, sr) -> None:
        """Resume local decoding of a held (handoff-candidate) request
        — the colocated fallback when no decode worker took it."""
        with self._lock:
            if self.engine is not None and sr.request_id is not None:
                self.engine.release_hold(sr.request_id)
        self._work.set()

    def finish_stream(self, sr) -> None:
        """Deregister a streaming request. If the client disconnected
        mid-stream (the request is not finished), cancel it so the
        slot stops generating tokens nobody will read — and count it
        as aborted, not served."""
        if sr.result is not None:
            self._record_finished(sr.result, sr)
            return
        if self.sched.cancel(sr):
            self._m_aborted.inc()
        elif sr.result is not None:
            # Finished during the cancel race: cancel() popped the
            # finished request into sr.result instead of aborting.
            self._record_finished(sr.result, sr)

    # ------------------------------------------------------------ handoff
    def handoff_target(self, header_value: Optional[str]
                       ) -> Optional[str]:
        """The decode worker this request should hand off to — None on
        non-prefill replicas (and when neither the router header nor a
        live static peer names one), in which case the request decodes
        locally exactly as before."""
        if self.role != 'prefill':
            return None
        return disagg_lib.pick_target(header_value,
                                      self.handoff_targets)

    def start_handoff(self, sr, target: str) -> Optional[Dict[str, Any]]:
        """Export ``sr``'s KV (int8 stays int8 on the wire) and POST it
        to ``target``'s ``/kv/ingest``; the response IS the decode
        worker's continuation token stream. On success the LOCAL
        request is cancelled (the slot frees for more prefill work; its
        full prefix pages stay cached) and the caller relays the
        stream. Returns None on ANY failure — the caller keeps serving
        locally (colocated fallback; the outbox still holds every
        token)."""
        if self._faults is not None:
            # Deterministic handoff failure (site 'handoff', kind
            # partial_response): the POST "breaks" before it is sent —
            # drives the exact colocated-fallback path a dead decode
            # worker would.
            rule = self._faults.fire('handoff')
            if rule is not None and rule.kind == 'partial_response':
                self._m_handoff['failed'].inc()
                logger.warning('handoff suppressed (injected '
                               'partial_response); decoding locally')
                return None
        with self._lock:
            if self.engine is None:
                return None
            snap, events = self.engine.export_kv_snapshot(
                sr.request_id)
        if events:
            # Tokens drained from the async pipeline during export
            # belong to their outboxes exactly like step() events.
            self.sched.on_events(self.engine, events)
        if snap is None or sr.result is not None:
            return None          # finished/cancelled during the drain
        t0 = time.monotonic()
        try:
            blob = kv_transfer.encode_handoff(snap)
            if self._faults is not None:
                # Deterministic wire corruption (site 'kv_wire', kind
                # kv_corruption): one byte of the encoded container
                # flips in transit — the receiver's CRC layer must
                # refuse it all-or-nothing (a retryable 400 → this
                # prefill falls back to local decode, never a
                # byte-wrong continuation).
                rule = self._faults.fire('kv_wire')
                if rule is not None and rule.kind == 'kv_corruption':
                    blob = faults_lib.corrupt_blob(blob, rule)
                    logger.warning('injected kv_corruption on the '
                                   'handoff wire (1 byte flipped)')
            # The handoff hop carries the fleet trace: the decode
            # worker's continuation joins this request's trace id with
            # the prefill span as its causal parent.
            trace = None
            if sr.trace_ctx and sr.trace_ctx.get('trace_id'):
                trace = {'trace_id': sr.trace_ctx['trace_id'],
                         'parent_span': 'prefill'}
            resp = wire.urlopen(
                target + '/kv/ingest', data=blob,
                headers={'Content-Type': 'application/octet-stream',
                         'X-SLO-Tier': sr.tier},
                trace=trace, timeout=120)
        except urllib.error.HTTPError as e:
            body = e.read()
            outcome = 'no_capacity' if e.code == 503 else 'failed'
            self._m_handoff[outcome].inc()
            logger.warning(
                f'handoff to {target} refused (HTTP {e.code}: '
                f'{body[:120]!r}); decoding locally')
            return None
        except Exception as e:  # pylint: disable=broad-except
            self._m_handoff['failed'].inc()
            logger.warning(f'handoff to {target} failed '
                           f'({type(e).__name__}: {e}); decoding '
                           'locally')
            return None
        self._m_kv_bytes['export'].inc(len(blob))
        self._h_kv_transfer.observe(time.monotonic() - t0)
        self._m_handoff['sent'].inc()
        # The continuation now lives on the decode worker: release the
        # local slot. The snapshot's registered prefix pages survive in
        # the LRU, so a migration resubmit landing back here re-matches
        # them.
        self.sched.cancel(sr)
        return {'prelude': [int(t) for t in snap['output']],
                'resp': resp, 'target': target}

    def _collect_handoff(self, sr, target: str, prompt,
                         sampling: Dict[str, Any]
                         ) -> Optional[Dict[str, Any]]:
        """Non-streaming handoff: run ``start_handoff`` and drain the
        decode worker's SSE continuation into one result dict. A
        decode-side failure mid-continuation resubmits
        ``prompt + tokens so far`` LOCALLY (the prefix cache makes the
        recompute cheap) so the caller still gets a complete answer —
        zero lost requests without an LB in the path."""
        ho = self.start_handoff(sr, target)
        if ho is None:
            return None
        tokens = list(ho['prelude'])
        finish_reason = None
        broke: Optional[str] = None
        try:
            with ho['resp'] as resp:
                for raw in resp:
                    if not raw.startswith(b'data:'):
                        continue
                    try:
                        ev = json.loads(raw[5:].strip())
                    except ValueError:
                        continue
                    if 'error' in ev:
                        broke = str(ev['error'])
                        break
                    if ev.get('done'):
                        finish_reason = ev.get('finish_reason',
                                               'length')
                        break
                    if 'token' in ev:
                        tokens.append(int(ev['token']))
        except Exception as e:  # pylint: disable=broad-except
            broke = f'{type(e).__name__}: {e}'
        if finish_reason is None:
            # Decode worker died mid-continuation: finish locally from
            # the generated prefix.
            self._m_handoff['failed'].inc()
            logger.warning(f'handoff continuation on {ho["target"]} '
                           f'broke ({broke}); resuming locally with '
                           f'{len(tokens)} token(s) generated')
            remaining = sr.max_new_tokens - len(tokens)
            if remaining > 0:
                sr2 = self.sched.submit(
                    list(prompt) + tokens, max_new_tokens=remaining,
                    tier=sr.tier, **sampling)
                while True:
                    token, finished = sr2.outbox.get()
                    if token is None:
                        raise RuntimeError(
                            f'engine failed: {sr2.outbox.error}')
                    if finished:
                        break
                req2 = sr2.result
                # req2.output is the authoritative continuation (stop
                # sequences arrive trimmed).
                tokens = tokens + list(req2.output
                                       if req2 is not None else [])
                hit_eos = (req2 is not None and req2.eos_id is not None
                           and req2.output
                           and req2.output[-1] == req2.eos_id)
                finish_reason = ('stop' if req2 is not None
                                 and (req2.stop_hit or hit_eos)
                                 else 'length')
            else:
                finish_reason = 'length'
        else:
            self._m_handoff['completed'].inc()
        self._m_served.inc()
        ttft = (round((sr.first_token_time - sr.submit_time) * 1e3, 3)
                if sr.first_token_time is not None else None)
        return {
            'request_id': sr.request_id,
            'tokens': tokens,
            'ttft_ms': ttft,
            'finish_reason': finish_reason,
            'prompt_tokens': len(prompt),
            'handoff': True,
        }

    # --------------------------------------------------- spot checkpoint
    def export_checkpoint(self, max_entries: int = 8):
        """The replica's resilience checkpoint as ``(bytes, n_entries)``:
        the hottest prefix-cache page chains (SKPF) plus snapshots of
        every in-flight decoding request (SKKV), in one SKCK container.
        Request entries are landed as prefix WARMTH by the receiver,
        never re-executed — the LB's in-flight recovery owns
        re-execution, so a checkpointed request that also migrates is
        warm on arrival instead of double-run. Safe on a cold/loading
        engine (empty container)."""
        entries: List[Dict[str, Any]] = []
        events: List[Any] = []
        eng = self.engine
        if eng is not None:
            with self._lock:
                if self._gang is not None:
                    # Gang checkpoint: record the pipeline flush the
                    # exports below perform, so followers flush at the
                    # same log position and stay event-aligned.
                    self._gang.append_op({'k': 'flush'})
                for rid in eng.decoding_request_ids():
                    if len(entries) >= max_entries:
                        break
                    snap, ev = eng.export_kv_snapshot(rid)
                    events.extend(ev)
                    if snap is not None:
                        entries.append(snap)
                pentries, ev = eng.export_prefix_snapshots(
                    max_entries=max_entries)
                events.extend(ev)
                entries.extend(pentries)
                if self._gang is not None and events:
                    self._gang.digest.update(eng, events)
            if events:
                # Tokens drained from the async pipeline during the
                # export belong to their outboxes exactly like step()
                # events.
                self.sched.on_events(eng, events)
        blob = kv_transfer.encode_checkpoint(entries)
        if self._gang is not None:
            # Checkpoint completes only when every rank acks — the
            # gang-atomic contract: "checkpointed" means the WHOLE
            # replica reached this state, not just rank 0. Bounded
            # wait (GC116); stragglers degrade to a leader-only
            # checkpoint with a loud log, never a hang.
            cid = self._gang.command('checkpoint')
            if not self._gang.wait_acked(
                    cid, timeout=min(10.0,
                                     4 * self.gang.heartbeat_timeout_s)):
                logger.warning(
                    'gang checkpoint: not every rank acked in time '
                    f'({self._gang.status()["members"]}); exporting '
                    'leader state anyway')
        self._m_kv_bytes['export'].inc(len(blob))
        return blob, len(entries)

    def export_prefix_blob(self, hash_hex: str):
        """One digest-named hot prefix chain as a CRC-checked SKCK
        container (single SKPF entry) — ``(blob, n_rows)``, or
        ``(None, 0)`` when the chain is unknown or already evicted.
        The prefix-affinity LB fetches this from the chain's home
        replica and POSTs it to the migration target's ``/kv/warmup``
        instead of letting the target recompute the prefix."""
        eng = self.engine
        if eng is None:
            return None, 0
        with self._lock:
            if self._gang is not None:
                # Record the pipeline flush the export performs so
                # followers flush at the same op-log position (same
                # contract as export_checkpoint).
                self._gang.append_op({'k': 'flush'})
            entry, events = eng.export_prefix_entry(hash_hex)
            if self._gang is not None and events:
                self._gang.digest.update(eng, events)
        if events:
            self.sched.on_events(eng, events)
        if entry is None:
            return None, 0
        blob = kv_transfer.encode_checkpoint([entry])
        self._m_kv_bytes['export'].inc(len(blob))
        return blob, int(entry['n_rows'])

    def warm_from_checkpoint(self, blob: bytes) -> Dict[str, Any]:
        """Land a checkpoint container into the engine's prefix cache:
        every entry (request snapshots included) lands as prefix
        warmth via ``warm_prefix`` — byte-exact KV, content-addressed,
        no request is seated or re-executed. Best-effort under pool
        pressure: landing stops at the first capacity refusal (the
        hottest entries land first). Raises ``ValueError`` on a
        malformed container and ``RuntimeError`` when no engine is
        loaded."""
        entries = kv_transfer.decode_checkpoint(blob)
        warmed_rows = 0
        landed = 0
        skipped_capacity = 0
        with self._lock:
            if self.engine is None:
                raise RuntimeError('engine not loaded')
            if self._gang is not None:
                # Fan the landing out through the op log (under the
                # engine lock: op order == execution order) so every
                # rank's prefix cache warms with the identical entries
                # — a warm leader over cold followers would diverge on
                # later prefix-cache hits.
                import base64
                self._gang.append_op({
                    'k': 'warmup',
                    'blob': base64.b64encode(blob).decode()})
            for entry in entries:
                try:
                    rows = self.engine.warm_prefix(entry)
                except kv_transfer.HandoffCapacityError:
                    skipped_capacity = len(entries) - landed
                    break
                if rows:
                    landed += 1
                warmed_rows += rows
        self._m_kv_bytes['ingest'].inc(len(blob))
        return {'entries': len(entries), 'landed': landed,
                'warmed_rows': warmed_rows,
                'skipped_capacity': skipped_capacity}

    def _persist_checkpoint(self) -> None:
        """Write the resilience checkpoint to ``checkpoint_path``
        (atomic rename) — the warm-boot source for a restarted
        standalone replica."""
        assert self.checkpoint_path is not None
        try:
            blob, n = self.export_checkpoint()
            tmp = self.checkpoint_path + '.tmp'
            with open(tmp, 'wb') as f:
                f.write(blob)
            os.replace(tmp, self.checkpoint_path)
            logger.info(f'Checkpointed {n} entr(ies) '
                        f'({len(blob)} bytes) to '
                        f'{self.checkpoint_path}')
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Checkpoint persist failed '
                           f'({type(e).__name__}: {e})')

    # -------------------------------------------------------------- drain
    def begin_drain(self, deadline_s: Optional[float] = None
                    ) -> Dict[str, Any]:
        """Enter graceful drain: the scheduler stops admitting (new
        submits get a retryable 503 + Retry-After), in-flight requests
        run to completion, and a monitor thread records the drain
        duration — failing whatever is still running once the deadline
        passes (the LB migrates those). Idempotent; returns the status
        payload."""
        with self._drain_lock:
            if self._drain_started is None:
                self._drain_started = time.monotonic()
                self._drain_deadline = self._drain_started + (
                    float(deadline_s) if deadline_s else
                    self.drain_deadline_s)
                self.sched.begin_drain()
                self._work.set()      # wake the loop to run the tail
                if self._gang is not None:
                    # Gang drain: the command pins the current op-log
                    # index; a follower acks only once it has applied
                    # everything up to it, so "gang drained" means
                    # every rank reached the drained state.
                    self._gang_drain_cid = self._gang.command(
                        'drain', {'deadline_s': float(deadline_s)
                                  if deadline_s else
                                  self.drain_deadline_s})
                if self.checkpoint_path:
                    # Persist the prefix-cache checkpoint alongside
                    # the drain (off-thread: the drain response must
                    # not wait on the KV gather) — the warm-boot
                    # source for a restarted replica.
                    threading.Thread(target=self._persist_checkpoint,
                                     daemon=True).start()
                threading.Thread(target=self._drain_monitor,
                                 daemon=True).start()
                logger.info(
                    'drain started: deadline '
                    f'{self._drain_deadline - self._drain_started:.1f}s,'
                    f' {self.sched.inflight} request(s) in flight')
        return self.drain_status()

    def _drain_monitor(self) -> None:
        import random
        with self._drain_lock:
            started, deadline = self._drain_started, self._drain_deadline
        while time.monotonic() < deadline:
            if self.sched.drained and self._gang_drain_acked():
                break
            # Jittered poll (graftcheck GC112: no fixed-sleep loops).
            time.sleep(0.05 * (0.5 + random.random()))
        dur = time.monotonic() - started
        clean = self.sched.drained and self._gang_drain_acked()
        self._h_drain.observe(dur)
        self._drained.set()
        if clean:
            logger.info(f'drain complete in {dur:.2f}s')
        else:
            # Deadline exceeded: fail the stragglers with a retryable
            # error — the LB resubmits them to a surviving replica, so
            # the teardown that follows still loses nothing.
            logger.warning(
                f'drain deadline exceeded after {dur:.1f}s with '
                f'{self.sched.inflight} request(s) still running; '
                'failing them over')
            self.sched.fail_all('drain deadline exceeded; retry on '
                                'another replica')

    def _gang_drain_acked(self) -> bool:
        """True once every gang rank acked the drain command (always
        True for non-gang replicas and before a drain started)."""
        if self._gang is None:
            return True
        with self._drain_lock:
            cid = self._gang_drain_cid
        return cid is None or self._gang.acked(cid)

    def drain_status(self) -> Dict[str, Any]:
        with self._drain_lock:
            started, deadline = self._drain_started, self._drain_deadline
        now = time.monotonic()
        out = {
            'draining': started is not None,
            'drained': (self._drained.is_set() and self.sched.drained
                        and self._gang_drain_acked()),
            'inflight': self.sched.inflight,
            'deadline_remaining_s': (round(max(0.0, deadline - now), 2)
                                     if deadline is not None else None),
        }
        if self._gang is not None:
            out['gang_drain_acked'] = self._gang_drain_acked()
        return out

    # -------------------------------------------------------- idempotency
    def lookup_request_key(self, key: Optional[str]
                           ) -> Optional[Dict[str, Any]]:
        if not key:
            return None
        with self._keys_lock:
            return self._completed_keys.get(key)

    def record_request_key(self, key: Optional[str],
                           result: Dict[str, Any]) -> None:
        """Remember a completed keyed request (bounded LRU): a replay
        of the same key returns this result instead of executing the
        request a second time."""
        if not key:
            return
        with self._keys_lock:
            self._completed_keys[key] = result
            self._completed_keys.move_to_end(key)
            while len(self._completed_keys) > self._max_completed_keys:
                self._completed_keys.popitem(last=False)

    def _record_finished(self, req, sr) -> None:
        """Fold one finished request into the registry: served counter
        plus the TTFT / TPOT / queue-wait latency decomposition (the
        queue-wait span comes off the request's telemetry trace), and
        the stages of its time to first token. ``sr`` is the streamed
        request's scheduler record: what its handler thread stamped
        (first flush, SSE write time) is folded in HERE, once, when the
        engine thread is done with the trace."""
        self._m_served.inc()
        if req.ttft_ms is not None:
            self._h_ttft.observe(req.ttft_ms)
        if (req.first_token_time is not None
                and req.finish_time is not None
                and len(req.output) > 1):
            self._h_tpot.observe(
                (req.finish_time - req.first_token_time) * 1e3
                / (len(req.output) - 1))
        streamed = sr.first_flush_time is not None
        if streamed:
            self._h_sse_write.observe(sr.sse_write_s * 1e3)
        # By the request's own 128-bit id (written back on admission),
        # never by the engine-local request id: with telemetry off
        # that found another request's trace.
        trace_id = (sr.trace_ctx or {}).get('trace_id')
        trace = (tracing.get_trace_buffer().find_trace(trace_id)
                 if trace_id else None)
        if trace is None:
            return
        queue_ms = trace.span_ms('queue')
        if queue_ms is not None:
            self._h_queue_wait.observe(queue_ms)
        surfaced = trace.first_token_at()
        if streamed and surfaced is not None:
            trace.add('emit_first', surfaced,
                      max(surfaced, sr.first_flush_time))
        for stage, ms in trace.ttft_stages().items():
            self._h_ttft_stage[stage].observe(ms)

    # ----------------------------------------------------------- metrics
    def _update_gauges(self) -> None:
        """Refresh the scrape-time registry gauges from engine state.
        Gauges are registered here get-or-create, so the Prometheus
        schema is stable from the first scrape (zeros before the
        engine loads or a feature turns on)."""
        eng = self.engine
        spec = eng.spec_metrics() if eng is not None else {}
        g = self._reg.gauge
        g('skytpu_active_slots',
          'Occupied decode slots').set(eng.num_active if eng else 0)
        # Queue depth = engine queue (kept ~empty by the scheduler) +
        # the scheduler's own tier backlog: the number operators (and
        # the queue-depth LB policy) actually care about.
        g('skytpu_queue_depth',
          'Requests waiting for a slot').set(
              (eng.queue_depth if eng else 0) + self.sched.backlog)
        g('skytpu_sched_engine_work_tokens',
          'Estimated work tokens ahead in the engine '
          '(prefill tails + decode budgets)').set(
              eng.remaining_work_tokens() if eng else 0)
        g('skytpu_prefill_inflight',
          'Slots still streaming prompt chunks in').set(
              len(eng._prefill_off) if eng else 0)
        g('skytpu_max_batch', 'Configured decode batch').set(
            self.max_batch)
        # Wedge-watchdog age: 0 between steps; sustained growth means
        # a step is stuck (the gauge operators alert on BEFORE the
        # watchdog deadline fires).
        self._g_wd_age.set(round(self.watchdog_age_s(), 3))
        # Serving mesh shape, one series per logical axis — all 1s on
        # a single-chip replica, configured values before the engine
        # loads (stable schema: the series never appear/disappear).
        for axis, size in self._mesh_axes().items():
            g('skytpu_mesh_shape',
              'Serving mesh axis size (1 = axis unused)',
              axis=axis).set(size)
        # Multi-step decode: the pinned fused steps per jitted decode
        # call (0 = the loop's adaptive horizon). Registered every
        # scrape get-or-create: present-and-zero before the knob (or
        # the engine) exists.
        g('skytpu_decode_steps_per_call',
          'Pinned fused decode steps per jitted call '
          '(0 = adaptive horizon)').set(
              getattr(eng, 'decode_steps_per_call', None)
              or self.decode_steps_per_call or 0)
        g('skytpu_speculate_k',
          'Speculative proposal depth (0 = off)').set(
              spec.get('speculate_k', 0))
        g('skytpu_spec_accept_rate',
          'Accepted / proposed draft tokens').set(
              spec.get('spec_accept_rate', 0.0))
        g('skytpu_spec_tokens_per_step',
          'Mean tokens committed per slot per verify call').set(
              spec.get('spec_tokens_per_step', 0.0))
        g('skytpu_spec_proposed_total',
          'Draft tokens proposed').set(spec.get('spec_proposed', 0))
        g('skytpu_spec_accepted_total',
          'Draft tokens accepted').set(spec.get('spec_accepted', 0))
        g('skytpu_spec_rounds_total',
          'Speculative verify rounds').set(spec.get('spec_rounds', 0))
        # KV pool capacity/pressure (shared engine schema; zeros until
        # the engine loads). The kv_cache_dtype label is constant for
        # the process, so the series set is stable from first scrape.
        pool = self._kv_pool_stats()
        dtype = pool['kv_cache_dtype']
        g('skytpu_kv_pool_tokens',
          'KV cache pool tokens by state (paged: page-granular)',
          state='used', kv_cache_dtype=dtype).set(pool['tokens_used'])
        g('skytpu_kv_pool_tokens',
          'KV cache pool tokens by state (paged: page-granular)',
          state='free', kv_cache_dtype=dtype).set(pool['tokens_free'])
        g('skytpu_kv_pool_token_capacity',
          'Total KV pool token capacity',
          kv_cache_dtype=dtype).set(pool['pool_token_capacity'])
        g('skytpu_kv_pool_preemptions_total',
          'Pool-pressure preemptions (recompute requeues)').set(
              pool['preemptions'])

    def _mesh_axes(self) -> Dict[str, int]:
        """The replica's mesh shape: the live engine's view once
        loaded, the configured (tp, dp) spec before — same keys either
        way (every logical axis, 1 when unused)."""
        eng = self.engine
        if eng is not None:
            return eng.mesh_axes()
        from skypilot_tpu.parallel import mesh as mesh_lib
        return {a: int(s) for a, s in zip(mesh_lib.MESH_AXES,
                                          self._mesh_spec.shape)}

    def _kv_pool_stats(self) -> Dict[str, Any]:
        """Engine KV pool stats with a stable all-zeros fallback before
        the engine loads (the dtype resolves from the configured flags
        so the gauge label never flips once serving starts)."""
        eng = self.engine
        if eng is not None:
            return eng.kv_pool_stats()
        from skypilot_tpu.inference.engine import resolve_kv_cache_dtype
        return {
            'kv_cache_dtype': resolve_kv_cache_dtype(
                self.kv_cache_dtype, self.quantize),
            'pool_token_capacity': 0, 'tokens_used': 0,
            'tokens_free': 0, 'preemptions': 0, 'kv_token_bytes': 0,
        }

    def _engine_path(self) -> Dict[str, Any]:
        """The JSON ``engine`` block: what the engine resolved at
        construction (``decode_impl`` after ``auto``, pool pages, whether
        the pool was sized from live device memory) and where its bytes
        sit. Same keys before the engine loads (``decode_impl`` None:
        nothing resolved yet)."""
        eng = self.engine
        if eng is not None:
            return eng.resolved_path()
        return {
            'decode_impl': None, 'decode_interpret': False,
            'prefill_attn': None, 'page_size': 0, 'kv_pool_pages': 0,
            'pool_auto_sized': False,
            'bytes_by_device': {'params': {}, 'kv_pool': {}},
            'kda_qkv': None,
            'jit_first_calls': 0, 'jit_first_call_seconds': 0.0,
        }

    def _lora_stats(self) -> Dict[str, Any]:
        """The JSON ``lora`` block with a stable all-zeros fallback
        before the engine loads (or with the adapter bank off) — same
        keys either way, sized from the configured flags so the schema
        never flips once serving starts."""
        eng = self.engine
        reg = getattr(eng, 'adapters', None) if eng is not None else None
        if reg is not None:
            return reg.stats()
        return {
            'slots': self.adapter_slots, 'used': 0,
            'free': self.adapter_slots,
            'rank': self.adapter_rank if self.adapter_slots else 0,
            'targets': [], 'loads_total': 0, 'evictions_total': 0,
            'last_load_ms': 0.0, 'loaded': [], 'pinned': {},
        }

    def _counter_value(self, name: str) -> float:
        """A registry counter another module owns (0 before it
        registers: the engine's profiler does at engine load)."""
        metric = self._reg.get(name)
        return metric.value if metric is not None else 0.0

    def _metrics_json_payload(self) -> Dict[str, Any]:
        """The PR-3 stable-schema JSON gauge block, now sourced from
        the telemetry registry (every key ALWAYS present and numeric;
        0 when idle / a feature is off — scrapers see one stable
        schema, never a key that appears only once traffic or
        speculation starts)."""
        eng = self.engine
        spec = eng.spec_metrics() if eng is not None else {}
        pool = self._kv_pool_stats()
        loop_s = (eng.profiler if eng is not None
                  else profiler_lib.NullProfiler()).loop_seconds()
        sched_stats = self.sched.json_stats()
        return {
            'requests_served': int(self._m_served.value),
            'requests_aborted': int(self._m_aborted.value),
            'active_slots': eng.num_active if eng else 0,
            'queue_depth': ((eng.queue_depth if eng else 0)
                            + self.sched.backlog),
            # Estimated work tokens ahead (engine prefill tails +
            # decode budgets + scheduler backlog) — what the
            # queue-depth LB policy load-ranks replicas by.
            'queue_tokens_total': (
                (eng.remaining_work_tokens() if eng else 0)
                + sum(t['queue_tokens']
                      for t in sched_stats['tiers'].values())),
            # Slots still streaming prompt chunks in — decodable
            # occupancy = active - this.
            'prefill_inflight': (len(getattr(
                eng, '_prefill_off', ())) if eng else 0),
            'max_batch': self.max_batch,
            'ttft_ms_median': round(self._h_ttft.quantile(0.5), 1),
            'ttft_ms_p90': round(self._h_ttft.quantile(0.9), 1),
            'ttft_window': self._h_ttft.window_len,
            'tpot_ms_median': round(self._h_tpot.quantile(0.5), 2),
            'tpot_ms_p90': round(self._h_tpot.quantile(0.9), 2),
            'queue_wait_ms_median': round(
                self._h_queue_wait.quantile(0.5), 1),
            'queue_wait_ms_p90': round(
                self._h_queue_wait.quantile(0.9), 1),
            # The time to first token by stage (stable schema: every
            # stage present, zeros before traffic), over the
            # registry's rolling window.
            'ttft_stages': {
                stage: {'p50': round(h.quantile(0.5), 3),
                        'p95': round(h.quantile(0.95), 3),
                        'n': h.window_len}
                for stage, h in self._h_ttft_stage.items()},
            # Cumulative counters of the engine loop, and the monotonic
            # clock they were read on: a scraper takes shares and
            # means from the difference of two scrapes.
            'engine_loop': {
                'clock_s': clock.monotonic(),
                'lock_held_seconds_total': self._m_lock_held.value,
                'lock_wait_seconds_total': self._m_lock_wait.value,
                'decode_substeps_total': self._counter_value(
                    profiler_lib.SUBSTEP_METRIC),
                'decode_live_rows_total': self._counter_value(
                    profiler_lib.LIVE_ROWS_METRIC),
                'moe_layer_steps_total': self._counter_value(
                    profiler_lib.MOE_LAYER_STEPS_METRIC),
                'moe_distinct_experts_total': self._counter_value(
                    profiler_lib.MOE_DISTINCT_METRIC),
                'moe_assignments_total': self._counter_value(
                    profiler_lib.MOE_ASSIGNMENTS_METRIC),
                'prefill_attn_pairs_total': self._counter_value(
                    profiler_lib.PREFILL_PAIRS_METRIC),
                'decode_attn_pages_live_total': self._counter_value(
                    profiler_lib.ATTN_PAGES_LIVE_METRIC),
                'decode_attn_pages_table_total': self._counter_value(
                    profiler_lib.ATTN_PAGES_TABLE_METRIC),
                'prefill_tokens_total': self._counter_value(
                    profiler_lib.PREFILL_TOKENS_METRIC),
                'prefill_wide_pieces_total': self._counter_value(
                    profiler_lib.PREFILL_WIDE_PIECES_METRIC),
                'prefill_wide_tokens_total': self._counter_value(
                    profiler_lib.PREFILL_WIDE_TOKENS_METRIC),
                'prefill_wide_width': self._counter_value(
                    profiler_lib.PREFILL_WIDE_WIDTH_METRIC),
                'prefill_scan_tokens_total': self._counter_value(
                    profiler_lib.PREFILL_SCAN_TOKENS_METRIC),
                'kv_cache_layers': self._counter_value(
                    profiler_lib.KV_CACHE_LAYERS_METRIC),
                'kv_token_bytes': self._counter_value(
                    profiler_lib.KV_TOKEN_BYTES_METRIC),
                'pool_write_rows_live_total': self._counter_value(
                    profiler_lib.POOL_ROWS_LIVE_METRIC),
                'pool_write_rows_offered_total': self._counter_value(
                    profiler_lib.POOL_ROWS_OFFERED_METRIC),
                'moe_held_experts': self._counter_value(
                    profiler_lib.MOE_HELD_EXPERTS_METRIC),
                'moe_assignments_held_total': self._counter_value(
                    profiler_lib.MOE_ASSIGNMENTS_HELD_METRIC),
                'recurrent_layers': self._counter_value(
                    profiler_lib.RECURRENT_LAYERS_METRIC),
                'recurrent_state_bytes': self._counter_value(
                    profiler_lib.RECURRENT_STATE_BYTES_METRIC),
                'state_resets_total': self._counter_value(
                    profiler_lib.STATE_RESETS_METRIC),
                'state_recompute_tokens_total': self._counter_value(
                    profiler_lib.STATE_RECOMPUTE_METRIC),
                # The loop account: the engine thread's seconds by cause
                # (the open span counted to now, so they add up to the
                # thread's clock) and the turns that had work.
                **{f'loop_{cause}_seconds_total': loop_s[cause]
                   for cause in profiler_lib.LOOP_CAUSES},
                'loop_turns_total': self._counter_value(
                    profiler_lib.LOOP_TURNS_METRIC),
            },
            # Speculative decoding gauges (zeros when off).
            'speculate_k': spec.get('speculate_k', 0),
            'spec_accept_rate': round(
                spec.get('spec_accept_rate', 0.0), 4),
            'spec_tokens_per_step': round(
                spec.get('spec_tokens_per_step', 0.0), 3),
            'spec_proposed': spec.get('spec_proposed', 0),
            'spec_accepted': spec.get('spec_accepted', 0),
            'spec_rounds': spec.get('spec_rounds', 0),
            # KV pool capacity/pressure (zeros before the engine loads;
            # kv_cache_dtype is the configured resolution either way).
            'kv_cache_dtype': pool['kv_cache_dtype'],
            'kv_pool_token_capacity': pool['pool_token_capacity'],
            'kv_pool_tokens_used': pool['tokens_used'],
            'kv_pool_tokens_free': pool['tokens_free'],
            'kv_pool_preemptions': pool['preemptions'],
            # Serving mesh shape (stable: configured values before the
            # engine loads, 1s on a single-chip replica). The LB's
            # replica view and the adaptive-TP policy read this.
            'mesh': dict(self._mesh_axes(),
                         devices=self.tp * self.dp),
            # The device as JAX reports it (platform, device_kind,
            # device_count, per-device memory_stats), the engine's
            # resolved path, and the process's XLA compile count: a
            # replica that came up on the CPU, dropped to the gather
            # path or recompiles in steady state shows from outside.
            'device': dict(device_lib.device_identity(),
                           memory=device_lib.device_memory()),
            'engine': self._engine_path(),
            'compiles': self._compiles.stats(),
            # Disaggregation block (stable schema: role + every handoff
            # outcome and transfer direction, zeros when idle). The
            # phase-aware LB policy routes and picks handoff targets
            # from this plus kv_pool_tokens_free above.
            'disagg': disagg_lib.json_block(self.role),
            # Gang block (stable schema: world 1 / barrier true on a
            # non-gang replica). The LB's replica view carries it for
            # health accounting — follower ranks have no routable
            # endpoint of their own.
            'gang': self.gang_status(),
            # Multi-step decode pin (0 = adaptive horizon) — stable
            # schema like every other key.
            'decode_steps_per_call': int(
                getattr(eng, 'decode_steps_per_call', None)
                or self.decode_steps_per_call or 0),
            'scheduler': {
                'prefill_chunk_tokens': getattr(eng, 'chunk', 0) or 0,
                'decode_priority_ratio': getattr(
                    eng, 'decode_priority_ratio', 0) or 0,
                'decode_steps_per_call': int(
                    getattr(eng, 'decode_steps_per_call', None)
                    or self.decode_steps_per_call or 0),
                'speculate_k': spec.get('speculate_k', 0),
            },
            # SLO scheduler block (stable schema: every tier and every
            # key present from the first scrape, zeros when idle).
            'sched': sched_stats,
            # Multi-tenant LoRA bank (stable schema: zeros/empty with
            # the bank off or before the engine loads). slots/used/free
            # are what the LB or an operator watches for bank-pressure
            # churn; loads/evictions count row re-uploads (never
            # recompiles).
            'lora': self._lora_stats(),
            # Hot-prefix digest (stable schema: page 0 / empty entries
            # before the engine loads). Built from
            # the engine's HOST-SIDE heat tracker only — shipping it on
            # every probe adds zero d2h and zero recompiles (pinned by
            # the jaxpr-audit serve preset). The prefix-affinity LB
            # policy routes by longest match against these hashes.
            'prefix_digest': {
                'page': int(eng.page) if eng is not None else 0,
                'entries': (eng.hot_prefix_digest()
                            if eng is not None else []),
            },
        }

    # --------------------------------------------------------------- HTTP
    def _make_handler(server):  # noqa: N805
        class Handler(http.server.BaseHTTPRequestHandler):
            # Socket-op timeout (graftcheck GC107): a client that stops
            # reading its stream must not pin a handler thread (and its
            # engine slot) forever. Above the 300s stream-queue wait so
            # a healthy-but-slow engine never trips it first; the
            # finally: finish_stream path cancels the slot on timeout.
            timeout = 330

            def log_message(self, *args):
                del args

            def _json(self, code: int, payload: Dict[str, Any],
                      extra_headers: Optional[Dict[str, str]] = None
                      ) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header('Content-Type', 'application/json')
                self.send_header('Content-Length', str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _shed(self, e: 'scheduler_lib.ShedError') -> None:
                """Admission refusal: HTTP 429 (overload) or 503
                (draining), always with Retry-After from live queue
                telemetry — clients back off for a meaningful interval
                instead of hammering a saturated or leaving replica."""
                self._json(e.http_status, {'error': {
                    'message': str(e),
                    'type': ('draining' if e.reason == 'draining'
                             else 'overloaded'),
                    'tier': e.tier,
                    'reason': e.reason,
                    'retry_after_s': e.retry_after_s,
                }}, extra_headers={'Retry-After': str(e.retry_after_s)})

            def _request_key(self, payload) -> Optional[str]:
                """Client-supplied idempotency key: JSON field wins
                over the X-Request-ID header (the LB mints one for
                recoverable requests)."""
                key = payload.get('request_key')
                if key is None:
                    key = self.headers.get('X-Request-ID')
                return str(key) if key else None

            def _slo_tier(self, payload) -> Optional[str]:
                """Per-request SLO tier: JSON field (``slo_tier``) wins
                over the ``X-SLO-Tier`` header; None -> server
                default. Unknown values 400 via resolve_tier."""
                tier = payload.get('slo_tier')
                if tier is None:
                    tier = self.headers.get('X-SLO-Tier')
                return server.sched.resolve_tier(tier)

            def _gang_sync(self) -> None:
                """One follower heartbeat against the leader's gang
                bus: registers/refreshes the member, verifies its
                finished-request digests, returns the op-log tail and
                pending commands (404 on a non-gang replica)."""
                if server._gang is None:
                    self._json(404, {'error': 'not a gang leader'})
                    return
                length = int(self.headers.get('Content-Length', 0))
                try:
                    payload = json.loads(self.rfile.read(length))
                    rank = int(payload['rank'])
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as e:
                    self._json(400, {'error': f'{type(e).__name__}: '
                                              f'{e}'})
                    return
                gid = payload.get('gang_id')
                if gid and server.gang.gang_id and \
                        gid != server.gang.gang_id:
                    self._json(409, {'failed': f'gang id mismatch: '
                                               f'{gid!r} != '
                                               f'{server.gang.gang_id!r}'})
                    return
                self._json(200, server._gang.sync(
                    rank, int(payload.get('applied', 0)),
                    payload.get('acks') or [],
                    payload.get('finished') or {}))

            def do_GET(self):  # noqa: N802
                parsed = urllib.parse.urlparse(self.path)
                query = urllib.parse.parse_qs(parsed.query)
                if parsed.path == '/readiness':
                    if server._degraded is not None:
                        # Gray failure contained: the process is alive
                        # (that is the POINT of a gray failure) but the
                        # data plane is not trustworthy — the manager's
                        # probe escalation fails the replica over.
                        self._json(503, {'status': 'degraded',
                                         'cause': server._degraded,
                                         'watchdog_age_s': round(
                                             server.watchdog_age_s(),
                                             3)})
                    elif server._error is not None:
                        self._json(503, {'status': 'failed',
                                         'error': server._error})
                    elif server.sched.draining:
                        # Out of rotation: probes see 503 so the LB /
                        # controller stop routing here while the tail
                        # of in-flight work finishes.
                        self._json(503, dict(
                            server.drain_status(), status='draining'))
                    elif (server._gang is not None
                          and not server._gang.all_joined):
                        # Gang barrier gates readiness: the replica is
                        # servable only once EVERY rank joined within
                        # the join timeout — a partial gang never
                        # enters LB rotation.
                        self._json(503, dict(server.gang_status(),
                                             status='gang_joining'))
                    elif server._ready.is_set():
                        self._json(200, {'status': 'ready',
                                         'model': server.cfg_name,
                                         'device': device_lib.
                                         device_identity(),
                                         'gang': server.gang_status()})
                    else:
                        self._json(503, {'status': 'loading'})
                elif parsed.path == '/gang/status':
                    self._json(200, server.gang_status())
                elif parsed.path == '/drain':
                    self._json(200, server.drain_status())
                elif parsed.path == '/metrics':
                    server._update_gauges()
                    if query.get('format', [''])[0] == 'json':
                        self._json(200, server._metrics_json_payload())
                        return
                    body = server._reg.render_prometheus().encode()
                    self.send_response(200)
                    self.send_header(
                        'Content-Type',
                        'text/plain; version=0.0.4; charset=utf-8')
                    self.send_header('Content-Length', str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif parsed.path == '/kv/prefix/export':
                    h = query.get('hash', [''])[0]
                    blob, n_rows = server.export_prefix_blob(h)
                    if blob is None:
                        self._json(404, {'error': {
                            'message': f'prefix {h!r} not cached',
                            'type': 'prefix_not_found'}})
                        return
                    self.send_response(200)
                    self.send_header('Content-Type',
                                     'application/octet-stream')
                    self.send_header('X-Prefix-Rows', str(n_rows))
                    self.send_header('Content-Length', str(len(blob)))
                    self.end_headers()
                    self.wfile.write(blob)
                elif parsed.path == '/telemetry/summary':
                    # Fleet-plane scrape: the controller pulls this on
                    # the probe path. ``since`` is the caller's trace
                    # cursor (resume semantics — only traces completed
                    # after it ship); the clock block lets the
                    # controller compute per-process skew at scrape
                    # time and apply it at trace assembly.
                    try:
                        since = int(query.get('since', ['0'])[0])
                    except ValueError:
                        since = 0
                    server._update_gauges()
                    cursor, traces = (tracing.get_trace_buffer()
                                      .summaries_since(since))
                    self._json(200, {
                        'clock': {'wall': time.time(),
                                  'monotonic': time.monotonic()},
                        'registry': server._reg.export_wire(),
                        'traces': traces,
                        'cursor': cursor,
                    })
                elif parsed.path == '/debug/requests':
                    try:
                        limit = int(query.get('limit', ['64'])[0])
                    except ValueError:
                        limit = 64
                    self._json(200, {'requests': tracing.
                                     get_trace_buffer().to_json(limit)})
                elif parsed.path == '/v1/models':
                    self._json(200, {
                        'object': 'list',
                        'data': [{'id': server.cfg_name,
                                  'object': 'model',
                                  'owned_by': 'skypilot-tpu'}],
                    })
                else:
                    self._json(404, {'error': f'no route {self.path}'})

            def _stream_generate(self, prompt, is_text, kwargs,
                                 key=None) -> None:
                """Server-sent events: one ``data:`` line per token as
                the engine emits it, a final ``done`` event with the
                full sequence. Token streaming end to end — the LB
                passes text/event-stream responses through unbuffered.
                Tokens arrive through the request's scheduler outbox,
                fed fire-and-forget off the engine loop: a slow reader
                here never stalls the step.

                Prefill role: once the first token lands (prefill
                complete), the request's KV hands off to a decode
                worker and this handler relays its continuation stream
                — one client stream either way. Any handoff failure
                falls back to local decoding seamlessly (the pre-read
                first token re-enters the loop)."""
                tok = server.tokenizer
                target = server.handoff_target(
                    self.headers.get('X-Handoff-Target'))
                sr = server.submit_stream(prompt,
                                          hold=target is not None,
                                          trace_ctx=self._trace_ctx(),
                                          **kwargs)
                tokens = []
                # Everything after registration lives under the finally:
                # even a client that drops before the headers flush must
                # reach finish_stream, or the slot decodes to
                # max_new_tokens for nobody.
                try:
                    self.send_response(200)
                    self.send_header('Content-Type', 'text/event-stream')
                    self.send_header('Cache-Control', 'no-cache')
                    self.send_header('Connection', 'close')
                    self.end_headers()
                    pre = None
                    if target is not None:
                        pre = sr.outbox.get(timeout=300)
                        if pre[0] is not None and not pre[1]:
                            ho = server.start_handoff(sr, target)
                            if ho is not None:
                                self._relay_handoff(ho, sr, tokens,
                                                    is_text, tok, key)
                                return
                            server._m_handoff['fallback_local'].inc()
                            server.release_hold(sr)
                    self._stream_loop(sr, tokens, is_text, tok, key,
                                      pre=pre)
                except (BrokenPipeError, ConnectionResetError):
                    pass    # client vanished; finish_stream cancels
                finally:
                    server.finish_stream(sr)
                    self.close_connection = True

            def _relay_handoff(self, ho, sr, tokens, is_text, tok,
                               key=None) -> None:
                """Relay a handoff continuation: the snapshot's prelude
                tokens (generated here during prefill) followed by the
                decode worker's live SSE events, merged into ONE client
                stream whose done event carries the full token list. A
                broken decode leg surfaces as a retryable error event
                with ``tokens_so_far`` — exactly what the LB's
                in-flight recovery needs to resubmit
                ``prompt + prefix`` to a surviving replica."""
                def emit(ev) -> None:
                    self.wfile.write(
                        f'data: {json.dumps(ev)}\n\n'.encode())
                    self.wfile.flush()

                def token_event(t: int) -> Dict[str, Any]:
                    ev = {'token': int(t)}
                    if is_text:
                        ev['text'] = sanitize_text(tok.decode([int(t)]))
                    return ev

                for t in ho['prelude']:
                    tokens.append(int(t))
                    emit(token_event(t))
                broke = None
                try:
                    with ho['resp'] as resp:
                        for raw in resp:
                            if not raw.startswith(b'data:'):
                                continue
                            try:
                                ev = json.loads(raw[5:].strip())
                            except ValueError:
                                continue
                            if 'error' in ev:
                                broke = str(ev['error'])
                                break
                            if ev.get('done'):
                                done = {'done': True,
                                        'request_id': sr.request_id,
                                        'tokens': list(tokens)}
                                if 'finish_reason' in ev:
                                    done['finish_reason'] = \
                                        ev['finish_reason']
                                if is_text:
                                    done['text'] = sanitize_text(tok.decode(tokens))
                                server.record_request_key(
                                    key, dict(done))
                                emit(done)
                                server._m_handoff['completed'].inc()
                                server._m_served.inc()
                                return
                            if 'token' in ev:
                                tokens.append(int(ev['token']))
                                emit(token_event(ev['token']))
                    if broke is None:
                        broke = 'decode worker stream ended early'
                except (BrokenPipeError, ConnectionResetError):
                    raise       # OUR client vanished — outer cleanup
                except Exception as e:  # pylint: disable=broad-except
                    broke = f'{type(e).__name__}: {e}'
                # Decode worker died mid-continuation: a retryable
                # error event with the generated prefix — the LB
                # resubmits prompt+prefix to a surviving replica (the
                # client sees one stream); direct clients retry.
                server._m_handoff['failed'].inc()
                logger.warning(f'handoff continuation on '
                               f'{ho["target"]} broke ({broke})')
                # failed_upstream names the DEAD replica (the decode
                # worker) — this relay is healthy, and the LB's
                # migration must exclude the right one.
                emit({'error': f'decode worker failed mid-stream: '
                               f'{broke}',
                      'retryable': True, 'retry_after_s': 1,
                      'failed_upstream': ho['target'],
                      'tokens_so_far': list(tokens)})

            def _sse_token(self, sr, line: bytes) -> None:
                """Write and flush one token's SSE line, on the
                request's own tally: two clock reads, no lock and no
                registry call (folded in once, at the finish)."""
                t0 = clock.monotonic()
                self.wfile.write(line)
                self.wfile.flush()
                t1 = clock.monotonic()
                sr.sse_write_s += t1 - t0
                if sr.first_flush_time is None:
                    sr.first_flush_time = t1

            def _stream_loop(self, sr, tokens, is_text, tok,
                             key=None, pre=None) -> None:
                pending = [] if pre is None else [pre]
                while True:
                    token, finished = (pending.pop(0) if pending
                                       else sr.outbox.get(timeout=300))
                    if token is None:       # engine died / shed
                        # Retryable stream failure: the error event
                        # carries enough for the LB (or a client) to
                        # resubmit elsewhere instead of giving up.
                        self.wfile.write(
                            ('data: ' + json.dumps({
                                'error': sr.outbox.error
                                or 'engine failed',
                                'retryable': True,
                                'retry_after_s': 1}) + '\n\n').encode())
                        break
                    tokens.append(int(token))
                    event = {'token': int(token)}
                    if is_text:
                        event['text'] = sanitize_text(tok.decode([int(token)]))
                    self._sse_token(
                        sr, f'data: {json.dumps(event)}\n\n'.encode())
                    if finished:
                        done = {'done': True,
                                'request_id': sr.request_id,
                                'tokens': tokens}
                        if is_text:
                            done['text'] = sanitize_text(tok.decode(tokens))
                        server.record_request_key(key, dict(
                            done, request_id=sr.request_id))
                        self.wfile.write(
                            f'data: {json.dumps(done)}\n\n'.encode())
                        break

            def _replay_stream(self, cached, is_text, tok) -> None:
                """Replay a completed keyed request as one SSE burst —
                the duplicate of an already-answered request streams
                the SAME tokens, never a second execution."""
                try:
                    self.send_response(200)
                    self.send_header('Content-Type', 'text/event-stream')
                    self.send_header('Cache-Control', 'no-cache')
                    self.send_header('Connection', 'close')
                    self.end_headers()
                    for t in cached.get('tokens', []):
                        event = {'token': int(t)}
                        if is_text:
                            event['text'] = sanitize_text(tok.decode([int(t)]))
                        self.wfile.write(
                            f'data: {json.dumps(event)}\n\n'.encode())
                    done = dict(cached, done=True, deduped=True)
                    self.wfile.write(
                        f'data: {json.dumps(done)}\n\n'.encode())
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError):
                    pass    # replay consumer vanished; nothing to free
                finally:
                    self.close_connection = True

            # ---------------- OpenAI-compatible surface ----------------
            # The reference's serving recipes expose vLLM's OpenAI API
            # (llm/llama-3/llama3.yaml, llm/vllm/README.md) — clients
            # built against it work against these routes unchanged.
            def _parse_sampling(self, payload, tok):
                stop = payload.get('stop')
                if stop is not None:
                    if isinstance(stop, (str, bytes)):
                        stop = [stop]
                    stop = [tok.encode(s, bos=False)
                            if isinstance(s, str)
                            else [int(t) for t in s] for s in stop]
                kwargs = dict(
                    max_new_tokens=int(payload.get(
                        'max_tokens', payload.get('max_new_tokens', 128))),
                    temperature=float(payload.get('temperature', 0.0)),
                    top_k=int(payload.get('top_k', 0)),
                    top_p=float(payload.get('top_p', 1.0)),
                    stop=stop,
                    eos_id=payload.get('eos_id', tok.eos_id))
                # Multi-tenant LoRA + constrained decoding: adapter
                # name (also OpenAI-style 'model: base:adapter'),
                # tenant attribution label, and grammar ('json' |
                # allowed-token-id list). Only forwarded when present
                # so adapter-free deployments see the exact legacy
                # call.
                adapter = payload.get('adapter')
                model = payload.get('model')
                if adapter is None and isinstance(model, str) \
                        and ':' in model:
                    base, _, suffix = model.partition(':')
                    # Colon-bearing model ids (e.g. 'llama3:8b' tags)
                    # were always ignored on adapter-free deployments;
                    # only read 'base:adapter' when this replica has a
                    # bank, or the prefix names the served model (an
                    # unambiguous adapter request either way).
                    if getattr(server, 'adapter_slots', 0) \
                            or base == server.cfg_name:
                        adapter = suffix or None
                if adapter is not None:
                    kwargs['adapter'] = str(adapter)
                if payload.get('tenant') is not None:
                    kwargs['tenant'] = str(payload['tenant'])
                grammar = payload.get('grammar',
                                      payload.get('response_format'))
                if isinstance(grammar, dict):
                    # OpenAI response_format: {'type': 'json_object'}.
                    grammar = ('json' if grammar.get('type')
                               in ('json_object', 'json') else None)
                if grammar is not None:
                    kwargs['grammar'] = grammar
                return kwargs

            def _trace_ctx(self):
                """Parse the inbound cross-process trace context (LB or
                client supplied ``X-Skytpu-Trace``); None when absent
                or malformed — the engine mints a fresh root id."""
                return tracing.parse_trace_header(
                    self.headers.get(tracing.TRACE_HEADER))

            def _openai_completions(self, payload, chat: bool) -> None:
                import time as time_mod
                tok = server.tokenizer
                if chat:
                    msgs = payload['messages']
                    # Minimal role-tagged template (no in-repo chat
                    # templates; HF tokenizers with one still consume
                    # plain text fine for completion-style serving).
                    text = ''.join(
                        f"{m['role']}: {m['content']}\n" for m in msgs)
                    text += 'assistant:'
                else:
                    text = payload['prompt']
                    # OpenAI accepts str | [str] | [int] | [[int]];
                    # single-element wrappers unwrap (n>1 prompts need
                    # one request per prompt — the engine queue batches
                    # them anyway).
                    if (isinstance(text, list) and text
                            and isinstance(text[0], (list, str))):
                        if len(text) != 1:
                            raise ValueError(
                                'multiple prompts per request are not '
                                'supported; send one request per '
                                'prompt')
                        text = text[0]
                prompt_ids = (tok.encode(text) if isinstance(text, str)
                              else [int(t) for t in text])
                kwargs = self._parse_sampling(payload, tok)
                kwargs['tier'] = self._slo_tier(payload)
                if payload.get('stream'):
                    self._openai_stream(prompt_ids, payload, chat,
                                        kwargs)
                    return
                result = server.submit(
                    prompt_ids, handoff_target=server.handoff_target(
                        self.headers.get('X-Handoff-Target')),
                    trace_ctx=self._trace_ctx(), **kwargs)
                out_text = sanitize_text(tok.decode(result['tokens']))
                created = int(time_mod.time())
                if chat:
                    choice = {'index': 0,
                              'message': {'role': 'assistant',
                                          'content': out_text},
                              'finish_reason': result['finish_reason']}
                    obj = 'chat.completion'
                else:
                    choice = {'index': 0, 'text': out_text,
                              'logprobs': None,
                              'finish_reason': result['finish_reason']}
                    obj = 'text_completion'
                self._json(200, {
                    'id': f'cmpl-{result["request_id"]}',
                    'object': obj,
                    'created': created,
                    'model': server.cfg_name,
                    'choices': [choice],
                    'usage': {
                        'prompt_tokens': result['prompt_tokens'],
                        'completion_tokens': len(result['tokens']),
                        'total_tokens': (result['prompt_tokens'] +
                                         len(result['tokens'])),
                    },
                })

            def _openai_stream(self, prompt_ids, payload, chat,
                               kwargs) -> None:
                import time as time_mod
                tok = server.tokenizer
                sr = server.submit_stream(
                    prompt_ids, trace_ctx=self._trace_ctx(), **kwargs)
                created = int(time_mod.time())
                obj = ('chat.completion.chunk' if chat
                       else 'text_completion')
                def chunk_of(choice):
                    return {'id': f'cmpl-{sr.request_id}',
                            'object': obj,
                            'created': created,
                            'model': server.cfg_name,
                            'choices': [choice]}

                def emit(data) -> None:
                    self.wfile.write(f'data: {data}\n\n'.encode())
                    self.wfile.flush()
                try:
                    self.send_response(200)
                    self.send_header('Content-Type', 'text/event-stream')
                    self.send_header('Cache-Control', 'no-cache')
                    self.send_header('Connection', 'close')
                    self.end_headers()
                    if chat:
                        # OpenAI chat streams open with a role delta.
                        emit(json.dumps(chunk_of(
                            {'index': 0,
                             'delta': {'role': 'assistant'},
                             'finish_reason': None})))
                    while True:
                        token, finished = sr.outbox.get(timeout=300)
                        if token is None:
                            # Engine died mid-stream: an explicit error
                            # event (and NO [DONE]) so clients can tell
                            # truncation from completion.
                            emit(json.dumps({'error': {
                                'message': 'engine failed'}}))
                            break
                        piece = sanitize_text(tok.decode([int(token)]))
                        if chat:
                            choice = {'index': 0,
                                      'delta': {'content': piece},
                                      'finish_reason': None}
                        else:
                            choice = {'index': 0, 'text': piece,
                                      'finish_reason': None}
                        self._sse_token(
                            sr, ('data: ' + json.dumps(chunk_of(choice))
                                 + '\n\n').encode())
                        if finished:
                            # Terminal chunk: empty delta/text with the
                            # real finish_reason, then [DONE] — the
                            # OpenAI truncation-detection contract.
                            # sr.result is populated BEFORE the
                            # finished token lands in the outbox.
                            req = sr.result
                            hit_eos = (req is not None
                                       and req.eos_id is not None
                                       and req.output
                                       and req.output[-1] == req.eos_id)
                            reason = ('stop' if req is not None
                                      and (req.stop_hit or hit_eos)
                                      else 'length')
                            final = ({'index': 0, 'delta': {},
                                      'finish_reason': reason} if chat
                                     else {'index': 0, 'text': '',
                                           'finish_reason': reason})
                            emit(json.dumps(chunk_of(final)))
                            emit('[DONE]')
                            break
                except (BrokenPipeError, ConnectionResetError):
                    pass
                finally:
                    server.finish_stream(sr)
                    self.close_connection = True

            def _kv_ingest(self) -> None:
                """Land a prefill worker's KV handoff and stream the
                continuation back ON THIS RESPONSE: the length-prefixed
                wire blob (``inference/kv_transfer.py``) is decoded,
                validated, and seated directly in the engine
                (``ingest_kv_snapshot`` — decode resumes at the exact
                original KV bytes), then every newly decoded token
                streams back as an SSE event, ending in a ``done``
                event carrying the FULL merged token list and
                finish_reason. Refusals: 400 (malformed/mismatched —
                permanent), 503 + Retry-After (no slot/pool capacity,
                or draining — retryable elsewhere)."""
                length = int(self.headers.get('Content-Length', 0))
                data = self.rfile.read(length) if length else b''
                t0 = time.monotonic()
                try:
                    snap = kv_transfer.decode_handoff(data)
                    tier = server.sched.resolve_tier(
                        self.headers.get('X-SLO-Tier'))
                except ValueError as e:
                    server._m_handoff['rejected'].inc()
                    if 'checksum mismatch' in str(e):
                        # A bit-flipped wire container, caught by the
                        # CRC layer before any row landed.
                        faults_lib.gray_failure_counter(
                            'kv_corruption').inc()
                    self._json(400, {'error': {
                        'message': str(e),
                        'type': 'invalid_handoff'}})
                    return
                if server.sched.draining:
                    self._json(503, {'error': {
                        'message': 'replica is draining; hand off to '
                                   'another decode worker',
                        'type': 'draining', 'retry_after_s': 5}},
                        extra_headers={'Retry-After': '5'})
                    return
                trace_ctx = self._trace_ctx()
                if trace_ctx:
                    # The handoff hop carries the trace on the header,
                    # not in the KV wire container — the decode-side
                    # request adopts the prefill worker's trace id.
                    snap['trace'] = trace_ctx
                try:
                    with server._lock:
                        rid = server.engine.ingest_kv_snapshot(snap)
                        # Adopt under the engine lock: fail_all cannot
                        # slip between seat and registration.
                        sr = server.sched.adopt(
                            rid, tier=tier, prompt=snap['prompt'],
                            output=snap['output'],
                            max_new_tokens=snap['max_new_tokens'],
                            trace_ctx=trace_ctx)
                except kv_transfer.HandoffCapacityError as e:
                    server._m_handoff['no_capacity'].inc()
                    retry = server.sched.retry_after_s(
                        tier, len(snap['prompt'])
                        + int(snap['max_new_tokens']))
                    self._json(503, {'error': {
                        'message': str(e), 'type': 'no_capacity',
                        'retry_after_s': retry}},
                        extra_headers={'Retry-After': str(retry)})
                    return
                except ValueError as e:
                    server._m_handoff['rejected'].inc()
                    self._json(400, {'error': {
                        'message': str(e),
                        'type': 'invalid_handoff'}})
                    return
                except RuntimeError as e:
                    self._json(500, {'error': {'message': str(e)}})
                    return
                server._m_kv_bytes['ingest'].inc(len(data))
                server._h_kv_transfer.observe(time.monotonic() - t0)
                server._m_handoff['ingested'].inc()
                server._work.set()        # wake the engine loop
                try:
                    self.send_response(200)
                    self.send_header('Content-Type',
                                     'text/event-stream')
                    self.send_header('Cache-Control', 'no-cache')
                    self.send_header('Connection', 'close')
                    self.end_headers()
                    while True:
                        token, finished = sr.outbox.get(timeout=300)
                        if token is None:
                            self.wfile.write(
                                ('data: ' + json.dumps({
                                    'error': sr.outbox.error
                                    or 'engine failed',
                                    'retryable': True,
                                    'retry_after_s': 1})
                                 + '\n\n').encode())
                            break
                        self.wfile.write(
                            ('data: '
                             + json.dumps({'token': int(token)})
                             + '\n\n').encode())
                        self.wfile.flush()
                        if finished:
                            req = sr.result
                            hit_eos = (req is not None
                                       and req.eos_id is not None
                                       and req.output
                                       and req.output[-1]
                                       == req.eos_id)
                            reason = ('stop' if req is not None
                                      and (req.stop_hit or hit_eos)
                                      else 'length')
                            done = {'done': True, 'request_id': rid,
                                    'tokens': (list(req.output)
                                               if req is not None
                                               else []),
                                    'finish_reason': reason}
                            self.wfile.write(
                                f'data: {json.dumps(done)}\n\n'
                                .encode())
                            break
                except (BrokenPipeError, ConnectionResetError):
                    pass    # prefill relay vanished; cancel below
                finally:
                    if sr.result is None:
                        # Relay gone mid-continuation: free the slot
                        # (the prefill side / LB resubmits elsewhere).
                        server.sched.cancel(sr)
                    self.close_connection = True

            def _checkpoint(self) -> None:
                """Export the spot-resilience checkpoint. The response
                body IS the SKCK container (octet-stream) — or, with a
                ``path`` in the JSON body, the container is written to
                that file and a JSON summary returned (the standalone
                / shared-filesystem flavor)."""
                length = int(self.headers.get('Content-Length', 0))
                try:
                    payload = (json.loads(self.rfile.read(length))
                               if length else {})
                except json.JSONDecodeError:
                    self._json(400, {'error': 'bad json'})
                    return
                try:
                    blob, n = server.export_checkpoint(
                        int(payload.get('max_entries', 8)))
                except Exception as e:  # pylint: disable=broad-except
                    self._json(500, {'error': {'message':
                                               f'{type(e).__name__}: '
                                               f'{e}'}})
                    return
                path = payload.get('path')
                if path:
                    tmp = path + '.tmp'
                    with open(tmp, 'wb') as f:
                        f.write(blob)
                    os.replace(tmp, path)
                    self._json(200, {'entries': n, 'bytes': len(blob),
                                     'path': path})
                    return
                self.send_response(200)
                self.send_header('Content-Type',
                                 'application/octet-stream')
                self.send_header('X-Checkpoint-Entries', str(n))
                self.send_header('Content-Length', str(len(blob)))
                self.end_headers()
                self.wfile.write(blob)

            def _kv_warmup(self) -> None:
                """Land a checkpoint container into this replica's
                prefix cache (the recovery-warmup half of
                /checkpoint). 400 on a malformed container; partial
                landings under pool pressure are reported, not
                errors."""
                length = int(self.headers.get('Content-Length', 0))
                data = self.rfile.read(length) if length else b''
                try:
                    self._json(200, server.warm_from_checkpoint(data))
                except ValueError as e:
                    if 'checksum mismatch' in str(e):
                        faults_lib.gray_failure_counter(
                            'kv_corruption').inc()
                    self._json(400, {'error': {
                        'message': str(e),
                        'type': 'invalid_checkpoint'}})
                except RuntimeError as e:
                    self._json(503, {'error': {'message': str(e)}},
                               extra_headers={'Retry-After': '5'})

            def do_POST(self):  # noqa: N802
                routes = ('/generate', '/v1/completions',
                          '/v1/chat/completions', '/drain',
                          '/kv/ingest', '/checkpoint', '/kv/warmup',
                          '/gang/sync')
                if self.path not in routes:
                    self._json(404, {'error': f'no route {self.path}'})
                    return
                if self.path == '/gang/sync':
                    self._gang_sync()
                    return
                if self.path == '/drain':
                    length = int(self.headers.get('Content-Length', 0))
                    try:
                        payload = (json.loads(self.rfile.read(length))
                                   if length else {})
                    except json.JSONDecodeError:
                        self._json(400, {'error': 'bad json'})
                        return
                    self._json(200, server.begin_drain(
                        payload.get('deadline_s')))
                    return
                if server._degraded is not None:
                    # Retryable refusal: the LB treats a replica 503 as
                    # never-executed and retries on another replica.
                    self._json(503, {'status': 'degraded',
                                     'cause': server._degraded,
                                     'retry_after_s': 5},
                               extra_headers={'Retry-After': '5'})
                    return
                if not server._ready.is_set():
                    self._json(503, {'status': 'loading'},
                               extra_headers={'Retry-After': '5'})
                    return
                if self.path == '/kv/ingest':
                    if server._gang is not None:
                        # A gang leader cannot adopt foreign KV: the
                        # seat would bypass the op log and desync
                        # every follower. Retryable — phase routing
                        # picks another decode worker.
                        self._json(503, {'error': {
                            'message': 'gang replicas do not accept '
                                       'KV handoffs',
                            'type': 'gang', 'retry_after_s': 5}},
                            extra_headers={'Retry-After': '5'})
                        return
                    self._kv_ingest()
                    return
                if self.path == '/checkpoint':
                    self._checkpoint()
                    return
                if self.path == '/kv/warmup':
                    self._kv_warmup()
                    return
                if self.path != '/generate':
                    length = int(self.headers.get('Content-Length', 0))
                    try:
                        payload = json.loads(self.rfile.read(length))
                        self._openai_completions(
                            payload, chat=self.path.endswith(
                                'chat/completions'))
                    except (KeyError, ValueError, TypeError,
                            json.JSONDecodeError) as e:
                        self._json(400, {'error': {
                            'message': f'{type(e).__name__}: {e}',
                            'type': 'invalid_request_error'}})
                    except scheduler_lib.ShedError as e:
                        # Before RuntimeError: ShedError subclasses it,
                        # and a shed is a 429 contract, not a 500.
                        self._shed(e)
                    except RuntimeError as e:
                        self._json(500, {'error': {'message': str(e)}})
                    return
                length = int(self.headers.get('Content-Length', 0))
                try:
                    payload = json.loads(self.rfile.read(length))
                    prompt = payload['prompt']
                    tok = server.tokenizer
                    is_text = isinstance(prompt, str)
                    if is_text:
                        prompt = tok.encode(prompt)
                    key = self._request_key(payload)
                    cached = server.lookup_request_key(key)
                    if cached is not None:
                        # Idempotent replay: the key already completed
                        # here — return the SAME answer instead of
                        # executing a second time (the one-answer
                        # guarantee behind the LB's hedged retry).
                        if payload.get('stream'):
                            self._replay_stream(cached, is_text, tok)
                        else:
                            self._json(200, dict(cached, deduped=True))
                        return
                    kwargs = self._parse_sampling(payload, tok)
                    kwargs['tier'] = self._slo_tier(payload)
                    # /generate's legacy defaults: eos only applies to
                    # text prompts unless explicitly requested.
                    if 'eos_id' not in payload and not is_text:
                        kwargs['eos_id'] = None
                    if payload.get('stream'):
                        self._stream_generate(prompt, is_text, kwargs,
                                              key)
                        return
                    result = server.submit(
                        prompt, handoff_target=server.handoff_target(
                            self.headers.get('X-Handoff-Target')),
                        trace_ctx=self._trace_ctx(), **kwargs)
                    if is_text:
                        result['text'] = sanitize_text(tok.decode(result['tokens']))
                    server.record_request_key(key, result)
                    self._json(200, result)
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as e:
                    self._json(400, {'error': f'{type(e).__name__}: {e}'})
                except scheduler_lib.ShedError as e:
                    self._shed(e)
                except RuntimeError as e:
                    self._json(500, {'error': str(e)})

        return Handler

    def start(self, block: bool = True) -> None:
        self._engine_thread = threading.Thread(target=self._engine_loop,
                                               daemon=True)
        self._engine_thread.start()
        if self._gang is not None:
            threading.Thread(target=self._gang_monitor,
                             daemon=True).start()
        if self.step_watchdog_s > 0:
            threading.Thread(target=self._watchdog_loop,
                             daemon=True).start()
        handler = self._make_handler()
        self._httpd = http.server.ThreadingHTTPServer(('0.0.0.0', self.port),
                                                      handler)
        logger.info(f'Model server listening on :{self.port}')
        if block:
            self._httpd.serve_forever()
        else:
            threading.Thread(target=self._httpd.serve_forever,
                             daemon=True).start()

    def stop(self) -> None:
        """Shut down the HTTP front end AND the engine loop, dropping
        the engine reference — the daemon loop thread would otherwise
        keep the model weights + KV pool alive (on TPU, several GB of
        HBM) for the life of the process."""
        self._stopping = True
        if self._gang is not None:
            # Clean gang teardown: followers get the shutdown command
            # (or, if they miss it, lose the coordinator and
            # self-terminate — either way nobody outlives the gang).
            # Bounded grace for the acks (GC116), then shut down
            # regardless.
            cid = self._gang.command('shutdown')
            self._gang.wait_acked(
                cid, timeout=min(1.0, 2 * self.gang.heartbeat_s))
        self._work.set()                      # wake the loop to exit
        if self._httpd is not None:
            self._httpd.shutdown()
        if self._engine_thread is not None:
            self._engine_thread.join(timeout=60)
        self.engine = None


def main() -> None:
    # No prefix matching: a removed flag (--kv-cache) must be unknown,
    # not read as the start of another (--kv-cache-dtype).
    parser = argparse.ArgumentParser(allow_abbrev=False)
    parser.add_argument('--model', default='tiny',
                        help='preset config name (random weights)')
    parser.add_argument('--model-path', default=None,
                        help='HF checkpoint dir (real weights + tokenizer)')
    parser.add_argument('--quantize', default=None,
                        choices=['int8', 'int4'],
                        help='weight quantization: int8 halves the '
                             'decode weight stream (the KV cache '
                             'follows via --kv-cache-dtype auto); '
                             'int4 packs two codes per byte with '
                             'fused dequant — half the streamed '
                             'weight bytes again on top of int8 (KV '
                             'follows to int4 under auto)')
    parser.add_argument('--tp', type=int, default=None,
                        help='tensor-parallel degree: shard weights + '
                             'KV heads over this many chips (decode '
                             'TPOT improves ~linearly; required once '
                             'the model outgrows one chip). Default: '
                             'SKYTPU_TP env (the controller\'s '
                             'adaptive-TP placement), else 1')
    parser.add_argument('--dp', type=int, default=None,
                        help='data-parallel degree: shard the decode '
                             'batch over chip groups (aggregate tok/s '
                             'scales; TPOT unchanged). Default: '
                             'SKYTPU_DP env, else 1. The mesh uses '
                             'tp*dp visible devices')
    parser.add_argument('--kv-cache-dtype', default=None,
                        choices=['bf16', 'int8', 'int4'],
                        help='KV cache storage dtype; default follows '
                             '--quantize (int8 weights => int8 KV, '
                             'int4 weights => int4 KV). '
                             'int8 halves KV HBM traffic in decode and '
                             '~doubles paged pool token capacity, with '
                             'dequant fused into the attention kernels; '
                             'int4 packs two nibble codes per byte — '
                             '~4x bf16 pool capacity at a further '
                             'bounded accuracy cost')
    parser.add_argument('--decode-impl', default=None,
                        choices=['gather', 'pallas', 'cross_layer'],
                        help='paged decode attention path '
                             '(default = engine auto). '
                             'cross_layer batches ALL layers\' KV '
                             'page reads per page visit — one kernel '
                             'pass per decode step instead of one '
                             'per layer')
    parser.add_argument('--page-size', type=int, default=None,
                        help='paged-cache page granularity (tokens); '
                             'default auto-selects a fast-path size '
                             '(int8 decode needs a multiple of 128 to '
                             'stay on the manual-DMA fast path)')
    parser.add_argument('--prefill-chunk-tokens', type=int, default=None,
                        help='chunked-prefill chunk width (tokens); '
                             'prompts prefill in chunks interleaved '
                             'with decode so running requests keep '
                             'streaming behind long prompts. Engine '
                             'default 256')
    parser.add_argument('--decode-priority-ratio', type=float,
                        default=None,
                        help='decode share of the interleaved token '
                             'budget while prompts are mid-prefill '
                             '(0..1); higher favors streaming TPOT, '
                             'lower favors TTFT. Default: engine-tuned')
    parser.add_argument('--decode-steps-per-call', type=int,
                        default=None,
                        help='multi-step on-device decode: fuse '
                             'EXACTLY this many decode steps (with '
                             'on-device sampling) into each jitted '
                             'call, so per-step dispatch, readback and '
                             'sampling host-syncs amortize k x. '
                             'Default: adaptive horizon (8 idle / 32 '
                             'saturated). Ignored while --speculate-k '
                             'drives decode')
    parser.add_argument('--speculate-k', type=int, default=0,
                        help='speculative decoding: propose up to K '
                             'tokens per verify step via prompt-lookup '
                             '(n-gram) matching against each request\'s '
                             'own history (0 = off). Greedy outputs are '
                             'identical to vanilla decode; sampling '
                             'keeps the output distribution. Biggest '
                             'win on repetitive/extractive text')
    parser.add_argument('--adapter-slots', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ADAPTER_SLOTS', '0')),
                        help='Device-resident LoRA adapter bank rows '
                             '(0 = multi-tenant adapters off). Each '
                             'request may name an adapter; slots '
                             'load/evict by LRU with row re-uploads, '
                             'never recompiles. Env fallback: the '
                             'controller ships the adapters: spec '
                             'block as SKYTPU_ADAPTER_*.')
    parser.add_argument('--adapter-dir',
                        default=os.environ.get('SKYTPU_ADAPTER_DIR')
                        or None,
                        help='Directory of <name>.npz LoRA checkpoints '
                             '(models/multilora.save_adapter layout) '
                             'loaded on first use by name.')
    parser.add_argument('--adapter-rank', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_ADAPTER_RANK', '8')),
                        help='Adapter bank rank: lower-rank '
                             'checkpoints zero-pad into the bank; '
                             'higher-rank ones are rejected.')
    parser.add_argument('--prefill-w8a8', action='store_true',
                        help='quantize prefill activations to int8 '
                             '(2x MXU rate on the compute-bound '
                             'prefill; adds quantization noise to '
                             'prefilled KV rows — decode unaffected)')
    parser.add_argument('--slo-tier-default', default='latency',
                        choices=list(scheduler_lib.TIERS),
                        help='SLO tier for requests that declare none '
                             '(per-request override: "slo_tier" in the '
                             'JSON body or the X-SLO-Tier header). '
                             'latency = interactive TTFT contract, '
                             'throughput = batch tokens/s contract')
    parser.add_argument('--max-queue-tokens', type=int, default=None,
                        help='per-tier admission bound in work tokens '
                             '(prompt + decode budget); a request that '
                             'would overflow its tier is shed with '
                             'HTTP 429 + Retry-After instead of '
                             'queueing. Default: 2x the KV pool token '
                             'capacity')
    parser.add_argument('--latency-admit-frac', type=float, default=0.7,
                        help='share of admitted work tokens reserved '
                             'for the latency tier while both tiers '
                             'are backlogged (0..1, exclusive)')
    parser.add_argument('--drain-deadline-s', type=float, default=30.0,
                        help='graceful-drain deadline (seconds): on '
                             'POST /drain new requests get a retryable '
                             '503 + Retry-After while in-flight ones '
                             'run to completion; stragglers past the '
                             'deadline are failed over (retryable)')
    parser.add_argument('--step-watchdog-s', type=float, default=None,
                        help='wedge-watchdog deadline (seconds) on '
                             'each engine step: a step stuck longer '
                             'flips /readiness to a degraded 503 and '
                             'fails in-flight requests over '
                             '(retryable — the LB resubmits them to '
                             'surviving replicas). Default: '
                             'SKYTPU_STEP_WATCHDOG_S env, else 120; '
                             '0 disables')
    parser.add_argument('--fault-spec', default=None,
                        help='deterministic fault-injection spec (JSON '
                             'or @/path/to/spec.json; default: the '
                             'SKYTPU_FAULT_SPEC env var). Unset = '
                             'injection compiled out of the hot path')
    parser.add_argument('--role', default=None,
                        choices=list(disagg_lib.ROLES),
                        help='disaggregated-serving phase role: '
                             'prefill workers hand each finished '
                             'prefill\'s KV (int8 stays int8 on the '
                             'wire) to a decode worker via POST '
                             '/kv/ingest and relay its token stream; '
                             'decode workers run high-batch decode '
                             'without prefill stalls; colocated '
                             '(default) interleaves both phases. '
                             'Default: SKYTPU_ROLE env (the '
                             'controller\'s disaggregation plan), '
                             'else colocated')
    parser.add_argument('--checkpoint-path', default=None,
                        help='local prefix-cache checkpoint file '
                             '(default: SKYTPU_KV_CHECKPOINT_PATH '
                             'env). When set: a drain/preemption '
                             'warning persists the hottest prefix '
                             'chains + in-flight KV snapshots here, '
                             'and a (re)booting server warms its '
                             'prefix cache from the file BEFORE '
                             'declaring readiness — near-warm TTFT '
                             'after spot recovery instead of cold')
    parser.add_argument('--handoff-targets', default=None,
                        help='comma-separated decode-worker base URLs '
                             'a prefill replica may hand off to when '
                             'no router supplied X-Handoff-Target '
                             '(picked by live KV-pool headroom). '
                             'Default: SKYTPU_HANDOFF_TARGETS env')
    parser.add_argument('--gang-rank', type=int, default=None,
                        help='multi-host gang rank (0 = leader: HTTP '
                             'front end + scheduler; >0 = follower '
                             'loop executing the leader\'s op log). '
                             'Default: SKYTPU_RANK env, else 0')
    parser.add_argument('--gang-world', type=int, default=None,
                        help='gang size (processes per replica; 1 = '
                             'not a gang). Default: SKYTPU_WORLD env')
    parser.add_argument('--gang-coordinator', default=None,
                        help='rank 0\'s base URL (the gang bus; '
                             'required on nonzero ranks). Default: '
                             'SKYTPU_COORDINATOR env')
    parser.add_argument('--gang-id', default=None,
                        help='shared gang identity (the replica '
                             'manager\'s unit of drain/checkpoint/'
                             'teardown). Default: SKYTPU_GANG_ID env')
    parser.add_argument('--max-batch', type=int, default=8)
    parser.add_argument('--max-seq', type=int, default=1024)
    parser.add_argument('--port', type=int,
                        default=int(os.environ.get('SKYTPU_REPLICA_PORT',
                                                   '8081')))
    args = parser.parse_args()
    gang_spec = gang_lib.GangSpec.from_env(
        rank=args.gang_rank, world=args.gang_world,
        coordinator=args.gang_coordinator, gang_id=args.gang_id)
    if gang_spec.is_gang and not gang_spec.is_leader:
        run_follower(gang_spec, args)
        return
    server = ModelServer(args.model, max_batch=args.max_batch,
                         max_seq=args.max_seq, port=args.port,
                         model_path=args.model_path,
                         quantize=args.quantize,
                         tp=args.tp, dp=args.dp,
                         kv_cache_dtype=args.kv_cache_dtype,
                         page_size=args.page_size,
                         decode_impl=args.decode_impl,
                         prefill_w8a8=args.prefill_w8a8,
                         prefill_chunk_tokens=args.prefill_chunk_tokens,
                         decode_priority_ratio=args.decode_priority_ratio,
                         decode_steps_per_call=args.decode_steps_per_call,
                         speculate_k=args.speculate_k,
                         adapter_slots=args.adapter_slots,
                         adapter_dir=args.adapter_dir,
                         adapter_rank=args.adapter_rank,
                         slo_tier_default=args.slo_tier_default,
                         max_queue_tokens=args.max_queue_tokens,
                         latency_admit_frac=args.latency_admit_frac,
                         drain_deadline_s=args.drain_deadline_s,
                         fault_spec=args.fault_spec,
                         role=args.role,
                         handoff_targets=(args.handoff_targets.split(',')
                                          if args.handoff_targets
                                          else None),
                         checkpoint_path=args.checkpoint_path,
                         gang=gang_spec,
                         step_watchdog_s=args.step_watchdog_s)
    server.start(block=True)


def run_follower(spec: 'gang_lib.GangSpec', args) -> None:
    """Nonzero-rank gang entry: build the identical engine rank 0
    builds (same config, same warmup — `build_engine` is the shared
    recipe), join the coordinator, and replay its op log until
    shutdown or gang death. The process exit code reflects the cause:
    0 for a clean shutdown, nonzero when the gang died — the replica
    manager treats a dead rank as a dead gang either way."""
    import sys
    from skypilot_tpu.parallel import mesh as mesh_lib
    mesh_spec = mesh_lib.serving_spec_from_env(tp=args.tp, dp=args.dp)
    logger.info(f'gang follower rank {spec.rank}/{spec.world} '
                f'(gang {spec.gang_id or "?"}) building engine...')
    engine = build_engine(
        args.model, max_batch=args.max_batch, max_seq=args.max_seq,
        model_path=args.model_path, quantize=args.quantize,
        kv_cache_dtype=args.kv_cache_dtype, page_size=args.page_size,
        decode_impl=getattr(args, 'decode_impl', None),
        prefill_w8a8=args.prefill_w8a8,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        decode_priority_ratio=args.decode_priority_ratio,
        decode_steps_per_call=getattr(args, 'decode_steps_per_call',
                                      None),
        speculate_k=args.speculate_k,
        adapter_slots=getattr(args, 'adapter_slots', 0),
        adapter_dir=getattr(args, 'adapter_dir', None),
        adapter_rank=getattr(args, 'adapter_rank', 8),
        tp=mesh_spec.tp, dp=mesh_spec.dp, gang=spec)
    follower = gang_lib.GangFollower(
        spec, engine,
        faults=faults_lib.make_injector(args.fault_spec))
    cause = follower.run()
    logger.info(f'gang follower rank {spec.rank} exiting: {cause}')
    sys.exit(0 if cause in ('shutdown', 'stopped') else 1)


if __name__ == '__main__':
    main()
