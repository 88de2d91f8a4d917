"""SLO-aware async serving core: admission, scheduling, shedding,
streaming.

An engine that sustains its decode rate can still deliver a fraction
of it over HTTP at saturation (a CPU-era reading, not measured on the
chip, had the HTTP path at under half the engine's rate with a
many-second median TTFT): head-of-line blocking and admission
starvation in the serve loop, not engine slowness. This module is the
piece that closes that gap: it sits between the HTTP front end and the
inference engine and owns
every decision the old server made implicitly (FIFO into the engine
queue, unbounded growth, block-until-done handlers):

- **SLO tiers.** Every request carries a tier — ``latency``
  (interactive: TTFT is the contract) or ``throughput`` (batch:
  tokens/s is the contract) — declared per request (``slo_tier`` in
  the JSON payload or the ``X-SLO-Tier`` header) with a server
  default. Tiers map to engine admission priorities, drive the
  admission budget split, and get their own TTFT/TPOT/queue-wait
  quantiles in ``/metrics``.

- **Priority + shortest-remaining-work scheduling.** Queued requests
  wait in per-tier queues; each engine step the scheduler tops the
  engine up from them (``fill_engine``), splitting the chunked-prefill
  admission token budget across tiers by a deficit counter
  (``latency_admit_frac`` of admitted work goes to the latency tier
  while both tiers are backlogged — neither tier can starve the
  other). Within a tier the next request is the one with the least
  estimated remaining work (prompt + budgeted decode tokens — the
  SJF/SRW policy of "Scalable Joint Resource Allocation for
  SLO-Constrained LLM Inference"), FIFO on ties, so one long prompt
  stops stalling a line of short interactive requests.

- **Admission control + load shedding.** Per-tier queues are bounded
  in TOKENS (``max_queue_tokens``; auto-derived from the engine's KV
  pool capacity). A request that would overflow its tier's bound is
  shed IMMEDIATELY with HTTP 429 and a ``Retry-After`` computed from
  live telemetry (work ahead of the request / the measured token
  throughput) instead of silently joining a queue it will time out
  in. Shed counts ride ``skytpu_sched_shed_total{tier,reason}``.

- **Incremental streaming off the engine loop.** Every request owns an
  :class:`Outbox` the engine loop feeds fire-and-forget (``put`` never
  blocks the step); HTTP handler threads (or an asyncio consumer via
  :meth:`Outbox.aget` — graftcheck GC111 bans blocking engine calls
  inside ``serve/`` coroutines) drain it at their own pace. A slow or
  disconnected client never back-pressures the engine step; disconnect
  cancels the request engine-side through :meth:`RequestScheduler.
  cancel`, releasing the slot.

Locking: the scheduler has its own queue lock (``_q_lock``) and is
handed the serve layer's engine lock. Order is ALWAYS engine lock
outer, queue lock inner; nothing blocking runs under either.
``fill_engine``/``on_events`` are called by the engine-loop thread,
``submit``/``cancel`` by handler threads.
"""
from __future__ import annotations

import collections
import math
import queue as queue_mod
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from skypilot_tpu import telemetry
from skypilot_tpu import tpu_logging
from skypilot_tpu.telemetry import clock
from skypilot_tpu.telemetry import tracing

logger = tpu_logging.init_logger(__name__)

# SLO tiers, best first. The tuple order IS the engine admission
# priority order (index = engine priority hint: lower wins a free
# slot).
TIERS: Tuple[str, ...] = ('latency', 'throughput')

# Shed reasons (the stable label set of skytpu_sched_shed_total —
# every (tier, reason) series is registered at scheduler construction
# so the /metrics schema never grows mid-flight). 'draining' is the
# graceful-drain refusal: the replica is leaving rotation, so the
# caller gets a retryable 503 instead of the overload 429.
SHED_REASONS: Tuple[str, ...] = ('queue_full', 'engine_error',
                                 'draining')

_RETRY_AFTER_MIN_S = 1
_RETRY_AFTER_MAX_S = 120


class ShedError(RuntimeError):
    """Admission refused: the caller should answer ``http_status``
    (429 for overload, 503 while draining — both retryable) with the
    ``retry_after_s`` hint (derived from live queue telemetry — the
    work ahead of this request over the measured token throughput)."""

    def __init__(self, tier: str, reason: str, retry_after_s: int,
                 detail: str):
        super().__init__(detail)
        self.tier = tier
        self.reason = reason
        self.retry_after_s = retry_after_s

    @property
    def http_status(self) -> int:
        # Draining is not overload: the replica is healthy but
        # leaving — 503 + Retry-After tells the client (and the LB's
        # transparent retry) to go elsewhere, now.
        return 503 if self.reason == 'draining' else 429


class Outbox:
    """Per-request stream of ``(token, finished)`` tuples, fed by the
    engine loop and drained by exactly one consumer. ``put`` never
    blocks (unbounded queue — bounded upstream by the request's own
    ``max_new_tokens``), so a stalled consumer can never back-pressure
    the engine step. ``(None, True)`` is the failure sentinel (engine
    death / shed after admission); ``error`` then carries the reason."""

    def __init__(self) -> None:
        self._q: 'queue_mod.Queue[Tuple[Optional[int], bool]]' = \
            queue_mod.Queue()
        self.error: Optional[str] = None

    def put(self, token: Optional[int], finished: bool) -> None:
        self._q.put((token, finished))

    def fail(self, error: str) -> None:
        """Terminal failure: record the reason and wake the consumer
        with the sentinel. Idempotent — the first reason wins."""
        if self.error is None:
            self.error = error
        self._q.put((None, True))

    def get(self, timeout: Optional[float] = None
            ) -> Tuple[Optional[int], bool]:
        return self._q.get(timeout=timeout)

    async def aget(self) -> Tuple[Optional[int], bool]:
        """Asyncio adapter: awaits the next token WITHOUT blocking the
        event loop (the blocking ``get`` runs on the default executor —
        the pattern graftcheck GC111 routes ``serve/`` coroutines to)."""
        import asyncio
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self.get)


class ScheduledRequest:
    """One request's scheduler-side state, submit to finish. The
    ``outbox`` is live from submission; ``request_id`` exists only once
    the request is admitted into an engine; ``result`` is the engine's
    finished ``Request`` object once complete."""

    __slots__ = ('tier', 'prompt', 'max_new_tokens', 'sampling', 'seq',
                 'submit_time', 'admit_time', 'outbox', 'request_id',
                 'result', 'first_token_time', 'cancelled', 'handoff',
                 'trace_ctx', 'first_flush_time', 'sse_write_s')

    def __init__(self, tier: str, prompt: List[int],
                 max_new_tokens: int, sampling: Dict[str, Any],
                 seq: int,
                 trace_ctx: Optional[Dict[str, Any]] = None):
        self.tier = tier
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling
        self.seq = seq
        self.submit_time = clock.now()
        self.admit_time: Optional[float] = None
        self.outbox = Outbox()
        self.request_id: Optional[int] = None
        self.result: Optional[Any] = None
        self.first_token_time: Optional[float] = None
        # Stamped by the ONE handler thread that streams this request
        # (monotonic): when the flush of the first token's SSE line
        # returned, and the seconds spent in SSE writes so far. Folded
        # into the trace and the registry once, when the request is
        # recorded as finished — never per token.
        self.first_flush_time: Optional[float] = None
        self.sse_write_s = 0.0
        self.cancelled = False
        # Wire-supplied trace context ({'trace_id', 'parent_span'}) —
        # the X-Skytpu-Trace hop header this request arrived with. On
        # admission the engine's RequestTrace adopts it and the
        # EFFECTIVE trace id (wire-supplied or locally minted) is
        # written back here, so downstream hops (KV handoff, gang
        # op-log) propagate the same fleet-wide id.
        self.trace_ctx = dict(trace_ctx) if trace_ctx else None
        # Adopted KV-handoff continuation (disaggregated serving): the
        # request was admitted and prefilled on ANOTHER replica, so
        # this replica's TTFT/queue-wait quantiles skip it — a near-
        # zero "TTFT" here would poison the latency telemetry the
        # autoscaler and routing policies read.
        self.handoff = False

    @property
    def work_tokens(self) -> int:
        """Remaining-work estimate while queued: the whole prompt must
        prefill and up to ``max_new_tokens`` must decode. The SRW
        ordering key (and the unit the admission budget is spent in)."""
        return len(self.prompt) + self.max_new_tokens


class _TokenRateMeter:
    """Sliding-window output-token throughput (tok/s) — the live
    denominator of the Retry-After computation. Bounded window of
    (monotonic time, n_tokens) buckets; O(1) amortized."""

    def __init__(self, window_s: float = 30.0):
        self.window_s = window_s
        self._events: 'collections.deque[Tuple[float, int]]' = \
            collections.deque()
        self._total = 0

    def add(self, n_tokens: int, now: Optional[float] = None) -> None:
        now = clock.monotonic() if now is None else now
        self._events.append((now, n_tokens))
        self._total += n_tokens
        self._trim(now)

    def _trim(self, now: float) -> None:
        cutoff = now - self.window_s
        while self._events and self._events[0][0] < cutoff:
            _, n = self._events.popleft()
            self._total -= n

    def rate(self, now: Optional[float] = None) -> float:
        """tok/s over the window; 0.0 when no tokens were seen (the
        caller falls back to a capacity estimate)."""
        now = clock.monotonic() if now is None else now
        self._trim(now)
        if not self._events:
            return 0.0
        span = max(now - self._events[0][0], 1e-3)
        return self._total / span


class RequestScheduler:
    """The admission/scheduling core. One instance per model server;
    the engine binds late (``bind_engine``) because capacity-derived
    defaults need the loaded engine's pool size.

    Parameters
    ----------
    engine_lock:
        The serve layer's engine mutation lock. ``fill_engine`` is
        called WITH it held (from the engine loop); ``cancel`` and the
        finished-request bookkeeping take it internally.
    default_tier:
        Tier used when a request declares none.
    max_queue_tokens:
        Per-tier queue bound in work tokens (prompt + budgeted decode).
        ``None``/0 auto-derives at ``bind_engine``: 2x the engine's KV
        pool token capacity — roughly two full pools of work may wait,
        beyond that waiting is worse than retrying.
    latency_admit_frac:
        Share of admitted work tokens reserved for the latency tier
        while BOTH tiers are backlogged (deficit-weighted; an idle
        tier's share flows to the busy one). Default 0.7 — latency
        requests are short by contract, so a 70% token share admits
        far more than 70% of them.
    """

    def __init__(self, engine_lock: threading.Lock, *,
                 default_tier: str = 'latency',
                 max_queue_tokens: Optional[int] = None,
                 latency_admit_frac: float = 0.7,
                 wake: Optional[Callable[[], None]] = None):
        if default_tier not in TIERS:
            raise ValueError(f'unknown SLO tier {default_tier!r}; '
                             f'supported: {TIERS}')
        if not 0.0 < latency_admit_frac < 1.0:
            raise ValueError('latency_admit_frac must be in (0, 1), '
                             f'got {latency_admit_frac}')
        self._engine_lock = engine_lock
        self.default_tier = default_tier
        self.latency_admit_frac = latency_admit_frac
        self._max_queue_tokens = max_queue_tokens or None
        self._wake = wake or (lambda: None)
        # Gang op-log hooks (serve/gang.py): the gang leader records
        # every engine mutation so followers replay the identical call
        # stream. ``on_admit(rid, sr)`` fires right after a successful
        # ``engine.add_request`` (engine lock held — keep it cheap);
        # ``on_cancel(rid)`` fires after a successful engine-side
        # cancel. None (the default) costs one attribute check.
        self.on_admit: Optional[Callable[[int, 'ScheduledRequest'],
                                         None]] = None
        self.on_cancel: Optional[Callable[[int], None]] = None
        self._engine: Optional[Any] = None
        # Mesh throughput factor (tp x dp of the bound engine's mesh):
        # scales the WORK-TOKEN RATE estimates — the cold-meter
        # Retry-After fallback and nothing else. Work tokens themselves
        # stay mesh-independent (a token is a token); only how fast the
        # engine chews through them changes with mesh shape. Once the
        # live _TokenRateMeter warms up it dominates, so the factor
        # only matters for the first seconds after boot — exactly when
        # a tp=4 replica must not tell clients to back off 4x too long.
        self._mesh_speedup = 1
        self._q_lock = threading.Lock()
        self._queues: Dict[str, List[ScheduledRequest]] = {
            t: [] for t in TIERS}
        self._queued_tokens: Dict[str, int] = {t: 0 for t in TIERS}
        self._by_rid: Dict[int, ScheduledRequest] = {}
        self._seq = 0
        # Cumulative admitted work tokens per tier — the deficit
        # counter the per-step budget split rides.
        self._admitted_tokens: Dict[str, int] = {t: 0 for t in TIERS}
        self._rate = _TokenRateMeter()
        self._failed: Optional[str] = None
        self._draining = False
        self._init_metrics()

    # ------------------------------------------------------------ metrics
    def _init_metrics(self) -> None:
        """Register EVERY series up front (zeros rendered from the
        first scrape — the stable-schema contract scrapers rely on)."""
        reg = telemetry.get_registry()
        self._g_queue_tokens = {
            t: reg.gauge('skytpu_sched_queue_tokens',
                         'Work tokens waiting in the scheduler queue',
                         tier=t) for t in TIERS}
        self._g_queue_depth = {
            t: reg.gauge('skytpu_sched_queue_depth',
                         'Requests waiting in the scheduler queue',
                         tier=t) for t in TIERS}
        self._g_budget_share = {
            t: reg.gauge('skytpu_sched_admit_share',
                         'Share of admitted work tokens (cumulative)',
                         tier=t) for t in TIERS}
        self._c_shed = {
            (t, r): reg.counter('skytpu_sched_shed_total',
                                'Requests shed at admission (HTTP 429)',
                                tier=t, reason=r)
            for t in TIERS for r in SHED_REASONS}
        self._c_admitted = {
            t: reg.counter('skytpu_sched_admitted_total',
                           'Requests admitted into the engine', tier=t)
            for t in TIERS}
        self._h_ttft = {
            t: reg.histogram('skytpu_request_ttft_ms',
                             'Time to first token (ms)', tier=t)
            for t in TIERS}
        self._h_tpot = {
            t: reg.histogram('skytpu_request_tpot_ms',
                             'Mean time per output token after the '
                             'first (ms)', tier=t) for t in TIERS}
        self._h_queue_wait = {
            t: reg.histogram('skytpu_request_queue_wait_ms',
                             'Submit to engine admission (ms)', tier=t)
            for t in TIERS}

    # ------------------------------------------------------------- engine
    def bind_engine(self, engine: Any) -> None:
        """Attach the loaded engine; derives the auto queue bound from
        its KV pool capacity."""
        with self._q_lock:
            self._engine = engine
            if hasattr(engine, 'mesh_axes'):
                axes = engine.mesh_axes()
                self._mesh_speedup = max(
                    1, int(axes.get('tp', 1)) * int(axes.get('dp', 1)))
            if self._max_queue_tokens is None:
                cap = 0
                if hasattr(engine, 'kv_pool_stats'):
                    cap = int(engine.kv_pool_stats()
                              ['pool_token_capacity'])
                self._max_queue_tokens = max(
                    2 * cap, 4 * engine.max_batch * 256)
        logger.info(
            f'scheduler bound: max_queue_tokens={self._max_queue_tokens} '
            f'default_tier={self.default_tier} '
            f'latency_admit_frac={self.latency_admit_frac} '
            f'mesh_speedup={self._mesh_speedup}')

    @property
    def mesh_speedup(self) -> int:
        """tp x dp of the bound engine's mesh (1 until bound)."""
        return self._mesh_speedup

    @property
    def max_queue_tokens(self) -> int:
        return self._max_queue_tokens or 0

    # ------------------------------------------------------------- submit
    def resolve_tier(self, tier: Optional[str]) -> str:
        if tier in (None, ''):
            return self.default_tier
        if tier not in TIERS:
            raise ValueError(f'unknown SLO tier {tier!r}; supported: '
                             f'{", ".join(TIERS)}')
        return tier

    def submit(self, prompt: List[int], *, max_new_tokens: int,
               tier: Optional[str] = None,
               trace_ctx: Optional[Dict[str, Any]] = None,
               **sampling: Any) -> ScheduledRequest:
        """Admission-controlled submit from a handler thread. Returns
        the live :class:`ScheduledRequest` (its outbox streams tokens)
        or raises :class:`ShedError` (HTTP 429) when the tier's queue
        bound would be exceeded, with ``retry_after_s`` from live queue
        telemetry. Raises ``RuntimeError`` after an engine failure."""
        tier = self.resolve_tier(tier)
        if self._failed is not None:
            raise RuntimeError(f'engine failed: {self._failed}')
        work = len(prompt) + max_new_tokens
        with self._q_lock:
            if self._draining:
                # Graceful drain: already-accepted work runs to
                # completion, but nothing new is admitted — the client
                # retries (through the LB: on another replica).
                retry = max(_RETRY_AFTER_MIN_S,
                            min(5, _RETRY_AFTER_MAX_S))
                self._c_shed[(tier, 'draining')].inc()
                raise ShedError(
                    tier, 'draining', retry,
                    'replica is draining (graceful scale-down); '
                    f'retry on another replica in ~{retry}s')
            bound = self._max_queue_tokens
            if bound and self._queued_tokens[tier] + work > bound:
                retry = self._retry_after_locked(tier, work)
                self._c_shed[(tier, 'queue_full')].inc()
                raise ShedError(
                    tier, 'queue_full', retry,
                    f'{tier}-tier queue full '
                    f'({self._queued_tokens[tier]} + {work} > {bound} '
                    f'queued work tokens); retry in ~{retry}s')
            self._seq += 1
            sr = ScheduledRequest(tier, list(prompt), max_new_tokens,
                                  sampling, self._seq,
                                  trace_ctx=trace_ctx)
            self._queues[tier].append(sr)
            self._queued_tokens[tier] += work
        self._wake()
        return sr

    # -------------------------------------------------------- handoff
    def adopt(self, request_id: int, *, tier: Optional[str],
              prompt: List[int], output: List[int],
              max_new_tokens: int,
              trace_ctx: Optional[Dict[str, Any]] = None
              ) -> ScheduledRequest:
        """Register a KV-handoff continuation that was seated directly
        in the engine (``ingest_kv_snapshot``) — admission already
        happened on the prefill worker, so the request bypasses the
        tier queues; this wires up the outbox/event routing and the
        bookkeeping the engine loop relies on. The caller holds the
        engine lock across ingest+adopt so ``fail_all`` cannot miss
        the window between them."""
        tier = self.resolve_tier(tier)
        if self._failed is not None:
            raise RuntimeError(f'engine failed: {self._failed}')
        with self._q_lock:
            self._seq += 1
            sr = ScheduledRequest(tier, list(prompt) + list(output),
                                  max_new_tokens, {}, self._seq,
                                  trace_ctx=trace_ctx)
            sr.request_id = request_id
            sr.admit_time = sr.submit_time
            sr.first_token_time = sr.submit_time
            sr.handoff = True
            self._by_rid[request_id] = sr
        self._c_admitted[tier].inc()
        return sr

    # ------------------------------------------------------- retry-after
    def _retry_after_locked(self, tier: str, work: int) -> int:
        """Retry-After (whole seconds) for a request of ``work`` tokens
        arriving now: the work AHEAD of it (engine in-flight remainder
        + every queued token of tiers at or above this one) over the
        measured token throughput. Falls back to a capacity guess
        before the meter warms up. Clamped to [1, 120] — precision
        past that is noise to a client backoff loop."""
        ahead = sum(self._queued_tokens[t] for t in TIERS
                    if TIERS.index(t) <= TIERS.index(tier))
        eng = self._engine
        if eng is not None and hasattr(eng, 'remaining_work_tokens'):
            ahead += eng.remaining_work_tokens()
        rate = self._rate.rate()
        if rate <= 0.0:
            # Cold meter: assume the engine streams ~8 tok/s/slot (a
            # deliberately conservative interactive-decode floor),
            # scaled by the mesh's tp x dp — a sharded replica chews
            # the same work tokens proportionally faster, and quoting
            # a single-chip Retry-After off a tp=4 mesh overstates the
            # backoff 4x right when the replica is freshest.
            eng_batch = eng.max_batch if eng is not None else 8
            rate = 8.0 * max(1, eng_batch) * self._mesh_speedup
        return int(min(_RETRY_AFTER_MAX_S,
                       max(_RETRY_AFTER_MIN_S,
                           math.ceil((ahead + work) / rate))))

    def retry_after_s(self, tier: str, work: int = 0) -> int:
        with self._q_lock:
            return self._retry_after_locked(tier, work)

    # ---------------------------------------------------------- admission
    def _pick_tier_locked(self) -> Optional[str]:
        """Deficit-weighted tier choice: the latency tier owns
        ``latency_admit_frac`` of cumulative admitted work while both
        tiers wait; an idle tier's share flows to the other."""
        waiting = [t for t in TIERS if self._queues[t]]
        if not waiting:
            return None
        if len(waiting) == 1:
            return waiting[0]
        total = sum(self._admitted_tokens.values())
        if total == 0:
            return TIERS[0]
        lat_share = self._admitted_tokens[TIERS[0]] / total
        return (TIERS[0] if lat_share < self.latency_admit_frac
                else TIERS[1])

    def _pop_srw_locked(self, tier: str) -> ScheduledRequest:
        """Shortest-remaining-work pop, FIFO on ties (``seq`` is the
        arrival stamp). Callers hold ``_q_lock`` (the ``_locked``
        suffix contract); the checker cannot see the cross-method
        lock context."""
        q = self._queues[tier]
        best = min(range(len(q)),
                   key=lambda i: (q[i].work_tokens, q[i].seq))
        sr = q.pop(best)
        self._queued_tokens[tier] -= sr.work_tokens   # graftcheck: disable=GC101
        return sr

    def fill_engine(self, engine: Any) -> bool:
        """Top the engine up from the tier queues — called by the
        engine loop each step WITH the engine lock held, BEFORE
        ``engine.step()``. Admits at most as many requests as the
        engine has free slots (the engine's own queue stays empty, so
        ordering stays HERE), picking the tier by budget deficit and
        the request by shortest remaining work. Each admission carries
        the tier's engine priority hint, so engine-internal requeues
        (paged preemption) keep tier ordering too."""
        admitted = False
        # Requests whose adapter could not get a bank slot THIS cycle:
        # held out of the queues until the loop exits (SRW would
        # deterministically re-pick them), then requeued — so a
        # bank-full adapter never head-of-line-blocks base-model or
        # other-adapter admissions.
        bank_deferred: List[Tuple[str, ScheduledRequest]] = []
        while True:
            free = (engine.max_batch - engine.num_active
                    - engine.queue_depth)
            if free <= 0:
                break
            with self._q_lock:
                tier = self._pick_tier_locked()
                if tier is None:
                    break
                sr = self._pop_srw_locked(tier)
                self._admitted_tokens[tier] += sr.work_tokens
            try:
                rid = engine.add_request(
                    sr.prompt, max_new_tokens=sr.max_new_tokens,
                    priority=TIERS.index(tier), **sr.sampling)
            except ValueError as e:
                # Invalid for THIS engine (e.g. prompt outgrew max_seq
                # between front-end validation and admission, or an
                # unknown adapter name): fail the one request, keep
                # admitting.
                sr.outbox.fail(f'rejected: {e}')
                continue
            except RuntimeError as e:
                from skypilot_tpu.inference.adapters import \
                    AdapterBankFullError
                if not isinstance(e, AdapterBankFullError):
                    raise
                # Every adapter-bank slot is pinned by a live request:
                # a RETRYABLE capacity condition, not a client error.
                # Defer just THIS request and keep admitting others;
                # pins release as requests finish, so it self-recovers
                # next cycle.
                with self._q_lock:
                    self._admitted_tokens[tier] -= sr.work_tokens
                bank_deferred.append((tier, sr))
                continue
            sr.request_id = rid
            sr.admit_time = clock.now()
            if hasattr(engine, 'adopt_trace_context'):
                # The engine trace joins the wire-supplied fleet trace
                # (or keeps its minted 128-bit id); the EFFECTIVE id is
                # written back so every downstream hop — KV handoff,
                # gang op-log, migration legs — carries the same id.
                ctx = sr.trace_ctx or {}
                # The wait in the tier queues, submit -> add_request,
                # becomes the trace's ``sched_wait`` span: the engine's
                # own ``queue`` span opens only at add_request.
                tid = engine.adopt_trace_context(
                    rid, trace_id=ctx.get('trace_id'),
                    parent_span=ctx.get('parent_span'),
                    submitted_at=sr.submit_time)
                if tid is not None:
                    sr.trace_ctx = dict(ctx, trace_id=tid)
            if self.on_admit is not None:
                self.on_admit(rid, sr)
            with self._q_lock:
                self._by_rid[rid] = sr
            self._c_admitted[tier].inc()
            self._h_queue_wait[tier].observe(
                (sr.admit_time - sr.submit_time) * 1e3)
            admitted = True
        if bank_deferred:
            with self._q_lock:
                for d_tier, d_sr in bank_deferred:
                    self._queues[d_tier].append(d_sr)
                    self._queued_tokens[d_tier] += d_sr.work_tokens
        return admitted

    @property
    def backlog(self) -> int:
        with self._q_lock:
            return sum(len(q) for q in self._queues.values())

    # -------------------------------------------------------------- events
    def on_events(self, engine: Any,
                  events: List[Tuple[int, int, bool]]) -> None:
        """Route one step's ``(request_id, token, finished)`` events to
        the owning outboxes — called by the engine loop WITHOUT the
        engine lock (outbox puts are lock-free; only the finished-
        request pop re-takes it briefly). A finished request's
        ``result`` is popped BEFORE its final token is put: the moment
        a consumer sees ``finished`` the ``Request`` object is already
        there (the streaming handlers read ``sr.result`` for the
        finish_reason on that very event)."""
        n_tokens = 0
        for rid, token, finished in events:
            with self._q_lock:
                sr = self._by_rid.get(rid)
            if sr is None:
                continue
            if token is not None and token < 0:
                # NaN blast-radius isolation: the engine evicted this
                # request on the device-side non-finite sentinel. Fail
                # exactly this outbox with a RETRYABLE error (the
                # stream handler emits `retryable: true`, so the LB's
                # in-flight recovery resubmits prompt + tokens-so-far
                # to a surviving replica); co-batched requests in the
                # same event batch continue untouched.
                with self._q_lock:
                    self._by_rid.pop(rid, None)
                telemetry.get_registry().counter(
                    'skytpu_gray_failures_total',
                    'Gray failures detected by the data-plane '
                    'defense layer', kind='nan_logits').inc()
                sr.outbox.fail(
                    'request evicted: non-finite logits (NaN/Inf) '
                    'detected on device; retry on another replica')
                continue
            n_tokens += 1
            if sr.first_token_time is None:
                sr.first_token_time = clock.now()
            if finished:
                with self._engine_lock:
                    sr.result = engine.pop_finished(rid)
                with self._q_lock:
                    self._by_rid.pop(rid, None)
            sr.outbox.put(token, finished)
            if finished:
                self._record_finished(sr)
        if n_tokens:
            self._rate.add(n_tokens)
        self._refresh_gauges()

    def _record_finished(self, sr: ScheduledRequest) -> None:
        req = sr.result
        if req is None or sr.handoff:
            # Handoff continuations: TTFT belongs to the prefill
            # worker that served the first token, not this replica.
            return
        if req.ttft_ms is not None:
            self._h_ttft[sr.tier].observe(req.ttft_ms)
        if (req.first_token_time is not None
                and req.finish_time is not None
                and len(req.output) > 1):
            self._h_tpot[sr.tier].observe(
                (req.finish_time - req.first_token_time) * 1e3
                / (len(req.output) - 1))

    def _refresh_gauges(self) -> None:
        with self._q_lock:
            tokens = dict(self._queued_tokens)
            depth = {t: len(self._queues[t]) for t in TIERS}
            admitted = dict(self._admitted_tokens)
        total = sum(admitted.values())
        for t in TIERS:
            self._g_queue_tokens[t].set(tokens[t])
            self._g_queue_depth[t].set(depth[t])
            self._g_budget_share[t].set(
                admitted[t] / total if total else 0.0)

    # -------------------------------------------------------------- cancel
    def cancel(self, sr: ScheduledRequest) -> bool:
        """Abort a live request (client disconnect): drop it from the
        tier queue if still waiting, or cancel it engine-side so the
        slot stops generating tokens nobody reads. Returns True when
        the request was still live (not finished)."""
        sr.cancelled = True
        with self._q_lock:
            q = self._queues[sr.tier]
            if sr in q:
                q.remove(sr)
                self._queued_tokens[sr.tier] -= sr.work_tokens
                sr.outbox.fail('cancelled')
                return True
        if sr.request_id is None or sr.result is not None:
            return False
        with self._engine_lock:
            engine = self._engine
            if engine is None:
                return False
            req = engine.pop_finished(sr.request_id)
            cancelled = req is None and engine.cancel(sr.request_id)
            if cancelled and self.on_cancel is not None:
                # Under the engine lock on purpose: the gang op log's
                # order must match engine execution order exactly (a
                # cancel logged after a step the leader ran post-cancel
                # would desync follower KV state).
                self.on_cancel(sr.request_id)
        with self._q_lock:
            self._by_rid.pop(sr.request_id, None)
        if req is not None:
            sr.result = req
            self._record_finished(sr)
            return False
        return cancelled

    # ------------------------------------------------------------- failure
    def fail_all(self, error: str) -> None:
        """Engine death: every queued and in-flight request is failed
        (queued ones count as shed reason ``engine_error`` — their
        admission never happened), and future submits raise."""
        with self._q_lock:
            self._failed = error
            stranded = [sr for q in self._queues.values() for sr in q]
            for t in TIERS:
                self._queues[t].clear()
                self._queued_tokens[t] = 0
            inflight = list(self._by_rid.values())
            self._by_rid.clear()
        for sr in stranded:
            self._c_shed[(sr.tier, 'engine_error')].inc()
            sr.outbox.fail(error)
        for sr in inflight:
            sr.outbox.fail(error)
        self._refresh_gauges()

    # -------------------------------------------------------------- drain
    def begin_drain(self) -> None:
        """Enter graceful drain: new submits shed with a retryable 503
        (reason ``draining``); queued and in-flight requests keep
        running to completion (``fill_engine`` still admits the
        backlog). Idempotent."""
        with self._q_lock:
            self._draining = True

    @property
    def draining(self) -> bool:
        with self._q_lock:
            return self._draining

    @property
    def inflight(self) -> int:
        """Requests the scheduler still owns: queued + engine-side."""
        with self._q_lock:
            return (sum(len(q) for q in self._queues.values())
                    + len(self._by_rid))

    @property
    def drained(self) -> bool:
        """True once every accepted request has finished (queues empty
        AND nothing in flight engine-side)."""
        return self.inflight == 0

    # ------------------------------------------------------------- surface
    def json_stats(self) -> Dict[str, Any]:
        """The stable-schema per-tier block of ``/metrics?format=json``:
        every key ALWAYS present and numeric (zeros when idle), so
        scrapers see one schema from the first request."""
        self._refresh_gauges()
        with self._q_lock:
            tokens = dict(self._queued_tokens)
            depth = {t: len(self._queues[t]) for t in TIERS}
            admitted = dict(self._admitted_tokens)
        total = sum(admitted.values())
        tiers: Dict[str, Any] = {}
        for t in TIERS:
            shed = sum(int(self._c_shed[(t, r)].value)
                       for r in SHED_REASONS)
            tiers[t] = {
                'queue_depth': depth[t],
                'queue_tokens': tokens[t],
                'admitted': int(self._c_admitted[t].value),
                'admitted_tokens': admitted[t],
                'admit_share': round(admitted[t] / total, 4) if total
                else 0.0,
                'shed_total': shed,
                'ttft_ms_median': round(
                    self._h_ttft[t].quantile(0.5), 1),
                'ttft_ms_p90': round(self._h_ttft[t].quantile(0.9), 1),
                'tpot_ms_median': round(
                    self._h_tpot[t].quantile(0.5), 2),
                'queue_wait_ms_median': round(
                    self._h_queue_wait[t].quantile(0.5), 1),
                'queue_wait_ms_p90': round(
                    self._h_queue_wait[t].quantile(0.9), 1),
            }
        return {
            'default_tier': self.default_tier,
            'max_queue_tokens': self.max_queue_tokens,
            'latency_admit_frac': self.latency_admit_frac,
            'mesh_speedup': self._mesh_speedup,
            'tiers': tiers,
        }
