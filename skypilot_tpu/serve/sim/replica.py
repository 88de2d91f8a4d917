"""Simulated replicas: a fluid queueing model of one model server,
with a service curve of serving-path readings, speaking exactly the
HTTP contract the control plane drives — so the REAL replica manager probes, drains, checkpoints and warms them
without knowing they are synthetic.

Service model (deliberately fluid, O(1) per event): a replica with
``slots`` concurrent decode slots processes ``slots`` service-seconds
of work per virtual second. One request of ``p`` prompt and ``g``
generated tokens costs ``svc = ttft_base + p/prefill_rate + g*tpot``
single-slot seconds; a batch of ``n`` advances the replica's
``busy_until`` horizon by ``n*svc/slots``, and the queue wait a new
arrival sees is ``max(0, busy_until - now)``. TTFT = queue wait +
prefill part (minus the warm-prefix discount when the replica was
warmed from a checkpoint — the PR-10 recovery contract, visible in
the sim's recovery-TTFT numbers). Waits beyond ``max_queue_wait_s``
model the SLO scheduler's token-bounded admission: the request is
shed with a retryable 429, exactly what the live scheduler does.

Calibration: :meth:`ServiceCurve.from_bench` scans record texts the
caller hands it (newest first) for the serving-path numbers —
``tpot_ms_median``, the prefix-cache hit/miss TTFT medians, the engine
``batch`` — and falls back per field to the default anchors below: a
CPU-era reading, not measured on the chip (the simulator's scenarios
are judged on counts and ratios, not on these absolute times).
Provision-latency distributions live in the scenario (they are a
property of the cloud, not the engine).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import re
from typing import Any, Callable, Dict, List, Optional

from skypilot_tpu import telemetry
from skypilot_tpu.telemetry import fleet as fleet_lib
from skypilot_tpu.telemetry import registry as registry_lib
from skypilot_tpu.telemetry import tracing

# The simulator's default anchors (round 5's readings, from before the
# chip was measured by the driver: not a statement about the v5e): tpot
# 23.22 ms, TTFT hit/miss 254.8/350.5 ms, batch 48, ~220-token prompts.
_FALLBACK = {'tpot_ms': 23.22, 'ttft_hit_ms': 254.8,
             'ttft_miss_ms': 350.5, 'batch': 48, 'avg_prompt': 220.0}

_NUM = r'([0-9]+(?:\.[0-9]+)?)'


@dataclasses.dataclass(frozen=True)
class ServiceCurve:
    """Per-replica service parameters (single SLO-tier-independent
    engine curve; tiers differ in SLO targets and admission, not in
    silicon speed)."""
    ttft_base_s: float          # fixed prefill overhead, cold prefix
    warm_ttft_base_s: float     # ... with a warm prefix cache
    prefill_tok_per_s: float    # prompt-token throughput
    tpot_s: float               # seconds per generated token (1 slot)
    slots: int                  # concurrent decode slots
    max_queue_wait_s: float     # admission bound (models 429 shedding)
    kv_pool_tokens: int         # advertised KV capacity (LB handoffs)

    def service_s(self, prompt_tokens: float, gen_tokens: float,
                  warm: bool = False) -> float:
        base = self.warm_ttft_base_s if warm else self.ttft_base_s
        return (base + prompt_tokens / self.prefill_tok_per_s
                + gen_tokens * self.tpot_s)

    def prefill_s(self, prompt_tokens: float, warm: bool) -> float:
        base = self.warm_ttft_base_s if warm else self.ttft_base_s
        return base + prompt_tokens / self.prefill_tok_per_s

    @classmethod
    def from_bench(cls, bench_texts: Optional[List[str]] = None,
                   max_queue_wait_s: float = 8.0) -> 'ServiceCurve':
        """Calibrate from record texts (newest first; the caller reads
        any files — this module does no I/O so it stays pure and
        GC117-clean). Falls back to the default anchors per-field."""
        vals = dict(_FALLBACK)
        found: Dict[str, float] = {}
        for text in bench_texts or []:
            for key, pat in (
                    ('tpot_ms', rf'"tpot_ms_median":\s*{_NUM}'),
                    ('ttft_hit_ms', rf'"ttft_ms_hit_median":\s*{_NUM}'),
                    ('ttft_miss_ms',
                     rf'"ttft_ms_miss_median":\s*{_NUM}'),
                    ('batch', rf'"batch":\s*{_NUM}'),
                    ('avg_prompt', rf'"avg_prompt":\s*{_NUM}')):
                if key in found:
                    continue
                m = re.search(pat, text)
                if m:
                    found[key] = float(m.group(1))
            if len(found) == 5:
                break
        vals.update(found)
        # TTFT decomposition: the miss median is base + avg_prompt /
        # prefill_rate; the hit median skips the shared-prefix
        # recompute — treat it as the warm base and attribute the
        # hit->miss delta to prompt streaming.
        warm_base = vals['ttft_hit_ms'] / 1e3
        miss = vals['ttft_miss_ms'] / 1e3
        prefill_rate = max(500.0,
                           vals['avg_prompt'] / max(1e-3,
                                                    miss - warm_base))
        slots = max(1, int(vals['batch']))
        return cls(ttft_base_s=miss - vals['avg_prompt'] / prefill_rate,
                   warm_ttft_base_s=warm_base,
                   prefill_tok_per_s=prefill_rate,
                   tpot_s=vals['tpot_ms'] / 1e3,
                   slots=slots,
                   max_queue_wait_s=max_queue_wait_s,
                   kv_pool_tokens=slots * 424)  # ~anchor tokens/slot


def canary_response_tokens(prompt: List[int], n: int) -> List[int]:
    """The deterministic greedy 'generation' every HEALTHY simulated
    replica answers a canary prompt with — same prompt, same tokens,
    fleet-wide (standing in for greedy decode on identical weights).
    A byzantine replica perturbs these (silent data corruption)."""
    seed = sum(int(t) * (i + 1) for i, t in enumerate(prompt))
    return [(seed * 31 + i * 7 + 3) % 997 for i in range(max(1, n))]


class SimHTTPError(RuntimeError):
    """A simulated HTTP failure (dead replica / 4xx-5xx) — the sim
    env raises it where urllib would raise, so the manager's error
    handling runs the same branches live and simulated."""

    def __init__(self, code: int, message: str):
        super().__init__(f'HTTP {code}: {message}')
        self.code = code


@dataclasses.dataclass
class SimJob:
    """One dispatched batch (``count`` identical requests riding one
    event — the fluid model's unit of work)."""
    job_id: int
    count: int
    prompt_tokens: float
    gen_tokens: float
    tier: str
    submit_t: float
    ttft_s: float               # per-request TTFT (queue wait + prefill)
    finish_t: float
    wait_s: float = 0.0                   # queue-wait part of the TTFT
    # 128-bit fleet trace id, minted at first admission and preserved
    # across migration legs — the controller assembles all legs of a
    # migrated job under ONE trace.
    trace_id: Optional[str] = None
    migrated_from: Optional[str] = None   # url of the replica that died
    failed_at: Optional[float] = None     # when its first replica died
    cancelled: bool = False
    lb_idx: int = 0                       # LB that dispatched it
    session: Optional[Dict[str, Any]] = None   # multi-turn identity


class SimReplica:
    """One synthetic model server. Owns only local state; the fleet
    wires completion scheduling and death notification."""

    # Page grid the simulated engine hashes prefix chains at, and the
    # heat-store bound — both mirror the live paged engine (64-token
    # pages, ``_PREFIX_HEAT_MAX = 64`` hottest chains).
    PAGE = 64
    PREFIX_STORE_CAP = 64
    DIGEST_MAX_ENTRIES = 16

    def __init__(self, cluster_name: str, url: str, curve: ServiceCurve,
                 now_fn: Callable[[], float], *,
                 role: str = 'colocated', zone: str = 'z0',
                 is_spot: bool = False, gang_id: Optional[str] = None,
                 gang_rank: int = 0, gang_world: int = 1,
                 tp: int = 1, dp: int = 1,
                 never_drain: bool = False):
        self.cluster_name = cluster_name
        self.url = url
        self.curve = curve
        self._now = now_fn
        self.role = role
        self.zone = zone
        self.is_spot = is_spot
        self.gang_id = gang_id
        self.gang_rank = gang_rank
        self.gang_world = gang_world
        self.tp = tp
        self.dp = dp
        self.alive = True
        self.draining = False
        self.drain_started_t: Optional[float] = None
        self._drain_observed = False
        # Scenario knob: a straggler that acks /drain but never
        # reports drained — the deadline-failover path's test double.
        self.never_drain = never_drain
        self.warm = False                  # warmed from a checkpoint
        self.slowdown = 1.0                # straggler fault multiplier
        # Gray-failure fault switches (round 13):
        # wedged: the engine loop is stuck — the replica ACCEPTS work
        # that never finishes and its /readiness reports degraded (the
        # probe escalation must replace it; in-flight jobs migrate at
        # teardown). byzantine: silently corrupted — serves normally
        # but answers the manager's canary prompt WRONG (the
        # quarantine path must catch it).
        self.wedged = False
        self.byzantine = False
        self.busy_until = 0.0
        self.inflight: Dict[int, SimJob] = {}
        self._next_job = 1
        # Hot-prefix chain store (hash-hex -> [covered_len, hits]),
        # LRU-bounded like the live engine's heat tracker — session
        # working sets beyond the cap thrash out, which is exactly the
        # capacity effect affinity routing is supposed to dodge.
        self._prefix_store: 'collections.OrderedDict[str, List[int]]' = (
            collections.OrderedDict())
        # Fleet-plane telemetry (round 19): each simulated server owns
        # a PRIVATE registry + trace buffer — never the process-global
        # one, which thousands of sim replicas would share — scraped
        # by the REAL replica manager over /telemetry/summary exactly
        # like a live model server, so the controller-side aggregation
        # runs identical code on the virtual clock.
        self._reg = registry_lib.MetricsRegistry()
        self._trace_buf = tracing.TraceBuffer()
        # SimWorld.request strips query strings, so the scrape's
        # ``since`` cursor cannot reach us; a replica-side shipped
        # cursor gives the same at-most-once delivery (exactly one
        # controller scrapes a replica).
        self._trace_shipped = 0

    # ------------------------------------------------------ prefix cache
    def note_prefix(self, chain_hash: str, chain_len: int) -> None:
        """Record that this replica now holds a KV chain covering
        ``chain_len`` prompt tokens (computed locally or warmed from a
        migration blob); LRU-evicts beyond the heat-store cap."""
        rec = self._prefix_store.get(chain_hash)
        if rec is not None:
            rec[0] = max(rec[0], int(chain_len))
            rec[1] += 1
            self._prefix_store.move_to_end(chain_hash)
            return
        while len(self._prefix_store) >= self.PREFIX_STORE_CAP:
            self._prefix_store.popitem(last=False)
        self._prefix_store[chain_hash] = [int(chain_len), 1]

    def match_prefix(self, chain_hashes: List[str]) -> int:
        """Longest resident chain: ``chain_hashes[k-1]`` is the hash of
        the request's first ``k`` pages; returns the covered page count
        (0 = fully cold)."""
        for k in range(len(chain_hashes), 0, -1):
            rec = self._prefix_store.get(chain_hashes[k - 1])
            if rec is not None:
                rec[1] += 1
                self._prefix_store.move_to_end(chain_hashes[k - 1])
                return k
        return 0

    def prefix_digest(self) -> Dict[str, Any]:
        """The ``prefix_digest`` block a live model server publishes on
        ``/metrics?format=json``: hottest chains, bounded, determinis-
        tically ordered by (-hits, hash)."""
        by_heat = sorted(self._prefix_store.items(),
                         key=lambda kv: (-kv[1][1], kv[0]))
        return {'page': self.PAGE,
                'entries': [{'hash': h, 'len': rec[0], 'hits': rec[1]}
                            for h, rec
                            in by_heat[:self.DIGEST_MAX_ENTRIES]]}

    # ----------------------------------------------------------- service
    def enqueue(self, now: float, count: int, prompt_tokens: float,
                gen_tokens: float, tier: str,
                warm_tokens: float = 0.0) -> Optional[SimJob]:
        """Admit a batch; returns the job (with its completion time for
        the fleet to schedule) or None when admission sheds it (queue
        wait beyond the scheduler bound — the 429 path).
        ``warm_tokens`` prompt tokens are already resident in this
        replica's KV pages (a prefix-affinity hit or a migrated chain):
        they skip prefill entirely and the warm TTFT base applies —
        the discount the affinity policy's hit-rate numbers measure."""
        if not self.alive:
            raise SimHTTPError(502, 'replica dead')
        if self.draining:
            raise SimHTTPError(503, 'draining')
        if self.wedged:
            # The gray part of a wedged replica: it still ACCEPTS the
            # work (HTTP alive, queue open) — the job just never
            # finishes. It migrates when the probe escalation finally
            # tears the replica down.
            job = SimJob(job_id=self._next_job, count=count,
                         prompt_tokens=prompt_tokens,
                         gen_tokens=gen_tokens, tier=tier,
                         submit_t=now, ttft_s=float('inf'),
                         finish_t=now + 1e12)
            self._next_job += 1
            self.inflight[job.job_id] = job
            # Admitted (the gray part: the queue IS open) but no
            # latency observation — the request never finishes.
            self._reg.counter(fleet_lib.ADMIT_METRIC,
                              'Requests admitted by the scheduler',
                              tier=tier).inc(count)
            return job
        cold_tokens = max(0.0, prompt_tokens - max(0.0, warm_tokens))
        warm = self.warm or warm_tokens > 0
        svc = self.curve.service_s(cold_tokens, gen_tokens,
                                   warm) * self.slowdown
        wait = max(0.0, self.busy_until - now)
        if wait > self.curve.max_queue_wait_s:
            self._reg.counter(fleet_lib.SHED_METRIC,
                              'Requests shed at admission',
                              tier=tier, reason='queue_wait').inc(count)
            return None
        self.busy_until = (max(now, self.busy_until)
                           + count * svc / self.curve.slots)
        ttft = wait + self.curve.prefill_s(cold_tokens,
                                           warm) * self.slowdown
        job = SimJob(job_id=self._next_job, count=count,
                     prompt_tokens=prompt_tokens,
                     gen_tokens=gen_tokens, tier=tier, submit_t=now,
                     ttft_s=ttft, finish_t=now + wait + svc,
                     wait_s=wait,
                     trace_id=self._mint_trace_id(now))
        self._next_job += 1
        self.inflight[job.job_id] = job
        self._observe_admit(tier, count, ttft)
        return job

    def _mint_trace_id(self, now: float) -> str:
        """Deterministic 128-bit trace id: same seed, same admissions,
        same ids — the sim counterpart of the LB's seeded-RNG mint."""
        raw = f'{self.url}|{self._next_job}|{now:.6f}'.encode()
        return hashlib.md5(raw).hexdigest()

    def _observe_admit(self, tier: str, count: int,
                       ttft_s: float) -> None:
        """Record one admitted batch in the replica's private registry
        using the exact series names the fleet SLO evaluator reads —
        the sim and the live scheduler must agree on the schema."""
        self._reg.counter(fleet_lib.ADMIT_METRIC,
                          'Requests admitted by the scheduler',
                          tier=tier).inc(count)
        ttft_h = self._reg.histogram(fleet_lib.TTFT_METRIC,
                                     'Time to first token (ms)',
                                     tier=tier)
        tpot_h = self._reg.histogram(fleet_lib.TPOT_METRIC,
                                     'Time per output token (ms)',
                                     tier=tier)
        tpot_ms = self.curve.tpot_s * self.slowdown * 1e3
        for _ in range(max(1, int(count))):
            ttft_h.observe(ttft_s * 1e3)
            tpot_h.observe(tpot_ms)

    def complete(self, job: SimJob) -> None:
        self.inflight.pop(job.job_id, None)
        self._record_trace(job)

    def _record_trace(self, job: SimJob) -> None:
        """One completed-trace leg on the VIRTUAL clock: queue-wait /
        prefill / decode spans, shipped to the controller on the next
        ``/telemetry/summary`` scrape. A migrated job keeps its trace
        id, so the controller assembles the legs from every replica
        that served it under one trace."""
        trace = tracing.RequestTrace(job.job_id,
                                     trace_id=job.trace_id)
        # Re-anchor the real-clock stamps the constructor took onto
        # virtual time: span offsets become seconds-since-submit.
        trace.t0 = 0.0
        trace.wall0 = job.submit_t
        prefill_end = min(job.ttft_s, job.finish_t - job.submit_t)
        for name, t0, t1 in (
                ('queue_wait', 0.0, job.wait_s),
                ('prefill', job.wait_s, prefill_end),
                ('decode', prefill_end, job.finish_t - job.submit_t)):
            span = tracing.Span(name, t0, job.submit_t + t0)
            span.t1 = max(t0, t1)
            trace.spans.append(span)
        trace.meta.update(tier=job.tier, count=job.count,
                          replica=self.cluster_name)
        if job.migrated_from is not None:
            trace.meta.update(migrated_from=job.migrated_from,
                              cause='migration')
        trace.done = True
        self._trace_buf.add(trace)

    def kill(self) -> List[SimJob]:
        """Hard death: returns the in-flight jobs the LB must migrate;
        the replica stops answering anything."""
        self.alive = False
        jobs = [j for j in self.inflight.values() if not j.cancelled]
        for j in jobs:
            j.cancelled = True
        self.inflight.clear()
        return jobs

    def queue_tokens_total(self, now: float) -> int:
        """The work-token estimate a live scheduler would publish:
        backlog seconds converted back to decode tokens."""
        backlog_s = max(0.0, self.busy_until - now)
        return int(backlog_s * self.curve.slots / self.curve.tpot_s)

    def kv_pool_tokens_free(self) -> int:
        used = sum(j.count * (j.prompt_tokens + j.gen_tokens)
                   for j in self.inflight.values())
        return max(0, int(self.curve.kv_pool_tokens - used))

    # -------------------------------------------------------------- HTTP
    def handle(self, path: str, payload: Optional[Dict[str, Any]],
               data: Optional[bytes]) -> Any:
        """The model-server contract surface the control plane drives
        (readiness, drain, checkpoint, warmup, metrics JSON)."""
        if not self.alive:
            raise SimHTTPError(502, 'connection refused')
        now = self._now()
        if path == '/readiness':
            if self.wedged:
                # The live model server's wedge watchdog flips
                # readiness to a degraded 503; the probe escalation
                # (NOT_READY -> FAILED_PROBE) then replaces it.
                raise SimHTTPError(503, 'degraded: wedged engine step')
            return {'ready': not self.draining, 'draining': self.draining}
        if path == '/generate':
            # The canary surface: greedy tokens deterministic in the
            # prompt, identical on every healthy replica; a byzantine
            # replica answers perturbed tokens (silent corruption the
            # manager's digest compare must catch).
            prompt = [int(t) for t in (payload or {}).get('prompt', [])]
            n = int((payload or {}).get('max_new_tokens', 8))
            toks = canary_response_tokens(prompt, n)
            if self.byzantine:
                toks = [(t + 1) % 997 for t in toks]
            return {'tokens': toks, 'request_id': 0}
        if path == '/drain':
            if payload is not None or data is not None:   # POST: begin
                if not self.draining:
                    self.draining = True
                    self.drain_started_t = now
                return {'draining': True, 'inflight': len(self.inflight)}
            drained = (self.draining and not self.never_drain
                       and self.busy_until <= now
                       and not self.inflight)
            if drained and not self._drain_observed:
                # The live model server's monitor observes the drain
                # histogram when the scheduler reports drained; the
                # sim replica honors the same telemetry contract.
                self._drain_observed = True
                telemetry.get_registry().histogram(
                    'skytpu_replica_drain_seconds',
                    'Graceful-drain duration: drain start to idle (s)',
                    buckets=telemetry.registry.DEFAULT_SECONDS_BUCKETS,
                ).observe(max(0.0, now - (self.drain_started_t or now)))
            return {'draining': self.draining, 'drained': drained,
                    'inflight': len(self.inflight)}
        if path == '/checkpoint':
            blob = json.dumps({
                'format': 'SIMCKPT', 'source': self.url,
                'exported_t': now, 'warm': True,
                'hot_prefixes': 4,
            }).encode()
            return blob
        if path == '/kv/warmup':
            if not data:
                raise SimHTTPError(400, 'empty warmup body')
            try:
                blob = json.loads(data)
            except (ValueError, UnicodeDecodeError) as e:
                raise SimHTTPError(400, f'bad container: {e}') from e
            if blob.get('format') != 'SIMCKPT':
                raise SimHTTPError(400, 'unknown container format')
            self.warm = True
            return {'warmed_rows': int(blob.get('hot_prefixes', 0))
                    * 128, 'entries': int(blob.get('hot_prefixes', 0))}
        if path == '/telemetry/summary':
            # The fleet scrape surface (round 19): identical shape to
            # the live server's route; 'wall' is the virtual clock, so
            # the controller computes a zero skew offset per source.
            cursor, traces = self._trace_buf.summaries_since(
                self._trace_shipped)
            self._trace_shipped = cursor
            return {'clock': {'wall': now, 'monotonic': now},
                    'registry': self._reg.export_wire(),
                    'traces': traces, 'cursor': cursor}
        if path.startswith('/metrics'):
            return {
                'queue_tokens_total': self.queue_tokens_total(now),
                'kv_pool_tokens_free': self.kv_pool_tokens_free(),
                'mesh': {'tp': self.tp, 'dp': self.dp},
                'disagg': {'role': self.role},
                'prefix_digest': self.prefix_digest(),
            }
        if path == '/gang/status':
            # Adoption probe surface (round 15): a restarted manager
            # recovers gang identity from the live replica.
            if self.gang_id is None:
                raise SimHTTPError(404, 'not a gang member')
            return {'gang_id': self.gang_id, 'rank': self.gang_rank,
                    'world': self.gang_world}
        raise SimHTTPError(404, f'no route {path}')
