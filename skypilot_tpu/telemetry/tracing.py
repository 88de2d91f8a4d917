"""Per-request lifecycle tracing.

A :class:`RequestTrace` is minted when a request enters an engine
(``add_request``) and carried on the ``Request`` object through its
whole life: scheduler wait (stamped by the serve scheduler, which held
the request before the engine saw it) → queue-wait → prefill (one span
per chunk in chunked mode) → first-token lag (the async pipeline
between the last chunk's dispatch and the token's readback) → first
emit (the handler's flush of that token's SSE line) → decode →
speculative propose/verify rounds → finish or cancel. Spans are
HOST-DISPATCH-ALIGNED: a span covers the host-side time of the stage
(the device executes asynchronously behind the dispatch pipeline),
which is exactly the latency a client observes and what the "where did
this request's latency go" question needs. The first five are the
stages of the time to first token (:data:`TTFT_STAGES`): contiguous,
each stamped where it happens, so they add up to submit → first flush.

Completed traces land in a bounded ring buffer (:class:`TraceBuffer`,
default 256 — a long-lived replica keeps CURRENT traffic, memory
bounded) served by the model server at ``/debug/requests`` and
exportable as a chrome trace through the existing
``utils/timeline.py`` writer (:func:`export_chrome_trace`).

Engines only ever touch traces from their single engine thread, so
span mutation is unlocked; the buffer (crossed by HTTP handler
threads) is locked.
"""
from __future__ import annotations

import collections
import os
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

from skypilot_tpu.telemetry import clock

DEFAULT_BUFFER = int(os.environ.get('SKYTPU_TRACE_BUFFER', '256'))

# The time to first token, in the order a request passes through them.
TTFT_STAGES = ('sched_wait', 'queue', 'prefill', 'first_token_lag',
               'emit_first')

# ------------------------------------------------- cross-process trace ids
# The wire header every skytpu process propagates on outbound hops
# (LB -> replica /generate, prefill -> decode /kv/ingest, LB <-> LB
# idempotency pushes, migration/retry legs). Value:
# ``<trace_id>[;<parent_span>]`` — trace_id is 128-bit hex, parent_span
# names the span on the SENDING process this hop is causally under.
TRACE_HEADER = 'X-Skytpu-Trace'

_TRACE_ID_RE = re.compile(r'^[0-9a-f]{8,64}$')
_PARENT_RE = re.compile(r'^[\w.:/-]{1,128}$')


def mint_trace_id(rng: Optional[Any] = None) -> str:
    """A 128-bit hex trace id. Pass a seeded ``random.Random`` (the
    sim env's RNG stream) for deterministic ids; without one the id is
    drawn from ``os.urandom`` — pid-recycle-proof, unlike the old
    ``pid-seq`` locals that collided across replica restarts."""
    if rng is not None:
        return f'{rng.getrandbits(128):032x}'
    return os.urandom(16).hex()


def format_trace_header(trace_id: str,
                        parent_span: Optional[str] = None) -> str:
    """The ``X-Skytpu-Trace`` header value for one outbound hop."""
    if parent_span:
        return f'{trace_id};{parent_span}'
    return trace_id


def parse_trace_header(value: Optional[str]
                       ) -> Optional[Dict[str, Optional[str]]]:
    """Parse an incoming ``X-Skytpu-Trace`` value into
    ``{'trace_id', 'parent_span'}``; None for absent/garbage values
    (a malformed header must never break request handling — the
    receiver just mints a fresh local trace)."""
    if not value or not isinstance(value, str):
        return None
    trace_id, _, parent = value.strip().partition(';')
    trace_id = trace_id.strip().lower()
    if not _TRACE_ID_RE.match(trace_id):
        return None
    parent = parent.strip() or None
    if parent is not None and not _PARENT_RE.match(parent):
        parent = None
    return {'trace_id': trace_id, 'parent_span': parent}


class Span:
    __slots__ = ('name', 't0', 't1', 'wall0', 'meta')

    def __init__(self, name: str, t0: float, wall0: float,
                 meta: Optional[Dict[str, Any]] = None):
        self.name = name
        self.t0 = t0                # monotonic
        self.t1: Optional[float] = None
        self.wall0 = wall0          # wall clock (chrome-trace ts)
        self.meta = meta or {}

    @property
    def dur_ms(self) -> Optional[float]:
        if self.t1 is None:
            return None
        return (self.t1 - self.t0) * 1e3


class RequestTrace:
    """One request's span timeline. Engine-thread-only mutation."""

    def __init__(self, request_id: int,
                 trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None):
        self.request_id = request_id
        self.trace_id = trace_id or mint_trace_id()
        self.parent_span = parent_span
        self.t0 = clock.monotonic()
        self.wall0 = clock.now()
        self.spans: List[Span] = []
        self.done = False
        self.meta: Dict[str, Any] = {}

    def adopt_wire_context(self, trace_id: Optional[str] = None,
                           parent_span: Optional[str] = None) -> None:
        """Adopt a wire-supplied trace context (an upstream hop's
        ``X-Skytpu-Trace``): the request joins the fleet-wide trace
        instead of keeping its locally minted id."""
        if trace_id:
            self.trace_id = trace_id
        if parent_span:
            self.parent_span = parent_span

    # ------------------------------------------------------------- spans
    def begin(self, name: str, **meta: Any) -> Span:
        span = Span(name, clock.monotonic(), clock.now(), meta or None)
        self.spans.append(span)
        return span

    def end(self, name: str, at: Optional[float] = None) -> None:
        """Close the most recent still-open span named ``name``
        (no-op when none is open — re-admission paths may re-begin),
        now or at the monotonic time ``at``."""
        for span in reversed(self.spans):
            if span.name == name and span.t1 is None:
                span.t1 = clock.monotonic() if at is None else at
                return

    def last_end(self, name: str) -> Optional[float]:
        """Monotonic end of the most recent completed span ``name``."""
        for span in reversed(self.spans):
            if span.name == name and span.t1 is not None:
                return span.t1
        return None

    def prepend(self, name: str, wall_start: float, **meta: Any) -> None:
        """Record a stage that ENDED where this trace begins and began
        at the wall-clock time ``wall_start`` (the scheduler held the
        request before the engine minted the trace). The trace's
        origin moves back to it, so ``submitted_at`` is the request's
        real submission and every ``start_ms`` stays non-negative."""
        t1 = self.t0
        self.t0 -= max(0.0, self.wall0 - wall_start)
        self.wall0 = min(self.wall0, wall_start)
        span = Span(name, self.t0, self.wall0, meta or None)
        span.t1 = t1
        self.spans.insert(0, span)

    def add(self, name: str, t0: float, t1: float, **meta: Any) -> Span:
        """Record a pre-timed span (monotonic endpoints)."""
        span = Span(name, t0, clock.now() - (clock.monotonic() - t0),
                    meta or None)
        span.t1 = t1
        self.spans.append(span)
        return span

    def instant(self, name: str, **meta: Any) -> None:
        t = clock.monotonic()
        span = Span(name, t, clock.now(), meta or None)
        span.t1 = t
        self.spans.append(span)

    def finish(self, **meta: Any) -> None:
        """Close every open span and mark the trace complete."""
        t1 = clock.monotonic()
        for span in self.spans:
            if span.t1 is None:
                span.t1 = t1
        self.meta.update(meta)
        self.done = True

    # ----------------------------------------------------------- queries
    def span_ms(self, name: str) -> Optional[float]:
        """Duration of the FIRST completed span named ``name``."""
        for span in self.spans:
            if span.name == name and span.t1 is not None:
                return span.dur_ms
        return None

    def first_token_at(self) -> Optional[float]:
        """Monotonic time the first token was read back: the end of
        the first ``first_token_lag`` span (a re-admission after a
        preemption records a later one)."""
        return next((s.t1 for s in self.spans
                     if s.name == 'first_token_lag'), None)

    def ttft_stages(self) -> Dict[str, float]:
        """Milliseconds in each of :data:`TTFT_STAGES` this trace holds,
        up to the first token ({} when none surfaced). A request
        preempted before its first token queues and prefills twice: a
        stage sums every span of its name that began before the first
        token was read back."""
        t_first = self.first_token_at()
        if t_first is None:
            return {}
        out: Dict[str, float] = {}
        for span in self.spans:
            if span.name in TTFT_STAGES and span.t1 is not None and (
                    span.t0 < t_first or span.name == 'emit_first'):
                out[span.name] = out.get(span.name, 0.0) + span.dur_ms
        return out

    def to_dict(self) -> Dict[str, Any]:
        spans = []
        for span in self.spans:
            d: Dict[str, Any] = {
                'name': span.name,
                'start_ms': round((span.t0 - self.t0) * 1e3, 3),
            }
            if span.t1 is not None:
                d['dur_ms'] = round((span.t1 - span.t0) * 1e3, 3)
            if span.meta:
                d['meta'] = dict(span.meta)
            spans.append(d)
        d = {'trace_id': self.trace_id,
             'request_id': self.request_id,
             'submitted_at': self.wall0,
             'done': self.done,
             'meta': dict(self.meta),
             'spans': spans}
        if self.parent_span is not None:
            d['parent_span'] = self.parent_span
        return d


class TraceBuffer:
    """Bounded ring of COMPLETED traces (oldest evicted first).

    Each added trace gets a monotonically increasing sequence number
    so the controller's sync-time scrape (``summaries_since``) ships
    each completed trace at most once — the cursor survives ring
    eviction (missed traces are simply gone, never re-sent)."""

    # Span cap per shipped summary: a pathological chunked-prefill
    # request must not blow up the controller's bounded trace store.
    SUMMARY_MAX_SPANS = 64

    def __init__(self, maxlen: int = DEFAULT_BUFFER):
        self._lock = threading.Lock()
        self._traces: 'collections.deque[RequestTrace]' = \
            collections.deque(maxlen=max(1, maxlen))
        self._seqs: 'collections.deque[int]' = \
            collections.deque(maxlen=max(1, maxlen))
        self._next_seq = 1

    def add(self, trace: RequestTrace) -> None:
        with self._lock:
            self._traces.append(trace)
            self._seqs.append(self._next_seq)
            self._next_seq += 1

    def snapshot(self) -> List[RequestTrace]:
        with self._lock:
            return list(self._traces)

    def summaries_since(self, cursor: int,
                        limit: int = 128
                        ) -> Tuple[int, List[Dict[str, Any]]]:
        """(new_cursor, completed-trace dicts added after ``cursor``),
        oldest first, at most ``limit`` — the bounded payload a replica
        ships to the controller on the sync/probe path."""
        with self._lock:
            pairs = [(s, t) for s, t in zip(self._seqs, self._traces)
                     if s > cursor]
            tail_cursor = self._next_seq - 1
        trimmed = pairs[:max(0, int(limit))]
        out = []
        for _, trace in trimmed:
            d = trace.to_dict()
            if len(d['spans']) > self.SUMMARY_MAX_SPANS:
                d['spans'] = d['spans'][:self.SUMMARY_MAX_SPANS]
                d['meta']['spans_truncated'] = True
            out.append(d)
        if len(trimmed) < len(pairs):
            # ``limit`` trimmed the batch: resume from the last shipped
            # trace, not the ring head — the rest ships next sync.
            return trimmed[-1][0] if trimmed else cursor, out
        return max(cursor, tail_cursor), out

    def to_json(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Newest-first trace dicts (the ``/debug/requests`` body)."""
        traces = self.snapshot()[::-1]
        if limit is not None:
            traces = traces[:max(0, int(limit))]
        return [t.to_dict() for t in traces]

    def find(self, request_id: int) -> Optional[RequestTrace]:
        for t in reversed(self.snapshot()):
            if t.request_id == request_id:
                return t
        return None

    def find_trace(self, trace_id: str) -> Optional[RequestTrace]:
        """Lookup by 128-bit trace id, newest first (a retried leg
        shares its id with the leg before it)."""
        for t in reversed(self.snapshot()):
            if t.trace_id == trace_id:
                return t
        return None

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)


_buffer_lock = threading.Lock()
_buffer: Optional[TraceBuffer] = None


def get_trace_buffer() -> TraceBuffer:
    """THE process-wide completed-request trace buffer."""
    global _buffer
    with _buffer_lock:
        if _buffer is None:
            _buffer = TraceBuffer()
        return _buffer


def export_chrome_trace(path: str,
                        traces: Optional[List[RequestTrace]] = None
                        ) -> Optional[str]:
    """Write traces as a ``chrome://tracing`` file via the existing
    ``utils/timeline.py`` writer. One chrome thread (tid) per request;
    span args carry the meta. Returns the path (None when empty)."""
    from skypilot_tpu.utils import timeline
    if traces is None:
        traces = get_trace_buffer().snapshot()
    events: List[Dict[str, Any]] = []
    for trace in traces:
        base_wall_us = trace.wall0 * 1e6
        for span in trace.spans:
            if span.t1 is None:
                continue
            ev: Dict[str, Any] = {
                'name': span.name,
                'ph': 'X',
                'ts': base_wall_us + (span.t0 - trace.t0) * 1e6,
                'dur': (span.t1 - span.t0) * 1e6,
                'pid': os.getpid(),
                'tid': trace.request_id,
            }
            args = {k: str(v) for k, v in span.meta.items()}
            args['trace_id'] = trace.trace_id
            ev['args'] = args
            events.append(ev)
    if not events:
        return None
    return timeline.write_trace(path, events)
