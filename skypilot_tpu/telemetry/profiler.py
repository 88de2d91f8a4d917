"""Engine step-phase profiler.

Answers "which step phase regressed": per-phase wall time of the
engine scheduling loop (admit, prefill-chunk, decode-enqueue,
spec-verify, sanctioned readback) and first-call-per-jit-key events
(the call that pays XLA compilation).

Measurement discipline — the reason this is safe on the hot path and
the jaxpr audit's ``telemetry`` preset stays green:

- Monotonic clocks only (``telemetry.clock``), taken strictly on the
  HOST side AROUND jitted dispatches — never inside a jit body (that
  would trace a constant) and never forcing a device sync (a phase
  ends when the dispatch returns, not when the device finishes; device
  completion is visible in the ``readback`` phase, which wraps the
  engines' one sanctioned ``host_sync``).
- First-compile events ride the engines' existing jit-key bookkeeping:
  a key never seen before has its first dispatch timed (jit compiles
  synchronously at first call, so the wall time ≈ trace+compile);
  seen keys pay one set lookup.

Per-phase times accumulate BOTH locally (``phase_stats()`` — bench's
per-engine latency decomposition) and into the process registry
(``skypilot_tpu_engine_step_phase_seconds{phase=...}`` — the
``/metrics`` surface). :class:`NullProfiler` is the telemetry-off
no-op twin with the same API.

The loop account: a phase's inclusive time counts its nested phases
again, so the histograms cannot be added up. Beside them the profiler
charges every instant of ONE thread (the one that built it: the engine
loop's) to exactly one cause — the innermost open phase, or ``loop``
for the thread's own code between phases — from the same clock pair
each phase already takes. ``loop_seconds()`` therefore adds up to that
thread's clock since construction; the cumulative counters are
``skytpu_engine_loop_seconds_total{cause=...}``, every cause of
:data:`LOOP_CAUSES` registered up front. Phases entered on other
threads (a handler exporting KV rows) keep their histogram and
annotation and charge nothing.

One timeline with the device: while a ``jax.profiler`` trace of the
process runs, every phase (and every first call per jit key) is also a
``jax.profiler.TraceAnnotation`` named ``skytpu:<phase>``, so it lands
on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the device
operations, in the same nanoseconds. While no trace runs, nothing is
constructed: one read of the profiler's flag, cheaper than the clock
pair beside it.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Tuple

from skypilot_tpu.telemetry import clock
from skypilot_tpu.telemetry import registry as registry_lib

PHASE_METRIC = 'skytpu_engine_step_phase_seconds'
COMPILE_METRIC = 'skytpu_jit_first_call_seconds'
SUBSTEP_METRIC = 'skytpu_engine_decode_substeps_total'
LIVE_ROWS_METRIC = 'skytpu_engine_decode_live_rows_total'
# Expert layers (models/latent_moe.py) and prefill attention: what the
# benchmark's roofline shares count the need from.
MOE_LAYER_STEPS_METRIC = 'skytpu_moe_layer_steps_total'
MOE_DISTINCT_METRIC = 'skytpu_moe_distinct_experts_total'
MOE_ASSIGNMENTS_METRIC = 'skytpu_moe_assignments_total'
# A model that holds a share of its routed experts (``n_held_experts``):
# the held count (a gauge) and the live assignments that fell to held
# experts, counted on the device beside the distinct experts.
MOE_HELD_EXPERTS_METRIC = 'skytpu_moe_held_experts'
MOE_ASSIGNMENTS_HELD_METRIC = 'skytpu_moe_assignments_held_total'
# Layers that keep a per-slot state in place of cache rows
# (``inference/paged.py`` ``RecurrentState``): how many, a slot's bytes
# over all of them (gauges), slots whose state was started from zeros,
# and tokens prefilled AGAIN after a preemption because no state was kept.
RECURRENT_LAYERS_METRIC = 'skytpu_recurrent_layers'
RECURRENT_STATE_BYTES_METRIC = 'skytpu_recurrent_state_bytes'
STATE_RESETS_METRIC = 'skytpu_state_resets_total'
STATE_RECOMPUTE_METRIC = 'skytpu_state_recompute_tokens_total'
PREFILL_PAIRS_METRIC = 'skytpu_prefill_attn_pairs_total'
PREFILL_TOKENS_METRIC = 'skytpu_prefill_tokens_total'
# Pieces wider than the chunk, taken by a prompt prefilled alone
# (``paged.wide_piece_width``): how many, their tokens (over the prefill
# tokens: how often the wide width engages), and the width (a gauge; the
# chunk itself where the model's chunk is not bound by weight reads).
PREFILL_WIDE_PIECES_METRIC = 'skytpu_prefill_wide_pieces_total'
PREFILL_WIDE_TOKENS_METRIC = 'skytpu_prefill_wide_tokens_total'
PREFILL_WIDE_WIDTH_METRIC = 'skytpu_prefill_wide_width'
# What one cached token is (gauges the engine sets once it is built): its
# cache layers (layers x passes of a looped model) and its stored bytes
# over all of them.
KV_CACHE_LAYERS_METRIC = 'skytpu_kv_cache_layers'
KV_TOKEN_BYTES_METRIC = 'skytpu_kv_token_bytes'
# The latent paged decode kernel (ops/latent_paged_attention.py): pages
# it reads against pages the padded table holds.
ATTN_PAGES_LIVE_METRIC = 'skytpu_decode_attn_pages_live_total'
ATTN_PAGES_TABLE_METRIC = 'skytpu_decode_attn_pages_table_total'
# Row writes into the paged pools (inference/paged.py
# ``_write_live_rows``): token rows due against token rows the programs'
# shapes carried; the difference is what the write loop skips.
POOL_ROWS_LIVE_METRIC = 'skytpu_pool_write_rows_live_total'
POOL_ROWS_OFFERED_METRIC = 'skytpu_pool_write_rows_offered_total'
# The loop account: seconds of the engine thread by cause, and the turns
# of the serving loop that had work.
LOOP_SECONDS_METRIC = 'skytpu_engine_loop_seconds_total'
LOOP_TURNS_METRIC = 'skytpu_engine_loop_turns_total'
# Every cause the account charges (a phase name, or ``loop``):
# ``wait_work`` the serving loop parked for lack of work; ``dispatch`` a
# jitted program handed to the runtime; ``upload`` a host->device copy;
# ``eager`` a device op outside the jitted programs (its annotation names
# the ``site``); ``first_call`` a call that compiles; ``pressure_drain``
# and ``preempt`` the pool-pressure paths of the decode enqueue.
LOOP_CAUSES = ('loop', 'wait_work', 'lock_wait', 'fill_engine',
               'route_events', 'readback', 'admit', 'admit_upload',
               'prefill_chunk', 'admit_token_merge', 'decode_enqueue',
               'pressure_drain', 'preempt', 'spec_verify', 'dispatch',
               'upload', 'eager', 'first_call')
ANNOTATION_PREFIX = 'skytpu:'


class NullProfiler:
    """Telemetry-off profiler: same API, zero work."""

    compile_events: List[Dict[str, Any]] = []

    @contextlib.contextmanager
    def phase(self, name: str, **key: Any):
        del name, key
        yield

    @contextlib.contextmanager
    def jit_key(self, fn: str, key: Tuple):
        del fn, key
        yield

    def tag(self, **key: Any) -> None:
        del key

    def note_substeps(self, name: str, n: int, live_rows: int = 0,
                      moe_layers: int = 0, top_k: int = 0) -> None:
        del name, n, live_rows, moe_layers, top_k

    def note_distinct_experts(self, n: int, layer_steps: int,
                              held_assignments: Optional[int] = None
                              ) -> None:
        del n, layer_steps, held_assignments

    def note_state_reset(self, recompute_tokens: int = 0) -> None:
        del recompute_tokens

    def note_prefill_pairs(self, n: int, tokens: int = 0,
                           wide: bool = False) -> None:
        del n, tokens, wide

    def note_wide_width(self, width: int) -> None:
        del width

    def note_decode_attn_pages(self, live: int, table: int) -> None:
        del live, table

    def note_pool_write(self, live: int, offered: int) -> None:
        del live, offered

    def note_turn(self) -> None:
        pass

    def loop_seconds(self) -> Dict[str, float]:
        return dict.fromkeys(LOOP_CAUSES, 0.0)

    def phase_stats(self) -> Dict[str, Any]:
        return {}


class StepProfiler:
    """Per-engine step-phase + first-compile recorder. The phase/jit
    context managers are called from the single engine thread;
    ``phase_stats()`` may be read from other threads (bench, handlers)
    — the small accumulator dict is guarded."""

    def __init__(self, engine: str = '',
                 registry: Optional[registry_lib.MetricsRegistry] = None):
        self.engine = engine
        self._reg = registry or registry_lib.get_registry()
        self._lock = threading.Lock()
        # phase -> [count, total_s, max_s]
        self._acc: Dict[str, List[float]] = {}
        # phase -> device SUBSTEPS its dispatches covered (multi-step
        # decode: one decode_enqueue dispatch fuses k substeps, so the
        # per-substep split = total_s / substeps — the number that
        # shows dispatch amortization instead of hiding it in a
        # fatter per-call mean).
        self._substeps: Dict[str, int] = {}
        # Registered at construction: zeros from the first scrape.
        self._substep_counter = self._reg.counter(
            SUBSTEP_METRIC,
            'Device decode substeps covered by enqueued dispatches '
            '(k per call under multi-step decode)')
        self._live_rows_counter = self._reg.counter(
            LIVE_ROWS_METRIC,
            'Batch rows that carried a request, summed over the decode '
            'substeps of enqueued dispatches (over the substeps '
            'counter: the mean live batch of a step)')
        self._moe_layer_steps = self._reg.counter(
            MOE_LAYER_STEPS_METRIC,
            'Expert layers run, summed over the decode substeps of '
            'enqueued dispatches')
        self._moe_distinct = self._reg.counter(
            MOE_DISTINCT_METRIC,
            'Distinct experts a decode substep read, summed over its '
            'expert layers (counted on the device, read back with the '
            "call's tokens; over the layer steps: the mean an expert "
            'layer reads)')
        self._moe_assignments = self._reg.counter(
            MOE_ASSIGNMENTS_METRIC,
            'Token-to-expert assignments of live rows, summed over '
            'expert layers and decode substeps')
        self._moe_assignments_held = self._reg.counter(
            MOE_ASSIGNMENTS_HELD_METRIC,
            'Of those assignments, the ones to experts this program '
            'holds (counted on the device; all of them where every '
            'expert is held)')
        self._state_resets = self._reg.counter(
            STATE_RESETS_METRIC,
            "Slots whose recurrent state a request's first chunk "
            'started from zeros')
        self._state_recompute = self._reg.counter(
            STATE_RECOMPUTE_METRIC,
            'Tokens prefilled again after a preemption because a '
            'recurrent state cannot be rebuilt from pages')
        self._prefill_pairs = self._reg.counter(
            PREFILL_PAIRS_METRIC,
            'Query-key pairs under the causal mask that enqueued '
            'prefill chunks needed, per layer')
        self._prefill_tokens = self._reg.counter(
            PREFILL_TOKENS_METRIC,
            'Prompt tokens of enqueued prefill chunks (padding left out)')
        self._prefill_wide_pieces = self._reg.counter(
            PREFILL_WIDE_PIECES_METRIC,
            'Enqueued prefill chunks that held one prompt in a piece wider '
            'than the chunk')
        self._prefill_wide_tokens = self._reg.counter(
            PREFILL_WIDE_TOKENS_METRIC,
            'Prompt tokens of those wide pieces (over the prefill tokens: '
            'the share prefilled wide)')
        self._prefill_wide_width = self._reg.gauge(
            PREFILL_WIDE_WIDTH_METRIC,
            'Tokens of the piece a prompt prefilled alone takes while its '
            'tail holds one (the chunk where no wider piece pays)')
        self._attn_pages_live = self._reg.counter(
            ATTN_PAGES_LIVE_METRIC,
            "Pages of live rows' own contexts (ceil(length / page) a "
            'row) that the latent paged decode kernel read a layer, '
            'summed over decode substeps')
        self._attn_pages_table = self._reg.counter(
            ATTN_PAGES_TABLE_METRIC,
            'Pages the padded page table of those dispatches held (slots '
            'x page bucket), summed over decode substeps (under the live '
            'counter: the share of the table that is live)')
        self._pool_rows_live = self._reg.counter(
            POOL_ROWS_LIVE_METRIC,
            'Token rows due for the paged pools in enqueued ring merges '
            'and prefill chunks (the sum of their slots\' valid_len)')
        self._pool_rows_offered = self._reg.counter(
            POOL_ROWS_OFFERED_METRIC,
            'Token rows those programs\' shapes carried (slots x tokens a '
            'slot; over the live counter: the share of a row write that '
            'the loop over live units skips)')
        self._turns = self._reg.counter(
            LOOP_TURNS_METRIC,
            'Turns of the serving loop that had runnable work')
        self._loop_counters = {cause: self._reg.counter(
            LOOP_SECONDS_METRIC,
            "Seconds of the engine thread charged to each cause: the "
            "innermost open phase, or 'loop' between phases (a span "
            'counts when it is left)', cause=cause) for cause in LOOP_CAUSES}
        # The account of the building thread: its seconds by cause, the
        # phases it has open (innermost last), and the cause charged now
        # with when that began.
        self._owner = threading.get_ident()
        self._self_s = dict.fromkeys(LOOP_CAUSES, 0.0)
        self._causes: List[str] = []
        self._cursor: Tuple[str, float] = ('loop', clock.monotonic())
        self._hists: Dict[str, registry_lib.Histogram] = {}
        self._seen_keys: Dict[str, set] = {}
        self.compile_events: List[Dict[str, Any]] = []
        # Bound here, not at import: the control plane imports
        # ``telemetry`` without JAX; only an engine builds a profiler.
        from jax import profiler as jax_profiler
        self._annotation = jax_profiler.TraceAnnotation
        # The annotations now open, innermost last (engine thread only;
        # empty while no trace runs).
        self._open: List[Any] = []

    def _charge(self, now: float, cause: str) -> None:
        """Charge the owner thread's time since the last transition to
        the cause charged until now, and charge ``cause`` from here on.
        Called with ``self._lock`` held."""
        was, since = self._cursor
        self._cursor = (cause, now)
        self._self_s[was] += now - since
        self._loop_counters[was].inc(now - since)

    def _enter(self, name: str, t0: float) -> bool:
        """Open ``name`` in the account if the caller is its thread."""
        if threading.get_ident() != self._owner:
            return False
        with self._lock:
            self._charge(t0, name)
            self._causes.append(name)
        return True

    def _leave(self, t1: float) -> None:
        """Close the innermost phase; with ``self._lock`` held."""
        self._causes.pop()
        self._charge(t1, self._causes[-1] if self._causes else 'loop')

    def _phase_hist(self, name: str) -> registry_lib.Histogram:
        hist = self._hists.get(name)
        if hist is None:
            hist = self._reg.histogram(
                PHASE_METRIC,
                'Engine scheduling-loop phase wall time (host-side, '
                'around async dispatches)',
                buckets=registry_lib.DEFAULT_SECONDS_BUCKETS,
                phase=name)
            self._hists[name] = hist
        return hist

    def _annotate(self, name: str, key: Dict[str, Any]) -> bool:
        """Open ``skytpu:<name>`` on the running ``jax.profiler`` trace;
        False, with nothing constructed, while none runs."""
        if not self._annotation.is_enabled():
            return False
        note = self._annotation(ANNOTATION_PREFIX + name, **key)
        note.__enter__()
        self._open.append(note)
        return True

    def tag(self, **key: Any) -> None:
        """Attach a program key (prompts, pages, horizon...) that is
        only known inside a phase to that phase's annotation."""
        if self._open:
            self._open[-1].set_metadata(**key)

    @contextlib.contextmanager
    def phase(self, name: str, **key: Any):
        annotated = self._annotate(name, key)
        t0 = clock.monotonic()
        counted = self._enter(name, t0)
        try:
            yield
        finally:
            t1 = clock.monotonic()
            dt = t1 - t0
            if annotated:
                self._open.pop().__exit__(None, None, None)
            self._phase_hist(name).observe(dt)
            with self._lock:
                if counted:
                    self._leave(t1)
                acc = self._acc.setdefault(name, [0, 0.0, 0.0])
                acc[0] += 1
                acc[1] += dt
                acc[2] = max(acc[2], dt)

    @contextlib.contextmanager
    def jit_key(self, fn: str, key: Tuple):
        """Time the FIRST dispatch of each (fn, static key) — the call
        that pays compilation, the account's ``first_call``. Subsequent
        calls: one set lookup."""
        seen = self._seen_keys.setdefault(fn, set())
        if key in seen:
            yield
            return
        annotated = self._annotate('first_call',
                                   {'fn': fn, 'key': repr(key)})
        t0 = clock.monotonic()
        counted = self._enter('first_call', t0)
        try:
            yield
        finally:
            t1 = clock.monotonic()
            dt = t1 - t0
            if annotated:
                self._open.pop().__exit__(None, None, None)
            if counted:
                with self._lock:
                    self._leave(t1)
            seen.add(key)
            self._reg.histogram(
                COMPILE_METRIC,
                'Wall time of the first dispatch per jit static key '
                '(trace + XLA compile)',
                buckets=registry_lib.DEFAULT_SECONDS_BUCKETS,
                fn=fn).observe(dt)
            with self._lock:
                self.compile_events.append(
                    {'fn': fn, 'key': repr(key),
                     'seconds': round(dt, 6)})

    def note_substeps(self, name: str, n: int, live_rows: int = 0,
                      moe_layers: int = 0, top_k: int = 0) -> None:
        """Record that the NEXT/current ``name`` dispatch covers ``n``
        device substeps (multi-step decode's per-substep attribution),
        ``live_rows`` of its batch rows carrying a request, each through
        ``moe_layers`` expert layers of ``top_k`` experts a token.
        Host-side counter bumps only — nothing touches the device."""
        if n <= 0:
            return
        self._substep_counter.inc(n)
        self._live_rows_counter.inc(n * live_rows)
        if moe_layers:
            self._moe_layer_steps.inc(n * moe_layers)
            self._moe_assignments.inc(n * moe_layers * live_rows * top_k)
        with self._lock:
            self._substeps[name] = self._substeps.get(name, 0) + n

    def note_distinct_experts(self, n: int, layer_steps: int,
                              held_assignments: Optional[int] = None
                              ) -> None:
        """Distinct experts a decode dispatch read over its
        ``layer_steps`` (substeps x expert layers), as read back with its
        tokens; traced, an instant ``skytpu:moe_readback`` carries both,
        so that a trace holds the counts of the calls it holds.
        ``held_assignments``: a model holding a share of its experts
        reads back how many live assignments fell to them."""
        self._moe_distinct.inc(n)
        if held_assignments is not None:
            self._moe_assignments_held.inc(held_assignments)
        if self._annotate('moe_readback', {'distinct': n,
                                           'layer_steps': layer_steps}):
            self._open.pop().__exit__(None, None, None)

    def note_state_reset(self, recompute_tokens: int = 0) -> None:
        """A slot of a recurrent model handed to a request: its state
        starts from zeros; ``recompute_tokens`` of a resumed request's
        context are prefilled a second time."""
        self._state_resets.inc(1)
        self._state_recompute.inc(recompute_tokens)

    def note_prefill_pairs(self, n: int, tokens: int = 0,
                           wide: bool = False) -> None:
        """A prefill chunk's dispatch: its query-key pairs a layer and
        its valid tokens, counted again as a wide piece's where ``wide``.
        Host arithmetic."""
        self._prefill_pairs.inc(n)
        self._prefill_tokens.inc(tokens)
        if wide:
            self._prefill_wide_pieces.inc(1)
            self._prefill_wide_tokens.inc(tokens)

    def note_wide_width(self, width: int) -> None:
        """The width of a wide piece, once the engine has resolved it."""
        self._prefill_wide_width.set(width)

    def note_decode_attn_pages(self, live: int, table: int) -> None:
        """Pages a decode dispatch's attention reads a layer (``live``:
        the live rows' own) and pages its padded table holds, each
        times the dispatch's substeps. Host arithmetic at the enqueue."""
        self._attn_pages_live.inc(live)
        self._attn_pages_table.inc(table)

    def note_pool_write(self, live: int, offered: int) -> None:
        """A ring merge's or a prefill chunk's dispatch: the token rows
        due for the pools (the sum of ``valid_len``) and the rows the
        program's shape carries (slots x tokens a slot). Host
        arithmetic."""
        self._pool_rows_live.inc(live)
        self._pool_rows_offered.inc(offered)

    def note_turn(self) -> None:
        """A turn of the serving loop that found runnable work."""
        self._turns.inc(1)

    def loop_seconds(self) -> Dict[str, float]:
        """The owner thread's seconds by cause, the cause charged now
        counted up to this call: they add up to that thread's clock
        since this profiler was built (the registry's counters lag by
        the open span)."""
        with self._lock:
            out = dict(self._self_s)
            cause, since = self._cursor
            out[cause] += clock.monotonic() - since
        return out

    def phase_stats(self) -> Dict[str, Any]:
        """Per-phase summary for THIS engine (bench's latency
        decomposition): phase -> count/total_s/mean_ms/max_ms (+
        substeps/per_substep_ms where dispatches fuse multiple device
        substeps), plus the first-compile event list."""
        with self._lock:
            acc = {k: list(v) for k, v in self._acc.items()}
            subs = dict(self._substeps)
            compiles = list(self.compile_events)
        out: Dict[str, Any] = {'phases': {}, 'compiles': compiles}
        for name, (count, total, mx) in sorted(acc.items()):
            entry = {
                'count': int(count),
                'total_s': round(total, 6),
                'mean_ms': round(total / count * 1e3, 3) if count else 0.0,
                'max_ms': round(mx * 1e3, 3),
            }
            if subs.get(name):
                entry['substeps'] = int(subs[name])
                entry['per_substep_ms'] = round(
                    total / subs[name] * 1e3, 4)
            out['phases'][name] = entry
        return out
