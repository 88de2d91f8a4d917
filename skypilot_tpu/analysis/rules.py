"""graftcheck part A: repo-specific AST lint rules.

The reference SkyPilot is ~94k LoC of lock-and-thread Python whose
concurrency discipline lives in reviewers' heads; this module turns the
discipline this repo actually relies on into machine-checked rules.
Two families:

Concurrency / control-plane hygiene (GC1xx):

- **GC101 unlocked-state-write** — an attribute that is written under a
  class's threading lock somewhere is part of that lock's protected
  state; writing it without the lock elsewhere is a race.
- **GC102 blocking-under-lock** — ``time.sleep``, socket/HTTP I/O,
  subprocess waits, unbounded ``.wait()/.get()/.join()``, and (under a
  *threading* lock) sqlite-backed state-module or cluster-RPC calls
  stall every thread contending for the lock. Locks whose name marks
  them as DB-serialization locks (``db_lock``, ``_state_lock``,
  ``_scheduler_lock``, ``FileLock``) are exempt from the state-module
  check only — serializing DB access is their entire job.
- **GC103 rpc-no-timeout** — ``urlopen``/``create_connection`` without
  a timeout turns a wedged peer into a wedged controller.
- **GC104 bare-except** — ``except:`` catches ``KeyboardInterrupt`` and
  ``SystemExit``; never acceptable.
- **GC105 swallowed-except** — ``except Exception`` whose body neither
  logs, raises, nor does any real work erases the only evidence of a
  failure. (Narrow exception types may be silently dropped; broad ones
  may not.)
- **GC107 handler-no-timeout** — an ``http.server`` request handler
  without a ``timeout`` class attribute lets one slow-loris client pin
  a server thread forever.
- **GC108 proposer-under-lock** — speculative-decoding proposer host
  work (``prepare_proposals``/``ngram_propose`` — per-slot numpy n-gram
  matching) invoked while holding a lock serializes every HTTP handler
  behind proposer CPU time; the serve loop runs it before locking.
- **GC109 adhoc-timing** — ``time.time()`` / ``perf_counter()`` /
  ``monotonic()`` calls in the ``inference/`` hot paths outside the
  telemetry helpers. Timing on the engine hot path must route through
  ``skypilot_tpu.telemetry`` (``clock`` / the step-phase profiler) so
  overhead is accounted, phases land in the registry, and a stray
  timing pair around a jitted dispatch can't masquerade as device
  time (inside jit bodies GC201 already fires; this rule covers the
  host side).
- **GC111 sync-engine-call-in-coroutine** — synchronous engine-path
  calls (``step``/``submit``/``add_request``/``run_to_completion``)
  or unbounded blocking waits (argless ``.get()``/``.wait()``/
  ``.join()`` with no timeout) inside an ``async def`` in ``serve/``.
  One such call freezes the whole event loop — every concurrent
  stream stalls behind one engine step. Coroutines must consume
  through the async adapters (``Outbox.aget``) or hand blocking work
  to a thread (``await loop.run_in_executor(...)``).
- **GC112 fixed-sleep-retry** — ``time.sleep`` with a loop-invariant
  delay inside a ``while``/``for`` loop in ``serve/`` or ``jobs/``.
  A fleet of controllers/retriers sleeping the same fixed interval
  produces synchronized retry storms (every replica relaunches
  against the same exhausted quota at the same instant) and
  lockstep DB/RPC polling. Retry/poll loops must back off (reassign
  the delay inside the loop), jitter (draw from ``random``), or wait
  on an ``Event`` with a timeout. The delay counts as dynamic when
  its expression contains a ``random``-module/RNG call or any name
  reassigned within the loop.
- **GC114 wide-float-kv-on-wire** — ``.astype`` to a wide float dtype
  (bfloat16/float16/float32/float64) or any ``dequant*`` call inside a
  KV transfer path (``inference/kv_transfer.py``, ``serve/disagg.py``).
  Disaggregated handoffs move int8 KV as codes + absmax scales in the
  STORED dtype; the wire codec never converts — widening KV for the
  wire doubles handoff bytes and silently defeats the whole
  disaggregation economics.
- **GC115 wallclock-in-scaling-path** — a direct ``time.time()`` /
  ``time.monotonic()`` call anywhere in ``serve/autoscalers.py`` or
  ``serve/forecaster.py``. Scaling and forecast decisions are
  clock-injectable (the ``now`` parameter / constructor ``clock=``)
  so tests replay recorded traces to identical decisions; one raw
  wall-clock read re-introduces nondeterminism invisibly. Referencing
  ``time.time`` as an injectable default argument is the mechanism
  itself and stays legal — only *calls* are flagged.
- **GC117 wallclock-in-simulator** — any ``time.time()`` /
  ``time.monotonic()`` / ``time.sleep()`` (and *_ns/perf_counter
  variants) call anywhere under ``serve/sim/``. The fleet simulator's
  one time axis is the virtual clock (``EventLoop.now`` /
  ``EventLoop.sleep``); a single wall-clock read or real sleep makes
  same-seed runs diverge and silently breaks the byte-identical
  event-log replay contract.
- **GC118 unknown-fault-site** — a ``faults.fire('<site>')`` call
  whose site string literal is not in the central site registry
  (``serve/faults.py FAULT_SITES``). A typo'd site parses fine, counts
  nothing, and SILENTLY never fires — the chaos test then passes
  because no fault was injected, which is the exact false confidence
  the fault subsystem exists to kill. Applies under ``serve/``
  (every injector hook lives there).
- **GC123 untraced-outbound-http** — a body-carrying
  ``urllib.request.Request``/``urlopen`` under ``serve/`` outside the
  trace-propagating helper (``serve/wire.py``). Every outbound hop
  that carries a request body (LB dispatch, KV ingest, gang sync,
  idempotency handoff) must ride the wire helpers so the
  ``X-Skytpu-Trace`` header survives the hop; read-only GETs and
  liveness probes (scope name mentions ``probe``) are exempt.

TPU hot-path hygiene (GC2xx), applied to the compute layer
(``inference/``, ``models/``, ``ops/``, ``train/``):

- **GC201 impure-jit** — impure or host-synchronizing calls inside a
  ``@jax.jit`` body (``time.time``, ``print``, ``np.*``, ``.item()``,
  ``float()`` on a traced value) either fail at trace time or bake a
  constant into the compiled program.
- **GC110 unscaled-int8-kv-write** — ``.astype(jnp.int8)`` in the
  compute layer outside the quantization helpers
  (``models/quantization.py``, ``quantize_*`` functions). Symmetric
  int8 KV is (codes, absmax/127 scales) pairs written through
  ``llama.quantize_kv_rows``; a bare astype silently truncates to
  ±1-integer range and drops the scale — garbage KV that still
  type-checks. (Classed with the 1xx rules because it polices a
  repo-wide write discipline, not a jaxpr property.)
- **GC119 bare-int4-bit-twiddling** — ``.astype(int4/uint4)`` or a
  hand-rolled nibble op (``<< 4`` / ``>> 4`` / ``& 0xF``) in the
  compute layer outside ``models/quantization.py``. Packed int4 has
  exactly ONE layout contract (pack axis = last contracted, low
  nibble first, sign-extended codes, absmax/7 scales) defined next to
  ``pack_int4``/``unpack_int4``/``qeinsum``; a local re-implementation
  that disagrees on any of those produces numerically-wrong weights
  that still type-check.
- **GC120 unjournaled-lifecycle-write** — a replica-row / journal /
  controller-note mutation (``serve_state`` spelling or the
  ``ControlPlaneEnv`` seam) in ``serve/replica_managers.py`` /
  ``serve/controller.py`` outside the journaled persist helpers
  (``_persist`` / ``_untrack`` / ``_journal_start`` /
  ``_journal_finish`` / ``_put_note`` / ``_del_note`` /
  ``_persist_autoscaler_state``). Restart reconciliation replays the
  journal; a write it didn't see is state it cannot rebuild.
- **GC121 per-layer-pool-read** — a per-layer pool slice
  (``lax.dynamic_index_in_dim`` over a ``[L, ...]`` KV pool, or a
  scalar layer subscript) in ANY function of ``inference/``, or a
  ``_gather_layer`` call inside a decode-scoped one. Slicing the
  stacked pool makes XLA materialize that layer's whole pool as a
  fresh operand — a read of the entire KV pool per program, growing
  with the pool and not with the work (decode: the traffic the
  paged-attention kernels exist to avoid; prefill: 19 % of the 7B
  chunk program before PR 28). Consumers take the FULL stacked pool
  and the layer as an index (the kernels' scalar prefetch,
  ``_gather_layer``'s flat gather). Prefill/verify-shaped functions
  may call ``_gather_layer`` (they need contiguous rows); decode
  reads go through the kernels.
- **GC122 unbounded-lb-map-growth** — a growth mutation on a
  ``self.*`` container (``self.x[k] = v``, ``.append``, ``.add``,
  ``.setdefault``, ``.update``, ...) in
  ``serve/load_balancing_policies.py`` outside the
  :class:`BoundedStore` helper. LB policies run for months and see
  millions of sessions/replicas churn through; a raw per-key insert
  on a policy attribute is a slow memory leak with no eviction and no
  telemetry. Every runtime table goes through ``BoundedStore``
  (TTL + LRU cap, evictions counted loudly); wholesale reassignment
  (``self.x = dict(...)``) stays legal — it replaces, never grows.
- **GC202 host-sync** — device->host readbacks outside the sanctioned
  :func:`skypilot_tpu.utils.host.host_sync` helper (bare
  ``np.asarray(x)``, ``.item()``, ``jax.device_get``,
  ``block_until_ready``, ``float(x)``). One accidental sync in the
  decode loop stalls the host on device completion *per step*.
  ``np.asarray(x, dtype)`` — the explicit
  host-side conversion idiom — is allowed; the bare one-argument form
  is the classic accidental-sync spelling.

Suppression: ``# graftcheck: disable=GC102`` (comma-list or ``all``)
on the offending line, or a checked-in baseline (``graftcheck.baseline``)
of fingerprints for pre-existing violations — new ones hard-fail.
"""
from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from typing import Dict, List, Optional, Set, Tuple

RULES: Dict[str, str] = {
    'GC101': 'unlocked-state-write: attribute guarded by a lock '
             'elsewhere is written without holding it',
    'GC102': 'blocking-under-lock: blocking call (sleep / socket / '
             'subprocess / sqlite state / cluster RPC / unbounded wait) '
             'while holding a lock',
    'GC103': 'rpc-no-timeout: network call with no timeout',
    'GC104': 'bare-except: except: catches KeyboardInterrupt/SystemExit',
    'GC105': 'swallowed-except: broad except whose body neither logs, '
             'raises, nor acts',
    'GC107': 'handler-no-timeout: http.server handler class without a '
             'timeout attribute (slow-loris pins a thread)',
    'GC108': 'proposer-under-lock: speculative-proposer host work '
             '(n-gram matching) invoked while holding a lock — call '
             'prepare_proposals() BEFORE taking the engine lock',
    'GC109': 'adhoc-timing: wall-clock/perf-counter call in an '
             'inference hot path — use skypilot_tpu.telemetry '
             '(clock / step-phase profiler) instead',
    'GC110': 'unscaled-int8-kv-write: .astype(jnp.int8) outside the '
             'quantization helpers — int8 KV/weight writes must go '
             'through quantize_kv_rows/models.quantization (codes + '
             'scales); a bare astype drops the scale',
    'GC111': 'sync-engine-call-in-coroutine: synchronous engine call '
             '(step/submit/add_request/...) or unbounded blocking '
             'wait inside an async def in serve/ freezes the event '
             'loop — use the async adapters (Outbox.aget) or '
             'await loop.run_in_executor(...)',
    'GC112': 'fixed-sleep-retry: time.sleep with a loop-invariant '
             'delay inside a retry/poll loop in serve/ or jobs/ — '
             'add exponential backoff and/or jitter, or wait on an '
             'Event with a timeout (fixed sleeps synchronize retry '
             'storms across the fleet)',
    'GC113': 'device-put-in-step-path: jax.device_put inside an '
             'inference/ step function — an implicit cross-mesh '
             'reshard of committed device state silently inserts '
             'collectives (or a full device round trip) into the hot '
             'loop. Host->device uploads of freshly built numpy '
             'operands go through utils.host.device_upload; placement '
             '(construction-time sharding) belongs in prepare_params '
             'or engine __init__',
    'GC114': 'wide-float-kv-on-wire: bf16/float32 conversion (or a '
             'dequantize call) on a KV transfer path — int8 KV must '
             'stay int8 codes + scales end to end (the wire codec '
             'helpers in inference/kv_transfer.py are the sanctioned '
             'spelling); dequantizing for the wire doubles handoff '
             'bytes and silently defeats the disaggregation win',
    'GC115': 'wallclock-in-scaling-path: direct time.time()/'
             'time.monotonic() call inside serve/autoscalers.py or '
             'serve/forecaster.py — scaling/forecast decisions must '
             'read the injected clock (the `now` parameter / '
             'self._clock) so recorded traces replay to identical '
             'decisions under test; referencing time.time as an '
             'injectable default is fine, calling it is not',
    'GC116': 'unbounded-gang-join: a distributed join/barrier/wait in '
             'the gang layer (serve/gang.py) with no timeout — a rank '
             'that never comes up (or a dead coordinator) would hang '
             'the whole gang forever instead of failing it fast; '
             'every distributed join must carry a timeout (and '
             'jax.distributed.initialize an initialization_timeout)',
    'GC117': 'wallclock-in-simulator: time.time()/time.monotonic()/'
             'time.sleep() call under serve/sim/ — the fleet '
             'simulator runs on the virtual clock ONLY (EventLoop.now'
             '/EventLoop.sleep); one wall-clock read makes same-seed '
             'runs diverge and silently breaks the byte-identical '
             'event-log contract',
    'GC118': 'unknown-fault-site: .fire(<site>) with a site string '
             'not in the serve/faults.py FAULT_SITES registry — a '
             'typo\'d site silently never fires, so the chaos test '
             'passes WITHOUT injecting anything (register the site '
             'or fix the spelling)',
    'GC119': 'bare-int4-bit-twiddling: int4/uint4 astype or manual '
             'nibble packing (<<4 / >>4 / &0xF) in a compute dir '
             'outside models/quantization.py — the packed-nibble '
             'layout (pack axis, sign extension, scale grouping) is '
             'defined in exactly one place; hand-rolled twiddling '
             'silently diverges from it (use pack_int4/unpack_int4/'
             'qeinsum)',
    'GC120': 'unjournaled-lifecycle-write: a replica-row / journal / '
             'note mutation in serve/replica_managers.py or '
             'serve/controller.py outside the journaled persist '
             'helpers (_persist/_untrack/_journal_start/'
             '_journal_finish/_put_note/_del_note/'
             '_persist_autoscaler_state) — crash-safe restart '
             'reconciliation is only sound if the journal can never '
             'drift from what the state machines actually did',
    'GC121': 'per-layer-pool-read: per-layer KV-pool slice '
             '(dynamic_index_in_dim / scalar layer subscript) in any '
             'inference function, or a _gather_layer call in a '
             'decode-scoped one — consumers take the FULL stacked '
             'pool and the layer as an index (paged-attention '
             'kernels: scalar prefetch; _gather_layer: flat gather), '
             'never a materialized per-layer pool copy; '
             'prefill/verify-shaped functions may call _gather_layer '
             '(they need contiguous rows)',
    'GC122': 'unbounded-lb-map-growth: growth mutation on a self.* '
             'container (subscript-assign / append / add / setdefault '
             '/ update / ...) in serve/load_balancing_policies.py '
             'outside the BoundedStore helper — LB-policy tables see '
             'unbounded session/replica churn, so every runtime map '
             'goes through BoundedStore (TTL + LRU cap, evictions '
             'counted); wholesale reassignment stays legal',
    'GC123': 'untraced-outbound-http: body-carrying urllib '
             'Request/urlopen under serve/ outside serve/wire.py — a '
             'raw POST drops the X-Skytpu-Trace context at that hop '
             'and the assembled fleet trace gets a hole exactly where '
             'the cross-process leg happened; route body-carrying '
             'calls through the wire helpers (build_request / '
             'post_json / post_bytes). Read-only GETs and liveness '
             'probes are exempt',
    'GC201': 'impure-jit: impure or host-synchronizing call inside a '
             '@jax.jit body',
    'GC202': 'host-sync: device->host readback outside the '
             'host_sync()/host_block() helpers (compute layer only)',
}

# Directories (relative to the package root) where the GC2xx hot-path
# rules apply.
COMPUTE_DIRS = ('inference', 'models', 'ops', 'train')

# The sanctioned-sync helper module: GC202 does not apply to its own
# implementation.
HOST_HELPER_SUFFIX = 'utils/host.py'

# The sanctioned quantization module: GC110 does not apply to its own
# implementation (nor to any function whose name carries 'quantize' —
# llama.quantize_kv_rows is the KV write helper the rule points at).
QUANT_HELPER_SUFFIX = 'models/quantization.py'
# Spellings of the int8 dtype as an astype argument.
_INT8_DTYPES = {'jnp.int8', 'jax.numpy.int8', 'np.int8', 'numpy.int8'}

# --------------------------------------------------------------------- GC119
# int4 nibble spellings: 4-bit dtypes as astype/asarray args, plus the
# manual bit-twiddling shapes (shift-by-4 / low-nibble mask) that
# re-implement the packed layout by hand. The quantization module is
# the one sanctioned home of both (pack_int4 / unpack_int4 / qeinsum).
_INT4_DTYPES = {'jnp.int4', 'jax.numpy.int4', 'np.int4', 'numpy.int4',
                'jnp.uint4', 'jax.numpy.uint4', 'ml_dtypes.int4',
                'ml_dtypes.uint4'}
_INT4_DTYPE_STRINGS = {'int4', 'uint4'}
# Scope names whose functions ARE nibble helpers by construction
# (mirrors GC110's 'quantize' scope exemption).
_NIBBLE_SCOPE_MARKERS = ('quantize', 'pack_int4', 'unpack_int4')

# --------------------------------------------------------------------- GC121
# A per-layer pool slice forces XLA to materialize that layer's whole
# pool as a fresh operand of the consumer, in every program shape: the
# KV-bandwidth-bound decode step, and the prefill chunk too (19 % of
# the 7B chunk program on the chip; PERF.md, PR 28). So the slice half
# applies to every function in inference/: consumers take the FULL
# stacked pool and the layer as an index (ops/paged_attention.py: the
# layer rides scalar prefetch; the cross-layer variant runs every layer
# in one pallas_call; _gather_layer folds it into the gather's row
# index). The gather-per-layer half stays decode-scoped:
# prefill/verify-shaped functions need contiguous rows for
# cached_attention; the one legacy gather fallback inside
# paged_decode_horizon is suppressed inline, so any NEW site
# hard-fails.
_POOL_SLICE_FNS = {'lax.dynamic_index_in_dim',
                   'jax.lax.dynamic_index_in_dim',
                   'dynamic_index_in_dim'}
_GATHER_LAYER_FNS = {'_gather_layer', 'gather_layer'}
_POOL_SCALE_NAMES = {'k_scale', 'v_scale'}
_GC121_GATHER_SCOPE_MARKERS = ('prefill', 'verify')

# --------------------------------------------------------------------- GC114
# KV transfer paths: the disaggregated-serving wire codec and handoff
# plumbing. int8 KV rides the wire as codes + scales; ANY wide-float
# conversion (or dequantize call) here is a silent 2x on handoff
# bytes — the codec never changes dtype, so these files stay free of
# both spellings entirely.
TRANSFER_PATH_SUFFIXES = ('inference/kv_transfer.py', 'serve/disagg.py')
_WIDE_FLOAT_DTYPES = {
    'jnp.bfloat16', 'jax.numpy.bfloat16', 'jnp.float32',
    'jax.numpy.float32', 'jnp.float16', 'jax.numpy.float16',
    'np.float32', 'numpy.float32', 'np.float16', 'numpy.float16',
    'np.float64', 'numpy.float64', 'ml_dtypes.bfloat16',
}
_WIDE_FLOAT_NAMES = {'bfloat16', 'float16', 'float32', 'float64'}

_SUPPRESS_RE = re.compile(r'graftcheck:\s*disable=([A-Za-z0-9,\s]+)')

# --------------------------------------------------------------------- GC102
# Calls that block regardless of what lock is held.
_ALWAYS_BLOCKING = {
    'time.sleep', 'sleep',
    'urllib.request.urlopen', 'urlopen',
    'subprocess.run', 'subprocess.call', 'subprocess.check_call',
    'subprocess.check_output',
    'socket.create_connection',
}
# Methods that block regardless of arguments.
_BLOCKING_METHODS = {'recv', 'accept', 'communicate', 'serve_forever'}
# Methods that block *unboundedly* when called with no args and no
# timeout= (Event.wait, Queue.get, Thread.join, Popen.wait).
_UNBOUNDED_WAIT_METHODS = {'wait', 'get', 'join'}
# sqlite-backed state modules and cluster-RPC-grade modules: calling
# them under a *threading* lock stalls every contending thread behind
# disk/SSH latency. (Under a DB-named lock the sqlite calls are the
# point.)
_STATE_MODULES = {'state', 'serve_state', 'global_state', 'job_lib',
                  'agent_job_lib'}
_RPC_MODULES = {'core', 'execution', 'backend_utils', 'provisioner'}
# --------------------------------------------------------------------- GC108
# Speculative-proposer host entry points: O(history x max_ngram) numpy
# matching per slot. Under the serve layer's engine lock this work
# serializes every HTTP handler behind proposer CPU time — the serve
# loop must call prepare_proposals() BEFORE locking (the engine
# revalidates and recomputes stale entries inside step()).
_PROPOSER_HOST_FNS = {'prepare_proposals', 'ngram_propose'}

# --------------------------------------------------------------------- GC111
# Synchronous engine-path entry points banned inside serve/ coroutines:
# each one either drives the engine (step / run_to_completion), takes
# the scheduler/engine locks (submit / add_request / fill_engine /
# cancel-side pops), or runs proposer CPU work — all of it blocks the
# event loop for every concurrent stream. The directory the rule
# applies to:
SERVE_DIR = 'serve'
_ENGINE_SYNC_CALLS = {'step', 'submit', 'submit_stream', 'add_request',
                      'run_to_completion', 'fill_engine', 'pop_finished',
                      'prepare_proposals'}
# Argless no-timeout waits that park the event loop (Outbox.get /
# Event.wait / Queue.get / Thread.join). With a timeout they are still
# wrong in a coroutine, but bounded — the unbounded form is the
# deadlock-shaped one this rule hard-fails.
_ASYNC_BLOCKING_WAITS = {'get', 'wait', 'join'}

# --------------------------------------------------------------------- GC112
# Directories whose retry/poll loops must back off or jitter: the
# serve control plane (replica relaunch, drain/DB polls) and the jobs
# layer (status polls, recovery relaunches) both run MANY concurrent
# loops against shared, failure-correlated resources.
RETRYLOOP_DIRS = ('serve', 'jobs')
# RNG method spellings whose presence in a sleep delay expression
# marks it as jittered (module `random`, a Random instance, numpy).
_JITTER_METHODS = {'random', 'uniform', 'expovariate', 'gauss',
                   'betavariate', 'triangular', 'randint', 'randrange',
                   'choice', 'rand', 'random_sample'}

# --------------------------------------------------------------------- GC115
# Scaling-decision modules: every decision path is clock-injectable
# (`now` parameter / constructor `clock=`), so a direct wall-clock CALL
# anywhere in them silently breaks deterministic trace replay. Name
# *references* (`clock=time.time` default args) are the injection
# mechanism itself and stay legal.
SCALING_PATH_SUFFIXES = ('serve/autoscalers.py', 'serve/forecaster.py')
_SCALING_WALLCLOCK = {'time.time', 'time.monotonic'}
_SCALING_WALLCLOCK_BARE = {'monotonic'}   # from time import monotonic

# --------------------------------------------------------------------- GC116
# The gang layer: every distributed join — barrier waits, member
# joins, follower sync waits — must be BOUNDED, or one rank that never
# comes up hangs the whole gang (the exact half-alive failure mode
# gang-atomicity exists to kill). Argless no-timeout wait/join/get/
# barrier calls are flagged file-wide (not just under locks or in
# coroutines like GC102/GC111), and jax.distributed.initialize must
# carry initialization_timeout.
GANG_PATH_SUFFIXES = ('serve/gang.py',)
_GANG_JOIN_METHODS = {'wait', 'join', 'get', 'barrier'}

# --------------------------------------------------------------------- GC117
# The fleet simulator: deterministic virtual time ONLY. Any time.*
# call here (including sleep — virtual sleeps go through
# EventLoop.sleep / the env seam) desynchronizes same-seed replays.
# Name references (e.g. passing a clock callable) stay legal, as do
# method calls like loop.sleep(...) — only the time-module spellings
# are flagged.
SIM_PATH_MARKER = '/serve/sim/'
_SIM_WALLCLOCK = {'time.time', 'time.monotonic', 'time.sleep',
                  'time.perf_counter', 'time.perf_counter_ns',
                  'time.time_ns', 'time.monotonic_ns',
                  'time.process_time'}
# from-import spellings flagged bare (ambiguous ones like 'sleep' and
# 'time' are skipped — a sim module has no business importing them
# from time either, but the dotted form is the realistic miss).
_SIM_WALLCLOCK_BARE = {'monotonic', 'perf_counter', 'time_ns',
                       'monotonic_ns'}

# --------------------------------------------------------------------- GC120
# The controller failure domain's one invariant: every lifecycle-state
# mutation (replica rows, journal ops, controller notes — spelled as a
# direct serve_state call or through the env seam) in the manager/
# controller modules goes through the journaled persist helpers, so
# restart reconciliation replays EXACTLY what the state machines did.
# Reads (get_replicas / pending_ops / get_notes / load_replica_rows)
# are not gated; service-level rows (set_service_status / ...) belong
# to the service lifecycle, not the replica journal.
LIFECYCLE_PATH_SUFFIXES = ('serve/replica_managers.py',
                           'serve/controller.py')
_LIFECYCLE_MUTATORS = {'add_or_update_replica', 'set_replica_status',
                       'remove_replica', 'persist_replica',
                       'journal_op_start', 'journal_op_finish',
                       'put_note', 'del_note'}
_LIFECYCLE_HELPER_SCOPES = ('_persist', '_untrack', '_journal_start',
                            '_journal_finish', '_put_note',
                            '_del_note', '_persist_autoscaler_state')

# --------------------------------------------------------------------- GC122
# The LB-policy module's one sanctioned mutable map is BoundedStore
# (TTL + LRU cap, loud evictions). Any OTHER growth mutation on a
# ``self.*`` container there is a slow leak: policies are resident for
# months while sessions, request keys and replica URLs churn
# unboundedly beneath them. Wholesale reassignment (``self.x =
# dict(...)``) replaces rather than grows and stays legal, as do
# mutations of locals (per-call, garbage-collected).
LB_POLICY_PATH_SUFFIXES = ('serve/load_balancing_policies.py',)
_GC122_EXEMPT_SCOPE_MARKERS = ('BoundedStore',)
_GC122_GROW_METHODS = {'append', 'appendleft', 'add', 'setdefault',
                       'update', 'extend', 'insert'}

# --------------------------------------------------------------------- GC123
# The trace-propagating outbound-HTTP helper (serve/wire.py) stamps
# X-Skytpu-Trace on every body-carrying hop (dispatch, KV ingest,
# gang sync, idempotency handoff, controller nudges). A raw
# urllib Request/urlopen WITH a body under serve/ silently drops the
# trace context at that hop — the assembled fleet trace then has a
# hole exactly where the interesting cross-process leg happened.
# Read-only GETs (no body: metrics scrapes, checkpoint exports) and
# liveness probes carry no causal payload and stay on urllib.
WIRE_HELPER_SUFFIX = 'serve/wire.py'
_GC123_HTTP_CALLS = {'urllib.request.urlopen', 'urlopen',
                     'urllib.request.Request', 'request.Request'}
_GC123_EXEMPT_SCOPE_MARKERS = ('probe',)

# --------------------------------------------------------------------- GC118
# The central fault-site registry, resolved lazily (the faults module
# imports telemetry; pulling it at import time would make the linter's
# import graph heavier than it needs to be). Falls back to None when
# the serve package is unavailable (standalone lint runs) — the rule
# then skips rather than false-positives.
_FAULT_SITES_CACHE: Optional[frozenset] = None


def _known_fault_sites() -> Optional[frozenset]:
    global _FAULT_SITES_CACHE
    if _FAULT_SITES_CACHE is None:
        try:
            from skypilot_tpu.serve import faults as _faults
        except ImportError:
            return None      # standalone lint run: skip, don't guess
        _FAULT_SITES_CACHE = frozenset(_faults.FAULT_SITES)
    return _FAULT_SITES_CACHE


# --------------------------------------------------------------------- GC109
# Ad-hoc timing calls banned from inference/ hot paths: telemetry's
# clock/profiler are the sanctioned spellings there (GC201 covers the
# inside-jit case; this covers the host side of the engine loop).
_ADHOC_TIMING = {
    'time.time', 'time.monotonic', 'time.perf_counter',
    'time.perf_counter_ns', 'time.process_time', 'time.thread_time',
}
# from-import spellings (``from time import perf_counter``).
_ADHOC_TIMING_BARE = {'perf_counter', 'perf_counter_ns', 'monotonic',
                      'process_time', 'thread_time'}

# --------------------------------------------------------------------- GC201
_IMPURE_IN_JIT = {
    'time.time', 'time.sleep', 'time.monotonic', 'time.perf_counter',
    'print', 'open', 'input',
    'np.asarray', 'np.array', 'numpy.asarray', 'numpy.array',
    'jax.device_get', 'jax.block_until_ready',
}
_IMPURE_PREFIXES_IN_JIT = ('np.random.', 'numpy.random.', 'random.')

_LOCK_FACTORIES = {'threading.Lock', 'threading.RLock',
                   'threading.Condition', 'Lock', 'RLock', 'Condition'}
_DB_LOCK_MARKERS = ('db_lock', 'state_lock', 'scheduler_lock', 'filelock')


@dataclasses.dataclass
class Violation:
    rule: str
    path: str               # repo-relative path
    line: int
    col: int
    func: str               # enclosing scope qualname ('' = module)
    message: str
    source: str             # stripped source line

    @property
    def fingerprint(self) -> str:
        """Stable identity for the baseline: deliberately excludes the
        line number so unrelated edits above a known violation don't
        invalidate the suppression."""
        return f'{self.path}::{self.rule}::{self.func}::{self.source}'

    def format(self) -> str:
        return (f'{self.path}:{self.line}:{self.col}: {self.rule} '
                f'{self.message}\n    {self.source}')


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return '.'.join(reversed(parts))
    return None


def _self_attr(node: ast.AST) -> Optional[str]:
    """'x' for ``self.x`` (through one Subscript level: ``self.x[k]``)."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == 'self'):
        return node.attr
    return None


def _has_timeout(call: ast.Call) -> bool:
    return any(kw.arg == 'timeout' for kw in call.keywords)


def _lock_category(item: ast.AST, lock_attrs: Set[str],
                   db_locals: Optional[Set[str]] = None) -> Optional[str]:
    """Classify a with-item expression: None (not a lock), 'thread'
    (in-process mutual exclusion), or 'db' (a lock whose purpose is
    serializing DB/file access — sqlite calls under it are exempt).
    ``db_locals`` are local names known to hold file locks
    (``x = filelock.FileLock(...)``)."""
    expr = item
    if isinstance(expr, ast.Call):
        expr = expr.func
    name = _dotted(expr)
    if name is None:
        return None
    low = name.lower()
    attr = _self_attr(expr)
    if any(m in low for m in _DB_LOCK_MARKERS):
        return 'db'
    if db_locals and isinstance(expr, ast.Name) and expr.id in db_locals:
        return 'db'
    if attr is not None and attr in lock_attrs:
        return 'thread'
    if 'lock' in low.rsplit('.', 1)[-1]:
        return 'thread'
    return None


class _ClassPrepass(ast.NodeVisitor):
    """First pass over a ClassDef: find lock attributes and the set of
    self-attributes ever written while holding one (the lock's
    protected state)."""

    def __init__(self):
        self.lock_attrs: Set[str] = set()
        self.guarded_attrs: Set[str] = set()
        self._lock_depth = 0
        self._in_init = False

    def visit_FunctionDef(self, node):
        outer = self._in_init
        if node.name in ('__init__', '__new__'):
            self._in_init = True
        self.generic_visit(node)
        self._in_init = outer

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node):
        value = node.value
        factory = None
        if isinstance(value, ast.Call):
            factory = _dotted(value.func)
        for tgt in node.targets:
            attr = _self_attr(tgt)
            if attr is None:
                continue
            if factory in _LOCK_FACTORIES:
                self.lock_attrs.add(attr)
            elif self._lock_depth and not self._in_init:
                self.guarded_attrs.add(attr)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        attr = _self_attr(node.target)
        if attr and self._lock_depth and not self._in_init:
            self.guarded_attrs.add(attr)
        self.generic_visit(node)

    def visit_With(self, node):
        held = any(_lock_category(i.context_expr, self.lock_attrs)
                   == 'thread' for i in node.items)
        self._lock_depth += 1 if held else 0
        self.generic_visit(node)
        self._lock_depth -= 1 if held else 0


class _Checker(ast.NodeVisitor):

    def __init__(self, rel: str, lines: List[str], is_compute: bool,
                 is_inference: bool = False,
                 is_quant_helper: bool = False,
                 is_serve: bool = False,
                 is_retryloop_dir: bool = False,
                 is_transfer_path: bool = False,
                 is_scaling_path: bool = False,
                 is_gang_path: bool = False,
                 is_sim_path: bool = False,
                 is_lifecycle_path: bool = False,
                 is_lb_policy_path: bool = False,
                 is_wire_helper: bool = False):
        self.rel = rel
        self.lines = lines
        self.is_compute = is_compute
        self.is_inference = is_inference
        self.is_quant_helper = is_quant_helper
        self.is_serve = is_serve
        self.is_retryloop_dir = is_retryloop_dir
        self.is_transfer_path = is_transfer_path
        self.is_scaling_path = is_scaling_path
        self.is_gang_path = is_gang_path
        self.is_sim_path = is_sim_path
        self.is_lifecycle_path = is_lifecycle_path
        self.is_lb_policy_path = is_lb_policy_path
        self.is_wire_helper = is_wire_helper
        self._flagged_sleeps: Set[int] = set()   # node ids (GC112 dedupe)
        # Aliased time-module spellings seen in this file:
        # ``import time as t`` -> {'t': 'time'};
        # ``from time import monotonic as mono`` -> {'mono':
        # 'time.monotonic'}. The timing rules (GC109/GC115/GC117)
        # canonicalize call names through this map so an alias can't
        # smuggle a wall-clock read past them.
        self._time_aliases: Dict[str, str] = {}
        self.violations: List[Violation] = []
        self._scope: List[str] = []
        self._class: List[Tuple[Set[str], Set[str]]] = []  # (locks, guarded)
        self._locks: List[str] = []     # categories of locks held
        self._db_locals: Set[str] = set()   # names bound to FileLocks
        self._jit_depth = 0
        self._in_init = 0
        # Innermost-function asyncness (a sync def nested inside an
        # async def runs off-loop when handed to an executor, so only
        # the IMMEDIATE enclosing function decides GC111).
        self._async_stack: List[bool] = []

    # ------------------------------------------------------------ helpers
    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, 'lineno', 1)
        src = (self.lines[line - 1].strip()
               if 0 < line <= len(self.lines) else '')
        self.violations.append(Violation(
            rule=rule, path=self.rel, line=line,
            col=getattr(node, 'col_offset', 0) + 1,
            func='.'.join(self._scope), message=message, source=src))

    @property
    def _lock_attrs(self) -> Set[str]:
        return self._class[-1][0] if self._class else set()

    @property
    def _guarded(self) -> Set[str]:
        return self._class[-1][1] if self._class else set()

    def _thread_lock_held(self) -> bool:
        return 'thread' in self._locks

    def _any_lock_held(self) -> bool:
        return bool(self._locks)

    # ------------------------------------------------------------- scopes
    def visit_ClassDef(self, node):
        pre = _ClassPrepass()
        pre.visit(node)
        self._class.append((pre.lock_attrs, pre.guarded_attrs))
        self._scope.append(node.name)
        self._check_handler_timeout(node)
        self.generic_visit(node)
        self._scope.pop()
        self._class.pop()

    def _check_handler_timeout(self, node: ast.ClassDef) -> None:
        bases = {(_dotted(b) or '').rsplit('.', 1)[-1]
                 for b in node.bases}
        if not bases & {'BaseHTTPRequestHandler', 'StreamRequestHandler',
                        'SimpleHTTPRequestHandler'}:
            return
        for stmt in node.body:
            if isinstance(stmt, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == 'timeout'
                    for t in stmt.targets):
                return
            if (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)
                    and stmt.target.id == 'timeout'):
                return
        self._add('GC107', node,
                  f'{node.name} extends an http.server handler but sets '
                  'no `timeout` class attribute — a slow-loris client '
                  'pins one server thread forever')

    def _is_jit_decorated(self, node) -> bool:
        for dec in node.decorator_list:
            d = dec
            if isinstance(d, ast.Call):
                fname = _dotted(d.func)
                if fname in ('jax.jit', 'jit'):
                    return True
                if fname in ('functools.partial', 'partial') and d.args:
                    if _dotted(d.args[0]) in ('jax.jit', 'jit'):
                        return True
                continue
            if _dotted(d) in ('jax.jit', 'jit'):
                return True
        return False

    def _visit_func(self, node, is_async: bool):
        jit = self._is_jit_decorated(node)
        self._jit_depth += 1 if jit else 0
        self._in_init += 1 if node.name in ('__init__', '__new__') else 0
        self._scope.append(node.name)
        self._async_stack.append(is_async)
        self.generic_visit(node)
        self._async_stack.pop()
        self._scope.pop()
        self._in_init -= 1 if node.name in ('__init__', '__new__') else 0
        self._jit_depth -= 1 if jit else 0

    def visit_FunctionDef(self, node):
        self._visit_func(node, is_async=False)

    def visit_AsyncFunctionDef(self, node):
        self._visit_func(node, is_async=True)

    # ------------------------------------------------- time aliases
    def visit_Import(self, node):
        for alias in node.names:
            if alias.name == 'time' and alias.asname:
                self._time_aliases[alias.asname] = 'time'
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.module == 'time' and not node.level:
            for alias in node.names:
                if alias.asname:
                    self._time_aliases[alias.asname] = \
                        f'time.{alias.name}'
        self.generic_visit(node)

    def _canon_time_name(self, name: str) -> str:
        """Canonical time.* spelling for an aliased call name:
        ``t.monotonic`` -> ``time.monotonic`` (import time as t),
        ``now`` -> ``time.time`` (from time import time as now).
        Unaliased names pass through untouched, so the bare-name
        fallbacks in the timing rules keep working."""
        if not name or not self._time_aliases:
            return name
        head, dot, rest = name.partition('.')
        target = self._time_aliases.get(head)
        if target is None:
            return name
        if dot:
            return f'time.{rest}' if target == 'time' else name
        return target

    @property
    def _in_async(self) -> bool:
        return bool(self._async_stack) and self._async_stack[-1]

    def visit_With(self, node):
        cats = [c for c in (_lock_category(i.context_expr,
                                           self._lock_attrs,
                                           self._db_locals)
                            for i in node.items) if c]
        self._locks.extend(cats)
        self.generic_visit(node)
        del self._locks[len(self._locks) - len(cats):]

    visit_AsyncWith = visit_With

    # ------------------------------------------------------------- GC112
    def visit_While(self, node):
        if self.is_retryloop_dir:
            self._check_fixed_sleep_loop(node)
        self.generic_visit(node)

    def visit_For(self, node):
        if self.is_retryloop_dir:
            self._check_fixed_sleep_loop(node)
        self.generic_visit(node)

    def _check_fixed_sleep_loop(self, loop) -> None:
        """GC112: a ``time.sleep`` whose delay never changes across
        iterations, inside a loop in serve//jobs/. The delay counts as
        dynamic when its expression draws from an RNG (jitter) or
        references a name reassigned inside the loop (backoff)."""
        assigned: Set[str] = set()
        for sub in ast.walk(loop):
            if isinstance(sub, ast.Assign):
                for tgt in sub.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            assigned.add(n.id)
            elif isinstance(sub, ast.AugAssign):
                for n in ast.walk(sub.target):
                    if isinstance(n, ast.Name):
                        assigned.add(n.id)
            elif isinstance(sub, ast.For):
                for n in ast.walk(sub.target):
                    if isinstance(n, ast.Name):
                        assigned.add(n.id)
        for sub in ast.walk(loop):
            if not isinstance(sub, ast.Call) or id(sub) in \
                    self._flagged_sleeps:
                continue
            name = _dotted(sub.func)
            if name not in ('time.sleep', 'sleep') or not sub.args:
                continue
            if self._sleep_delay_is_fixed(sub.args[0], assigned):
                self._flagged_sleeps.add(id(sub))
                self._add('GC112', sub,
                          'fixed-delay sleep inside a retry/poll loop '
                          'synchronizes retry storms across the fleet '
                          '— add backoff (reassign the delay in the '
                          'loop) and/or jitter (multiply by a random '
                          'draw), or wait on an Event with a timeout')

    @staticmethod
    def _sleep_delay_is_fixed(arg: ast.AST, assigned: Set[str]) -> bool:
        """Loop-invariant delay heuristic: fixed unless the expression
        contains an RNG call, a name reassigned inside the loop, or an
        attribute/subscript/call read (unknown value — conservatively
        treated as dynamic to keep the rule low-noise)."""
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Call):
                cname = _dotted(sub.func) or ''
                leaf = cname.rsplit('.', 1)[-1]
                if (cname.split('.', 1)[0] == 'random'
                        or leaf in _JITTER_METHODS):
                    return False
                # Any other call: value unknown per-iteration — assume
                # dynamic (poll_interval()-style accessors).
                return False
            if isinstance(sub, ast.Name) and sub.id in assigned:
                return False
            if isinstance(sub, (ast.Attribute, ast.Subscript)):
                return False
        return True

    # ------------------------------------------------------------- GC101
    def _check_state_write(self, target: ast.AST, node: ast.AST) -> None:
        attr = _self_attr(target)
        if (attr and attr in self._guarded and attr not in self._lock_attrs
                and not self._in_init and not self._thread_lock_held()):
            self._add('GC101', node,
                      f'self.{attr} is written under a lock elsewhere in '
                      'this class but written here without it')

    def visit_Assign(self, node):
        if isinstance(node.value, ast.Call):
            factory = _dotted(node.value.func) or ''
            if factory.rsplit('.', 1)[-1] == 'FileLock':
                self._db_locals.update(
                    t.id for t in node.targets
                    if isinstance(t, ast.Name))
        for tgt in node.targets:
            self._check_state_write(tgt, node)
            if self.is_lb_policy_path:
                self._check_lb_map_growth_target(tgt, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_state_write(node.target, node)
        if self.is_lb_policy_path:
            self._check_lb_map_growth_target(node.target, node)
        self.generic_visit(node)

    # ----------------------------------------------------------- excepts
    def visit_ExceptHandler(self, node):
        if node.type is None:
            if not self._reraises(node):
                self._add('GC104', node,
                          'bare `except:` (catches KeyboardInterrupt / '
                          'SystemExit); catch Exception or narrower')
        elif self._is_broad(node.type) and self._is_swallowed(node):
            self._add('GC105', node,
                      'broad except swallows the failure silently — log '
                      'it, re-raise, or narrow the exception type')
        self.generic_visit(node)

    @staticmethod
    def _is_broad(type_node: ast.AST) -> bool:
        names = ([_dotted(e) for e in type_node.elts]
                 if isinstance(type_node, ast.Tuple)
                 else [_dotted(type_node)])
        return any(n in ('Exception', 'BaseException') for n in names)

    @staticmethod
    def _reraises(node: ast.ExceptHandler) -> bool:
        return any(isinstance(n, ast.Raise)
                   for n in ast.walk(node))  # type: ignore[arg-type]

    @staticmethod
    def _is_swallowed(node: ast.ExceptHandler) -> bool:
        """True when the handler body does nothing observable: no call
        (logging or otherwise), no raise, no assignment — just
        pass/continue/constant-return."""
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.Call, ast.Raise, ast.Assign,
                                    ast.AugAssign, ast.Yield,
                                    ast.YieldFrom)):
                    return False
        return True

    # -------------------------------------------------------------- calls
    def visit_Call(self, node):
        name = _dotted(node.func) or ''
        method = (node.func.attr
                  if isinstance(node.func, ast.Attribute) else '')
        self._check_timeouts(node, name)
        if self.is_compute:
            # Applies inside jit bodies too — int8 KV writes live in
            # the jitted prefill/decode scans.
            self._check_int8_write(node, method)
            self._check_int4_write(node, method)
        if self.is_inference:
            self._check_device_put(node, name)
            self._check_pool_slice_call(node, name)
        if self.is_transfer_path:
            self._check_wire_dtype(node, name, method)
        if self.is_scaling_path:
            self._check_scaling_clock(node, name)
        if self.is_sim_path:
            self._check_sim_wallclock(node, name)
        if self.is_gang_path:
            self._check_gang_join(node, name, method)
        if self.is_serve and method == 'fire':
            self._check_fault_site(node)
        if self.is_serve and not self.is_wire_helper:
            self._check_untraced_http(node, name)
        if self.is_lifecycle_path:
            self._check_lifecycle_write(node, name, method)
        if self.is_lb_policy_path:
            self._check_lb_map_growth_call(node, method)
        if self.is_serve and self._in_async:
            self._check_async_engine_call(node, name, method)
        if self._any_lock_held():
            self._check_blocking_under_lock(node, name, method)
        if self._jit_depth:
            self._check_jit_purity(node, name, method)
        elif self.is_compute:
            self._check_host_sync(node, name, method)
            if self.is_inference:
                self._check_adhoc_timing(node, name)
        self.generic_visit(node)

    # Functions where jax.device_put IS the sanctioned spelling:
    # construction-time placement of params and caches (runs once, off
    # the step path). Everything else in inference/ uses
    # utils.host.device_upload (h2d-only by contract) — or is a bug.
    _PLACEMENT_FUNCS = ('prepare_params', '__init__', 'from_pretrained')

    def _check_device_put(self, node: ast.Call, name: str) -> None:
        """GC113: bare ``jax.device_put`` in an inference/ step path.
        On a committed (mesh-sharded) array device_put is an implicit
        RESHARD — a collective (or full host round trip) the zero-
        resharding steady-state contract bans; on host operands it is
        an upload that must use the auditable ``device_upload``
        spelling instead."""
        if name != 'jax.device_put':
            return
        if any(s in self._PLACEMENT_FUNCS for s in self._scope):
            return
        self._add('GC113', node,
                  'jax.device_put outside the sanctioned placement '
                  'helpers (prepare_params / __init__ / '
                  'from_pretrained) — use utils.host.device_upload '
                  'for per-step host uploads; resharding committed '
                  'state in the step path is banned')

    # ------------------------------------------------------------- GC121
    @staticmethod
    def _is_pool_named(node: ast.AST) -> bool:
        """A KV pool (or its scale pool) by naming convention: the last
        identifier segment mentions 'pool' (pool_k / ks_pool /
        cache.pool_v) or is a scale-pool field (cache.k_scale)."""
        dotted = _dotted(node)
        if not dotted:
            return False
        seg = dotted.rsplit('.', 1)[-1]
        return 'pool' in seg or seg in _POOL_SCALE_NAMES

    def _gc121_decode_scope(self) -> bool:
        """GC121's gather half polices DECODE-scoped functions only:
        prefill/verify-shaped scopes legitimately gather contiguous
        rows for ``cached_attention``."""
        if any(m in s for s in self._scope
               for m in _GC121_GATHER_SCOPE_MARKERS):
            return False
        return any('decode' in s for s in self._scope)

    def _check_pool_slice_call(self, node: ast.Call, name: str) -> None:
        """GC121 (call half): ``lax.dynamic_index_in_dim(pool, li)``
        anywhere in inference/, or ``_gather_layer(...)`` in a decode
        scope — a materialized per-layer pool read."""
        short = name.rsplit('.', 1)[-1]
        if (name in _POOL_SLICE_FNS and node.args
                and self._is_pool_named(node.args[0])):
            self._add('GC121', node,
                      'per-layer pool slice — dynamic_index_in_dim '
                      'materializes a copy of the layer\'s whole pool '
                      'per layer step; hand the consumer the FULL '
                      'stacked pool and the layer as an index '
                      '(paged-attention kernels: scalar prefetch; '
                      '_gather_layer: flat gather)')
        elif short in _GATHER_LAYER_FNS and self._gc121_decode_scope():
            self._add('GC121', node,
                      'gather-per-layer on the paged decode path — '
                      '_gather_layer materializes a full KV copy per '
                      'layer per step; decode reads go through the '
                      'paged-attention kernels instead')

    def visit_Subscript(self, node):
        """GC121 (subscript half): a scalar layer subscript of a pool
        (``pool_k[li]`` / ``pool_k[0]`` / ``pool_k[li, ...]``) anywhere
        in inference/ — the same materialized per-layer read as the
        dynamic_index_in_dim spelling."""
        if self.is_inference and self._is_pool_named(node.value):
            idx = node.slice
            if isinstance(idx, ast.Tuple) and idx.elts:
                idx = idx.elts[0]
            scalar = (isinstance(idx, ast.Name)
                      or (isinstance(idx, ast.Constant)
                          and isinstance(idx.value, int)))
            if scalar:
                self._add('GC121', node,
                          'scalar layer subscript of a KV pool — a '
                          'materialized per-layer pool read; hand the '
                          'consumer the FULL stacked pool and the '
                          'layer as an index')
        self.generic_visit(node)

    def _check_wire_dtype(self, node: ast.Call, name: str,
                          method: str) -> None:
        """GC114: wide-float conversion or dequantize call on a KV
        transfer path. The wire codec moves KV in its STORED dtype —
        int8 codes + fp32 scales stay exactly as resident — so a
        ``.astype(bfloat16/float32/...)`` (or anything spelled
        ``dequant*``) in these files means someone is widening KV for
        the wire: 2x the handoff bytes, silently."""
        leaf = (method or name.rsplit('.', 1)[-1]).lower()
        if 'dequant' in leaf:
            self._add('GC114', node,
                      f'{leaf}() on a KV transfer path — handoffs move '
                      'int8 KV as codes + scales (the kv_transfer wire '
                      'codec); dequantizing for the wire doubles the '
                      'bytes')
            return
        if method != 'astype' or not node.args:
            return
        arg = node.args[0]
        dtype = _dotted(arg)
        wide = (dtype in _WIDE_FLOAT_DTYPES
                or (isinstance(arg, ast.Constant)
                    and arg.value in _WIDE_FLOAT_NAMES))
        if wide:
            self._add('GC114', node,
                      '.astype(wide float) on a KV transfer path — '
                      'int8 KV must stay int8 codes + scales end to '
                      'end; serialize with the kv_transfer wire codec '
                      '(no dtype conversion)')

    def _check_int8_write(self, node: ast.Call, method: str) -> None:
        """GC110: ``x.astype(jnp.int8)`` / ``x.astype('int8')`` outside
        the quantization helpers. Exempt: the quantization module
        itself, and any enclosing function whose name carries
        'quantize' (``quantize_kv_rows``, ``_quantize_array``, ...) —
        those ARE the sanctioned spellings this rule routes writers
        to."""
        if (self.is_quant_helper or method != 'astype'
                or not node.args):
            return
        if any('quantize' in s for s in self._scope):
            return
        arg = node.args[0]
        dtype = _dotted(arg)
        is_int8 = (dtype in _INT8_DTYPES
                   or (isinstance(arg, ast.Constant)
                       and arg.value == 'int8'))
        if is_int8:
            self._add('GC110', node,
                      '.astype(int8) outside the quantization helpers '
                      'silently drops the scale — write int8 KV/weights '
                      'through llama.quantize_kv_rows / '
                      'models.quantization (codes + absmax scales)')

    def _check_int4_write(self, node: ast.Call, method: str) -> None:
        """GC119 (call half): ``x.astype(jnp.int4/uint4)`` — or the
        string spellings — outside the quantization module. A bare
        4-bit astype bypasses the one packed-nibble layout contract
        (pack axis, sign extension, scale grouping)."""
        if (self.is_quant_helper or method != 'astype'
                or not node.args):
            return
        if any(m in s for s in self._scope
               for m in _NIBBLE_SCOPE_MARKERS):
            return
        arg = node.args[0]
        dtype = _dotted(arg)
        is_int4 = (dtype in _INT4_DTYPES
                   or (isinstance(arg, ast.Constant)
                       and arg.value in _INT4_DTYPE_STRINGS))
        if is_int4:
            self._add('GC119', node,
                      '.astype(int4/uint4) outside the quantization '
                      'helpers — the packed-nibble layout is defined '
                      'once in models/quantization.py (pack_int4/'
                      'unpack_int4/qeinsum); a bare 4-bit cast '
                      'silently diverges from it')

    def visit_BinOp(self, node):
        """GC119 (operator half): manual nibble twiddling — ``<< 4`` /
        ``>> 4`` / ``& 0xF`` — in a compute dir outside the
        quantization module's sanctioned pack/unpack helpers."""
        if (self.is_compute and not self.is_quant_helper
                and not any(m in s for s in self._scope
                            for m in _NIBBLE_SCOPE_MARKERS)):
            nibble = (
                (isinstance(node.op, (ast.LShift, ast.RShift))
                 and isinstance(node.right, ast.Constant)
                 and node.right.value == 4)
                or (isinstance(node.op, ast.BitAnd)
                    and any(isinstance(s, ast.Constant)
                            and s.value == 0xF
                            for s in (node.left, node.right))))
            if nibble:
                self._add('GC119', node,
                          'manual nibble bit-twiddling (<<4 / >>4 / '
                          '&0xF) in a compute dir — int4 packing has '
                          'exactly one layout, defined in models/'
                          'quantization.py; use pack_int4/unpack_int4 '
                          '(or qeinsum for fused dequant)')
        self.generic_visit(node)

    def _check_async_engine_call(self, node: ast.Call, name: str,
                                 method: str) -> None:
        """GC111: a synchronous engine call or an unbounded blocking
        wait inside an ``async def`` in ``serve/`` parks the event
        loop — every concurrent stream stalls behind it."""
        target = method or name.rsplit('.', 1)[-1]
        if target in _ENGINE_SYNC_CALLS:
            self._add('GC111', node,
                      f'synchronous engine call {target}() inside an '
                      'async coroutine blocks the event loop for every '
                      'concurrent stream — await the async adapter '
                      '(Outbox.aget) or hand it to a thread via '
                      'await loop.run_in_executor(...)')
        elif (target in _ASYNC_BLOCKING_WAITS and not node.args
              and not _has_timeout(node)
              and not name.startswith('asyncio.')):
            self._add('GC111', node,
                      f'unbounded .{target}() inside an async '
                      'coroutine parks the event loop — await an '
                      'async primitive or run the wait in an executor')

    def _check_gang_join(self, node: ast.Call, name: str,
                         method: str) -> None:
        """GC116: an unbounded distributed join in the gang layer. A
        barrier/join/wait/get with neither a positional bound nor a
        ``timeout=`` hangs the whole gang on one dead rank; the gang
        contract is fail-fast (join timeout, heartbeat timeout), so
        every wait must carry one. ``jax.distributed.initialize`` must
        pass ``initialization_timeout`` for the same reason."""
        leaf = method or name.rsplit('.', 1)[-1]
        if name.endswith('distributed.initialize'):
            if not any(kw.arg == 'initialization_timeout'
                       for kw in node.keywords):
                self._add('GC116', node,
                          'jax.distributed.initialize without '
                          'initialization_timeout in the gang layer — '
                          'a member that never starts must fail the '
                          'gang, not hang its bootstrap forever')
            return
        if (leaf in _GANG_JOIN_METHODS and not node.args
                and not _has_timeout(node)):
            self._add('GC116', node,
                      f'unbounded .{leaf}() in the gang layer — a '
                      'distributed join with no timeout hangs the '
                      'whole gang on one dead rank; pass timeout= '
                      '(the gang contract is fail-fast)')

    def _check_scaling_clock(self, node: ast.Call, name: str) -> None:
        """GC115: a direct wall-clock CALL in a scaling-decision
        module. The autoscaler/forecaster decision paths take an
        explicit ``now`` or draw from the injected ``clock`` — a raw
        ``time.time()`` makes the decision unreplayable under test
        (and silently divergent between the test's synthetic trace and
        production)."""
        name = self._canon_time_name(name)
        if (name in _SCALING_WALLCLOCK
                or ('.' not in name and name in _SCALING_WALLCLOCK_BARE)):
            self._add('GC115', node,
                      f'{name}() inside a scaling decision path — use '
                      'the injected clock (the `now` parameter / '
                      'self._clock) so scaling logic stays '
                      'deterministic under test')

    def _check_fault_site(self, node: ast.Call) -> None:
        """GC118: every literal site string handed to ``.fire()``
        under ``serve/`` must exist in the central registry
        (``faults.FAULT_SITES``). A typo'd site is legal Python that
        counts invocations of a site NO RULE will ever name — the hook
        silently never fires and the chaos test it was written for
        passes vacuously. Non-literal sites (a loop over a site tuple,
        e.g. the simulator's storm sweep) are skipped — their tuples
        hold registry members the fixture tests pin."""
        site = None
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            site = node.args[0].value
        else:
            for kw in node.keywords:
                if kw.arg == 'site' and isinstance(kw.value,
                                                   ast.Constant) \
                        and isinstance(kw.value.value, str):
                    site = kw.value.value
        if site is None:
            return
        known = _known_fault_sites()
        if known is None or site in known:
            return
        self._add('GC118', node,
                  f'.fire({site!r}) names a site missing from '
                  'serve/faults.py FAULT_SITES — this hook will '
                  'SILENTLY never fire (no rule can ever match an '
                  'unregistered site); register the site or fix the '
                  'spelling')

    def _check_untraced_http(self, node: ast.Call, name: str) -> None:
        """GC123: a body-carrying ``urllib`` Request/urlopen under
        ``serve/`` outside the wire helper. The body is what makes it
        a causal hop (dispatch, ingest, sync, handoff) — exactly the
        hops whose missing ``X-Skytpu-Trace`` header leaves a hole in
        the assembled fleet trace. Liveness probes (scope mentions
        'probe') and body-less calls (metrics GETs, checkpoint
        exports) are exempt."""
        if name not in _GC123_HTTP_CALLS:
            return
        data: Optional[ast.AST] = None
        if len(node.args) >= 2:
            data = node.args[1]
        for kw in node.keywords:
            if kw.arg == 'data':
                data = kw.value
        if data is None or (isinstance(data, ast.Constant)
                            and data.value is None):
            return
        if any(m in s.lower() for s in self._scope
               for m in _GC123_EXEMPT_SCOPE_MARKERS):
            return
        short = name.rsplit('.', 1)[-1]
        self._add('GC123', node,
                  f'body-carrying {short}() under serve/ bypasses the '
                  'trace-propagating wire helper — the X-Skytpu-Trace '
                  'header is dropped at this hop and the assembled '
                  'fleet trace gets a hole here; use serve/wire.py '
                  '(build_request / post_json / post_bytes)')

    def _check_lifecycle_write(self, node: ast.Call, name: str,
                               method: str) -> None:
        """GC120: a lifecycle-state mutation (replica row / journal op
        / controller note — via ``serve_state.*`` or the env seam)
        outside the journaled persist helpers. A write the journal
        doesn't see is a write restart reconciliation can't replay —
        the exact drift the controller failure domain exists to
        kill."""
        leaf = method or name.rsplit('.', 1)[-1]
        if leaf not in _LIFECYCLE_MUTATORS:
            return
        if any(s in _LIFECYCLE_HELPER_SCOPES for s in self._scope):
            return
        self._add('GC120', node,
                  f'{leaf}() mutates lifecycle state outside the '
                  'journaled persist helpers '
                  f'({", ".join(_LIFECYCLE_HELPER_SCOPES)}) — route '
                  'the write through them so the journal can never '
                  'drift from the state machine (restart '
                  'reconciliation replays the journal)')

    def _gc122_exempt(self) -> bool:
        return any(m in s for s in self._scope
                   for m in _GC122_EXEMPT_SCOPE_MARKERS)

    def _check_lb_map_growth_target(self, target: ast.AST,
                                    node: ast.AST) -> None:
        """GC122 (stores): ``self.x[k] = v`` / ``self.x[k] += v`` in the
        LB-policy module grows a per-key table keyed by churning
        sessions/replicas — route it through BoundedStore (put/incr)
        so TTL + LRU bound it. Plain ``self.x = ...`` (wholesale
        reassignment) and mutations of locals stay legal."""
        if not isinstance(target, ast.Subscript):
            return
        attr = _self_attr(target)
        if attr is None or self._gc122_exempt():
            return
        self._add('GC122', node,
                  f'per-key write to self.{attr}[...] in the LB-policy '
                  'hot path — sessions and replica URLs churn '
                  'unboundedly, so runtime maps here must be a '
                  'BoundedStore (put/incr: TTL + LRU cap, evictions '
                  'counted), not a raw container')

    def _check_lb_map_growth_call(self, node: ast.Call,
                                  method: str) -> None:
        """GC122 (methods): a growth-method call (append/add/update/...)
        on a ``self.*`` container in the LB-policy module — same leak,
        spelled as a method."""
        if method not in _GC122_GROW_METHODS:
            return
        if not isinstance(node.func, ast.Attribute):
            return
        attr = _self_attr(node.func.value)
        if attr is None or self._gc122_exempt():
            return
        self._add('GC122', node,
                  f'self.{attr}.{method}(...) grows a container in the '
                  'LB-policy hot path — sessions and replica URLs '
                  'churn unboundedly, so runtime collections here '
                  'must go through BoundedStore (TTL + LRU cap, '
                  'evictions counted)')

    def _check_sim_wallclock(self, node: ast.Call, name: str) -> None:
        """GC117: a wall-clock read (or real sleep) inside the fleet
        simulator. The sim's one time axis is the virtual clock
        (``EventLoop.now``/``EventLoop.sleep``); a single ``time.*``
        call makes same-seed runs diverge — silently, since the run
        still *works*, it just stops being byte-replayable."""
        name = self._canon_time_name(name)
        if (name in _SIM_WALLCLOCK
                or ('.' not in name and name in _SIM_WALLCLOCK_BARE)):
            self._add('GC117', node,
                      f'{name}() inside serve/sim/ — the simulator '
                      'runs on the virtual clock only (EventLoop.now '
                      '/ EventLoop.sleep); a wall-clock read breaks '
                      'the byte-identical same-seed replay contract')

    def _check_adhoc_timing(self, node: ast.Call, name: str) -> None:
        name = self._canon_time_name(name)
        if (name in _ADHOC_TIMING
                or ('.' not in name and name in _ADHOC_TIMING_BARE)):
            self._add('GC109', node,
                      f'{name}() in an inference hot path — route '
                      'timing through skypilot_tpu.telemetry '
                      '(clock.now()/clock.monotonic() or the '
                      'step-phase profiler) so overhead is accounted '
                      'and the phase lands in the registry')

    def _check_timeouts(self, node: ast.Call, name: str) -> None:
        if name.rsplit('.', 1)[-1] == 'urlopen' and not _has_timeout(node):
            self._add('GC103', node,
                      'urlopen without timeout= — a wedged peer wedges '
                      'this thread (and any lock it holds) forever')
        elif (name.endswith('create_connection')
              and not _has_timeout(node) and len(node.args) < 2):
            self._add('GC103', node,
                      'socket.create_connection without a timeout')

    def _check_blocking_under_lock(self, node: ast.Call, name: str,
                                   method: str) -> None:
        if name.rsplit('.', 1)[-1] in _PROPOSER_HOST_FNS:
            self._add('GC108', node,
                      f'{name}() (speculative-proposer host work) while '
                      'holding a lock — run it before taking the '
                      'engine lock; the engine revalidates stale '
                      'proposals itself')
            return
        if name in _ALWAYS_BLOCKING:
            self._add('GC102', node,
                      f'{name}() while holding a lock stalls every '
                      'contending thread')
            return
        if method in _BLOCKING_METHODS:
            self._add('GC102', node,
                      f'.{method}() (blocking I/O) while holding a lock')
            return
        if (method in _UNBOUNDED_WAIT_METHODS and not node.args
                and not _has_timeout(node)):
            self._add('GC102', node,
                      f'unbounded .{method}() while holding a lock — '
                      'pass timeout= or move it outside the lock')
            return
        if self._thread_lock_held():
            root = name.split('.', 1)[0]
            if root in _STATE_MODULES and '.' in name:
                self._add('GC102', node,
                          f'sqlite-backed {name}() under a threading '
                          'lock — hoist the DB write out of the hot '
                          'lock (dedicated *_db_lock locks are exempt)')
            elif root in _RPC_MODULES and '.' in name:
                self._add('GC102', node,
                          f'cluster RPC {name}() under a threading lock')

    def _check_jit_purity(self, node: ast.Call, name: str,
                          method: str) -> None:
        if (name in _IMPURE_IN_JIT
                or any(name.startswith(p)
                       for p in _IMPURE_PREFIXES_IN_JIT)):
            self._add('GC201', node,
                      f'{name}() inside a @jax.jit body is impure or '
                      'host-synchronizing — it runs at trace time, not '
                      'per step')
        elif method in ('item', 'block_until_ready') and not node.args:
            self._add('GC201', node,
                      f'.{method}() on a traced value inside @jax.jit')
        elif (name in ('float', 'int', 'bool')
              and len(node.args) == 1
              and isinstance(node.args[0], (ast.Name, ast.Subscript))):
            self._add('GC201', node,
                      f'{name}() on a traced value inside @jax.jit '
                      'forces a concretization error or a baked-in '
                      'constant')

    def _check_host_sync(self, node: ast.Call, name: str,
                         method: str) -> None:
        if name in ('jax.device_get', 'jax.block_until_ready'):
            self._add('GC202', node,
                      f'{name}() outside host_sync()/host_block() — '
                      'route the readback through '
                      'skypilot_tpu.utils.host')
        elif method in ('item', 'block_until_ready') and not node.args:
            self._add('GC202', node,
                      f'.{method}() is an implicit device sync — use '
                      'host_sync()/host_block()')
        elif (name in ('np.asarray', 'numpy.asarray')
              and len(node.args) == 1 and not node.keywords):
            self._add('GC202', node,
                      'bare np.asarray(x) on a (possibly device) array '
                      'is the classic accidental sync — use host_sync() '
                      'for readbacks, or np.asarray(x, dtype) for '
                      'explicit host-side conversion')
        elif (name == 'float' and len(node.args) == 1
              and isinstance(node.args[0], (ast.Name, ast.Subscript))):
            self._add('GC202', node,
                      'float(x) implicitly syncs a device value — use '
                      'host_sync()')


def _line_suppressions(source: str) -> Dict[int, Set[str]]:
    """line -> set of rule ids disabled on that line ('all' disables
    everything)."""
    out: Dict[int, Set[str]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if m:
                rules = {r.strip().upper() if r.strip().lower() != 'all'
                         else 'all' for r in m.group(1).split(',')}
                out.setdefault(tok.start[0], set()).update(
                    r for r in rules if r)
    except tokenize.TokenizeError:
        pass
    return out


def check_source(rel: str, source: str) -> List[Violation]:
    """Run every rule over one file's source; returns violations with
    line-level suppressions already applied."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Violation(rule='GC000', path=rel, line=e.lineno or 1,
                          col=e.offset or 1, func='',
                          message=f'syntax error: {e.msg}', source='')]
    norm = rel.replace('\\', '/')
    is_compute = (any(f'/{d}/' in f'/{norm}' for d in COMPUTE_DIRS)
                  and not norm.endswith(HOST_HELPER_SUFFIX))
    is_inference = is_compute and '/inference/' in f'/{norm}'
    checker = _Checker(norm, source.splitlines(), is_compute,
                       is_inference,
                       is_quant_helper=norm.endswith(
                           QUANT_HELPER_SUFFIX),
                       is_serve=f'/{SERVE_DIR}/' in f'/{norm}',
                       is_retryloop_dir=any(
                           f'/{d}/' in f'/{norm}'
                           for d in RETRYLOOP_DIRS),
                       is_transfer_path=norm.endswith(
                           TRANSFER_PATH_SUFFIXES),
                       is_scaling_path=norm.endswith(
                           SCALING_PATH_SUFFIXES),
                       is_gang_path=norm.endswith(GANG_PATH_SUFFIXES),
                       is_sim_path=SIM_PATH_MARKER in f'/{norm}',
                       is_lifecycle_path=norm.endswith(
                           LIFECYCLE_PATH_SUFFIXES),
                       is_lb_policy_path=norm.endswith(
                           LB_POLICY_PATH_SUFFIXES),
                       is_wire_helper=norm.endswith(
                           WIRE_HELPER_SUFFIX))
    checker.visit(tree)
    suppressed = _line_suppressions(source)
    out = []
    for v in checker.violations:
        rules_off = suppressed.get(v.line, set())
        if 'all' in rules_off or v.rule in rules_off:
            continue
        out.append(v)
    return out
