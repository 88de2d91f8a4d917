"""graftcheck part A: rule unit tests on fixture snippets, plus the
whole-repo regression gate (zero violations outside the checked-in
baseline). The gate is what makes the concurrency/hot-path discipline
machine-checked: a PR reintroducing a blocking call under a lock or a
host sync in the decode loop fails HERE, not in a bench regression
three rounds later."""
import textwrap

import pytest

from skypilot_tpu.analysis import lint as lint_lib
from skypilot_tpu.analysis import rules as rules_lib
from skypilot_tpu.analysis.cli import main as graftcheck_main


def check(src, path='skypilot_tpu/serve/x.py'):
    return rules_lib.check_source(path, textwrap.dedent(src))


def rule_ids(src, path='skypilot_tpu/serve/x.py'):
    return [v.rule for v in check(src, path)]


# ------------------------------------------------------------------ GC101
def test_gc101_unlocked_write_flagged():
    src = '''
    import threading
    class M:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0          # init writes are setup, not races
        def locked(self):
            with self._lock:
                self._n += 1
        def racy(self):
            self._n = 5
    '''
    vs = check(src)
    assert [v.rule for v in vs] == ['GC101']
    assert vs[0].func == 'M.racy'


def test_gc101_consistently_unlocked_attr_not_flagged():
    # An attr never written under the lock isn't claimed by it.
    src = '''
    import threading
    class M:
        def __init__(self):
            self._lock = threading.Lock()
        def a(self):
            self._free = 1
        def b(self):
            self._free = 2
    '''
    assert rule_ids(src) == []


# ------------------------------------------------------------------ GC102
def test_gc102_sleep_and_urlopen_under_lock():
    src = '''
    import threading, time, urllib.request
    class M:
        def __init__(self):
            self._lock = threading.Lock()
        def bad(self):
            with self._lock:
                time.sleep(1)
                urllib.request.urlopen('http://x', timeout=5)
    '''
    ids = rule_ids(src)
    assert ids.count('GC102') == 2


def test_gc102_sqlite_state_under_thread_lock_flagged():
    src = '''
    import threading
    from skypilot_tpu.serve import serve_state
    class M:
        def __init__(self):
            self._lock = threading.Lock()
        def bad(self):
            with self._lock:
                serve_state.remove_replica('s', 1)
    '''
    assert 'GC102' in rule_ids(src)


def test_gc102_db_named_locks_exempt_for_state_calls():
    # A lock whose job is serializing DB access may hold it across the
    # DB call — that's the replica-manager _db_lock protocol and the
    # jobs scheduler's state.db_lock().
    src = '''
    import threading
    from skypilot_tpu.jobs import state
    class M:
        def __init__(self):
            self._db_lock = threading.Lock()
        def ok(self):
            with self._db_lock:
                state.set_schedule_state(1, 2)
        def also_ok(self):
            with state.db_lock():
                state.set_schedule_state(1, 2)
    '''
    assert rule_ids(src) == []


def test_gc102_filelock_local_exempt():
    src = '''
    import filelock
    from skypilot_tpu import global_state
    def f():
        lock = filelock.FileLock('/tmp/x')
        with lock:
            global_state.add_or_update_cluster('c', None)
    '''
    assert rule_ids(src) == []


def test_gc102_unbounded_wait_under_lock():
    src = '''
    import threading
    class M:
        def __init__(self):
            self._lock = threading.Lock()
            self.q = None
        def bad(self):
            with self._lock:
                self.q.get()
        def ok(self):
            with self._lock:
                self.q.get(timeout=5)
    '''
    assert rule_ids(src) == ['GC102']


# ------------------------------------------------------------------ GC103
def test_gc103_urlopen_without_timeout():
    src = '''
    import urllib.request
    def f(req):
        with urllib.request.urlopen(req) as r:
            return r.read()
    def g(req):
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.read()
    '''
    assert rule_ids(src) == ['GC103']


# ------------------------------------------------------- GC104 / GC105
def test_gc104_bare_except():
    assert rule_ids('''
    def f():
        try:
            return 1
        except:
            return None
    ''') == ['GC104']


def test_gc104_bare_except_reraise_ok():
    assert rule_ids('''
    def f():
        try:
            return 1
        except:
            raise
    ''') == []


def test_gc105_swallowed_broad_except():
    assert rule_ids('''
    def f():
        try:
            return 1
        except Exception:
            pass
    ''') == ['GC105']


def test_gc105_logged_or_narrow_excepts_ok():
    assert rule_ids('''
    import logging
    def f():
        try:
            return 1
        except Exception as e:
            logging.warning('boom %s', e)
        try:
            return 2
        except KeyError:
            pass
    ''') == []


# ------------------------------------------------------------------ GC107
def test_gc107_handler_without_timeout():
    src = '''
    import http.server
    class H(http.server.BaseHTTPRequestHandler):
        pass
    class H2(http.server.BaseHTTPRequestHandler):
        timeout = 60
    '''
    vs = check(src)
    assert [v.rule for v in vs] == ['GC107']
    assert 'H ' in vs[0].message


# ------------------------------------------------------------------ GC108
def test_gc108_proposer_under_lock_flagged():
    src = '''
    import threading
    class S:
        def __init__(self):
            self._lock = threading.Lock()
        def loop(self):
            with self._lock:
                self.engine.prepare_proposals()
    '''
    vs = check(src)
    assert [v.rule for v in vs] == ['GC108']
    assert 'prepare_proposals' in vs[0].message


def test_gc108_proposer_outside_lock_ok():
    src = '''
    import threading
    class S:
        def __init__(self):
            self._lock = threading.Lock()
        def loop(self):
            self.engine.prepare_proposals()
            with self._lock:
                self.engine.step()
    '''
    assert rule_ids(src) == []


def test_gc108_ngram_propose_under_lock_flagged():
    src = '''
    import threading
    lock = threading.Lock()
    def f(eng, hist):
        from skypilot_tpu.inference.speculative import ngram_propose
        with lock:
            return ngram_propose(hist, 4)
    '''
    assert rule_ids(src) == ['GC108']


# ------------------------------------------------------------------ GC109
def test_gc109_adhoc_timing_in_inference_flagged():
    src = '''
    import time
    from time import perf_counter
    def step(self):
        t0 = time.time()
        t1 = perf_counter()
        t2 = time.monotonic()
        return t0, t1, t2
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == \
        ['GC109', 'GC109', 'GC109']


def test_gc109_only_applies_to_inference():
    src = '''
    import time
    def f():
        return time.time()
    '''
    # Fine in the serve layer / other compute dirs — only the
    # inference hot paths must route through telemetry.
    assert rule_ids(src, 'skypilot_tpu/serve/x.py') == []
    assert rule_ids(src, 'skypilot_tpu/models/x.py') == []


def test_gc109_telemetry_clock_spelling_ok():
    src = '''
    from skypilot_tpu.telemetry import clock
    def step(self):
        with self._prof.phase('admit'):
            return clock.now(), clock.monotonic()
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == []


def test_gc109_inside_jit_stays_gc201():
    """Inside a jit body GC201 already fires; GC109 must not
    double-flag the same call."""
    src = '''
    import functools, time, jax
    @functools.partial(jax.jit)
    def step(x):
        return time.time()
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == ['GC201']


# ------------------------------------------------------------------ GC110
def test_gc110_bare_int8_astype_in_compute_flagged():
    src = '''
    import jax.numpy as jnp
    def write_kv(cache, rows):
        return cache.at[0].set(rows.astype(jnp.int8))
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == ['GC110']
    assert rule_ids(src, 'skypilot_tpu/ops/x.py') == ['GC110']


def test_gc110_string_and_np_spellings_flagged():
    src = '''
    import numpy as np
    def write_kv(rows, other):
        a = rows.astype('int8')
        b = other.astype(np.int8)
        return a, b
    '''
    assert rule_ids(src, 'skypilot_tpu/models/x.py') == \
        ['GC110', 'GC110']


def test_gc110_quantize_scope_exempt():
    # Functions named *quantize* ARE the sanctioned write helpers the
    # rule routes everyone else to — including nested helpers.
    src = '''
    import jax.numpy as jnp
    def quantize_kv_rows(rows):
        scale = 1.0
        return (rows / scale).astype(jnp.int8), scale
    def _quantize_array(x):
        def inner(y):
            return y.astype(jnp.int8)
        return inner(x)
    '''
    assert rule_ids(src, 'skypilot_tpu/models/x.py') == []


def test_gc110_quantization_module_and_other_dtypes_exempt():
    src = '''
    import jax.numpy as jnp
    def pack(x):
        return x.astype(jnp.int8)
    '''
    # The quantization module is the sanctioned implementation.
    assert rule_ids(src, 'skypilot_tpu/models/quantization.py') == []
    # Only the int8 dtype is policed; other casts are fine anywhere.
    src_ok = '''
    import jax.numpy as jnp
    def widen(x):
        return x.astype(jnp.int32), x.astype(jnp.bfloat16)
    '''
    assert rule_ids(src_ok, 'skypilot_tpu/inference/x.py') == []


# ------------------------------------------------------------------ GC119
def test_gc119_int4_astype_in_compute_flagged():
    src = '''
    import jax.numpy as jnp
    def write_w(rows, other):
        a = rows.astype(jnp.int4)
        b = other.astype('uint4')
        return a, b
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == \
        ['GC119', 'GC119']
    assert rule_ids(src, 'skypilot_tpu/models/x.py') == \
        ['GC119', 'GC119']


def test_gc119_manual_nibble_twiddling_flagged():
    src = '''
    def repack(codes):
        lo = codes & 0xF
        hi = codes >> 4
        return lo | (hi << 4)
    '''
    assert rule_ids(src, 'skypilot_tpu/ops/x.py') == \
        ['GC119', 'GC119', 'GC119']
    # Outside the compute dirs the operators are unpoliced (bit math
    # is normal in e.g. serve/ hashing).
    assert rule_ids(src, 'skypilot_tpu/serve/x.py') == []


def test_gc119_sanctioned_helpers_exempt():
    # The quantization module IS the layout's home.
    src = '''
    def repack(codes):
        return (codes & 0xF) | ((codes >> 4) << 4)
    '''
    assert rule_ids(src, 'skypilot_tpu/models/quantization.py') == []
    # pack_int4/unpack_int4/quantize-named scopes are the sanctioned
    # spellings wherever they live (mirrors GC110's scope exemption).
    src_scoped = '''
    import jax.numpy as jnp
    def pack_int4(codes):
        return codes >> 4
    def _quantize_array4(w):
        return w.astype(jnp.int4)
    '''
    assert rule_ids(src_scoped, 'skypilot_tpu/models/x.py') == []
    # Non-nibble shifts/masks stay legal in compute dirs.
    src_ok = '''
    def hash_mix(x):
        return (x >> 7) & 0x3F
    '''
    assert rule_ids(src_ok, 'skypilot_tpu/inference/x.py') == []


# ------------------------------------------------------------------ GC121
def test_gc121_per_layer_pool_slice_in_decode_flagged():
    src = '''
    from jax import lax
    def paged_decode_horizon(cache, li, table_p):
        pool_k = cache.pool_k
        pk = lax.dynamic_index_in_dim(pool_k, li, 0, keepdims=False)
        sk = lax.dynamic_index_in_dim(cache.k_scale, li, 0)
        ck, sck = _gather_layer(pk, sk, table_p)
        return ck, sck
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/paged.py') == \
        ['GC121', 'GC121', 'GC121']


def test_gc121_scalar_pool_subscript_in_decode_flagged():
    src = '''
    def decode_step(cache, li):
        a = cache.pool_k[li]
        b = cache.pool_v[0]
        c = cache.k_scale[li, :, :]
        return a, b, c
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/paged.py') == \
        ['GC121', 'GC121', 'GC121']


def test_gc121_prefill_verify_may_gather_but_not_slice():
    # Prefill/verify-shaped functions need contiguous rows, so they may
    # CALL the gather helper — handing it the stacked pool and the
    # layer; the helper's flat gather is no layer subscript; non-pool
    # slices stay legal in decode scopes (the ring is per-horizon, not
    # the pool).
    src = '''
    from jax import lax
    def paged_prefill_chunk(cache, li, table_p):
        return _gather_layer(cache.pool_k, cache.k_scale, li, table_p)
    def paged_spec_verify(cache, li, table_p):
        return _gather_layer(cache.pool_v, None, li, table_p)
    def _gather_layer(pool, scale_pool, li, table_p):
        rows = li * pool.shape[1] + table_p
        return pool.reshape((-1,) + pool.shape[2:])[rows], scale_pool
    def paged_decode_horizon(ring_k, li, lengths):
        rk = lax.dynamic_index_in_dim(ring_k, li, 0)
        n = lengths[li]
        return rk, n
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/paged.py') == []


@pytest.mark.parametrize('scope', ['paged_prefill_chunk',
                                   'paged_spec_verify', '_gather_layer',
                                   'merge_rows'])
def test_gc121_pool_slice_flagged_in_every_inference_scope(scope):
    # The slice was once exempt in prefill/verify scopes as
    # "compute-bound"; the device trace said 19 % of the prefill
    # program (PERF.md, PR 28). No scope of inference/ may slice a
    # layer's pool out, in either spelling.
    src = f'''
    from jax import lax
    def {scope}(cache, li, table_p):
        pk = lax.dynamic_index_in_dim(cache.pool_k, li, 0)
        sv = cache.v_scale[li]
        return pk, sv
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/paged.py') == \
        ['GC121', 'GC121']


def test_gc121_outside_inference_and_suppressions_clean():
    # The rule is scoped to inference/ (the ops kernels are the
    # sanctioned home of pool indexing), and the grandfathered legacy
    # fallback rides inline suppressions.
    src = '''
    from jax import lax
    def paged_decode_kernel(pool_k, li):
        return lax.dynamic_index_in_dim(pool_k, li, 0)
    '''
    assert rule_ids(src, 'skypilot_tpu/ops/x.py') == []
    src_sup = '''
    from jax import lax
    def paged_decode_horizon(pool_k, li):
        return lax.dynamic_index_in_dim(pool_k, li, 0)  # graftcheck: disable=GC121
    '''
    assert rule_ids(src_sup, 'skypilot_tpu/inference/paged.py') == []


# ------------------------------------------------------------------ GC111
def test_gc111_sync_engine_calls_in_coroutine_flagged():
    src = '''
    async def handler(engine, sched, prompt):
        sr = sched.submit(prompt, max_new_tokens=4)
        events = engine.step(horizon=8)
        engine.add_request(prompt)
        return sr, events
    '''
    assert rule_ids(src) == ['GC111', 'GC111', 'GC111']


def test_gc111_unbounded_wait_in_coroutine_flagged():
    src = '''
    async def consume(outbox, done):
        token, finished = outbox.get()
        done.wait()
        return token, finished
    '''
    vs = check(src)
    assert [v.rule for v in vs] == ['GC111', 'GC111']
    assert 'event loop' in vs[0].message


def test_gc111_async_adapters_and_executor_clean():
    # The sanctioned spellings: the async adapter, a wait handed to an
    # executor (the callable is passed, not called), bounded waits,
    # and asyncio's own primitives.
    src = '''
    import asyncio
    async def consume(outbox, loop, done):
        token, finished = await outbox.aget()
        more = await loop.run_in_executor(None, outbox.get)
        done.wait(timeout=5)
        await asyncio.wait([])
        return token, finished, more
    '''
    assert rule_ids(src) == []


def test_gc111_sync_functions_and_other_dirs_exempt():
    # The same calls are the NORMAL engine-loop idiom in sync code;
    # only serve/ coroutines are policed.
    src = '''
    def engine_loop(engine, outbox):
        events = engine.step(horizon=8)
        return outbox.get()
    '''
    assert rule_ids(src) == []
    src_async_elsewhere = '''
    async def run(engine):
        return engine.step(horizon=8)
    '''
    assert rule_ids(src_async_elsewhere,
                    'skypilot_tpu/inference/x.py') == []


def test_gc111_nested_sync_def_inside_coroutine_exempt():
    # A sync def nested in a coroutine is executor fodder — only the
    # IMMEDIATE enclosing function's asyncness decides.
    src = '''
    async def handler(engine, loop):
        def blocking():
            return engine.step(horizon=8)
        return await loop.run_in_executor(None, blocking)
    '''
    assert rule_ids(src) == []


def test_gc110_only_applies_to_compute_dirs():
    src = '''
    import numpy as np
    def shrink(x):
        return x.astype(np.int8)
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/x.py') == []


# ------------------------------------------------------------------ GC112
def test_gc112_fixed_sleep_in_retry_loop_flagged():
    src = '''
    import time
    GAP = 5.0
    def poll():
        while True:
            time.sleep(0.2)
    def poll_const(deadline):
        while time.time() < deadline:
            time.sleep(GAP)
    '''
    vs = check(src)
    assert [v.rule for v in vs] == ['GC112', 'GC112']
    assert 'retry storms' in vs[0].message
    # jobs/ is policed too.
    assert rule_ids(src, 'skypilot_tpu/jobs/x.py') == \
        ['GC112', 'GC112']


def test_gc112_jitter_and_backoff_clean():
    src = '''
    import random, time
    def jittered(poll_seconds):
        while True:
            time.sleep(poll_seconds * (0.5 + random.random()))
    def rng_method(self, interval):
        while True:
            time.sleep(interval * (0.5 + self._rng.random()))
    def backoff():
        gap = 1.0
        while True:
            time.sleep(gap)
            gap = min(gap * 2, 300)
    def event_wait(stop, tick):
        while not stop.is_set():
            stop.wait(tick)
    def dynamic_accessor(tc):
        while True:
            time.sleep(tc.poll_interval())
    '''
    assert rule_ids(src) == []


def test_gc112_other_dirs_and_non_loop_sleeps_exempt():
    src = '''
    import time
    def poll():
        while True:
            time.sleep(0.2)
    '''
    assert rule_ids(src, 'skypilot_tpu/provision/x.py') == []
    src_no_loop = '''
    import time
    def settle():
        time.sleep(0.5)
    '''
    assert rule_ids(src_no_loop) == []


def test_gc112_suppression_and_for_loops():
    src = '''
    import time
    def retry(urls):
        for u in urls:
            time.sleep(1.0)
    '''
    assert rule_ids(src) == ['GC112']
    suppressed = '''
    import time
    def retry(urls):
        for u in urls:
            time.sleep(1.0)  # graftcheck: disable=GC112
    '''
    assert rule_ids(suppressed) == []


# ------------------------------------------------------------------ GC113
def test_gc113_device_put_in_step_path_flagged():
    src = '''
    import jax
    def _enqueue_decode(self, table, lengths):
        table_d, lengths_d = jax.device_put((table, lengths))
        return table_d, lengths_d
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == ['GC113']
    # Only inference/ is policed — serve/models code places freely.
    assert rule_ids(src, 'skypilot_tpu/serve/x.py') == []
    assert rule_ids(src, 'skypilot_tpu/models/x.py') == []


def test_gc113_placement_helpers_exempt():
    src = '''
    import jax
    def prepare_params(cfg, params, mesh):
        return jax.device_put(params, mesh)
    class Engine:
        def __init__(self, cache, sh):
            self.cache = jax.device_put(cache, sh)
        @classmethod
        def from_pretrained(cls, params):
            return jax.device_put(params)
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == []


def test_gc113_device_upload_spelling_fine():
    src = '''
    from skypilot_tpu.utils.host import device_upload
    def _prefill_chunk_batch(self, tokens, starts):
        return device_upload((tokens, starts))
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == []


def test_gc113_inline_suppression():
    src = '''
    import jax
    def _spec_verify_call(self, rows):
        return jax.device_put(rows)  # graftcheck: disable=GC113
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == []


def test_gc113_whole_repo_clean():
    # The engines' own step paths ride device_upload; any new bare
    # device_put in inference/ fails here before it ships.
    from skypilot_tpu.analysis import lint
    new, _ = lint.lint_paths(None, baseline=lint.load_baseline(None))
    assert [v for v in new if v.rule == 'GC113'] == []


# ------------------------------------------------------------------ GC114
def test_gc114_wide_float_astype_on_transfer_path_flagged():
    src = '''
    import jax.numpy as jnp
    import numpy as np
    def encode_rows(codes, scales):
        wide = codes.astype(jnp.bfloat16) * scales
        return wide.astype(np.float32)
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/kv_transfer.py') == \
        ['GC114', 'GC114']
    # String dtype spellings count too.
    src2 = '''
    def pack(rows):
        return rows.astype('float32').tobytes()
    '''
    assert rule_ids(src2, 'skypilot_tpu/serve/disagg.py') == ['GC114']


def test_gc114_dequantize_call_on_transfer_path_flagged():
    src = '''
    from skypilot_tpu.models import quantization
    def export_rows(codes, scales):
        return quantization.dequantize_rows(codes, scales)
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/disagg.py') == ['GC114']


def test_gc114_only_polices_transfer_paths():
    # The same spellings are legal elsewhere (attention kernels
    # legitimately widen for compute; GC114 is a WIRE discipline).
    src = '''
    import jax.numpy as jnp
    def attend(codes, scales):
        return codes.astype(jnp.bfloat16) * scales
    '''
    assert rule_ids(src, 'skypilot_tpu/models/x.py') == []
    assert rule_ids(src, 'skypilot_tpu/serve/server.py') == []


def test_gc114_stored_dtype_codec_clean():
    # The sanctioned codec shape: raw bytes in the stored dtype, no
    # conversion anywhere.
    src = '''
    import numpy as np
    def encode(arr):
        return np.ascontiguousarray(arr, dtype=np.int8).tobytes()
    def decode(buf, shape):
        return np.frombuffer(buf, dtype=np.int8).reshape(shape)
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/kv_transfer.py') == []


def test_gc114_whole_repo_clean():
    # The real wire codec + handoff plumbing never widen KV.
    from skypilot_tpu.analysis import lint
    new, _ = lint.lint_paths(None, baseline=lint.load_baseline(None))
    assert [v for v in new if v.rule == 'GC114'] == []


# ------------------------------------------------------------------ GC115
def test_gc115_wallclock_call_in_autoscaler_flagged():
    src = '''
    import time
    def current_qps(self, now=None):
        now = time.time() if now is None else now
        return now
    def evaluate(self):
        t = time.monotonic()
        return t
    '''
    ids = rule_ids(src, 'skypilot_tpu/serve/autoscalers.py')
    assert ids == ['GC115', 'GC115']
    assert rule_ids(src, 'skypilot_tpu/serve/forecaster.py') == [
        'GC115', 'GC115']


def test_gc115_bare_monotonic_import_flagged():
    src = '''
    from time import monotonic
    def decide(self):
        return monotonic()
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/forecaster.py') == ['GC115']


def test_gc115_injected_clock_default_arg_clean():
    # The injection mechanism itself: referencing time.time (no call)
    # as the default clock, and calling the injected clock.
    src = '''
    import time
    class Autoscaler:
        def __init__(self, spec, clock=time.time):
            self._clock = clock
        def evaluate(self, now=None):
            return self._clock() if now is None else now
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/autoscalers.py') == []


def test_gc115_only_polices_scaling_paths():
    # The same calls are legal elsewhere in serve/ (servers measure
    # real wall time; only scaling DECISIONS must be replayable).
    src = '''
    import time
    def handler(self):
        return time.time()
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/server.py') == []
    assert rule_ids(src, 'skypilot_tpu/serve/replica_managers.py') == []


def test_gc115_whole_repo_clean():
    # The shipped autoscalers/forecaster are fully clock-injected.
    from skypilot_tpu.analysis import lint
    new, _ = lint.lint_paths(None, baseline=lint.load_baseline(None))
    assert [v for v in new if v.rule == 'GC115'] == []


# ------------------------------------------------------------------ GC116
def test_gc116_unbounded_gang_joins_flagged():
    src = '''
    import threading
    def barrier_wait(self):
        self._joined.wait()
    def drain_gang(self, t):
        self._acked.wait()
        self._thread.join()
    '''
    ids = rule_ids(src, 'skypilot_tpu/serve/gang.py')
    assert ids == ['GC116', 'GC116', 'GC116']


def test_gc116_bounded_joins_clean():
    # timeout= kwargs, positional bounds (str.join's iterable counts
    # as one), and non-join calls are all fine.
    src = '''
    def barrier_wait(self, timeout):
        return self._joined.wait(timeout=timeout)
    def sleep(self):
        self._stop.wait(timeout=self.heartbeat_s)
    def tail(self, parts):
        return ",".join(parts)
    def pop_one(self, q):
        return q.get(timeout=5)
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/gang.py') == []


def test_gc116_distributed_initialize_needs_timeout():
    src = '''
    import jax
    def boot(self, addr, world, rank):
        jax.distributed.initialize(coordinator_address=addr,
                                   num_processes=world,
                                   process_id=rank)
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/gang.py') == ['GC116']
    bounded = '''
    import jax
    def boot(self, addr, world, rank):
        jax.distributed.initialize(coordinator_address=addr,
                                   num_processes=world,
                                   process_id=rank,
                                   initialization_timeout=120)
    '''
    assert rule_ids(bounded, 'skypilot_tpu/serve/gang.py') == []


def test_gc116_only_polices_gang_paths():
    # Unbounded waits elsewhere stay governed by the existing rules
    # (GC102 under locks, GC111 in coroutines) — GC116 is the gang
    # layer's file-wide fail-fast contract.
    src = '''
    def wait_done(self):
        self._done.wait()
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/controller.py') == []
    assert rule_ids(src, 'skypilot_tpu/serve/gang.py') == ['GC116']


def test_gc116_whole_repo_clean():
    # The shipped gang layer carries a timeout on every join.
    from skypilot_tpu.analysis import lint
    new, _ = lint.lint_paths(None, baseline=lint.load_baseline(None))
    assert [v for v in new if v.rule == 'GC116'] == []


# ------------------------------------------------------------------ GC201
def test_gc201_impure_calls_inside_jit():
    src = '''
    import functools, time, jax
    import numpy as np
    @functools.partial(jax.jit, static_argnames=('n',))
    def step(x, n):
        t = time.time()
        y = np.asarray(x)
        return float(x)
    '''
    ids = rule_ids(src, 'skypilot_tpu/inference/x.py')
    assert ids == ['GC201', 'GC201', 'GC201']


def test_gc201_plain_jax_ops_fine():
    src = '''
    import jax
    import jax.numpy as jnp
    @jax.jit
    def step(x):
        return jnp.argmax(x, -1)
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == []


# ------------------------------------------------------------------ GC202
def test_gc202_bare_asarray_item_device_get_in_compute_dirs():
    src = '''
    import numpy as np
    import jax
    def f(x):
        a = np.asarray(x)          # bare: classic accidental sync
        b = x.item()
        c = jax.device_get(x)
        d = float(x)
        ok = np.asarray(x, np.int32)   # explicit host conversion
        return a, b, c, d, ok
    '''
    ids = rule_ids(src, 'skypilot_tpu/inference/x.py')
    assert ids == ['GC202'] * 4


def test_gc202_only_applies_to_compute_dirs():
    src = '''
    import numpy as np
    def f(x):
        return np.asarray(x)
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/x.py') == []
    assert rule_ids(src, 'skypilot_tpu/models/x.py') == ['GC202']
    # The helper module itself is exempt.
    assert rule_ids(src, 'skypilot_tpu/utils/host.py') == []


# ------------------------------------------------- suppression / baseline
def test_inline_suppression():
    src = '''
    def f():
        try:
            return 1
        except Exception:   # graftcheck: disable=GC105
            pass
    '''
    assert rule_ids(src) == []


def test_fingerprint_is_line_number_stable():
    src1 = 'def f():\n    try:\n        pass\n    except:\n        pass\n'
    src2 = '\n\n' + src1     # shifted two lines down
    fp1 = rules_lib.check_source('p.py', src1)[0].fingerprint
    fp2 = rules_lib.check_source('p.py', src2)[0].fingerprint
    assert fp1 == fp2


def test_baseline_round_trip(tmp_path):
    v = rules_lib.check_source(
        'p.py', 'try:\n    pass\nexcept:\n    pass\n')[0]
    path = str(tmp_path / 'base')
    lint_lib.write_baseline([v], path)
    assert v.fingerprint in lint_lib.load_baseline(path)


# ------------------------------------------------------------ repo gate
def test_repo_is_clean_modulo_baseline():
    """THE gate: zero violations outside graftcheck.baseline. If this
    fails, fix the violation (preferred) or — for a reviewed,
    deliberate pattern — add its fingerprint to the baseline with a
    justification comment."""
    new, _old = lint_lib.lint_paths()
    assert not new, ('graftcheck found new violations:\n\n'
                     + '\n'.join(v.format() for v in new))


def test_baseline_has_no_stale_entries():
    """Baseline entries whose violation was fixed must be pruned, or
    the suppression could silently re-cover a future regression."""
    baseline = lint_lib.load_baseline()
    _new, old = lint_lib.lint_paths()
    stale = baseline - {v.fingerprint for v in old}
    assert not stale, f'stale graftcheck.baseline entries: {stale}'


def test_cli_smoke(capsys):
    assert graftcheck_main(['rules']) == 0
    out = capsys.readouterr().out
    assert 'GC202' in out
    assert graftcheck_main(['lint']) == 0


# ------------------------------------------------------------------ GC117
def test_gc117_wallclock_in_sim_flagged():
    src = '''
    import time
    def run_until(self, t_end):
        t0 = time.time()
        time.sleep(0.1)
        return time.monotonic() - t0
    '''
    ids = rule_ids(src, 'skypilot_tpu/serve/sim/core.py')
    assert ids == ['GC117', 'GC117', 'GC117']


def test_gc117_bare_from_import_spellings_flagged():
    src = '''
    from time import monotonic, perf_counter
    def tick(self):
        return monotonic() + perf_counter()
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/sim/fleet.py') == [
        'GC117', 'GC117']


def test_gc117_virtual_clock_and_references_clean():
    # The sanctioned spellings: the EventLoop's own virtual clock,
    # method sleeps routed through the loop/env seam, and passing a
    # clock CALLABLE (name reference, no call).
    src = '''
    import time
    class EventLoop:
        def __init__(self):
            self.now = 0.0
        def sleep(self, s):
            self.now += s
    def drive(loop, env):
        loop.sleep(1.0)
        env.sleep(2.0)
        return loop.now
    def make_clock(fallback=time.time):
        return fallback
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/sim/env.py') == []


def test_gc117_only_polices_sim_paths():
    # The same wall-clock calls outside serve/sim/ are not GC117's
    # business (other rules may still apply in their own dirs).
    src = '''
    import time
    def probe(self):
        return time.time()
    '''
    assert 'GC117' not in rule_ids(src,
                                   'skypilot_tpu/serve/server_x.py')
    assert rule_ids(src, 'skypilot_tpu/serve/sim/replica.py') == [
        'GC117']


# ------------------------------------------------------------------ GC118
def test_gc118_unknown_fault_site_flagged():
    src = '''
    class M:
        def loop(self):
            rule = self._faults.fire('engin_step')
    '''
    vs = check(src)
    assert [v.rule for v in vs] == ['GC118']
    assert 'engin_step' in vs[0].message


def test_gc118_kwarg_spelling_flagged():
    src = '''
    class M:
        def loop(self):
            rule = self._faults.fire(site='kv_wires')
    '''
    assert rule_ids(src) == ['GC118']


def test_gc118_registered_sites_clean():
    # Every registry member is legal, positional or kwarg, and
    # non-literal sites (the simulator's site-tuple sweep) are skipped
    # — their tuples hold registry members by construction.
    src = '''
    SITES = ('sim_storm', 'sim_gray')
    class M:
        def loop(self):
            a = self._faults.fire('engine_step')
            b = self._faults.fire(site='canary')
            c = inj.fire('kv_wire')
            for s in SITES:
                inj.fire(s)
    '''
    assert rule_ids(src) == []


def test_gc118_only_polices_serve():
    # A .fire() outside serve/ is somebody else's API.
    src = '''
    class Gun:
        def pull(self):
            self.trigger.fire('bullet')
    '''
    assert 'GC118' not in rule_ids(src, 'skypilot_tpu/jobs/gun.py')


def test_gc118_every_live_fire_site_is_registered():
    # The repo-wide gate (test_repo_is_clean_modulo_baseline) enforces
    # this transitively; pin the registry contents the sim site-tuples
    # rely on explicitly too.
    from skypilot_tpu.serve import faults as faults_lib
    from skypilot_tpu.serve.sim import fleet as sim_fleet
    for site in sim_fleet.SIM_FAULT_SITES:
        assert site in faults_lib.FAULT_SITES, site
    for kind in faults_lib.GRAY_FAILURE_KINDS:
        assert kind in faults_lib.FAULT_KINDS, kind


# ------------------------------------------------------------------ GC120
def test_gc120_direct_row_write_flagged():
    # A serve_state row write outside the journaled persist helpers.
    src = '''
    from skypilot_tpu.serve import serve_state
    class ReplicaManager:
        def scale_up(self):
            serve_state.add_or_update_replica('svc', 1, 'c', 'READY',
                                              None, 1, False)
    '''
    vs = check(src, 'skypilot_tpu/serve/replica_managers.py')
    assert [v.rule for v in vs] == ['GC120']
    assert 'add_or_update_replica' in vs[0].message


def test_gc120_env_seam_write_flagged():
    # The env-seam spelling of the same mutation is gated too — the
    # journal invariant is about the WRITE, not the module it routes
    # through.
    src = '''
    class ReplicaManager:
        def probe_all(self):
            self._env.persist_replica('svc', 1, 'c', 'READY', None,
                                      1, False, 8081)
        def tick(self):
            self._env.put_note('svc', 'k', 1)
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/controller.py') == [
        'GC120', 'GC120']


def test_gc120_journaled_helpers_clean():
    # Inside the sanctioned helper scopes (nested closures included)
    # the same calls are THE implementation, not a violation; reads
    # are never gated.
    src = '''
    class ReplicaManager:
        def _persist(self, info):
            self._env.persist_replica('svc', 1, 'c', 'READY', None,
                                      1, False, 8081)
        def _untrack(self, rid):
            self._env.remove_replica('svc', rid)
        def _journal_start(self, kind, info):
            return self._env.journal_op_start('svc', kind, 1, None)
        def _journal_finish(self, op_id):
            self._env.journal_op_finish('svc', op_id)
        def _put_note(self, key, value):
            self._env.put_note('svc', key, value)
        def _persist_autoscaler_state(self):
            def retry():
                self._env.put_note('svc', 'autoscaler_state', {})
            retry()
        def reconcile(self):
            rows = self._env.load_replica_rows('svc')
            ops = self._env.pending_ops('svc')
            notes = self._env.get_notes('svc')
            return rows, ops, notes
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/replica_managers.py') == []


def test_gc120_only_polices_lifecycle_modules():
    # control_env.py (the seam's live implementation) and everything
    # else keep calling serve_state directly — the rule gates the
    # state machines, not the seam.
    src = '''
    from skypilot_tpu.serve import serve_state
    def persist_replica(service_name, replica_id):
        serve_state.add_or_update_replica(service_name, replica_id,
                                          'c', 'READY', None, 1, False)
    '''
    assert 'GC120' not in rule_ids(src,
                                   'skypilot_tpu/serve/control_env.py')
    assert 'GC120' not in rule_ids(src, 'skypilot_tpu/serve/rpc.py')


def test_gc120_journal_kinds_registered():
    # The manager only journals kinds serve_state validates — a typo'd
    # kind would raise at journal time, never silently no-op.
    from skypilot_tpu.serve import serve_state
    for kind in ('launch', 'drain', 'teardown'):
        assert kind in serve_state.JOURNAL_OP_KINDS
    import pytest as _pytest
    with _pytest.raises(ValueError, match='unknown journal op kind'):
        serve_state.journal_op_start('svc', 'meteor', 1, None)


# ------------------------------------------------------------------ GC122
LB_POLICY_PATH = 'skypilot_tpu/serve/load_balancing_policies.py'


def test_gc122_raw_map_growth_flagged():
    # Per-key writes and growth-method calls on self.* containers in
    # the LB-policy module — sessions/replica URLs churn unboundedly,
    # so every runtime map must be a BoundedStore.
    src = '''
    class SomePolicy:
        def select(self, key):
            self._sessions[key] = 'url'
            self._counts[key] += 1
            self._urls.append(key)
            self._seen.add(key)
            self._merged.update({key: 1})
    '''
    assert rule_ids(src, LB_POLICY_PATH) == ['GC122'] * 5


def test_gc122_bounded_store_and_reassignment_clean():
    # Inside BoundedStore the raw mutations ARE the implementation;
    # wholesale reassignment replaces rather than grows; locals are
    # per-call.
    src = '''
    class BoundedStore:
        def put(self, key, value):
            self._d[key] = value
            self._order.append(key)
    class SomePolicy:
        def set_ready_replicas(self, urls):
            self._gangs = dict(self._planned_gangs)
        def select(self, key):
            pool = {}
            pool[key] = 1
            ranked = []
            ranked.append(key)
            return pool, ranked
    '''
    assert rule_ids(src, LB_POLICY_PATH) == []


def test_gc122_only_polices_lb_policy_module():
    # The same source elsewhere in serve/ is out of scope — the rule
    # gates the long-resident policy tables, not every dict in the
    # tree.
    src = '''
    class Tracker:
        def note(self, key):
            self._seen[key] = 1
    '''
    assert 'GC122' not in rule_ids(src, 'skypilot_tpu/serve/server.py')


def test_gc122_real_policy_module_clean():
    # The shipped module itself holds the invariant: zero GC122 (and
    # zero anything else) with only explicitly annotated suppressions.
    import pathlib
    mod = pathlib.Path(rules_lib.__file__).resolve()
    repo = mod.parents[2]
    src = (repo / LB_POLICY_PATH).read_text()
    vs = rules_lib.check_source(LB_POLICY_PATH, src)
    assert vs == [], [f'{v.rule}:{v.line}' for v in vs]


# ------------------------------------------------------------------ GC123
def test_gc123_request_with_body_flagged():
    # A body-carrying hop built straight on urllib under serve/ cannot
    # carry the X-Skytpu-Trace header — the trace loses the leg.
    src = '''
    import urllib.request
    def push(url, body):
        req = urllib.request.Request(url, data=body, method='POST')
        return urllib.request.urlopen(req, timeout=5)
    '''
    vs = check(src)
    assert [v.rule for v in vs] == ['GC123']
    assert 'wire' in vs[0].message


def test_gc123_positional_data_flagged():
    # The data arg smuggled positionally is the same untraced hop.
    src = '''
    from urllib import request
    def push(url, body):
        return request.Request(url, body)
    '''
    assert 'GC123' in rule_ids(src)


def test_gc123_bodyless_get_clean():
    # GETs carry no body; probes/scrapes stay on plain urlopen.
    src = '''
    import urllib.request
    def scrape(url):
        req = urllib.request.Request(url, data=None)
        with urllib.request.urlopen(url, timeout=2) as resp:
            return resp.read()
    '''
    assert 'GC123' not in rule_ids(src)


def test_gc123_probe_scope_exempt():
    # Readiness probes may POST post_data by spec — they are not part
    # of any request odyssey, so the helper is not required.
    src = '''
    import urllib.request
    def probe_http(url, post_data):
        req = urllib.request.Request(url, data=post_data)
        return urllib.request.urlopen(req, timeout=5)
    '''
    assert 'GC123' not in rule_ids(src)


def test_gc123_wire_helper_itself_exempt():
    # serve/wire.py IS the helper — the raw call lives there by design.
    src = '''
    import urllib.request
    def post_json(url, payload):
        req = urllib.request.Request(url, data=payload)
        return urllib.request.urlopen(req, timeout=5)
    '''
    assert 'GC123' not in rule_ids(src, 'skypilot_tpu/serve/wire.py')


def test_gc123_only_polices_serve():
    src = '''
    import urllib.request
    def report(url, body):
        urllib.request.urlopen(url, body, 5)
    '''
    assert 'GC123' not in rule_ids(src, 'skypilot_tpu/usage_lib.py')


# --------------------------------------------- aliased-import timing
def test_gc109_aliased_time_imports_flagged():
    # ``from time import time as now`` / ``import time as t`` must not
    # smuggle wall-clock reads past the inference timing rule — the
    # checker canonicalizes aliases before matching.
    src = '''
    import time as t
    from time import time as now
    def step(self):
        return now() + t.monotonic()
    '''
    ids = rule_ids(src, 'skypilot_tpu/inference/engine_x.py')
    assert ids == ['GC109', 'GC109']


def test_gc115_aliased_time_imports_flagged():
    src = '''
    import time as t
    from time import monotonic as mono
    def evaluate(self):
        return t.time() + mono()
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/autoscalers.py') == [
        'GC115', 'GC115']


def test_gc117_aliased_time_imports_flagged():
    src = '''
    from time import time as wall
    import time as t
    def run_until(self, t_end):
        return wall() - t.perf_counter()
    '''
    assert rule_ids(src, 'skypilot_tpu/serve/sim/core_x.py') == [
        'GC117', 'GC117']


def test_time_alias_canonical_name_in_message():
    src = '''
    from time import time as now
    def step(self):
        return now()
    '''
    v = check(src, 'skypilot_tpu/inference/x.py')[0]
    assert 'time.time' in v.message


def test_non_time_aliases_not_canonicalized():
    # An alias of something that merely LOOKS like a clock must not
    # trip the rules: only the time module's names canonicalize.
    src = '''
    from mylib import time as now
    def step(self):
        return now()
    '''
    assert rule_ids(src, 'skypilot_tpu/inference/x.py') == []


# -------------------------------------------------- graftcheck --json
def test_cli_lint_json_schema(capsys):
    import json as json_lib
    assert graftcheck_main(['lint', '--json']) == 0
    doc = json_lib.loads(capsys.readouterr().out)
    assert set(doc) == {'ok', 'violations', 'baselined'}
    assert doc['ok'] is True and doc['violations'] == []
    assert isinstance(doc['baselined'], int)


def test_cli_lint_json_violation_fields(tmp_path, capsys):
    import json as json_lib
    bad = tmp_path / 'skypilot_tpu' / 'serve' / 'x.py'
    bad.parent.mkdir(parents=True)
    bad.write_text('try:\n    pass\nexcept:\n    pass\n')
    assert graftcheck_main(
        ['lint', '--json', '--baseline', str(tmp_path / 'empty'),
         str(bad)]) == 1
    doc = json_lib.loads(capsys.readouterr().out)
    assert doc['ok'] is False and len(doc['violations']) == 1
    v = doc['violations'][0]
    assert set(v) == {'rule', 'path', 'line', 'col', 'func',
                      'message', 'source'}
    assert v['rule'] == 'GC104'


# ----------------------------------------- byte-budget staleness gate
def test_byte_budgets_name_only_live_presets():
    """Same contract as the lint-baseline staleness gate, for byte
    budgets: a budget entry for a preset that no longer exists would
    silently gate nothing — fail loudly instead."""
    from skypilot_tpu.analysis import costmodel, jaxpr_audit
    stale = sorted(set(costmodel.BYTE_BUDGETS) -
                   set(jaxpr_audit.PRESETS))
    assert not stale, f'BYTE_BUDGETS names unknown presets: {stale}'


def test_byte_budget_classes_are_known():
    from skypilot_tpu.analysis import costmodel
    known = set(costmodel.ALL_CLASSES)
    for preset, labels in costmodel.BYTE_BUDGETS.items():
        for label, caps in labels.items():
            for key in caps:
                assert (key in known
                        or key.startswith('collective.')), (
                    preset, label, key)
