"""graftcheck part B: the runtime jaxpr-audit regression gate.

Asserts the invariants the serving tier's performance rests on: the
paged engine's steady-state decode + chunked-prefill loops perform
ZERO device->host transfers outside the sanctioned host_sync readback,
and compile exactly once per (horizon, sample) key and page bucket —
repeated same-shaped calls never grow the jit caches. A regression here
is a silent per-step tax in production, which is why it hard-fails in
CI instead of waiting for a bench round to notice."""
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.analysis import jaxpr_audit
from skypilot_tpu.utils import host as host_lib


# ------------------------------------------------------------ interceptor
def test_interceptor_flags_unsanctioned_sync():
    x = jnp.arange(4)
    events = []
    with jaxpr_audit.intercept_host_transfers(events):
        np.asarray(x)            # graftcheck: disable=GC202 (fixture)
        float(x[0])
    unsanctioned = [e for e in events if not e.sanctioned]
    assert len(unsanctioned) >= 2


def test_interceptor_marks_host_sync_sanctioned():
    x = jnp.arange(4)
    events = []
    with jaxpr_audit.intercept_host_transfers(events):
        out = host_lib.host_sync(x)
    assert isinstance(out, np.ndarray)
    assert events, 'host_sync itself must be counted'
    assert all(e.sanctioned for e in events)


def test_interceptor_restores_patches():
    before = type(jnp.zeros(())).__float__
    with jaxpr_audit.intercept_host_transfers([]):
        assert type(jnp.zeros(())).__float__ is not before
    assert type(jnp.zeros(())).__float__ is before


def test_host_scalars_unwraps():
    out = host_lib.host_scalars({'loss': jnp.float32(1.5), 'n': 3})
    assert out == {'loss': 1.5, 'n': 3}
    assert isinstance(out['loss'], float)


# ------------------------------------------------------------ jaxpr walk
def test_walk_jaxpr_finds_promotions_and_callbacks():
    import jax

    def f(a):
        b = a.astype(jnp.float32)           # bf16 -> f32 widening
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((2,),
                                                          np.float32), b)

    jx = jax.make_jaxpr(f)(jnp.ones(2, jnp.bfloat16))
    callbacks, promotions = jaxpr_audit.walk_jaxpr(jx)
    assert 'pure_callback' in callbacks
    assert any('float32' in p for p in promotions)


def test_check_donation_runs():
    import jax
    fn = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    warns = jaxpr_audit.check_donation(fn, jnp.ones(3), jnp.ones(3))
    assert isinstance(warns, list)   # content is backend-dependent


# ----------------------------------------------------------- engine gates
def _assert_hot_loop_clean(report):
    assert not report.unsanctioned_transfers, '\n' + report.format()
    assert not any(report.recompiles.values()), '\n' + report.format()
    assert not report.callback_prims, '\n' + report.format()
    assert not report.f64_promotions, '\n' + report.format()


def test_paged_engine_audit():
    """The decode step and the chunked-prefill step: zero d2h
    transfers outside host_sync, and exactly one compile per static
    key — the caches do not grow across repeated same-shaped calls."""
    report = jaxpr_audit.audit_engine()
    _assert_hot_loop_clean(report)
    # The sanctioned lagged readback itself must still be present
    # (the engine DOES read tokens back — through host_sync).
    assert report.transfers, 'expected sanctioned pipeline readbacks'
    # The audit exercised the chunked-prefill path and the recompile
    # key was observed.
    before, after = report.compile_counts['prefill']
    assert before >= 1 and after == before
    assert any('horizon' in k for k in report.static_keys)


def test_paged_engine_speculative_audit():
    """The speculative propose→verify→commit steady state: zero d2h
    transfers outside the sanctioned per-round commit sync, and the
    verify jit cache bounded by the (k, sample, P) key set — per-slot
    variable acceptance rides masked commits, never fresh shapes."""
    report = jaxpr_audit.audit_engine(speculate_k=4)
    _assert_hot_loop_clean(report)
    assert report.transfers, 'expected sanctioned commit readbacks'
    assert 'spec_verify' in report.compile_counts
    before, after = report.compile_counts['spec_verify']
    assert before >= 1 and after == before
    assert any('P' in k and k.get('k') == 4 for k in report.static_keys)


def test_llama_forward_jaxpr_audit():
    report = jaxpr_audit.audit_llama_forward()
    assert not report.callback_prims
    assert not report.f64_promotions


def test_telemetry_parity_audit():
    """Telemetry must be free at the device boundary: a
    telemetry-enabled engine run performs zero unsanctioned d2h
    transfers, zero steady-state recompiles, and its jit cache is
    byte-for-byte the same SIZE as a telemetry-off run's (profiling is
    host-side around dispatches, never inside programs)."""
    report = jaxpr_audit.audit_telemetry_parity()
    assert report.ok(), report.format()
    off, on = report.compile_counts['jit cache size (off vs on)']
    assert off == on and on > 0
    # The telemetry-on run still performs its sanctioned readbacks.
    assert report.transfers
    assert not report.unsanctioned_transfers


def test_kv_int8_paged_audit():
    """int8 KV over bf16 weights (the decoupled kv_cache_dtype path):
    quantize-on-write in the chunked-prefill and decode scans plus the
    fused-dequant reads add zero unsanctioned d2h transfers and zero
    steady-state recompiles — the jit key set stays what the bf16
    engine observes."""
    report = jaxpr_audit.audit_engine(kv_cache_dtype='int8')
    _assert_hot_loop_clean(report)
    assert report.transfers, 'expected sanctioned pipeline readbacks'


def test_kv_int8_presets_registered():
    """The kv-int8 preset gates CI through the default preset list,
    which holds no preset of the deleted slot engine."""
    assert 'kv-int8' in jaxpr_audit.PRESETS
    assert 'kv-int8' in jaxpr_audit.DEFAULT_PRESETS
    assert not [n for n in jaxpr_audit.PRESETS if 'slot' in n]


# ----------------------------------------------------- prefix digest
def test_digest_export_audit():
    """hot_prefix_digest() on the probe path: a scrape after every
    wave (hotter than the real ~1 Hz probe cadence) adds zero
    unsanctioned d2h and zero steady-state recompiles — the digest is
    built from the host-side heat tracker only — and every scrape
    returns the chains the waves registered."""
    report = jaxpr_audit.audit_digest_export()
    _assert_hot_loop_clean(report)
    assert report.ok(), report.format()
    assert report.compile_counts['scrapes returning entries'] == (2, 2)


def test_digest_preset_registered():
    """The digest preset gates CI through the default preset list."""
    assert 'digest' in jaxpr_audit.PRESETS
    assert 'digest' in jaxpr_audit.DEFAULT_PRESETS


# ------------------------------------------------------------ sharded (tp)
def _need_devices(n: int) -> None:
    import jax
    if jax.device_count() < n:
        pytest.skip(
            f'tp audit needs {n} devices, have {jax.device_count()}: '
            'run under XLA_FLAGS=--xla_force_host_platform_device_'
            f'count={n} (tests/conftest.py forces 8 — a single-device '
            'run means the forced count was overridden)')


def test_paged_tp_audit():
    """The sharded serving path (tp=2 CPU mesh): zero steady-state
    recompiles, zero unsanctioned d2h, and the collective census shows
    ONLY the known decode set — per-layer all-reduces plus the
    tp-sharded argmax's tiny top-candidate all-gathers; the pool merge
    (shard_map per-shard scatters) must be collective-FREE. A pool- or
    ring-shaped gather appearing here means an output sharding stopped
    matching the next step's input sharding."""
    _need_devices(2)
    report = jaxpr_audit.audit_engine(mesh_tp=2)
    _assert_hot_loop_clean(report)
    assert report.collectives, 'tp preset must census collectives'
    assert report.collective_violations() == [], report.format()
    assert report.collectives.get('merge') == {}, \
        'the shard_map pool merge must be collective-free'
    assert report.collectives['decode'].get('all-to-all', 0) == 0


@pytest.mark.slow
def test_paged_tp_int8_audit():
    _need_devices(2)
    report = jaxpr_audit.audit_engine(mesh_tp=2,
                                      kv_cache_dtype='int8')
    _assert_hot_loop_clean(report)
    assert report.collective_violations() == [], report.format()
    assert report.collectives.get('merge') == {}


def test_paged_tp_presets_registered():
    """The tp presets ride the default list AND declare their device
    need so single-device drivers (graftcheck CLI) re-exec instead of
    silently skipping."""
    assert 'paged-tp' in jaxpr_audit.PRESETS
    assert 'paged-tp-int8' in jaxpr_audit.PRESETS
    assert 'paged-tp' in jaxpr_audit.DEFAULT_PRESETS
    assert 'paged-tp-int8' in jaxpr_audit.DEFAULT_PRESETS
    assert jaxpr_audit.MULTI_DEVICE_PRESETS['paged-tp'] == 2


@pytest.mark.slow
def test_paged_gang_audit():
    """The gang-shaped mesh (tp=2 x dp=2 over 4 devices — standing in
    for a 2-process gang x 2 chips/process; the compiled HLO is
    identical whether the dp axis crosses process boundaries):
    steady-state transfer/recompile gates hold, the decode census
    shows only the known set, and the dp>1 merge's in-body ring-row
    all-gathers stay within their explicit budget — no all-to-all /
    collective-permute anywhere across the process axis."""
    _need_devices(4)
    report = jaxpr_audit.PRESETS['paged-gang']()
    _assert_hot_loop_clean(report)
    assert report.collectives, 'gang preset must census collectives'
    assert report.collective_violations() == [], report.format()
    assert report.collectives['decode'].get('all-to-all', 0) == 0
    assert report.collectives['decode'].get('collective-permute',
                                            0) == 0
    # The dp merge all-gathers ring-rows INSIDE its shard_map body by
    # design (dp pool replicas must not diverge) — bounded, budgeted.
    assert 0 < report.collectives['merge'].get('all-gather', 0) <= \
        report.allowed_all_gathers_by_label['merge']


def test_paged_gang_preset_registered():
    assert 'paged-gang' in jaxpr_audit.PRESETS
    assert 'paged-gang' in jaxpr_audit.DEFAULT_PRESETS
    assert jaxpr_audit.MULTI_DEVICE_PRESETS['paged-gang'] == 4


# ------------------------------------------------- int4 + multi-step
def test_int4_paged_audit():
    """int4 fused-dequant weights: the packed-nibble unpack inside
    qeinsum adds zero unsanctioned d2h and zero steady-state jit-cache
    growth on the paged hot loop (the `int4` default preset)."""
    report = jaxpr_audit.audit_engine(quantize='int4')
    _assert_hot_loop_clean(report)
    assert report.transfers, 'expected sanctioned pipeline readbacks'


def test_multistep_audit():
    """decode_steps_per_call pinned at k: a lockstep budget-bound
    round costs exactly ONE decode dispatch per k tokens, every
    dispatch at static horizon k, zero recompiles / unsanctioned
    d2h — ok() fails on any of it (the dispatch counts ride
    compile_counts as (expected, actual) pairs)."""
    report = jaxpr_audit.audit_multistep(k=4)
    _assert_hot_loop_clean(report)
    assert report.ok(), '\n' + report.format()
    assert all(key['horizon'] == 4 for key in report.static_keys)
    expected, actual = report.compile_counts[
        'decode dispatches (ONE per 4 tokens)']
    assert expected == actual == 4        # 2 rounds x 2 dispatches


@pytest.mark.slow
def test_int4_multistep_audit():
    report = jaxpr_audit.audit_multistep(k=4, quantize='int4')
    _assert_hot_loop_clean(report)
    assert report.ok(), '\n' + report.format()


def test_int4_multistep_presets_registered():
    for name in ('int4', 'multistep', 'int4-multistep'):
        assert name in jaxpr_audit.PRESETS, name
        assert name in jaxpr_audit.DEFAULT_PRESETS, name


# ------------------------------------------------------ KV round two
def test_kv_int4_paged_audit():
    """int4 KV codes (packed nibble rows + absmax/7 scales):
    quantize-on-write plus the in-kernel fused-dequant reads add zero
    unsanctioned d2h and zero steady-state jit-cache growth — halving
    KV bytes must not buy a single host round-trip."""
    report = jaxpr_audit.audit_engine(kv_cache_dtype='int4')
    _assert_hot_loop_clean(report)
    assert report.transfers, 'expected sanctioned pipeline readbacks'


def test_fused_attn_audit():
    """Cross-layer fused decode attention (decode_impl='cross_layer'):
    folding the ring+current-token merge into the kernel's final grid
    step must be free at the dispatch boundary — same transfer and
    recompile gates as the stock paged preset."""
    report = jaxpr_audit.audit_engine(decode_impl='cross_layer')
    _assert_hot_loop_clean(report)
    assert report.transfers, 'expected sanctioned pipeline readbacks'


def test_spec_multistep_audit():
    """In-scan speculative verify: speculate_k x decode_steps_per_call
    compose into ONE dispatch per `steps` verify rounds — pinned
    against a single-round reference engine's dispatch count (greedy
    byte-identity makes the round counts comparable), with zero
    single-round fallbacks and every fused jit key at rounds=steps."""
    report = jaxpr_audit.audit_spec_multistep(k=4, steps=3)
    _assert_hot_loop_clean(report)
    assert report.ok(), '\n' + report.format()
    key = next(k for k in report.compile_counts
               if k.startswith('fused dispatches'))
    expected, actual = report.compile_counts[key]
    assert expected == actual > 0
    assert report.compile_counts[
        'single-round fallback dispatches'] == (0, 0)
    assert all(k['rounds'] == 3 for k in report.static_keys)


def test_kv_round2_presets_registered():
    for name in ('kv-int4', 'fused-attn', 'spec-multistep'):
        assert name in jaxpr_audit.PRESETS, name
        assert name in jaxpr_audit.DEFAULT_PRESETS, name
