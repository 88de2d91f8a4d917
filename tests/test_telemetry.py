"""Unified telemetry: registry exposition + thread safety, per-request
trace timelines (queue → prefill → decode span order), chrome-trace
export through the timeline writer, and the model server's Prometheus
``/metrics`` + ``/debug/requests`` surfaces."""
import json
import math
import threading
import urllib.request

import jax
import pytest

from skypilot_tpu.telemetry import registry as registry_lib
from skypilot_tpu.telemetry import tracing

jax.config.update('jax_platforms', 'cpu')


def test_spot_scaling_series_registered_at_construction(
        tmp_path, monkeypatch):
    """Round-10 controller-side stable schema: constructing the
    forecast autoscaler and the replica manager registers every
    forecast/target/provision series — zeros from the first scrape,
    before any traffic, preemption or provision ever happened."""
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    from skypilot_tpu import telemetry
    from skypilot_tpu.serve import autoscalers as asc_lib
    from skypilot_tpu.serve import forecaster as forecaster_lib
    from skypilot_tpu.serve.replica_managers import ReplicaManager
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    # Fresh process registry: the zeros-from-first-scrape claim is
    # about CONSTRUCTION, so earlier tests' legitimate traffic on the
    # shared registry must not bleed in (get-or-create makes the swap
    # safe — later servers/engines re-create their handles).
    registry_lib.reset_registry()
    try:
        spec = SkyServiceSpec(
            readiness_path='/readiness', min_replicas=1, max_replicas=4,
            target_qps_per_replica=1.0, forecast_enabled=True,
            dynamic_ondemand_fallback=True)
        asc = asc_lib.Autoscaler.from_spec(spec)
        assert isinstance(asc, asc_lib.ForecastFallbackAutoscaler)
        ReplicaManager('spot-schema-test', spec, {})
        prom = telemetry.get_registry().render_prometheus()
    finally:
        registry_lib.reset_registry()
    assert '# TYPE skytpu_forecast_qps gauge' in prom
    for tier in forecaster_lib.TIERS:
        for horizon in forecaster_lib.HORIZONS:
            assert ('skytpu_forecast_qps{horizon="%s",tier="%s"} 0'
                    % (horizon, tier)) in prom, (tier, horizon)
    assert '# TYPE skytpu_autoscaler_target_replicas gauge' in prom
    for kind in asc_lib.TARGET_KINDS:
        assert (f'skytpu_autoscaler_target_replicas{{kind="{kind}"}} 0'
                in prom), kind
    assert '# TYPE skytpu_spot_preemptions_total counter' in prom
    assert 'skytpu_spot_preemptions_total 0' in prom
    assert '# TYPE skytpu_prefix_warmup_seconds histogram' in prom
    assert 'skytpu_prefix_warmup_seconds_bucket{le="+Inf"} 0' in prom
    assert '# TYPE skytpu_replica_provision_seconds histogram' in prom
    assert 'skytpu_replica_provision_seconds_bucket{le="+Inf"} 0' \
        in prom
    # Round-13 gray-failure series: the quarantine counter and every
    # gray detection kind register at MANAGER construction — zeros
    # before any canary mismatch, NaN eviction or checksum refusal.
    assert '# TYPE skytpu_replicas_quarantined_total counter' in prom
    assert 'skytpu_replicas_quarantined_total 0' in prom
    assert '# TYPE skytpu_gray_failures_total counter' in prom
    from skypilot_tpu.serve import faults as faults_lib
    for kind in faults_lib.GRAY_FAILURE_KINDS:
        assert (f'skytpu_gray_failures_total{{kind="{kind}"}} 0'
                in prom), kind


def test_lb_affinity_series_registered_at_construction(tmp_path,
                                                       monkeypatch):
    """PR-18 stable schema: constructing a prefix-affinity LB (never
    started, never synced) registers every affinity / horizontal-tier
    series — zeros from the first scrape, every outcome label
    pre-registered."""
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    from skypilot_tpu import telemetry
    from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
    registry_lib.reset_registry()
    try:
        SkyServeLoadBalancer(controller_url='http://127.0.0.1:1',
                             port=1, policy_name='prefix_affinity',
                             lb_id='lb-telemetry')
        prom = telemetry.get_registry().render_prometheus()
    finally:
        registry_lib.reset_registry()
    assert '# TYPE skytpu_lb_affinity_hits_total counter' in prom
    for outcome in ('hit', 'miss', 'migrated'):
        assert (f'skytpu_lb_affinity_hits_total{{outcome="{outcome}"}}'
                ' 0' in prom), outcome
    assert '# TYPE skytpu_prefix_recompute_tokens_total counter' in prom
    assert 'skytpu_prefix_recompute_tokens_total 0' in prom
    assert '# TYPE skytpu_lb_ring_size gauge' in prom
    # Pre-sync the ring is just this LB — the gauge starts at 0 and is
    # set on the first successful controller sync.
    assert 'skytpu_lb_ring_size 0' in prom
    assert '# TYPE skytpu_lb_handoff_total counter' in prom
    assert 'skytpu_lb_handoff_total 0' in prom


def test_gang_series_registered_at_construction():
    """Round-11 gang stable schema: ``gang.register_metrics()`` alone
    puts every gang series in the registry — zeros from the first
    scrape (gang_size 0 = not a gang), every failure cause
    pre-registered — and a GangCoordinator sets the live world size."""
    from skypilot_tpu import telemetry
    from skypilot_tpu.serve import gang as gang_lib
    registry_lib.reset_registry()
    try:
        gang_lib.register_metrics()
        prom = telemetry.get_registry().render_prometheus()
    finally:
        registry_lib.reset_registry()
    assert '# TYPE skytpu_gang_size gauge' in prom
    assert 'skytpu_gang_size 0' in prom
    assert '# TYPE skytpu_gang_join_seconds histogram' in prom
    assert 'skytpu_gang_join_seconds_bucket{le="+Inf"} 0' in prom
    assert '# TYPE skytpu_gang_failures_total counter' in prom
    for cause in gang_lib.FAILURE_CAUSES:
        assert (f'skytpu_gang_failures_total{{cause="{cause}"}} 0'
                in prom), cause
    assert '# TYPE skytpu_gang_heartbeat_age_seconds gauge' in prom
    assert 'skytpu_gang_heartbeat_age_seconds 0' in prom
    registry_lib.reset_registry()
    try:
        spec = gang_lib.GangSpec(gang_id='g-telemetry', rank=0, world=3)
        gang_lib.GangCoordinator(spec)
        prom = telemetry.get_registry().render_prometheus()
    finally:
        registry_lib.reset_registry()
    assert 'skytpu_gang_size 3' in prom


# ---------------------------------------------------------------------------
# Registry: Prometheus exposition golden test
# ---------------------------------------------------------------------------
def _golden_registry() -> registry_lib.MetricsRegistry:
    reg = registry_lib.MetricsRegistry()
    reg.counter('t_requests_total', 'Requests served').inc(3)
    reg.gauge('t_queue_depth', 'Queue depth')          # stays 0
    h = reg.histogram('t_latency_ms', 'Latency', buckets=(10, 100))
    h.observe(5)
    h.observe(50)
    h.observe(5000)
    reg.counter('t_probe_total', 'Probes', outcome='success').inc(2)
    reg.counter('t_probe_total', 'Probes', outcome='failure')
    return reg


def test_prometheus_exposition_golden():
    """Parse the exposition line by line: HELP/TYPE present once per
    family, every registered series emitted (zeros NOT omitted),
    histogram buckets cumulative and terminated by +Inf with matching
    _sum/_count."""
    text = _golden_registry().render_prometheus()
    lines = [ln for ln in text.splitlines() if ln]
    # Every family has exactly one HELP and one TYPE line.
    for fam, kind in [('t_requests_total', 'counter'),
                      ('t_queue_depth', 'gauge'),
                      ('t_latency_ms', 'histogram'),
                      ('t_probe_total', 'counter')]:
        assert lines.count(f'# TYPE {fam} {kind}') == 1, fam
        assert sum(1 for ln in lines
                   if ln.startswith(f'# HELP {fam} ')) == 1, fam
    # Samples are machine-parseable: "name{labels} value".
    samples = {}
    for ln in lines:
        if ln.startswith('#'):
            continue
        name, value = ln.rsplit(' ', 1)
        samples[name] = float(value)
    assert samples['t_requests_total'] == 3
    # Zero-valued gauge present, not omitted (stable schema).
    assert samples['t_queue_depth'] == 0
    # Histogram: cumulative buckets, +Inf terminator, sum/count.
    assert samples['t_latency_ms_bucket{le="10"}'] == 1
    assert samples['t_latency_ms_bucket{le="100"}'] == 2
    assert samples['t_latency_ms_bucket{le="+Inf"}'] == 3
    assert samples['t_latency_ms_count'] == 3
    assert samples['t_latency_ms_sum'] == 5055
    # Labeled series: both outcomes present, the zero one included.
    assert samples['t_probe_total{outcome="success"}'] == 2
    assert samples['t_probe_total{outcome="failure"}'] == 0
    # TYPE precedes its family's samples.
    type_idx = lines.index('# TYPE t_latency_ms histogram')
    first_sample = next(i for i, ln in enumerate(lines)
                        if ln.startswith('t_latency_ms_bucket'))
    assert type_idx < first_sample


def test_registry_json_rendering():
    data = _golden_registry().render_json()
    assert data['t_requests_total']['type'] == 'counter'
    assert data['t_requests_total']['series'][0]['value'] == 3
    hist = data['t_latency_ms']['series'][0]
    assert hist['count'] == 3 and hist['window'] == 3


def test_registry_get_or_create_and_type_conflict():
    reg = registry_lib.MetricsRegistry()
    c1 = reg.counter('x_total', 'X')
    c2 = reg.counter('x_total')
    assert c1 is c2
    with pytest.raises(TypeError):
        reg.gauge('x_total')
    with pytest.raises(ValueError):
        c1.inc(-1)


def test_registry_thread_safety():
    """Concurrent writers on one counter + one histogram: no lost
    increments or observations."""
    reg = registry_lib.MetricsRegistry()
    c = reg.counter('race_total')
    h = reg.histogram('race_ms', window=100000)
    n_threads, n_iter = 8, 2000

    def work():
        for i in range(n_iter):
            c.inc()
            h.observe(i % 50)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    snap = h.snapshot()
    assert snap['cumulative'][-1] == n_threads * n_iter


def test_windowed_quantiles():
    """ONE windowed-quantile implementation: exact rolling median/p90
    over a bounded window (old values age out)."""
    reg = registry_lib.MetricsRegistry()
    h = reg.histogram('q_ms', window=100)
    assert h.quantile(0.5) == 0.0          # empty -> 0, not missing
    for v in range(1, 101):
        h.observe(float(v))
    assert h.quantile(0.5) == 51
    assert h.quantile(0.9) == 91
    for _ in range(100):                   # roll the window over
        h.observe(1000.0)
    assert h.quantile(0.5) == 1000.0
    assert h.window_len == 100


# ---------------------------------------------------------------------------
# Per-request tracing: e2e span order through the engines
# ---------------------------------------------------------------------------
def _span_names(trace):
    return [s['name'] for s in trace.to_dict()['spans']]


def test_request_trace_span_order_e2e():
    """A finished request's trace holds queue → prefill (with per-chunk
    spans) → decode in order, all durations non-negative, published
    exactly once to the ring buffer."""
    from skypilot_tpu.models import configs
    cfg = configs.get_config('tiny')
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    eng = PagedInferenceEngine(cfg, max_batch=2, max_seq=64,
                               prefill_chunk_tokens=8)
    rid = eng.add_request([1, 2, 3] * 7, max_new_tokens=5)
    done = eng.run_to_completion(horizon=8)
    assert rid in done
    trace = tracing.get_trace_buffer().find(rid)
    assert trace is not None and trace.done
    d = trace.to_dict()
    names = [s['name'] for s in d['spans']]
    # Lifecycle order (by position in the span list).
    for earlier, later in [('queue', 'prefill'), ('prefill', 'decode')]:
        assert names.index(earlier) < names.index(later), names
    # 21 prompt tokens / chunk 8 -> at least 3 chunk spans.
    assert names.count('prefill_chunk') >= 3
    for span in d['spans']:
        assert span.get('dur_ms', 0.0) >= 0.0, span
        assert span['start_ms'] >= -1e-6, span
    assert d['meta']['output_tokens'] == 5
    # Queue-wait span is completed and measurable (the serve layer's
    # queue-wait histogram reads exactly this).
    assert trace.span_ms('queue') is not None


def test_trace_cancel_publishes_trace():
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64)
    rid = eng.add_request([1, 2, 3, 4], max_new_tokens=30)
    eng.step(horizon=1)
    assert eng.cancel(rid)
    trace = tracing.get_trace_buffer().find(rid)
    assert trace is not None and trace.done
    assert trace.meta.get('cancelled') is True


def test_telemetry_off_no_traces_no_phases():
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    before = len(tracing.get_trace_buffer())
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64, telemetry=False)
    rid = eng.add_request([1, 2, 3], max_new_tokens=3)
    done = eng.run_to_completion(horizon=4)
    assert rid in done and done[rid].trace is None
    assert len(tracing.get_trace_buffer()) == before
    assert eng.phase_stats() == {}


def test_chrome_trace_export(tmp_path):
    """Completed traces export as a chrome://tracing file via the
    utils/timeline.py writer."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64, prefill_chunk_tokens=8)
    rid = eng.add_request([5, 6, 7] * 5, max_new_tokens=4)
    eng.run_to_completion(horizon=8)
    out = tmp_path / 'req_trace.json'
    path = tracing.export_chrome_trace(
        str(out), traces=[tracing.get_trace_buffer().find(rid)])
    assert path == str(out)
    payload = json.loads(out.read_text())
    events = payload['traceEvents']
    assert events and all(
        ev['ph'] == 'X' and ev['dur'] >= 0 and 'ts' in ev
        for ev in events)
    assert any(ev['name'] == 'decode' for ev in events)


def test_step_phase_profiler_and_compile_events():
    """The engine records per-phase wall time and one first-call event
    per distinct jit key (steady state adds none)."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                               max_seq=64, prefill_chunk_tokens=8)
    for _ in range(2):
        eng.add_request([1, 2, 3] * 7, max_new_tokens=4)
        eng.run_to_completion(horizon=8)
    stats = eng.phase_stats()
    for phase in ('admit', 'decode_enqueue', 'readback',
                  'prefill_chunk'):
        assert phase in stats['phases'], stats
        assert stats['phases'][phase]['total_s'] >= 0
    n_compiles = len(stats['compiles'])
    assert n_compiles >= 2                  # >=1 prefill + >=1 decode key
    # Same shapes again: no new first-call events.
    eng.add_request([1, 2, 3] * 7, max_new_tokens=4)
    eng.run_to_completion(horizon=8)
    assert len(eng.phase_stats()['compiles']) == n_compiles


def test_kv_round2_series_registered_at_construction():
    """KV-round-two stable schema: constructing an engine alone puts
    the KV read-traffic gauge in the registry — zero from the first
    scrape, before any decode dispatch."""
    from skypilot_tpu import telemetry
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    registry_lib.reset_registry()
    try:
        PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                             max_seq=64)
        prom = telemetry.get_registry().render_prometheus()
    finally:
        registry_lib.reset_registry()
    assert '# TYPE skytpu_kv_read_bytes_per_step gauge' in prom
    assert 'skytpu_kv_read_bytes_per_step 0' in prom
    # The live-rows counter sits beside the substeps counter from the
    # first scrape too.
    assert 'skytpu_engine_decode_live_rows_total 0' in prom
    assert 'skytpu_engine_decode_substeps_total 0' in prom


def test_kv_round2_series_updated_by_decode():
    """After decode traffic the KV read gauge carries live-context x
    per-token bytes, and live rows / substeps is the mean live batch
    of a step (one request in two slots: exactly 1)."""
    from skypilot_tpu import telemetry
    from skypilot_tpu.inference.engine import kv_token_bytes
    from skypilot_tpu.models import configs
    registry_lib.reset_registry()
    try:
        cfg = configs.get_config('tiny')
        from skypilot_tpu.inference.paged import PagedInferenceEngine
        eng = PagedInferenceEngine(cfg, max_batch=2, max_seq=64,
                                   decode_impl='gather')
        eng.add_request([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.run_to_completion(horizon=4)
        reg = telemetry.get_registry()
        kv_gauge = reg.get('skytpu_kv_read_bytes_per_step')
        assert kv_gauge is not None and kv_gauge.value > 0
        # live context x per-token stored cost: bounded by the full
        # sequence capacity of the whole batch.
        assert kv_gauge.value <= kv_token_bytes(cfg, None) * 2 * 64
        substeps = reg.get('skytpu_engine_decode_substeps_total').value
        rows = reg.get('skytpu_engine_decode_live_rows_total').value
        assert substeps > 0 and rows == substeps
    finally:
        registry_lib.reset_registry()


def test_adapter_series_registered_at_construction():
    """PR-20 stable schema: an engine built with an adapter bank
    registers the bank-slot occupancy gauges, the load/eviction
    counters and the requests_total{adapter="none"} series at
    CONSTRUCTION — zeros (and full free slots) from the first scrape,
    before any adapter ever loads."""
    from skypilot_tpu import telemetry
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    registry_lib.reset_registry()
    try:
        PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                             max_seq=64, adapter_slots=3, adapter_rank=4)
        prom = telemetry.get_registry().render_prometheus()
    finally:
        registry_lib.reset_registry()
    assert '# TYPE skytpu_adapter_bank_slots gauge' in prom
    assert 'skytpu_adapter_bank_slots{state="used"} 0' in prom
    assert 'skytpu_adapter_bank_slots{state="free"} 3' in prom
    assert '# TYPE skytpu_adapter_loads_total counter' in prom
    assert 'skytpu_adapter_loads_total 0' in prom
    assert '# TYPE skytpu_adapter_evictions_total counter' in prom
    assert 'skytpu_adapter_evictions_total 0' in prom
    assert '# TYPE skytpu_requests_total counter' in prom
    assert 'skytpu_requests_total{adapter="none"} 0' in prom


def test_adapter_series_updated_by_traffic():
    """Adapter churn moves every series: loads/evictions count LRU
    misses/evictions, the occupancy gauges track used+free == slots,
    and per-adapter request counters appear as adapters are first
    seen."""
    import numpy as np
    from skypilot_tpu import telemetry
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs, multilora
    registry_lib.reset_registry()
    try:
        cfg = configs.get_config('tiny')
        eng = PagedInferenceEngine(cfg, max_batch=2, max_seq=64,
                                   adapter_slots=2, adapter_rank=4)
        reg = eng.adapters
        rng = np.random.default_rng(0)
        for i in range(3):
            tree = {}
            for t in reg.targets:
                a_shape, b_shape = multilora.target_shapes(cfg, t, 4)
                tree[t] = {
                    'a': rng.normal(0, 0.02, (cfg.n_layers,) + a_shape)
                    .astype(np.float32),
                    'b': rng.normal(0, 0.02, (cfg.n_layers,) + b_shape)
                    .astype(np.float32)}
            reg.register(f'ad{i}', tree, scale=1.0)
        rid0 = eng.add_request([1, 2, 3], max_new_tokens=2,
                               adapter='ad0')
        rid1 = eng.add_request([4, 5], max_new_tokens=2, adapter='ad1')
        done = eng.run_to_completion(horizon=4)
        assert set(done) == {rid0, rid1}
        # Bank full + both released: ad2 evicts the coldest.
        rid2 = eng.add_request([6], max_new_tokens=2, adapter='ad2')
        eng.add_request([7, 8], max_new_tokens=2)   # base-model request
        done = eng.run_to_completion(horizon=4)
        assert rid2 in done
        treg = telemetry.get_registry()
        assert treg.get('skytpu_adapter_loads_total').value == 3
        assert treg.get('skytpu_adapter_evictions_total').value == 1
        used = treg.get('skytpu_adapter_bank_slots', state='used').value
        free = treg.get('skytpu_adapter_bank_slots', state='free').value
        assert used == 2 and free == 0
        for label, want in (('ad0', 1), ('ad1', 1), ('ad2', 1),
                            ('none', 1)):
            c = treg.get('skytpu_requests_total', adapter=label)
            assert c is not None and c.value == want, label
    finally:
        registry_lib.reset_registry()


def test_adapter_request_labels_bounded():
    """The requests_total{adapter} label set is BOUNDED: past 4 x slots
    distinct names, new ones collapse into adapter="other" — a tenant
    flood cannot grow the metric cardinality without bound."""
    from skypilot_tpu import telemetry
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    registry_lib.reset_registry()
    try:
        eng = PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                                   max_seq=64, adapter_slots=1,
                                   adapter_rank=4)
        reg = eng.adapters
        for i in range(12):
            reg.note_request(f'tenant{i}')
        treg = telemetry.get_registry()
        prom = treg.render_prometheus()
        labels = [ln.split('adapter="')[1].split('"')[0]
                  for ln in prom.splitlines()
                  if ln.startswith('skytpu_requests_total{')]
        # 'none' (pre-registered) + cap(4 x 1 slots) incl. 'other'.
        assert len(labels) <= 1 + 4 * reg.slots + 1
        assert 'other' in labels
        assert treg.get('skytpu_requests_total',
                        adapter='other').value >= 12 - 4 * reg.slots
    finally:
        registry_lib.reset_registry()


# ---------------------------------------------------------------------------
# Model server: Prometheus /metrics + /debug/requests over HTTP
# ---------------------------------------------------------------------------
def _wait_ready(port, timeout=120.0):
    import time
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/readiness', timeout=5) as r:
                if r.status == 200:
                    return
        except Exception:  # pylint: disable=broad-except
            time.sleep(0.3)
    raise RuntimeError('server did not become ready')


def test_server_prometheus_metrics_and_debug_requests():
    """e2e: serve one request, then (a) /metrics parses as Prometheus
    text with the TTFT/TPOT/queue-wait histograms, step-phase timings
    and spec gauges, (b) /metrics?format=json keeps the stable gauge
    schema, (c) /debug/requests returns the request's complete span
    timeline in lifecycle order."""
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.utils import common_utils
    port = common_utils.find_free_port(18980)
    server = ModelServer('tiny', max_batch=2, max_seq=64, port=port)
    server.start(block=False)
    try:
        _wait_ready(port)
        body = json.dumps({'prompt': [3, 1, 4, 1, 5] * 4,
                           'max_new_tokens': 6}).encode()
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate', data=body,
            headers={'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=60) as r:
            result = json.loads(r.read())
        assert len(result['tokens']) == 6

        # (a) Prometheus exposition.
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/metrics', timeout=10) as r:
            assert 'text/plain' in r.headers.get('Content-Type', '')
            prom = r.read().decode()
        for needle in ('# TYPE skytpu_request_ttft_ms histogram',
                       '# TYPE skytpu_request_tpot_ms histogram',
                       '# TYPE skytpu_request_queue_wait_ms histogram',
                       '# TYPE skytpu_engine_step_phase_seconds '
                       'histogram',
                       '# TYPE skytpu_requests_served_total counter',
                       '# TYPE skytpu_spec_accept_rate gauge',
                       '# TYPE skytpu_queue_depth gauge',
                       '# TYPE skytpu_kv_pool_tokens gauge',
                       '# TYPE skytpu_kv_pool_preemptions_total gauge'):
            assert needle in prom, needle
        assert 'skytpu_request_ttft_ms_bucket{le="+Inf"}' in prom
        assert 'phase="decode_enqueue"' in prom
        # KV pool capacity/pressure gauges: both states present with
        # the kv_cache_dtype label, capacity nonzero once the engine
        # is up, used + free == capacity.
        pool = {}
        for ln in prom.splitlines():
            if ln.startswith('skytpu_kv_pool_tokens{'):
                assert 'kv_cache_dtype="bf16"' in ln, ln
                pool[ln.split('state="')[1].split('"')[0]] = \
                    float(ln.rsplit(' ', 1)[1])
        assert set(pool) == {'used', 'free'}
        cap_lines = [ln for ln in prom.splitlines()
                     if ln.startswith('skytpu_kv_pool_token_capacity')]
        cap = float(cap_lines[0].rsplit(' ', 1)[1])
        assert cap > 0 and pool['used'] + pool['free'] == cap
        # Every sample line parses.
        for ln in prom.splitlines():
            if not ln or ln.startswith('#'):
                continue
            value = float(ln.rsplit(' ', 1)[1])
            assert not math.isnan(value)

        # (b) Stable-schema JSON retained behind ?format=json.
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/metrics?format=json',
                timeout=10) as r:
            m = json.loads(r.read())
        for key in ('requests_served', 'active_slots', 'queue_depth',
                    'prefill_inflight', 'max_batch', 'ttft_ms_median',
                    'ttft_ms_p90', 'ttft_window', 'tpot_ms_median',
                    'queue_wait_ms_median', 'speculate_k',
                    'spec_accept_rate', 'spec_tokens_per_step',
                    'spec_proposed', 'spec_accepted', 'spec_rounds',
                    'kv_pool_token_capacity', 'kv_pool_tokens_used',
                    'kv_pool_tokens_free', 'kv_pool_preemptions'):
            assert key in m, key
            assert isinstance(m[key], (int, float)), key
        assert m['kv_cache_dtype'] == 'bf16'
        assert m['kv_pool_token_capacity'] > 0
        assert m['scheduler']['speculate_k'] == 0
        assert m['requests_served'] >= 1
        assert m['ttft_window'] >= 1

        # (b2b) Multi-LoRA stable schema (PR 20): the `lora` block is
        # present even with no bank configured — stable zeros, so
        # dashboards never key-error on bankless replicas.
        lora = m['lora']
        for key in ('slots', 'used', 'free', 'rank', 'targets',
                    'loads_total', 'evictions_total', 'last_load_ms',
                    'loaded', 'pinned'):
            assert key in lora, key
        assert lora['slots'] == 0 and lora['loaded'] == []

        # (b3) Serving-mesh shape: one gauge series per logical axis
        # with 1s on a single-chip replica (stable — the series never
        # appear/disappear with mesh shape), and the JSON mesh block
        # the LB's replica view reads, present from the first scrape.
        from skypilot_tpu.parallel import mesh as mesh_lib
        assert '# TYPE skytpu_mesh_shape gauge' in prom
        for axis in mesh_lib.MESH_AXES:
            assert f'skytpu_mesh_shape{{axis="{axis}"}} 1' in prom, axis
        assert set(m['mesh']) == set(mesh_lib.MESH_AXES) | {'devices'}
        assert m['mesh']['tp'] == 1 and m['mesh']['devices'] == 1
        assert m['sched']['mesh_speedup'] == 1

        # (b2) SLO-scheduler stable schema: every per-tier series is
        # registered at construction, so both tiers (and every shed
        # reason) render from the FIRST scrape — zeros, never omitted.
        from skypilot_tpu.serve import scheduler as sched_lib
        for tier in sched_lib.TIERS:
            assert f'skytpu_sched_queue_tokens{{tier="{tier}"}}' \
                in prom, tier
            assert f'skytpu_sched_queue_depth{{tier="{tier}"}}' \
                in prom, tier
            for reason in sched_lib.SHED_REASONS:
                assert ('skytpu_sched_shed_total{reason="%s",tier="%s"}'
                        % (reason, tier)) in prom, (tier, reason)
            assert (f'# TYPE skytpu_request_ttft_ms histogram' in prom
                    and f'tier="{tier}"' in prom)
        assert '# TYPE skytpu_sched_shed_total counter' in prom
        assert '# TYPE skytpu_sched_queue_tokens gauge' in prom

        # (b3) Robustness series (round 7): faults / migrations /
        # drain / recovery all register at construction — every series
        # renders as zeros from the first scrape even though no fault,
        # migration or drain ever happened on this server.
        from skypilot_tpu.serve import faults as faults_lib
        assert '# TYPE skytpu_faults_injected_total counter' in prom
        for kind in faults_lib.FAULT_KINDS:
            assert (f'skytpu_faults_injected_total{{kind="{kind}"}} 0'
                    in prom), kind
        assert '# TYPE skytpu_requests_migrated_total counter' in prom
        for outcome in faults_lib.MIGRATION_OUTCOMES:
            assert ('skytpu_requests_migrated_total'
                    f'{{outcome="{outcome}"}} 0' in prom), outcome
        assert '# TYPE skytpu_replica_drain_seconds histogram' in prom
        assert 'skytpu_replica_drain_seconds_bucket{le="+Inf"} 0' \
            in prom
        assert '# TYPE skytpu_replica_recovery_seconds histogram' \
            in prom
        assert 'skytpu_replica_recovery_seconds_bucket{le="+Inf"} 0' \
            in prom
        # (b4) Disaggregation series (round 9): every handoff outcome,
        # transfer direction, the transfer-latency histogram and the
        # per-role gauge register at construction — zeros from the
        # first scrape on a colocated replica that never hands off.
        from skypilot_tpu.serve import disagg as disagg_lib
        assert '# TYPE skytpu_disagg_handoff_total counter' in prom
        for outcome in disagg_lib.HANDOFF_OUTCOMES:
            assert (f'skytpu_disagg_handoff_total'
                    f'{{outcome="{outcome}"}} 0' in prom), outcome
        for direction in disagg_lib.KV_TRANSFER_DIRECTIONS:
            assert (f'skytpu_kv_transfer_bytes_total'
                    f'{{direction="{direction}"}} 0' in prom), direction
        assert '# TYPE skytpu_kv_transfer_seconds histogram' in prom
        assert 'skytpu_kv_transfer_seconds_bucket{le="+Inf"} 0' in prom
        assert 'skytpu_replica_role{role="colocated"} 1' in prom
        assert 'skytpu_replica_role{role="prefill"} 0' in prom
        assert 'skytpu_replica_role{role="decode"} 0' in prom
        # (b5) Spot-resilience series (round 10): the model server
        # registers the prefix-warmup histogram and the preemption
        # counter at construction, so both series render on the first
        # scrape. (Zeros-from-fresh is pinned by
        # test_spot_scaling_series_registered_at_construction on a
        # reset registry — earlier tests in this process may have
        # legitimately moved the shared series.)
        assert '# TYPE skytpu_prefix_warmup_seconds histogram' in prom
        assert 'skytpu_prefix_warmup_seconds_bucket{le="+Inf"}' in prom
        assert '# TYPE skytpu_spot_preemptions_total counter' in prom
        assert 'skytpu_spot_preemptions_total ' in prom
        # (b6) Gang series (round 11): registered at ModelServer
        # construction on gang and non-gang replicas alike, every
        # failure cause pre-registered. (Zeros-from-fresh is pinned by
        # test_gang_series_registered_at_construction on a reset
        # registry — earlier tests in this process may have moved the
        # shared series legitimately.)
        from skypilot_tpu.serve import gang as gang_lib
        assert '# TYPE skytpu_gang_size gauge' in prom
        assert '# TYPE skytpu_gang_join_seconds histogram' in prom
        assert 'skytpu_gang_join_seconds_bucket{le="+Inf"}' in prom
        assert '# TYPE skytpu_gang_failures_total counter' in prom
        for cause in gang_lib.FAILURE_CAUSES:
            assert (f'skytpu_gang_failures_total{{cause="{cause}"}}'
                    in prom), cause
        assert '# TYPE skytpu_gang_heartbeat_age_seconds gauge' in prom
        # JSON gang block: stable schema, non-gang truth.
        assert m['gang']['world'] == 1
        assert m['gang']['barrier'] is True
        # (b7) Gray-failure series (round 13): every detection kind
        # registers at construction; the wedge-watchdog age gauge is 0
        # between steps from the first scrape.
        assert '# TYPE skytpu_gray_failures_total counter' in prom
        for kind in faults_lib.GRAY_FAILURE_KINDS:
            assert (f'skytpu_gray_failures_total{{kind="{kind}"}}'
                    in prom), kind
        assert ('# TYPE skytpu_engine_step_watchdog_age_seconds '
                'gauge') in prom
        assert 'skytpu_engine_step_watchdog_age_seconds 0' in prom
        # (b8) Multi-step decode series (round 14): the pinned
        # steps-per-call gauge (0 = adaptive horizon on this server)
        # and the decode-substeps counter render from the first scrape
        # — the server's warmup request already drove fused substeps,
        # so the counter is strictly positive and the per-substep
        # phase attribution is live.
        assert '# TYPE skytpu_decode_steps_per_call gauge' in prom
        assert 'skytpu_decode_steps_per_call 0' in prom
        assert ('# TYPE skytpu_engine_decode_substeps_total '
                'counter') in prom
        sub = [ln for ln in prom.splitlines()
               if ln.startswith('skytpu_engine_decode_substeps_total ')]
        assert sub and float(sub[0].rsplit(' ', 1)[1]) > 0
        assert m['decode_steps_per_call'] == 0
        assert m['scheduler']['decode_steps_per_call'] == 0
        phases = server.engine.phase_stats()['phases']
        assert phases['decode_enqueue']['substeps'] > 0
        assert phases['decode_enqueue']['per_substep_ms'] >= 0
        assert m['gang']['members'] == {}
        # JSON disagg block: stable schema, zeros when idle.
        assert m['disagg']['role'] == 'colocated'
        assert set(m['disagg']['handoffs']) == \
            set(disagg_lib.HANDOFF_OUTCOMES)
        assert all(v == 0 for v in m['disagg']['handoffs'].values())
        assert m['disagg']['kv_transfer_bytes'] == {'export': 0,
                                                    'ingest': 0}

        # JSON: per-tier latency quantile keys always present and
        # numeric — zeros for the tier no request used.
        assert set(m['sched']['tiers']) == set(sched_lib.TIERS)
        for tier, block in m['sched']['tiers'].items():
            for key in ('queue_depth', 'queue_tokens', 'admitted',
                        'admitted_tokens', 'admit_share', 'shed_total',
                        'ttft_ms_median', 'ttft_ms_p90',
                        'tpot_ms_median', 'queue_wait_ms_median',
                        'queue_wait_ms_p90'):
                assert key in block, (tier, key)
                assert isinstance(block[key], (int, float)), (tier, key)
        # The default tier served the request above; the other saw
        # nothing and still renders a full (zeroed) block.
        assert m['sched']['tiers']['latency']['admitted'] >= 1
        assert m['sched']['tiers']['throughput']['admitted'] == 0
        assert m['sched']['tiers']['throughput']['ttft_ms_median'] == 0
        assert m['queue_tokens_total'] >= 0
        assert m['sched']['max_queue_tokens'] > 0

        # (c) /debug/requests: the finished request's span timeline.
        with urllib.request.urlopen(
                f'http://127.0.0.1:{port}/debug/requests?limit=8',
                timeout=10) as r:
            traces = json.loads(r.read())['requests']
        assert traces
        ours = next(t for t in traces
                    if t['request_id'] == result['request_id'])
        names = [s['name'] for s in ours['spans']]
        assert names.index('queue') < names.index('prefill') \
            < names.index('decode')
        assert all(s.get('dur_ms', 0) >= 0 for s in ours['spans'])
        assert ours['done']
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# One timeline: engine-loop phases on the device trace's clock, and the
# time to first token as stages that add up
# ---------------------------------------------------------------------------
def _profile(trace_dir, body):
    """Run ``body`` under a jax.profiler trace (host and device events,
    no Python tracer: what the benchmark's ``--trace 1`` records);
    returns (plane name, line name, event name, stats, start ns, end ns)
    of every event."""
    import glob
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(str(
        trace_dir / 'plugins' / 'profile' / '*' / '*.xplane.pb')))
    data = jax.profiler.ProfileData.from_file(found[-1])
    return [(p.name, ln.name, e.name, dict(e.stats), e.start_ns,
             e.start_ns + e.duration_ns)
            for p in data.planes for ln in p.lines for e in ln.events]


def _tiny_paged():
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    return PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                                max_seq=64, prefill_chunk_tokens=8)


def test_profiled_engine_phases_on_the_trace_clock(tmp_path):
    """A profiled engine run holds the profiler's phases as ``skytpu:``
    events on the host plane of the SAME trace as the device
    operations, the chunk and decode dispatches with their program
    keys, and the ring merge under its own program name."""
    eng = _tiny_paged()
    eng.add_request([1, 2, 3] * 7, max_new_tokens=5)
    eng.run_to_completion(horizon=4)            # compile outside

    def body():
        eng.add_request([4, 5, 6] * 7, max_new_tokens=5)
        eng.run_to_completion(horizon=4)

    events = _profile(tmp_path, body)
    host = [(name, stats) for plane, _, name, stats, _, _ in events
            if plane.startswith('/host:')]
    names = {name for name, _ in host}
    for phase in ('admit', 'admit_upload', 'admit_token_merge',
                  'prefill_chunk', 'decode_enqueue', 'readback'):
        assert f'skytpu:{phase}' in names, (phase, sorted(
            n for n in names if n.startswith('skytpu:')))
    chunk = next(st for n, st in host if n == 'skytpu:prefill_chunk')
    assert chunk['prompts'] == 1 and chunk['width'] == 8 \
        and chunk['pages'] >= 1
    # (a decode_enqueue that found no slot to decode carries no key)
    decode = next(st for n, st in host
                  if n == 'skytpu:decode_enqueue' and st)
    assert decode['horizon'] == 4 and decode['pages'] >= 1
    # The programs the device ran, by the name their operations carry.
    modules = {st['hlo_module'] for _, _, _, st, _, _ in events
               if 'hlo_module' in st}
    assert 'jit_merge_ring_into_pool' in modules, modules
    assert 'jit_decode_steps' in modules and 'jit_prefill' in modules
    assert not any('unknown' in m for m in modules), modules
    # No trace running: the same phases construct no annotation.
    assert not eng.profiler._open


def test_annotation_costs_nothing_without_a_trace(monkeypatch):
    """While no jax.profiler trace runs, a phase reads the profiler's
    flag and constructs nothing (the jaxpr audit's ``telemetry`` preset
    proves the rest: no sync, no transfer, no compile)."""
    from skypilot_tpu.telemetry import profiler as profiler_lib
    prof = profiler_lib.StepProfiler(
        engine='t', registry=registry_lib.MetricsRegistry())

    class Boom:
        built = 0

        def __init__(self, *a, **kw):
            Boom.built += 1

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(prof, '_annotation', Boom)
    with prof.phase('admit'), prof.phase('prefill_chunk', prompts=2):
        prof.tag(pages=4)
    with prof.jit_key('decode', (8, False, 1)):
        pass
    assert Boom.built == 0 and not prof._open
    assert set(prof.phase_stats()['phases']) == {'admit', 'prefill_chunk'}


# ---------------------------------------------------------------------------
# The loop account: every instant of the engine thread charged to one cause
# ---------------------------------------------------------------------------
def test_nested_phases_charge_the_innermost_cause(monkeypatch):
    """Self time: an outer phase is charged only while nothing is open
    inside it, a first dispatch is ``first_call``, the thread's time
    between phases is ``loop``, and the account adds up to the clock;
    the registry's counters lag by the span still open. A phase entered
    on another thread keeps its histogram and charges nothing."""
    from skypilot_tpu.telemetry import clock
    from skypilot_tpu.telemetry import profiler as profiler_lib
    now = [100.0]
    monkeypatch.setattr(clock, 'monotonic', lambda: now[0])
    reg = registry_lib.MetricsRegistry()
    prof = profiler_lib.StepProfiler(engine='t', registry=reg)
    # Every cause is on the registry before any phase: zeros.
    for cause in profiler_lib.LOOP_CAUSES:
        assert reg.get(profiler_lib.LOOP_SECONDS_METRIC,
                       cause=cause).value == 0.0
    assert reg.get(profiler_lib.LOOP_TURNS_METRIC).value == 0.0

    def at(t):
        now[0] = 100.0 + t

    at(1)
    with prof.phase('admit'):
        at(3)
        with prof.phase('upload'):
            at(4)
        at(6)
        with prof.jit_key('decode', (8, False, 1)):
            at(10)
        with prof.jit_key('decode', (8, False, 1)):   # seen: no cause
            at(10.5)
        at(11)
    at(15)
    seconds = prof.loop_seconds()
    assert seconds['loop'] == 1 + 4                   # 0-1, 11-15 (open)
    assert seconds['admit'] == 2 + 2 + 1              # 1-3, 4-6, 10-11
    assert seconds['upload'] == 1 and seconds['first_call'] == 4
    assert sum(seconds.values()) == 15
    assert set(seconds) == set(profiler_lib.LOOP_CAUSES)
    counted = {c: reg.get(profiler_lib.LOOP_SECONDS_METRIC, cause=c).value
               for c in ('loop', 'admit', 'upload', 'first_call')}
    assert counted == {'loop': 1, 'admit': 5, 'upload': 1,
                       'first_call': 4}
    # The inclusive histograms are as they were.
    phases = prof.phase_stats()['phases']
    assert phases['admit']['total_s'] == 10 and phases['upload']['total_s'] == 1

    def elsewhere():
        with prof.phase('upload'):
            at(20)
    worker = threading.Thread(target=elsewhere)
    worker.start()
    worker.join()
    assert prof.phase_stats()['phases']['upload']['count'] == 2
    seconds = prof.loop_seconds()
    assert seconds['upload'] == 1 and seconds['loop'] == 1 + 9
    prof.note_turn()
    assert reg.get(profiler_lib.LOOP_TURNS_METRIC).value == 1.0
    null = profiler_lib.NullProfiler()
    null.note_turn()
    assert null.loop_seconds() == dict.fromkeys(profiler_lib.LOOP_CAUSES,
                                                0.0)


def test_every_phase_of_the_package_is_a_cause():
    """The JSON block lists ``LOOP_CAUSES``: a phase named anywhere else
    would charge time that no key reports."""
    import pathlib
    import re
    from skypilot_tpu.telemetry import profiler as profiler_lib
    root = pathlib.Path(profiler_lib.__file__).parents[1]
    named = set()
    for path in root.rglob('*.py'):
        named.update(re.findall(r"\.phase\(\s*'(\w+)'", path.read_text()))
    assert {'wait_work', 'dispatch', 'upload', 'eager', 'preempt',
            'pressure_drain'} <= named
    assert named <= set(profiler_lib.LOOP_CAUSES), (
        named - set(profiler_lib.LOOP_CAUSES))


def _engine_line(events):
    """The host line of the thread that entered the ``skytpu:`` events,
    as (start, end, name, stats) sorted outer first."""
    lines = {}
    for plane, line, name, stats, s, e in events:
        if plane.startswith('/host:'):
            lines.setdefault(line, []).append((s, e, name, stats))
    best = max(lines.values(), key=lambda evs: sum(
        n.startswith('skytpu:') for _, _, n, _ in evs))
    return sorted(best, key=lambda ev: (ev[0], -ev[1]))


def _inside(ev, outer):
    return outer[0] <= ev[0] and ev[1] <= outer[1]


def test_profiled_engine_holds_the_loop_account_spans(tmp_path):
    """Traced, the decode enqueue holds ``skytpu:dispatch``,
    ``skytpu:upload`` and ``skytpu:eager`` with its ``site``, a new
    program key a ``skytpu:first_call`` inside its dispatch, and every
    program the thread hands the runtime outside a compile lies in a
    dispatch or an eager span."""
    eng = _tiny_paged()
    eng.add_request([1, 2, 3] * 7, max_new_tokens=5)
    eng.run_to_completion(horizon=4)            # compile outside

    def body():
        eng.add_request([4, 5, 6] * 7, max_new_tokens=5)
        eng.run_to_completion(horizon=4)
        eng.add_request([7, 8] * 5, max_new_tokens=3)
        eng.run_to_completion(horizon=2)        # a new decode key

    events = _profile(tmp_path, body)
    line = _engine_line(events)
    spans = [ev for ev in line if ev[2].startswith('skytpu:')]
    enqueues = [ev for ev in spans if ev[2] == 'skytpu:decode_enqueue']
    for cause in ('dispatch', 'upload', 'eager'):
        assert any(ev[2] == f'skytpu:{cause}' and _inside(ev, enq)
                   for ev in spans for enq in enqueues), cause
    sites = {ev[3].get('site') for ev in spans if ev[2] == 'skytpu:eager'}
    assert {'rng_split', 'token_slice', 'slot_meta',
            'first_token_take'} <= sites, sites
    assert all(ev[3].get('site') for ev in spans
               if ev[2] == 'skytpu:eager')
    first = [ev for ev in spans if ev[2] == 'skytpu:first_call']
    assert any(ev[3]['fn'] == 'decode'
               and ev[3]['key'].startswith('(2, False,')
               for ev in first), [ev[3] for ev in first]
    assert all(any(_inside(f, d) for d in spans
                   if d[2] == 'skytpu:dispatch') for f in first)
    holders = [ev for ev in spans if ev[2] in (
        'skytpu:dispatch', 'skytpu:eager', 'skytpu:first_call')]
    programs = [ev for ev in line if ev[2].startswith('PjitFunction(')]
    assert programs
    loose = [ev[2] for ev in programs
             if not any(_inside(ev, h) for h in holders)]
    assert not loose, loose


def test_request_trace_stage_arithmetic():
    """``prepend`` moves the trace's origin back to the scheduler's
    submit; ``ttft_stages`` sums a preempted request's repeated queue
    and prefill spans, and stops at the first token."""
    trace = tracing.RequestTrace(3)
    wall_submit = trace.wall0 - 0.25
    t_engine = trace.t0
    trace.prepend('sched_wait', wall_submit)
    assert trace.wall0 == wall_submit
    assert abs((t_engine - trace.t0) - 0.25) < 1e-9
    t = t_engine
    trace.add('queue', t, t + 0.010)
    trace.add('prefill', t + 0.010, t + 0.030)          # preempted
    trace.add('queue', t + 0.030, t + 0.050, preempted=True)
    trace.add('prefill', t + 0.050, t + 0.100)
    trace.add('prefill_chunk', t + 0.060, t + 0.100)
    trace.add('first_token_lag', t + 0.100, t + 0.300)
    trace.add('queue', t + 0.400, t + 0.500, preempted=True)   # later
    trace.add('first_token_lag', t + 0.600, t + 0.700)  # re-admission
    trace.add('emit_first', t + 0.300, t + 0.302)
    stages = trace.ttft_stages()
    assert list(stages) == list(tracing.TTFT_STAGES)
    want = {'sched_wait': 250.0, 'queue': 30.0, 'prefill': 70.0,
            'first_token_lag': 200.0, 'emit_first': 2.0}
    for stage, ms in want.items():
        assert abs(stages[stage] - ms) < 1e-6, (stage, stages)
    assert abs(sum(stages.values()) - 552.0) < 1e-6
    d = trace.to_dict()
    assert d['spans'][0]['name'] == 'sched_wait'
    assert d['spans'][0]['start_ms'] == 0.0
    assert d['submitted_at'] == wall_submit
    # No first token, no stages.
    assert tracing.RequestTrace(4).ttft_stages() == {}


def _stream_generate(port, prompt, max_new_tokens):
    """POST /generate streamed; (events, seconds from send to the
    first SSE line)."""
    import time
    body = json.dumps({'prompt': prompt, 'stream': True,
                       'max_new_tokens': max_new_tokens}).encode()
    req = urllib.request.Request(
        f'http://127.0.0.1:{port}/generate', data=body,
        headers={'Content-Type': 'application/json'})
    t0, first, events = time.monotonic(), None, []
    with urllib.request.urlopen(req, timeout=120) as r:
        for raw in r:
            if raw.startswith(b'data:'):
                if first is None:
                    first = time.monotonic() - t0
                events.append(json.loads(raw[5:]))
    return events, first


def _get_json(port, path):
    with urllib.request.urlopen(
            f'http://127.0.0.1:{port}{path}', timeout=10) as r:
        return json.loads(r.read())


@pytest.fixture(scope='module')
def stage_server():
    """One ModelServer on a fresh registry for the stage tests; what
    ``/metrics?format=json`` said before any traffic rides along."""
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.utils import common_utils
    registry_lib.reset_registry()
    port = common_utils.find_free_port(18940)
    server = ModelServer('tiny', max_batch=2, max_seq=64, port=port)
    recorded = []
    finish_stream = server.finish_stream

    def finish_and_keep(sr):
        finish_stream(sr)
        recorded.append(sr)

    server.finish_stream = finish_and_keep
    server.start(block=False)
    try:
        _wait_ready(port)
        yield server, port, _get_json(port, '/metrics?format=json'), \
            recorded
    finally:
        server.stop()
        registry_lib.reset_registry()


def test_ttft_stages_present_and_zero_before_traffic(stage_server):
    from skypilot_tpu.telemetry import profiler as profiler_lib
    _, _, before, _ = stage_server
    assert list(before['ttft_stages']) == list(tracing.TTFT_STAGES)
    for stage, block in before['ttft_stages'].items():
        assert block == {'p50': 0.0, 'p95': 0.0, 'n': 0}, stage
    loop = before['engine_loop']
    assert set(loop) == {'clock_s', 'lock_held_seconds_total',
                         'lock_wait_seconds_total',
                         'decode_substeps_total',
                         'decode_live_rows_total',
                         # expert layers and prefill attention (PR 30):
                         # zeros where the model routes nothing
                         'moe_layer_steps_total',
                         'moe_distinct_experts_total',
                         'moe_assignments_total',
                         'prefill_attn_pairs_total',
                         # the latent paged decode kernel (PR 31): zeros
                         # for a GQA cache
                         'decode_attn_pages_live_total',
                         'decode_attn_pages_table_total',
                         # what a cached token is, and the prompt tokens
                         # prefilled (PR 35)
                         'prefill_tokens_total', 'kv_cache_layers',
                         'kv_token_bytes',
                         # token rows due for the pools against rows the
                         # row-write programs' shapes carried (PR 36)
                         'pool_write_rows_live_total',
                         'pool_write_rows_offered_total',
                         # held experts of a model told its range, and
                         # the per-slot state of recurrent layers (PR
                         # 37): zeros for a model with neither
                         'moe_held_experts', 'moe_assignments_held_total',
                         'recurrent_layers', 'recurrent_state_bytes',
                         'state_resets_total',
                         'state_recompute_tokens_total',
                         # pieces wider than the chunk a prompt alone
                         # takes, their tokens and the width: zeros but
                         # the width (the chunk) where no wider piece pays
                         'prefill_wide_pieces_total',
                         'prefill_wide_tokens_total', 'prefill_wide_width',
                         # the loop account: the engine thread's seconds
                         # by cause, and the turns that had work
                         'loop_turns_total'} | {
        f'loop_{cause}_seconds_total'
        for cause in profiler_lib.LOOP_CAUSES}
    assert all(isinstance(v, (int, float)) for v in loop.values())
    assert loop['loop_turns_total'] == 0.0
    assert loop['loop_wait_work_seconds_total'] > 0.0    # parked
    assert loop['moe_layer_steps_total'] == 0
    assert loop['prefill_tokens_total'] > 0
    assert loop['prefill_wide_pieces_total'] == 0
    assert loop['prefill_wide_tokens_total'] == 0
    assert 0 < loop['pool_write_rows_live_total'] \
        < loop['pool_write_rows_offered_total']     # 1 live slot of 2
    assert loop['kv_cache_layers'] == 2             # tiny: 2 layers, 1 pass
    assert loop['kv_token_bytes'] == 2 * 2 * 2 * 16 * 2
    assert loop['prefill_attn_pairs_total'] > 0     # the warm-up's prompt
    # The boot's warm-up request ran on the engine directly: substeps
    # are counted, but no engine-loop turn has taken the lock yet.
    assert loop['lock_held_seconds_total'] == 0.0
    assert loop['decode_substeps_total'] > 0


def test_sse_request_ttft_stages_add_up(stage_server):
    """A request served over SSE carries all five stage spans on one
    trace, in order and not overlapping, and they add up to what they
    claim to split: first flush - submit."""
    server, port, before, recorded = stage_server
    events, client_first_s = _stream_generate(
        port, [3, 1, 4, 1, 5] * 4, 6)
    done = events[-1]
    assert done['done'] and len(done['tokens']) == 6
    import time
    deadline = time.time() + 10
    while not recorded and time.time() < deadline:
        time.sleep(0.01)                 # the handler's finally clause
    sr = recorded[-1]
    traces = _get_json(port, '/debug/requests?limit=8')['requests']
    ours = next(t for t in traces
                if t['request_id'] == done['request_id'])
    assert len(ours['trace_id']) == 32
    spans = {s['name']: s for s in ours['spans']
             if s['name'] in tracing.TTFT_STAGES}
    assert set(spans) == set(tracing.TTFT_STAGES)
    ordered = [spans[name] for name in tracing.TTFT_STAGES]
    assert ordered[0]['start_ms'] == 0.0
    for a, b in zip(ordered, ordered[1:]):
        end = a['start_ms'] + a['dur_ms']
        # contiguous: no overlap, and no hole beyond two clock reads
        # (a thread switch between them, at worst)
        assert -0.05 <= b['start_ms'] - end <= 20.0, (a, b)
    total = sum(s['dur_ms'] for s in ordered)
    trace = tracing.get_trace_buffer().find(done['request_id'])
    flush_minus_submit = (sr.first_flush_time - trace.t0) * 1e3
    assert abs(total - flush_minus_submit) <= 0.1 * flush_minus_submit
    # The client saw its first line no earlier than the flush returned
    # less the connection's own set-up, which no stage claims.
    assert total <= client_first_s * 1e3 + 1.0
    # The span list's lifecycle order still holds with the new stages.
    names = [s['name'] for s in ours['spans']]
    assert names.index('sched_wait') < names.index('queue') \
        < names.index('prefill') < names.index('first_token_lag') \
        < names.index('decode')
    after = _get_json(port, '/metrics?format=json')
    for stage in tracing.TTFT_STAGES:
        assert after['ttft_stages'][stage]['n'] == 1, stage
        assert abs(after['ttft_stages'][stage]['p50']
                   - spans[stage]['dur_ms']) < 0.01, stage
    loop0, loop1 = before['engine_loop'], after['engine_loop']
    held = (loop1['lock_held_seconds_total']
            - loop0['lock_held_seconds_total'])
    assert 0 < held <= loop1['clock_s'] - loop0['clock_s']
    rows = (loop1['decode_live_rows_total']
            - loop0['decode_live_rows_total'])
    substeps = (loop1['decode_substeps_total']
                - loop0['decode_substeps_total'])
    assert substeps > 0 and rows == substeps    # one request live


def test_stage_and_loop_series_in_prometheus_text(stage_server):
    _, port, _, _ = stage_server
    with urllib.request.urlopen(
            f'http://127.0.0.1:{port}/metrics', timeout=10) as r:
        prom = r.read().decode()
    for needle in ('# TYPE skytpu_request_ttft_stage_ms histogram',
                   '# TYPE skytpu_request_sse_write_ms histogram',
                   '# TYPE skytpu_engine_lock_held_seconds_total counter',
                   '# TYPE skytpu_engine_lock_wait_seconds_total counter',
                   '# TYPE skytpu_engine_decode_live_rows_total counter',
                   'phase="lock_wait"', 'phase="fill_engine"',
                   'phase="route_events"'):
        assert needle in prom, needle
    for stage in tracing.TTFT_STAGES:
        assert ('skytpu_request_ttft_stage_ms_bucket'
                f'{{le="+Inf",stage="{stage}"}}') in prom, stage
    assert 'skytpu_attn_kernel_ms' not in prom


def _idle_loop(port):
    """``engine_loop`` once the loop has parked: two scrapes 50 ms
    apart that count the same turns."""
    import time
    last = _get_json(port, '/metrics?format=json')['engine_loop']
    for _ in range(100):
        time.sleep(0.05)
        now = _get_json(port, '/metrics?format=json')['engine_loop']
        if now['loop_turns_total'] == last['loop_turns_total']:
            return now
        last = now
    raise AssertionError('the engine loop never parked')


def test_loop_account_adds_up_over_a_served_burst(stage_server):
    """Over a burst of streamed requests the engine thread's causes add
    up to its clock, each phase the loop enters at top level is its
    causes' self times, and the new spans took their share."""
    from skypilot_tpu.telemetry import profiler as profiler_lib
    server, port, _, _ = stage_server
    prof = server.engine.profiler
    top = ('lock_wait', 'fill_engine', 'readback', 'admit',
           'decode_enqueue', 'route_events')

    def inclusive():
        phases = prof.phase_stats()['phases']
        return sum(phases.get(p, {}).get('total_s', 0.0) for p in top)

    a = _idle_loop(port)
    inc_a = inclusive()
    workers = [threading.Thread(target=_stream_generate,
                                args=(port, [5, 3, 9, i] * 4, 6))
               for i in range(3)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    b = _idle_loop(port)
    inc_b = inclusive()
    d = {k: b[k] - a[k] for k in a}
    turns = d['loop_turns_total']
    assert turns > 0
    causes = {c: d[f'loop_{c}_seconds_total']
              for c in profiler_lib.LOOP_CAUSES}
    assert abs(sum(causes.values()) - d['clock_s']) <= 1e-3 * turns
    # Self times under the loop's top-level phases are those phases,
    # inclusive (the scrapes both fell in a wait, which neither counts).
    under_top = sum(v for c, v in causes.items()
                    if c not in ('loop', 'wait_work'))
    assert abs(under_top - (inc_b - inc_a)) <= 1e-3 * turns, (
        under_top, inc_b - inc_a)
    for cause in ('wait_work', 'lock_wait', 'fill_engine', 'decode_enqueue',
                  'route_events', 'dispatch', 'upload', 'eager'):
        assert causes[cause] > 0, (cause, causes)


def test_parked_server_waits_for_work(stage_server):
    """With nothing to do the engine thread is in ``wait_work`` (the
    open span counted to the scrape) and counts no turn."""
    import time
    _, port, _, _ = stage_server
    a = _idle_loop(port)
    time.sleep(0.3)
    b = _get_json(port, '/metrics?format=json')['engine_loop']
    clock_s = b['clock_s'] - a['clock_s']
    waited = (b['loop_wait_work_seconds_total']
              - a['loop_wait_work_seconds_total'])
    assert b['loop_turns_total'] == a['loop_turns_total']
    assert 0.9 * clock_s <= waited <= clock_s + 1e-3, (waited, clock_s)


def test_telemetry_off_no_annotation_no_stage(monkeypatch, tmp_path):
    """``SKYTPU_TELEMETRY=0``: a profiled request through the server
    enters no annotation, mints no trace and stamps no stage."""
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.telemetry import profiler as profiler_lib
    from skypilot_tpu.utils import common_utils
    monkeypatch.setenv('SKYTPU_TELEMETRY', '0')
    registry_lib.reset_registry()
    port = common_utils.find_free_port(18960)
    server = ModelServer('tiny', max_batch=2, max_seq=64, port=port)
    server.start(block=False)
    try:
        _wait_ready(port)
        assert isinstance(server.engine.profiler,
                          profiler_lib.NullProfiler)
        traces_before = len(tracing.get_trace_buffer())
        out = {}

        def body():
            out['events'], _ = _stream_generate(
                port, [2, 7, 1, 8] * 4, 4)

        events = _profile(tmp_path, body)
        assert out['events'][-1]['done']
        assert not [name for _, _, name, _, _, _ in events
                    if name.startswith('skytpu:')]
        assert len(tracing.get_trace_buffer()) == traces_before
        m = _get_json(port, '/metrics?format=json')
        assert all(block['n'] == 0
                   for block in m['ttft_stages'].values())
        assert m['requests_served'] == 1
        assert all(m['engine_loop'][f'loop_{cause}_seconds_total'] == 0.0
                   for cause in profiler_lib.LOOP_CAUSES)
    finally:
        server.stop()
        registry_lib.reset_registry()
