"""Spot-resilient serving (round 10): forecast-aware autoscaling,
preemption-survivable replicas, and prefix-cache checkpoint/warmup.

The contracts under test:

- The forecaster is pure and clock-injected (no sleeps): synthetic
  diurnal/bursty traces replay to identical forecasts, and the
  forecast autoscaler pre-scales *ahead* of a ramp by the learned
  provisioning lead time (strictly fewer modeled sheds than the
  reactive autoscaler on the identical trace).
- ``max_replicas: None`` means UNBOUNDED autoscaling — the target must
  never silently collapse to ``min_replicas``.
- On a preemption warning the replica's hot prefix-cache chains (and
  in-flight request snapshots) checkpoint through the SKKV/SKPF wire
  codec, and a recovered replica lands them BEFORE it enters rotation:
  the first prefix-hit continuation is byte-identical to the
  pre-preemption run, on both engines.
- Seeded spot kills through the LB lose ZERO requests.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from skypilot_tpu import telemetry
from skypilot_tpu.inference import kv_transfer
from skypilot_tpu.serve import autoscalers as asc_lib
from skypilot_tpu.serve import faults as faults_lib
from skypilot_tpu.serve import forecaster as forecaster_lib
from skypilot_tpu.serve.autoscalers import DecisionOperator, ReplicaView
from skypilot_tpu.serve.service_spec import SkyServiceSpec
from skypilot_tpu.utils import common_utils

jax.config.update('jax_platforms', 'cpu')


def _spec(**kw):
    defaults = dict(readiness_path='/readiness', min_replicas=1,
                    max_replicas=4, target_qps_per_replica=1.0,
                    upscale_delay_seconds=20.0,
                    downscale_delay_seconds=40.0)
    defaults.update(kw)
    return SkyServiceSpec(**defaults)


def _diurnal_trace(t0, seasons=3, season_s=300.0, burst_s=60.0,
                   base_qps=0.5, burst_qps=6.0):
    """Deterministic 'diurnal' arrivals: a quiet base rate with one
    burst window per season. Returns sorted timestamps."""
    out = []
    t = t0
    end = t0 + seasons * season_s
    while t < end:
        phase = (t - t0) % season_s
        rate = burst_qps if phase < burst_s else base_qps
        out.append(t)
        t += 1.0 / rate
    return out


# ---------------------------------------------------------------- forecaster
class TestForecaster:

    def test_flat_traffic_level(self):
        f = forecaster_lib.TrafficForecaster(bucket_s=10.0,
                                             season_s=300.0)
        t0 = 10_000.0
        f.observe([t0 + i * 0.5 for i in range(600)])   # 2 qps, 300 s
        now = t0 + 300.0
        assert f.qps('all', now) == pytest.approx(2.0, rel=0.15)
        # Flat traffic: every horizon forecasts ~the level.
        for h in (0.0, 30.0, 120.0):
            assert f.forecast_qps(h, 'all', now) == pytest.approx(
                2.0, rel=0.25), h

    def test_ramp_trend_projects_ahead(self):
        f = forecaster_lib.TrafficForecaster(bucket_s=10.0,
                                             season_s=10_000.0)
        t0 = 50_000.0
        # Linearly accelerating arrivals: bucket i carries i+1 events.
        ts = []
        for i in range(12):
            ts.extend(t0 + i * 10.0 + j * (10.0 / (i + 1))
                      for j in range(i + 1))
        f.observe(ts)
        now = t0 + 120.0
        level = f.qps('all', now)
        ahead = f.forecast_qps(60.0, 'all', now)
        assert ahead > level          # the trend projects the ramp on

    def test_seasonal_burst_predicted_before_it_lands(self):
        season = 300.0
        f = forecaster_lib.TrafficForecaster(bucket_s=10.0,
                                             season_s=season)
        t0 = 100_000.0
        f.observe(_diurnal_trace(t0, seasons=2, season_s=season))
        # Now sits in the QUIET phase just before season 3's burst.
        now = t0 + 2 * season - 30.0
        quiet = f.qps('all', now)
        # 40 s ahead lands inside the (seasonal) burst window.
        ahead = f.forecast_qps(40.0, 'all', now)
        assert quiet < 1.5
        assert ahead > 3.0            # seasonal component saw the burst
        assert ahead > 2 * quiet

    def test_ring_is_bounded(self):
        f = forecaster_lib.TrafficForecaster(bucket_s=1.0,
                                             season_s=10.0,
                                             ring_buckets=32)
        f.observe([float(i) for i in range(10_000)])
        assert len(f._counts['all']) <= 32

    def test_per_tier_series(self):
        f = forecaster_lib.TrafficForecaster(bucket_s=10.0,
                                             season_s=300.0)
        t0 = 1_000.0
        ts = [t0 + i * 0.5 for i in range(200)]
        tiers = ['latency' if i % 4 == 0 else 'throughput'
                 for i in range(200)]
        f.observe(ts, tiers)
        now = t0 + 100.0
        assert f.qps('all', now) > 0
        assert f.qps('throughput', now) > f.qps('latency', now) > 0

    def test_deterministic_replay(self):
        trace = _diurnal_trace(5_000.0)
        outs = []
        for _ in range(2):
            f = forecaster_lib.TrafficForecaster(bucket_s=10.0,
                                                 season_s=300.0)
            f.observe(trace)
            outs.append([f.forecast_qps(h, 'all', 5_000.0 + 700.0)
                         for h in (0, 30, 60, 120)])
        assert outs[0] == outs[1]


# -------------------------------------------------------- autoscaler units
class TestUnboundedMaxReplicas:

    def test_none_max_means_unbounded(self):
        # Satellite fix: the raw target used to collapse to
        # min_replicas whenever max_replicas was None.
        asc = asc_lib.RequestRateAutoscaler(
            _spec(max_replicas=None, upscale_delay_seconds=20.0))
        now = 1000.0
        asc.collect_request_information(
            [now - i * 0.01 for i in range(6000)])    # ~100 qps
        assert asc.evaluate_scaling([ReplicaView(1, True, False)],
                                    now=now) == []    # breach t0
        decisions = asc.evaluate_scaling([ReplicaView(1, True, False)],
                                         now=now + 20.0)
        ups = [d for d in decisions
               if d.operator == DecisionOperator.SCALE_UP]
        assert len(ups) >= 50         # NOT clamped back to min=1

    def test_update_spec_none_max_keeps_target(self):
        asc = asc_lib.RequestRateAutoscaler(_spec(max_replicas=8))
        asc.target_num_replicas = 6
        asc.update_spec(_spec(max_replicas=None), version=2)
        assert asc.target_num_replicas == 6   # not collapsed to 1
        asc.update_spec(_spec(max_replicas=3), version=3)
        assert asc.target_num_replicas == 3   # explicit bound applies

    def test_spec_yaml_unbounded_roundtrip(self):
        spec = SkyServiceSpec.from_yaml_config({
            'readiness_probe': '/readiness',
            'replica_policy': {'min_replicas': 2,
                               'target_qps_per_replica': 1.5},
        })
        assert spec.autoscaling_enabled
        assert spec.max_replicas is None
        spec2 = SkyServiceSpec.from_yaml_config(spec.to_yaml_config())
        assert spec2.max_replicas is None
        assert spec2 == spec

    def test_pending_timestamps_bounded_between_trims(self):
        asc = asc_lib.RequestRateAutoscaler(_spec())
        asc.MAX_PENDING_TIMESTAMPS = 500
        base = 10_000.0
        for wave in range(10):
            asc.collect_request_information(
                [base + wave + i * 1e-4 for i in range(200)])
        assert len(asc._request_timestamps) <= 500
        # The newest timestamps survive the cap.
        assert max(asc._request_timestamps) >= base + 9


class TestForecastAutoscaler:

    def _forecast_spec(self, **kw):
        defaults = dict(forecast_enabled=True,
                        forecast_bucket_seconds=10.0,
                        forecast_season_seconds=300.0,
                        forecast_horizon_seconds=60.0,
                        upscale_delay_seconds=10.0,
                        downscale_delay_seconds=20.0,
                        initial_delay_seconds=40.0)
        defaults.update(kw)
        return _spec(**defaults)

    def test_from_spec_selects_forecast_classes(self):
        asc = asc_lib.Autoscaler.from_spec(self._forecast_spec())
        assert isinstance(asc, asc_lib.ForecastRequestRateAutoscaler)
        asc = asc_lib.Autoscaler.from_spec(
            self._forecast_spec(dynamic_ondemand_fallback=True))
        assert isinstance(asc, asc_lib.ForecastFallbackAutoscaler)

    def test_lead_time_learned_from_provision_observations(self):
        asc = asc_lib.Autoscaler.from_spec(self._forecast_spec())
        assert asc.provision_lead_s() == 40.0      # spec default
        asc.note_provision_seconds(100.0)
        assert asc.provision_lead_s() == pytest.approx(100.0)
        asc.note_provision_seconds(20.0)           # EWMA moves toward it
        assert 20.0 < asc.provision_lead_s() < 100.0

    def test_prescales_ahead_of_seasonal_burst(self):
        """The headline behavior: at a QUIET moment whose lead window
        contains the (seasonal) burst, the forecast autoscaler's raw
        target already exceeds the reactive one."""
        season = 300.0
        asc = asc_lib.Autoscaler.from_spec(self._forecast_spec())
        t0 = 100_000.0
        asc.collect_request_information(
            _diurnal_trace(t0, seasons=2, season_s=season))
        asc.note_provision_seconds(40.0)
        now = t0 + 2 * season - 30.0   # quiet; burst lands in ~30 s
        reactive = asc._reactive_target(now)
        raw = asc._raw_target(now)
        assert reactive == 1           # the window sees only quiet
        assert raw >= 3                # the forecast sees the burst

    def test_never_drains_midburst(self):
        season = 300.0
        asc = asc_lib.Autoscaler.from_spec(self._forecast_spec())
        t0 = 100_000.0
        asc.collect_request_information(
            _diurnal_trace(t0, seasons=2, season_s=season))
        asc.note_provision_seconds(40.0)
        asc.target_num_replicas = 4
        now = t0 + 2 * season - 30.0   # burst inside the lead window
        assert not asc._downscale_allowed(1, now)
        # Deep inside the quiet phase with no burst in the window,
        # scale-down clears.
        quiet_now = t0 + 2 * season + 120.0
        asc.collect_request_information(
            [quiet_now - 60 + i * 2.0 for i in range(30)])
        assert asc._downscale_allowed(3, quiet_now)

    def test_forecast_sheds_strictly_fewer_than_reactive(self):
        """Capacity simulation over the identical diurnal trace:
        arrivals beyond (replicas x target_qps) in any second count as
        shed. Forecast pre-scaling must shed strictly less — the bench
        `spot` block records the same comparison on live servers."""
        season = 300.0
        trace = _diurnal_trace(0.0, seasons=4, season_s=season,
                               burst_qps=8.0)
        qps_per = 2.0

        def simulate(asc, lead_known):
            if lead_known and hasattr(asc, 'note_provision_seconds'):
                asc.note_provision_seconds(30.0)
            shed = 0
            replicas = [ReplicaView(1, True, False)]
            pending_ready = []      # (ready_at, view)
            next_id = 2
            idx = 0
            for now in np.arange(0.0, 4 * season, 10.0):
                batch = []
                while idx < len(trace) and trace[idx] < now:
                    batch.append(trace[idx])
                    idx += 1
                asc.collect_request_information(batch)
                # Replicas provision with a 30 s lead.
                pending_ready = [(t, v) for t, v in pending_ready
                                 if t > now or replicas.append(v)]
                decisions = asc.evaluate_scaling(
                    replicas + [v for _, v in pending_ready], now=now)
                for d in decisions:
                    if d.operator == DecisionOperator.SCALE_UP:
                        pending_ready.append(
                            (now + 30.0,
                             ReplicaView(next_id, True, False)))
                        next_id += 1
                    else:
                        rid = d.target['replica_id']
                        replicas = [v for v in replicas
                                    if v.replica_id != rid]
                # Shed accounting: arrivals this tick beyond capacity.
                cap = len(replicas) * qps_per * 10.0
                shed += max(0, len(batch) - int(cap))
            return shed

        reactive = asc_lib.RequestRateAutoscaler(
            _spec(target_qps_per_replica=qps_per, max_replicas=8,
                  upscale_delay_seconds=10.0,
                  downscale_delay_seconds=60.0))
        forecast = asc_lib.Autoscaler.from_spec(self._forecast_spec(
            target_qps_per_replica=qps_per, max_replicas=8,
            upscale_delay_seconds=10.0, downscale_delay_seconds=60.0,
            forecast_season_seconds=season))
        shed_reactive = simulate(reactive, lead_known=False)
        shed_forecast = simulate(forecast, lead_known=True)
        assert shed_forecast < shed_reactive, (shed_forecast,
                                               shed_reactive)


class TestFallbackBackfillMatrix:
    """Dynamic on-demand backfill decision matrix: (ready spot,
    pending spot, on-demand) in -> (spot ups, od ups, downs) out."""

    def _asc(self, target=3, base=0):
        spec = _spec(min_replicas=3, max_replicas=6,
                     base_ondemand_fallback_replicas=base,
                     dynamic_ondemand_fallback=True)
        asc = asc_lib.Autoscaler.from_spec(spec)
        assert isinstance(asc, asc_lib.FallbackRequestRateAutoscaler)
        asc.target_num_replicas = target
        return asc

    @staticmethod
    def _classify(decisions):
        spot_up = sum(1 for d in decisions
                      if d.operator == DecisionOperator.SCALE_UP
                      and d.target['use_spot'])
        od_up = sum(1 for d in decisions
                    if d.operator == DecisionOperator.SCALE_UP
                    and not d.target['use_spot'])
        downs = [d.target['replica_id'] for d in decisions
                 if d.operator == DecisionOperator.SCALE_DOWN]
        return spot_up, od_up, downs

    def test_all_spot_ready_no_backfill(self):
        views = [ReplicaView(i, True, True) for i in (1, 2, 3)]
        assert self._classify(self._asc().evaluate_scaling(
            views, now=1e3)) == (0, 0, [])

    def test_one_spot_preempted_backfills_od_and_respawns_spot(self):
        views = [ReplicaView(1, True, True), ReplicaView(2, True, True),
                 ReplicaView(3, False, True, is_terminal=True)]
        spot_up, od_up, downs = self._classify(
            self._asc().evaluate_scaling(views, now=1e3))
        assert (spot_up, od_up, downs) == (1, 1, [])

    def test_spot_recovering_not_ready_keeps_backfill(self):
        # Replacement spot is provisioning (alive, not ready): the
        # temporary on-demand replica must NOT be drained yet.
        views = [ReplicaView(1, True, True), ReplicaView(2, True, True),
                 ReplicaView(3, False, True),       # provisioning spot
                 ReplicaView(4, True, False)]       # od backfill
        spot_up, od_up, downs = self._classify(
            self._asc().evaluate_scaling(views, now=1e3))
        assert (spot_up, od_up, downs) == (0, 0, [])

    def test_spot_recovered_drains_backfill(self):
        views = [ReplicaView(i, True, True) for i in (1, 2, 3)]
        views.append(ReplicaView(4, True, False))   # od now excess
        spot_up, od_up, downs = self._classify(
            self._asc().evaluate_scaling(views, now=1e3))
        assert (spot_up, od_up, downs) == (0, 0, [4])

    def test_base_ballast_survives_spot_drought(self):
        asc = self._asc(target=3, base=1)
        views = [ReplicaView(1, True, False)]       # ballast od only
        spot_up, od_up, downs = self._classify(
            asc.evaluate_scaling(views, now=1e3))
        # 2 spot wanted + 2 od backfill for the unready spot (capped
        # at target 3 total od: 1 ballast + 2 backfill, have 1).
        assert spot_up == 2 and od_up == 2 and downs == []


# ------------------------------------------------------------ wire codec
class TestCheckpointCodec:

    def _entry(self, n_rows=8, dtype='bf16'):
        import ml_dtypes
        shape = (2, n_rows, 2, 4)
        if dtype == 'int8':
            rng = np.random.RandomState(0)
            return {
                'kv_cache_dtype': 'int8', 'n_rows': n_rows,
                'model': {'n_layers': 2, 'n_kv_heads': 2,
                          'head_dim': 4},
                'tokens': list(range(1, n_rows + 2)),
                'k': rng.randint(-127, 127, shape).astype(np.int8),
                'v': rng.randint(-127, 127, shape).astype(np.int8),
                'k_scale': rng.rand(2, n_rows, 2).astype(np.float32),
                'v_scale': rng.rand(2, n_rows, 2).astype(np.float32),
            }
        rng = np.random.RandomState(1)
        return {
            'kv_cache_dtype': 'bf16', 'n_rows': n_rows,
            'model': {'n_layers': 2, 'n_kv_heads': 2, 'head_dim': 4},
            'tokens': list(range(1, n_rows + 2)),
            'k': rng.rand(*shape).astype(ml_dtypes.bfloat16),
            'v': rng.rand(*shape).astype(ml_dtypes.bfloat16),
            'k_scale': None, 'v_scale': None,
        }

    @pytest.mark.parametrize('dtype', ['bf16', 'int8'])
    def test_prefix_roundtrip_exact(self, dtype):
        entry = self._entry(dtype=dtype)
        out = kv_transfer.decode_prefix_chain(
            kv_transfer.encode_prefix_chain(entry))
        assert out['tokens'] == entry['tokens']
        assert out['n_rows'] == entry['n_rows']
        for key in ('k', 'v'):
            np.testing.assert_array_equal(out[key], entry[key])
            assert out[key].dtype == entry[key].dtype
        if dtype == 'int8':
            np.testing.assert_array_equal(out['k_scale'],
                                          entry['k_scale'])

    def test_prefix_token_count_strict(self):
        entry = self._entry()
        entry['tokens'] = entry['tokens'][:-2]      # != n_rows + 1
        with pytest.raises(ValueError, match='n_rows'):
            kv_transfer.encode_prefix_chain(entry)

    def test_checkpoint_container_mixed_kinds(self):
        prefix = self._entry()
        request = {
            'kv_cache_dtype': 'bf16', 'n_rows': 8,
            'model': {'n_layers': 2, 'n_kv_heads': 2, 'head_dim': 4},
            'prompt': list(range(1, 8)), 'output': [9, 10],
            'max_new_tokens': 16, 'temperature': 0.0, 'top_k': 0,
            'top_p': 1.0, 'eos_id': None, 'stop': None, 'priority': 0,
            'k': prefix['k'], 'v': prefix['v'],
            'k_scale': None, 'v_scale': None,
        }
        blob = kv_transfer.encode_checkpoint([prefix, request])
        out = kv_transfer.decode_checkpoint(blob)
        assert [e['entry_kind'] for e in out] == ['prefix', 'request']
        # A request entry views as a prefix entry with ctx tokens.
        as_p = kv_transfer.as_prefix_entry(out[1])
        assert as_p['tokens'] == request['prompt'] + request['output']
        # Empty checkpoints are valid (cold replica answered anyway).
        assert kv_transfer.decode_checkpoint(
            kv_transfer.encode_checkpoint([])) == []

    def test_checkpoint_strict_rejections(self):
        blob = kv_transfer.encode_checkpoint([self._entry()])
        with pytest.raises(ValueError, match='magic'):
            kv_transfer.decode_checkpoint(b'XXXX' + blob[4:])
        with pytest.raises(ValueError, match='trailing'):
            kv_transfer.decode_checkpoint(blob + b'junk')
        with pytest.raises(ValueError):
            kv_transfer.decode_checkpoint(blob[:-3])   # truncated


# ------------------------------------------- engine checkpoint/recovery
def _make_engine(**kw):
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import configs
    return PagedInferenceEngine(configs.get_config('tiny'), max_batch=2,
                                max_seq=256, telemetry=False, **kw)


SHARED_PREFIX = [7 + (j % 50) for j in range(40)]


def test_preempt_checkpoint_recover_byte_identical():
    """The full preemption->checkpoint->recovery loop at engine level:
    a request mid-decode checkpoints (SKKV) and resumes
    BYTE-IDENTICALLY on a fresh engine; the hot prefix chains
    additionally checkpoint (SKPF) and a warmed fresh engine serves a
    shared-prefix prompt with a prefix HIT and the identical
    continuation."""
    eng = _make_engine()
    prompt = SHARED_PREFIX + [3, 4, 5]
    rid = eng.add_request(list(prompt), max_new_tokens=12)
    while True:
        eng.step(horizon=1)
        req = next((r for r in eng._slots
                    if r is not None and r.request_id == rid), None)
        if req is not None and len(req.output) >= 4:
            break
    snap, _ = eng.export_kv_snapshot(rid)
    assert snap is not None
    pentries, _ = eng.export_prefix_snapshots()
    assert pentries, 'hot prefix chains must export'
    entries = [snap] + pentries
    blob = kv_transfer.encode_checkpoint(entries)
    # Reference: the uninterrupted run.
    eng.run_to_completion(horizon=8)
    ref = list(eng.pop_finished(rid).output)

    decoded = kv_transfer.decode_checkpoint(blob)
    # (a) In-flight resume: byte-identical continuation on a FRESH
    # engine.
    eng2 = _make_engine()
    req_entry = next(e for e in decoded
                     if e['entry_kind'] == 'request')
    rid2 = eng2.ingest_kv_snapshot(req_entry)
    eng2.run_to_completion(horizon=8)
    assert list(eng2.pop_finished(rid2).output) == ref

    # (b) Prefix warmup: a warmed fresh engine prefix-HITS the shared
    # prefix and continues byte-identically.
    eng3 = _make_engine()
    rows = sum(eng3.warm_prefix(e) for e in decoded)
    assert rows > 0
    hits0 = eng3.alloc.prefix_hits
    rid3 = eng3.add_request(list(prompt), max_new_tokens=12)
    eng3.run_to_completion(horizon=8)
    out3 = list(eng3.pop_finished(rid3).output)
    assert eng3.alloc.prefix_hits > hits0   # warm, not recomputed
    # Byte-identical to the pre-preemption engine's continuation of
    # the same prompt.
    rid_ref = eng.add_request(list(prompt), max_new_tokens=12)
    eng.run_to_completion(horizon=8)
    assert out3 == list(eng.pop_finished(rid_ref).output)


def test_warm_prefix_idempotent_and_validated():
    eng = _make_engine()
    prompt = SHARED_PREFIX + [9, 9]
    rid = eng.add_request(list(prompt), max_new_tokens=4)
    eng.run_to_completion(horizon=8)
    eng.pop_finished(rid)
    entries, _ = eng.export_prefix_snapshots()
    assert entries
    eng2 = _make_engine()
    assert sum(eng2.warm_prefix(e) for e in entries) > 0
    # Idempotent: a second warmup of the same chains lands nothing.
    assert sum(eng2.warm_prefix(e) for e in entries) == 0
    # Model mismatch is a loud permanent refusal.
    bad = dict(entries[0])
    bad['model'] = dict(bad['model'], n_kv_heads=99)
    with pytest.raises(ValueError, match='model mismatch'):
        eng2.warm_prefix(bad)


def test_warm_prefix_capacity_refusal_is_retryable():
    from skypilot_tpu.inference.kv_transfer import HandoffCapacityError
    eng = _make_engine()
    long_prompt = [3 + (j % 90) for j in range(150)]
    rid = eng.add_request(list(long_prompt), max_new_tokens=4)
    eng.run_to_completion(horizon=8)
    eng.pop_finished(rid)
    entries, _ = eng.export_prefix_snapshots()
    assert entries
    # A pool too small for the chain refuses retryably.
    tiny = _make_engine(n_pages=3)
    with pytest.raises(HandoffCapacityError):
        for e in entries:
            tiny.warm_prefix(e)


# ------------------------------------------------- replica manager flows
def _make_manager(tmp_path, monkeypatch, **spec_kw):
    monkeypatch.setenv('SKYTPU_SERVE_DIR', str(tmp_path / 'serve'))
    from skypilot_tpu.serve.replica_managers import ReplicaManager
    spec = SkyServiceSpec(readiness_path='/readiness', **spec_kw)
    return ReplicaManager('spot-test', spec, {})


class _FakeReplica:
    """A minimal replica model server: /readiness, /checkpoint (serves
    a canned container), /kv/warmup (records the landing and whether
    the manager had already marked any replica READY), /drain."""

    def __init__(self, ckpt_blob=b'', manager=None):
        import http.server
        outer = self
        self.warmup_calls = []
        self.checkpoint_calls = 0
        self.ready_urls_at_warmup = None

        class H(http.server.BaseHTTPRequestHandler):
            timeout = 30

            def log_message(self, *a):
                del a

            def _send(self, code, body, ctype='application/json'):
                self.send_response(code)
                self.send_header('Content-Type', ctype)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                self._send(200, json.dumps(
                    {'status': 'ready', 'draining': True,
                     'drained': True, 'inflight': 0}).encode())

            def do_POST(self):  # noqa: N802
                length = int(self.headers.get('Content-Length', 0))
                data = self.rfile.read(length) if length else b''
                if self.path == '/checkpoint':
                    outer.checkpoint_calls += 1
                    self._send(200, ckpt_blob,
                               'application/octet-stream')
                elif self.path == '/kv/warmup':
                    outer.warmup_calls.append(len(data))
                    if manager is not None:
                        outer.ready_urls_at_warmup = \
                            manager.ready_urls()
                    self._send(200, json.dumps(
                        {'entries': 1, 'warmed_rows': 32,
                         'landed': 1}).encode())
                elif self.path == '/drain':
                    self._send(200, json.dumps(
                        {'draining': True, 'drained': True,
                         'inflight': 0}).encode())
                else:
                    self._send(404, b'{}')

        import http.server as hs
        self.port = common_utils.find_free_port(19800)
        self.httpd = hs.ThreadingHTTPServer(('127.0.0.1', self.port), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return f'http://127.0.0.1:{self.port}'

    def stop(self):
        self.httpd.shutdown()


def test_preemption_warning_checkpoints_then_drains(tmp_path,
                                                    monkeypatch):
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import ReplicaInfo
    mgr = _make_manager(tmp_path, monkeypatch)
    fake = _FakeReplica(ckpt_blob=kv_transfer.encode_checkpoint([]))
    try:
        info = ReplicaInfo(1, 'spot-warn-c', 1, True, fake.port)
        info.url = fake.url
        info.status = serve_state.ReplicaStatus.READY
        with mgr._lock:
            mgr._replicas[1] = info
        preempt0 = mgr._m_spot_preempt.value
        assert mgr.handle_preemption_warning(1, deadline_s=5) is True
        assert fake.checkpoint_calls == 1
        assert mgr.checkpoint_for_warmup() is not None
        assert mgr._m_spot_preempt.value == preempt0 + 1
        deadline = time.time() + 20
        while time.time() < deadline and 1 in mgr._replicas:
            time.sleep(0.1)
        assert 1 not in mgr._replicas
    finally:
        fake.stop()


def test_preemption_warning_racefree_with_inflight_drain(tmp_path,
                                                         monkeypatch):
    """A warning landing while a drain is ALREADY running still
    checkpoints exactly once and never double-drains."""
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import ReplicaInfo
    mgr = _make_manager(tmp_path, monkeypatch)
    fake = _FakeReplica(ckpt_blob=kv_transfer.encode_checkpoint([]))
    try:
        info = ReplicaInfo(2, 'spot-race-c', 1, True, fake.port)
        info.url = fake.url
        info.status = serve_state.ReplicaStatus.READY
        with mgr._lock:
            mgr._replicas[2] = info
        assert mgr.drain(2, deadline_s=10) is True     # scale-down drain
        # The warning arrives mid-drain: drain() refuses a second
        # drain (idempotent), but the checkpoint still runs.
        assert mgr.handle_preemption_warning(2, deadline_s=10) is False
        assert fake.checkpoint_calls == 1
        assert mgr.checkpoint_for_warmup() is not None
        # And a re-delivered warning does not re-checkpoint.
        mgr.handle_preemption_warning(2, deadline_s=10)
        assert fake.checkpoint_calls == 1
    finally:
        fake.stop()


def test_spot_preemption_site_counts_only_spot(tmp_path, monkeypatch):
    """The seeded spot-kill schedule: `at: 2` on the spot_preemption
    site kills the SECOND SPOT sweep — on-demand replicas never
    advance the counter."""
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import ReplicaInfo
    mgr = _make_manager(tmp_path, monkeypatch)
    fake = _FakeReplica(ckpt_blob=kv_transfer.encode_checkpoint([]))
    try:
        spot = ReplicaInfo(1, 'spot-a', 1, True, fake.port)
        od = ReplicaInfo(2, 'od-b', 1, False, fake.port)
        for i, info in ((1, spot), (2, od)):
            info.url = fake.url
            info.status = serve_state.ReplicaStatus.READY
            with mgr._lock:
                mgr._replicas[i] = info
        mgr._faults = faults_lib.FaultInjector({'rules': [
            {'kind': 'preempt_signal', 'site': 'spot_preemption',
             'at': 2}]})
        monkeypatch.setattr(mgr, '_check_preempted', lambda info: False)
        monkeypatch.setattr(mgr, '_probe_one', lambda info: True)
        mgr.probe_all()                  # spot sweep #1: no fire
        assert spot.status == serve_state.ReplicaStatus.READY
        assert mgr._faults.site_count('spot_preemption') == 1  # spot only
        mgr.probe_all()                  # spot sweep #2: fires
        assert spot.status in (serve_state.ReplicaStatus.DRAINING,
                               serve_state.ReplicaStatus.SHUTTING_DOWN)
        assert od.status == serve_state.ReplicaStatus.READY
        assert fake.checkpoint_calls == 1
    finally:
        fake.stop()


def test_recovered_replica_warms_before_ready(tmp_path, monkeypatch):
    """The recovery-warmup ordering contract: the stored checkpoint
    lands via /kv/warmup BEFORE the replica is marked READY — it never
    enters ready_urls cold — and the provision latency is observed."""
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve.replica_managers import ReplicaInfo
    mgr = _make_manager(tmp_path, monkeypatch)
    fake = _FakeReplica(manager=mgr)
    try:
        with mgr._ckpt_lock:
            mgr._ckpt_bytes = kv_transfer.encode_checkpoint([])
            mgr._ckpt_time = time.time()
        info = ReplicaInfo(3, 'spot-recover-c', 1, True, fake.port)
        info.url = fake.url
        info.status = serve_state.ReplicaStatus.STARTING
        info.created_time = time.time() - 2.0
        with mgr._lock:
            mgr._replicas[3] = info
        monkeypatch.setattr(mgr, '_check_preempted', lambda i: False)
        monkeypatch.setattr(mgr, '_probe_one', lambda i: True)
        h_warm = telemetry.get_registry().get(
            'skytpu_prefix_warmup_seconds')
        h_prov = telemetry.get_registry().get(
            'skytpu_replica_provision_seconds')
        warm0, prov0 = h_warm.count, h_prov.count
        mgr.probe_all()
        assert info.status == serve_state.ReplicaStatus.READY
        assert fake.warmup_calls == [len(mgr._ckpt_bytes)]
        # At warmup time NO replica was in rotation yet.
        assert fake.ready_urls_at_warmup == []
        assert h_warm.count == warm0 + 1
        assert h_prov.count == prov0 + 1
        assert mgr.pop_provision_observations() == [pytest.approx(
            2.0, abs=1.5)]
        # Warmup runs once per replica, not on every sweep.
        mgr.probe_all()
        assert len(fake.warmup_calls) == 1
    finally:
        fake.stop()


# -------------------------------------------------------- server e2e
def _start_server(port, **kw):
    from skypilot_tpu.serve.server import ModelServer
    kw.setdefault('max_batch', 2)
    kw.setdefault('max_seq', 256)
    srv = ModelServer('tiny', port=port, **kw)
    srv.start(block=False)
    return srv


def _generate(base, payload, timeout=120, headers=None):
    h = {'Content-Type': 'application/json'}
    h.update(headers or {})
    req = urllib.request.Request(base + '/generate',
                                 json.dumps(payload).encode(), h)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_server_checkpoint_warmup_e2e():
    """POST /checkpoint on a warm replica -> POST /kv/warmup on a cold
    one -> the cold replica serves a shared-prefix prompt with the
    byte-identical continuation."""
    p1 = common_utils.find_free_port(19900)
    p2 = common_utils.find_free_port(19950)
    srv1 = _start_server(p1)
    srv2 = _start_server(p2)
    try:
        base1 = f'http://127.0.0.1:{p1}'
        base2 = f'http://127.0.0.1:{p2}'
        srv1._ready.wait(120)
        srv2._ready.wait(120)
        prompt = SHARED_PREFIX + [3, 4, 5]
        ref = _generate(base1, {'prompt': prompt,
                                'max_new_tokens': 8})['tokens']
        req = urllib.request.Request(
            base1 + '/checkpoint', json.dumps({}).encode(),
            {'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=60) as r:
            blob = r.read()
            n_entries = int(r.headers['X-Checkpoint-Entries'])
        assert n_entries >= 1
        kv_transfer.decode_checkpoint(blob)     # well-formed container
        req = urllib.request.Request(
            base2 + '/kv/warmup', blob,
            {'Content-Type': 'application/octet-stream'})
        with urllib.request.urlopen(req, timeout=60) as r:
            res = json.loads(r.read())
        assert res['warmed_rows'] > 0
        out = _generate(base2, {'prompt': prompt,
                                'max_new_tokens': 8})['tokens']
        assert out == ref
        # Malformed container: loud 400, nothing landed.
        req = urllib.request.Request(
            base2 + '/kv/warmup', b'garbage',
            {'Content-Type': 'application/octet-stream'})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 400
    finally:
        srv1.stop()
        srv2.stop()


def test_server_warm_boot_from_checkpoint_file(tmp_path):
    """The standalone restart path: a drain persists the checkpoint
    file; a fresh server with the same --checkpoint-path warms itself
    BEFORE readiness and serves the shared prefix byte-identically."""
    ckpt = str(tmp_path / 'kv.ckpt')
    p1 = common_utils.find_free_port(20000)
    srv1 = _start_server(p1, checkpoint_path=ckpt)
    try:
        base1 = f'http://127.0.0.1:{p1}'
        srv1._ready.wait(120)
        prompt = SHARED_PREFIX + [8, 8, 8]
        ref = _generate(base1, {'prompt': prompt,
                                'max_new_tokens': 8})['tokens']
        req = urllib.request.Request(
            base1 + '/drain', json.dumps({}).encode(),
            {'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=30) as r:
            json.loads(r.read())
        deadline = time.time() + 30
        while time.time() < deadline and not os.path.exists(ckpt):
            time.sleep(0.1)
        assert os.path.exists(ckpt)
    finally:
        srv1.stop()
    p2 = common_utils.find_free_port(20050)
    srv2 = _start_server(p2, checkpoint_path=ckpt)
    try:
        base2 = f'http://127.0.0.1:{p2}'
        srv2._ready.wait(120)
        hits0 = srv2.engine.alloc.prefix_hits
        out = _generate(base2, {'prompt': prompt,
                                'max_new_tokens': 8})['tokens']
        assert out == ref
        assert srv2.engine.alloc.prefix_hits > hits0   # served warm
    finally:
        srv2.stop()


# ------------------------------------------- zero lost through the LB
class _FakeController:
    """Answers the LB's sync POST with a settable replica list."""

    def __init__(self, replica_urls):
        import http.server
        self.replica_urls = list(replica_urls)
        outer = self

        class H(http.server.BaseHTTPRequestHandler):
            timeout = 30

            def log_message(self, *a):
                del a

            def do_POST(self):  # noqa: N802
                body = json.dumps({
                    'ready_replica_urls': outer.replica_urls,
                    'retry_after_s': 2,
                }).encode()
                self.send_response(200)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        import http.server as hs
        self.port = common_utils.find_free_port(20100)
        self.httpd = hs.ThreadingHTTPServer(('127.0.0.1', self.port), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return f'http://127.0.0.1:{self.port}'

    def stop(self):
        self.httpd.shutdown()


def test_seeded_spot_kills_zero_lost_through_lb(monkeypatch):
    """2 spot + 1 on-demand replica behind the LB; both spot replicas
    die mid-run (checkpoint -> drain -> gone, exactly the
    spot_preemption path). Every request completes with the
    byte-identical greedy answer — zero lost.

    Ordering is event-gated, not wall-clock-raced: each kill fires
    only after the LB has observably served at least one request of
    the current wave (a Condition on completion counts), and each
    victim drains with a completion-gated deadline so accepted
    requests are never failed over on a 30s wall clock under
    full-suite CPU load. Wall-clock timeouts remain only as generous
    hang insurance. Whether the remaining wave requests are still in
    flight at kill time is load-dependent — the zero-lost contract
    must hold either way."""
    from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
    monkeypatch.setenv('SKYTPU_LB_SYNC', '3600')
    ports = [common_utils.find_free_port(20200 + i * 37)
             for i in range(3)]
    servers = [_start_server(p) for p in ports]
    urls = [f'http://127.0.0.1:{p}' for p in ports]
    ctrl = _FakeController(urls)
    lb_port = common_utils.find_free_port(20400)
    lb = SkyServeLoadBalancer(controller_url=ctrl.url, port=lb_port,
                              max_attempts=4)
    lb.start()
    lb._sync_once()
    lb_base = f'http://127.0.0.1:{lb_port}'
    try:
        for s in servers:
            assert s._ready.wait(120)
        prompts = [[11 + i] + SHARED_PREFIX + [5 + i]
                   for i in range(8)]
        # Reference outputs (greedy, deterministic across replicas).
        refs = [_generate(urls[2], {'prompt': p,
                                    'max_new_tokens': 6})['tokens']
                for p in prompts]

        results = [None] * len(prompts)
        errors = []
        cv = threading.Condition()
        wave_done = [0, 0]            # completions per wave (A, B)

        def one(i):
            try:
                results[i] = _generate(
                    lb_base, {'prompt': prompts[i],
                              'max_new_tokens': 6},
                    timeout=300)['tokens']
            except Exception as e:  # pylint: disable=broad-except
                errors.append((i, repr(e)))
            finally:
                with cv:
                    wave_done[0 if i < 4 else 1] += 1
                    cv.notify_all()

        def await_wave(wave, n):
            """Event gate: block until ``n`` wave completions landed
            (deadline is hang insurance only, never the scheduler)."""
            with cv:
                assert cv.wait_for(lambda: wave_done[wave] >= n,
                                   timeout=300), (wave, n, wave_done)

        def spot_preempt(kill):
            """The spot_preemption flow a manager drives: checkpoint
            -> completion-gated drain -> out of the controller list.
            The drain deadline is generous so stragglers accepted by
            the victim run to completion instead of being failed over
            on a wall clock mid-assert."""
            victim = urls[kill]
            req = urllib.request.Request(
                victim + '/checkpoint', json.dumps({}).encode(),
                {'Content-Type': 'application/json'})
            with urllib.request.urlopen(req, timeout=120):
                pass
            req = urllib.request.Request(
                victim + '/drain',
                json.dumps({'deadline_s': 600}).encode(),
                {'Content-Type': 'application/json'})
            with urllib.request.urlopen(req, timeout=120):
                pass
            ctrl.replica_urls = urls[kill + 1:]
            lb._sync_once()

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads[:4]:
            t.start()
        # Kill #1 only once the LB has demonstrably served wave A
        # traffic — never racing replica warmup/compilation.
        await_wave(0, 1)
        spot_preempt(0)
        for t in threads[4:]:
            t.start()
        # Kill #2 gated on wave B progress the same way.
        await_wave(1, 1)
        spot_preempt(1)
        await_wave(0, 4)
        await_wave(1, 4)
        for t in threads:
            t.join(timeout=30)        # all done per the gates above
        servers[0].stop()
        servers[1].stop()
        assert not errors, errors
        assert results == refs        # zero lost, byte-identical
    finally:
        ctrl.stop()
        lb.stop()
        for s in servers:
            s.stop()
