"""Fleet telemetry aggregation: merge exactness, burn windows, traces.

Pins the contracts ``telemetry/fleet.py`` documents:

- counters sum EXACTLY across N replica registries, and keep summing
  monotonically through a replica restart (high-water-mark reset
  detection),
- histograms with identical bucket bounds merge exactly (the merged
  exposition is byte-identical to a pooled-sample histogram), and
  quantiles from merged buckets land within one bucket width of
  pooled-sample truth,
- SLO burn rates are multi-window: a late burst flips the 5-minute
  burn above 1 while the 1-hour window stays below,
- trace assembly applies per-source clock-skew offsets so a
  multi-process odyssey renders in causal order,
- everything is bounded: trace store evicts oldest, per-trace legs
  cap, per-source series cap drops (and counts) the excess.
"""
import json

import pytest

from skypilot_tpu.telemetry import fleet as fleet_lib
from skypilot_tpu.telemetry import registry as registry_lib
from skypilot_tpu.telemetry import tracing


def _clock(start=0.0):
    state = {'now': float(start)}

    def now():
        return state['now']

    now.state = state
    return now


def _agg(clock=None, **kwargs):
    return fleet_lib.FleetAggregator(clock=clock or _clock(), **kwargs)


def _wire_counter(name, value, **labels):
    return {name: {'kind': 'counter', 'help': 'h',
                   'series': [{'labels': labels, 'value': value}]}}


def _prom_family(text, name):
    return sorted(line for line in text.splitlines()
                  if line.startswith(name) and not line.startswith('#'))


# ----------------------------------------------------------- counters
def test_counter_exact_sum_across_sources():
    agg = _agg()
    values = [3.0, 11.0, 0.0, 25.0, 7.0]
    for i, v in enumerate(values):
        agg.ingest(f'replica-{i}', {
            'clock': {'wall': 0.0},
            'registry': _wire_counter(fleet_lib.ADMIT_METRIC, v,
                                      tier='latency')})
    merged = agg.render_json()[fleet_lib.ADMIT_METRIC]['series']
    assert len(merged) == 1
    assert merged[0]['labels'] == {'tier': 'latency'}
    assert merged[0]['value'] == sum(values)     # exact, not approximate
    assert agg.source_count() == len(values)


def test_counter_monotonic_across_restart():
    """A rebooted replica's counter restarting at 0 must ADD its
    pre-reboot total as a base — the fleet sum never decreases."""
    agg = _agg()

    def total():
        return agg.render_json()[fleet_lib.SHED_METRIC][
            'series'][0]['value']

    seen = []
    for value in (10.0, 100.0, 5.0, 6.0):    # 100 -> 5 is the restart
        agg.ingest('r0', {
            'clock': {'wall': 0.0},
            'registry': _wire_counter(fleet_lib.SHED_METRIC, value,
                                      tier='latency',
                                      reason='queue_wait')})
        seen.append(total())
    assert seen == [10.0, 100.0, 105.0, 106.0]
    assert seen == sorted(seen)              # monotone through restart


def test_histogram_restart_high_water_mark():
    reg = registry_lib.MetricsRegistry()
    h = reg.histogram(fleet_lib.TTFT_METRIC, 'ttft', tier='latency')
    for _ in range(10):
        h.observe(50.0)
    agg = _agg()
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': reg.export_wire()})
    # The replica restarts: a FRESH registry with fewer observations.
    reg2 = registry_lib.MetricsRegistry()
    h2 = reg2.histogram(fleet_lib.TTFT_METRIC, 'ttft', tier='latency')
    for _ in range(3):
        h2.observe(50.0)
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': reg2.export_wire()})
    series = agg.render_json()[fleet_lib.TTFT_METRIC]['series'][0]
    assert series['count'] == 13             # 10 pre-reboot + 3 after
    assert series['sum'] == pytest.approx(13 * 50.0)


# --------------------------------------------------------- histograms
def test_histogram_merge_exact_vs_pooled():
    """Merged-across-replicas exposition must be byte-identical to one
    histogram fed ALL the pooled samples — elementwise-exact merge."""
    samples = {
        'a': [0.5, 3.0, 40.0, 900.0, 12000.0],
        'b': [2.0, 2.0, 75.0, 75.0, 450.0, 70000.0],
        'c': [9.0, 9.0, 9.0, 9999.0],
    }
    agg = _agg()
    for source, vals in samples.items():
        reg = registry_lib.MetricsRegistry()
        h = reg.histogram(fleet_lib.TTFT_METRIC, 'ttft', tier='lat')
        for v in vals:
            h.observe(v)
        agg.ingest(source, {'clock': {'wall': 0.0},
                            'registry': reg.export_wire()})
    pooled_reg = registry_lib.MetricsRegistry()
    pooled = pooled_reg.histogram(fleet_lib.TTFT_METRIC, 'ttft',
                                  tier='lat')
    for vals in samples.values():
        for v in vals:
            pooled.observe(v)
    assert (_prom_family(agg.render_prometheus(), fleet_lib.TTFT_METRIC)
            == _prom_family(pooled_reg.render_prometheus(),
                            fleet_lib.TTFT_METRIC))


def test_bucket_quantile_within_one_bucket_width():
    samples = [1.5, 4.0, 8.0, 30.0, 30.0, 60.0, 120.0, 300.0, 800.0,
               2000.0, 2000.0, 7000.0]
    reg = registry_lib.MetricsRegistry()
    h = reg.histogram('m', 'h')
    for v in samples:
        h.observe(v)
    snap = h.snapshot()
    buckets = list(h.buckets)
    for q in (0.5, 0.9, 0.99):
        est = fleet_lib.bucket_quantile(buckets, snap['cumulative'], q)
        truth = sorted(samples)[min(len(samples) - 1,
                                    int(q * len(samples)))]
        # Width of the bucket the true quantile lands in — the best a
        # fixed-bucket store can promise.
        prev = 0.0
        for upper in buckets:
            if truth <= upper:
                break
            prev = upper
        assert abs(est - truth) <= (upper - prev)
    assert fleet_lib.bucket_quantile(buckets, [], 0.5) == 0.0
    assert fleet_lib.bucket_quantile([], [], 0.9) == 0.0


def test_histogram_bucket_layout_mismatch_skipped_not_crashed():
    agg = _agg()
    reg = registry_lib.MetricsRegistry()
    reg.histogram('m', 'h', buckets=(1, 2, 4)).observe(1.5)
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': reg.export_wire()})
    other = registry_lib.MetricsRegistry()
    other.histogram('m', 'h', buckets=(1, 2, 4, 8)).observe(1.5)
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': other.export_wire()})
    skipped = agg.render_json()['skytpu_fleet_merge_skipped_total'][
        'series'][0]['value']
    assert skipped >= 1


# ---------------------------------------------------------------- SLO
def _observe_tier(reg, ttft_ms, n):
    h = reg.histogram(fleet_lib.TTFT_METRIC, 'ttft', tier='latency')
    for _ in range(n):
        h.observe(ttft_ms)
    reg.counter(fleet_lib.ADMIT_METRIC, 'admitted',
                tier='latency').inc(n)


def test_burn_rate_multi_window_burst():
    """A burst confined to the final five minutes of an hour must page
    (5m burn >> 1) without tripping the ticket window (1h burn < 1)."""
    clock = _clock()
    slo = fleet_lib.TierSLO(tier='latency', ttft_ms=100.0, target=0.9)
    agg = _agg(clock=clock, slos=[slo])
    reg = registry_lib.MetricsRegistry()
    t = 0.0
    while t <= 3300.0:                      # 55 healthy minutes
        clock.state['now'] = t
        _observe_tier(reg, 10.0, 10)
        agg.ingest('r0', {'clock': {'wall': t},
                          'registry': reg.export_wire()})
        t += 60.0
    status = agg.slo_status()['latency']
    assert status['burn_5m'] == 0.0
    assert status['attainment'] == 1.0
    while t <= 3600.0:                      # 5-minute latency burst
        clock.state['now'] = t
        _observe_tier(reg, 10000.0, 10)
        agg.ingest('r0', {'clock': {'wall': t},
                          'registry': reg.export_wire()})
        t += 60.0
    status = agg.slo_status()['latency']
    assert status['burn_5m'] > 1.0          # page
    assert status['burn_1h'] < 1.0          # no ticket
    assert status['attainment'] < slo.target
    prom = agg.render_prometheus()
    assert 'skytpu_slo_burn_rate{tier="latency",window="5m"}' in prom
    assert 'skytpu_slo_burn_rate{tier="latency",window="1h"}' in prom
    assert 'skytpu_slo_attainment{tier="latency"}' in prom


def test_shed_rate_objective_burns():
    clock = _clock()
    slo = fleet_lib.TierSLO(tier='latency', shed_rate=0.05, target=0.99)
    agg = _agg(clock=clock, slos=[slo])
    reg = registry_lib.MetricsRegistry()
    reg.counter(fleet_lib.ADMIT_METRIC, 'a', tier='latency').inc(50)
    reg.counter(fleet_lib.SHED_METRIC, 's', tier='latency').inc(50)
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': reg.export_wire()})
    clock.state['now'] = 10.0
    reg.counter(fleet_lib.ADMIT_METRIC, 'a', tier='latency').inc(50)
    reg.counter(fleet_lib.SHED_METRIC, 's', tier='latency').inc(50)
    agg.ingest('r0', {'clock': {'wall': 10.0},
                      'registry': reg.export_wire()})
    # 50% shed against a 5% objective: burn = 0.5 / 0.05 = 10.
    assert agg.slo_status()['latency']['burn_5m'] == pytest.approx(10.0)


def test_set_slos_replaces_objectives():
    agg = _agg(clock=_clock(),
               slos=[fleet_lib.TierSLO(tier='latency', ttft_ms=100.0),
                     fleet_lib.TierSLO(tier='throughput',
                                       ttft_ms=5000.0)])
    reg = registry_lib.MetricsRegistry()
    _observe_tier(reg, 10.0, 5)
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': reg.export_wire()})
    assert set(agg.slo_status()) == {'latency', 'throughput'}
    agg.set_slos([fleet_lib.TierSLO(tier='latency', ttft_ms=100.0)])
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': reg.export_wire()})
    assert set(agg.slo_status()) == {'latency'}


def test_slos_from_config_sorted_and_typed():
    slos = fleet_lib.slos_from_config({
        'throughput': {'ttft_ms': 5000, 'target': 0.95},
        'latency': {'ttft_ms': 200, 'tpot_ms': 20,
                    'shed_rate': 0.01}})
    assert [s.tier for s in slos] == ['latency', 'throughput']
    assert slos[0].tpot_ms == 20
    assert slos[0].target == 0.99            # default
    assert slos[1].error_budget == pytest.approx(0.05)
    assert fleet_lib.slos_from_config(None) == []


# ------------------------------------------------------------- traces
def _leg(trace_id, request_id, submitted_at, spans):
    return {'trace_id': trace_id, 'request_id': request_id,
            'submitted_at': submitted_at, 'done': True, 'meta': {},
            'spans': [{'name': n, 'start_ms': s, 'dur_ms': d}
                      for n, s, d in spans]}


def test_trace_assembly_applies_skew_for_causal_order():
    """The replica's clock runs 500 s behind the LB's: raw wall stamps
    would render decode BEFORE the dispatch that caused it. The
    per-source skew recorded at scrape time must restore causal
    order."""
    clock = _clock(1000.0)
    agg = _agg(clock=clock)
    tid = 'ab' * 16
    # LB clock == controller clock (skew 0); its dispatch span starts
    # at wall 1000.
    agg.ingest('lb-0', {
        'clock': {'wall': 1000.0},
        'traces': [_leg(tid, 1, 1000.0,
                        [('lb.dispatch', 0.0, 40.0)])]})
    # Replica clock is 500 s behind: wall 500.01 at controller 1000.
    agg.ingest('replica-3', {
        'clock': {'wall': 500.0},
        'traces': [_leg(tid, 1, 500.01,
                        [('prefill', 0.0, 30.0),
                         ('decode', 30.0, 100.0)])]})
    assembled = agg.assemble_trace(tid)
    names = [s['name'] for s in assembled['spans']]
    assert names == ['lb.dispatch', 'prefill', 'decode']
    walls = [s['t_wall'] for s in assembled['spans']]
    assert walls == sorted(walls)
    assert walls[1] == pytest.approx(1000.01)    # skew-adjusted
    by_name = {s['name']: s for s in assembled['spans']}
    assert by_name['prefill']['source'] == 'replica-3'
    assert agg.assemble_trace('not-a-trace') is None


def test_migration_and_handoff_odyssey_is_one_causal_trace():
    """The acceptance odyssey: LB dispatch -> prefill worker -> KV
    handoff to a decode worker -> mid-stream migration to a second
    decode worker, four processes with three different clocks — ONE
    assembled trace, every leg present, spans in causal order after
    skew adjustment, the migration leg carrying its cause."""
    clock = _clock(10_000.0)
    agg = _agg(clock=clock)
    tid = tracing.mint_trace_id(__import__('random').Random(3))
    # LB: clock agrees with the controller.
    agg.ingest('lb-0', {
        'clock': {'wall': 10_000.0},
        'traces': [_leg(tid, 1, 10_000.0,
                        [('lb.dispatch', 0.0, 20.0)])]})
    # Prefill worker: clock 30 s ahead of the controller.
    agg.ingest('prefill-0', {
        'clock': {'wall': 10_030.0},
        'traces': [_leg(tid, 1, 10_030.01,
                        [('prefill', 0.0, 50.0),
                         ('kv.handoff', 50.0, 15.0)])]})
    # Decode worker: clock 200 s behind.
    clock.state['now'] = 10_000.2
    agg.ingest('decode-0', {
        'clock': {'wall': 9_800.2},
        'traces': [_leg(tid, 1, 9_800.3,
                        [('decode', 0.0, 80.0)])]})
    # Migration target after decode-0 died mid-stream: same skew
    # domain as the controller.
    clock.state['now'] = 10_000.5
    leg = _leg(tid, 1, 10_000.5, [('decode.resume', 0.0, 60.0)])
    leg['meta'] = {'cause': 'migration', 'migrated_from': 'decode-0'}
    agg.ingest('decode-1', {'clock': {'wall': 10_000.5},
                            'traces': [leg]})
    assert agg.trace_ids() == [tid]          # ONE trace, four legs
    assembled = agg.assemble_trace(tid)
    assert len(assembled['legs']) == 4
    assert {leg['source'] for leg in assembled['legs']} == {
        'lb-0', 'prefill-0', 'decode-0', 'decode-1'}
    names = [s['name'] for s in assembled['spans']]
    assert names == ['lb.dispatch', 'prefill', 'kv.handoff', 'decode',
                     'decode.resume']
    walls = [s['t_wall'] for s in assembled['spans']]
    assert walls == sorted(walls)
    causes = [leg['meta'].get('cause') for leg in assembled['legs']
              if leg.get('meta')]
    assert 'migration' in causes


def test_chrome_events_export(tmp_path):
    agg = _agg(clock=_clock())
    tid = 'cd' * 16
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'traces': [_leg(tid, 7, 1.0,
                                      [('prefill', 0.0, 5.0)])]})
    events = agg.chrome_events(tid)
    assert events and events[0]['ph'] == 'X'
    assert events[0]['args']['trace_id'] == tid
    from skypilot_tpu.utils import timeline
    path = timeline.write_trace(str(tmp_path / 'trace.json'), events)
    data = json.loads(open(path).read())
    assert data['traceEvents'][0]['name'] == 'prefill'
    assert agg.chrome_events('missing') is None


def test_trace_store_bounded_and_legs_capped():
    agg = _agg(clock=_clock(), trace_capacity=4)
    for i in range(10):
        agg.ingest_traces('r0', [_leg(f'{i:032x}', i, float(i),
                                      [('decode', 0.0, 1.0)])])
    ids = agg.trace_ids()
    assert len(ids) == 4
    assert ids == [f'{i:032x}' for i in range(6, 10)]   # oldest evicted
    evicted = agg.render_json()['skytpu_fleet_traces_evicted_total'][
        'series'][0]['value']
    assert evicted == 6
    tid = 'ee' * 16
    legs = [_leg(tid, 1, 0.0, [('decode', 0.0, 1.0)])
            for _ in range(fleet_lib.MAX_LEGS_PER_TRACE + 10)]
    agg.ingest_traces('r1', legs)
    assert (len(agg.assemble_trace(tid)['legs'])
            == fleet_lib.MAX_LEGS_PER_TRACE)


def test_per_source_series_cap_drops_and_counts(monkeypatch):
    monkeypatch.setattr(fleet_lib, 'MAX_SERIES_PER_SOURCE', 2)
    agg = _agg()
    reg = registry_lib.MetricsRegistry()
    for i in range(5):
        reg.counter('skytpu_thing_total', 'h', idx=str(i)).inc(1)
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': reg.export_wire()})
    out = agg.render_json()
    assert len(out['skytpu_thing_total']['series']) == 2
    dropped = out['skytpu_fleet_series_dropped_total'][
        'series'][0]['value']
    assert dropped == 3


def test_forget_source_drops_live_state_keeps_merged_history():
    agg = _agg()
    agg.ingest('r0', {'clock': {'wall': 0.0},
                      'registry': _wire_counter(
                          fleet_lib.ADMIT_METRIC, 5.0, tier='t')})
    tid = 'ff' * 16
    agg.ingest_traces('r0', [_leg(tid, 1, 0.0, [('d', 0.0, 1.0)])])
    assert agg.source_count() == 1
    agg.forget_source('r0')
    assert agg.source_count() == 0
    assert agg.trace_ids() == [tid]          # history survives


# ------------------------------------------- trace ids / wire headers
def test_mint_trace_id_seeded_deterministic():
    import random
    a = tracing.mint_trace_id(random.Random(7))
    b = tracing.mint_trace_id(random.Random(7))
    assert a == b and len(a) == 32
    assert int(a, 16) >= 0
    assert len(tracing.mint_trace_id()) == 32


def test_trace_header_roundtrip_and_garbage():
    tid = tracing.mint_trace_id()
    value = tracing.format_trace_header(tid, 'lb.dispatch')
    parsed = tracing.parse_trace_header(value)
    assert parsed == {'trace_id': tid, 'parent_span': 'lb.dispatch'}
    assert tracing.parse_trace_header(tid) == {
        'trace_id': tid, 'parent_span': None}
    for garbage in (None, '', 'zz;span', 'short', 42,
                    'deadbeef' * 9):         # 72 hex > 64 cap
        assert tracing.parse_trace_header(garbage) is None
    # A malformed parent must not poison a good trace id.
    assert tracing.parse_trace_header(tid + ';bad space')[
        'parent_span'] is None


def test_request_trace_adopts_wire_context():
    trace = tracing.RequestTrace(9)
    original = trace.trace_id
    assert len(original) == 32
    trace.adopt_wire_context(trace_id='ab' * 16,
                             parent_span='lb.dispatch')
    assert trace.trace_id == 'ab' * 16 != original
    trace.begin('decode')
    trace.finish()
    d = trace.to_dict()
    assert d['trace_id'] == 'ab' * 16
    assert 'legacy_id' not in d
    assert d['parent_span'] == 'lb.dispatch'


def test_trace_buffer_cursor_ships_each_trace_once():
    buf = tracing.TraceBuffer(maxlen=8)
    for i in range(3):
        t = tracing.RequestTrace(i)
        t.begin('decode')
        t.finish()
        buf.add(t)
    cursor, out = buf.summaries_since(0)
    assert len(out) == 3 and cursor == 3
    cursor2, out2 = buf.summaries_since(cursor)
    assert out2 == [] and cursor2 == 3
    t = tracing.RequestTrace(99)
    t.finish()
    buf.add(t)
    cursor3, out3 = buf.summaries_since(cursor2)
    assert [d['request_id'] for d in out3] == [99] and cursor3 == 4
    # limit trims and resumes from the last SHIPPED trace.
    cursor4, first = buf.summaries_since(0, limit=2)
    assert len(first) == 2
    _, rest = buf.summaries_since(cursor4, limit=10)
    assert [d['request_id'] for d in first + rest] == [0, 1, 2, 99]


# -------------------------------------------------- sim end-to-end SLO
def test_slo_burst_scenario_pages_short_window_only():
    """The acceptance drill: a seeded burst in the final five minutes
    flips burn{5m} above 1 while burn{1h} stays below — on the fleet
    aggregator the controller scrapes over the virtual clock."""
    from skypilot_tpu.serve.sim import scenarios as sim_scenarios
    rep = sim_scenarios.run_scenario('slo_burst', seed=1)
    assert rep['fleet']['sources'] == 3          # every replica scraped
    latency = rep['fleet']['slo']['latency']
    assert latency['burn_5m'] > 1.0
    assert latency['burn_1h'] < 1.0
    assert latency['attainment'] < 0.9
    assert set(rep['fleet']['slo']) == {'latency', 'throughput'}
    assert rep['requests']['lost'] == 0


def test_slo_burst_scenario_deterministic():
    from skypilot_tpu.serve.sim import scenarios as sim_scenarios
    a = sim_scenarios.run_scenario('slo_burst', seed=7)
    b = sim_scenarios.run_scenario('slo_burst', seed=7)
    assert a['event_log_sha256'] == b['event_log_sha256']
    assert a['fleet'] == b['fleet']
