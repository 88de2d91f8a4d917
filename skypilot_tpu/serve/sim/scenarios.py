"""Chaos scenario library: named, seeded failure storms over the
fleet simulator.

Each scenario bundles a service spec (which REAL autoscaler runs), a
traffic trace, an LB policy, a fault spec (``serve/faults.py`` rules —
including the sim-targeted sites: correlated spot storms, zone
outages, flaky probes, stragglers, gang churn) and the simulator
knobs. ``run_scenario(name, seed=...)`` is the single entry point the
``skytpu sim`` CLI and the tests share.

Scenario service curves are calibrated from the repo's BENCH records
(:func:`calibrated_curve`), scaled to a known per-replica capacity
(``slots`` sized so one replica serves ~2 req/s of anchor-shaped
requests — matching ``target_qps_per_replica: 2`` in the specs, so
autoscaler math and queueing behavior line up the way they do in the
live benches).

``forecast_vs_reactive`` reproduces the PR-10 shed replay as a fleet
scenario: the identical 4-season bursty trace (60 s of 8 QPS per 300 s
season over a 0.5 QPS floor, 30 s provision latency) run once under
the reactive ``RequestRateAutoscaler`` and once under the forecast
autoscaler — the forecast run must shed STRICTLY fewer requests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

from skypilot_tpu.serve.service_spec import SkyServiceSpec
from skypilot_tpu.serve.sim import fleet as sim_fleet
from skypilot_tpu.serve.sim import replica as sim_replica
from skypilot_tpu.serve.sim import traffic as sim_traffic

_CURVE_CACHE: Dict[int, sim_replica.ServiceCurve] = {}


def calibrated_curve(slots: int = 10) -> sim_replica.ServiceCurve:
    """The simulator's default service curve with ``slots`` concurrency
    (slots sized by the scenario so per-replica capacity matches its
    spec's ``target_qps_per_replica``)."""
    if slots not in _CURVE_CACHE:
        _CURVE_CACHE[slots] = dataclasses.replace(
            sim_replica.ServiceCurve.from_bench(), slots=slots,
            kv_pool_tokens=slots * 424)
    return _CURVE_CACHE[slots]


def _spec(**kw: Any) -> SkyServiceSpec:
    base = dict(readiness_path='/readiness',
                initial_delay_seconds=120.0,
                upscale_delay_seconds=10.0,
                downscale_delay_seconds=60.0)
    base.update(kw)
    return SkyServiceSpec(**base)


@dataclasses.dataclass
class Scenario:
    name: str
    description: str
    spec_fn: Callable[[], SkyServiceSpec]
    trace_fn: Callable[[], sim_traffic.Trace]
    policy: str = 'queue_depth'
    fault_rules: Optional[List[Dict[str, Any]]] = None
    fault_seed: int = 0
    # True when every injected failure is covered by the recovery
    # contract (LB migration + backfill) — the report's ``lost`` count
    # MUST be zero for these.
    recovery_covered: bool = True
    slots: int = 10
    sim_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Custom runner (comparison scenarios); default = single fleet run.
    runner: Optional[Callable[['Scenario', int, Optional[str]],
                              Dict[str, Any]]] = None

    def build(self, seed: int = 0, policy: Optional[str] = None,
              **overrides: Any) -> sim_fleet.FleetSimulator:
        kwargs: Dict[str, Any] = dict(self.sim_kwargs)
        kwargs.update(overrides)
        fault_spec = (
            {'seed': self.fault_seed, 'rules': list(self.fault_rules)}
            if self.fault_rules else None)
        return sim_fleet.FleetSimulator(
            spec=self.spec_fn(), trace=self.trace_fn(), seed=seed,
            policy_name=policy or self.policy,
            curve=calibrated_curve(self.slots),
            fault_spec=fault_spec, **kwargs)

    def run(self, seed: int = 0, policy: Optional[str] = None,
            **overrides: Any) -> Dict[str, Any]:
        if self.runner is not None:
            report = self.runner(self, seed, policy)
        else:
            report = self.build(seed, policy, **overrides).run()
        report['scenario'] = self.name
        report['recovery_covered'] = self.recovery_covered
        return report


# ------------------------------------------------------------- scenarios
def _forecast_vs_reactive_runner(scn: 'Scenario', seed: int,
                                 policy: Optional[str]
                                 ) -> Dict[str, Any]:
    """The PR-10 shed replay at fleet scale: identical trace, reactive
    vs forecast autoscaler, forecast must shed strictly fewer."""
    del scn

    def spec(forecast: bool) -> SkyServiceSpec:
        kw: Dict[str, Any] = dict(
            min_replicas=1, max_replicas=8, target_qps_per_replica=2.0,
            upscale_delay_seconds=10.0, downscale_delay_seconds=60.0)
        if forecast:
            kw.update(forecast_enabled=True,
                      forecast_bucket_seconds=10.0,
                      forecast_season_seconds=300.0,
                      forecast_horizon_seconds=60.0)
        return _spec(**kw)

    def one(forecast: bool) -> Dict[str, Any]:
        sim = sim_fleet.FleetSimulator(
            spec=spec(forecast),
            trace=sim_traffic.bursty(0.5, 8.0, 60.0, 300.0, 4),
            seed=seed, policy_name=policy or 'queue_depth',
            curve=calibrated_curve(10), provision_s=30.0,
            provision_jitter=0.0, sync_s=5.0, tick_s=10.0,
            keep_log=False)
        return sim.run()

    reactive = one(False)
    forecast = one(True)

    def sheds(rep: Dict[str, Any]) -> int:
        return sum(rep['requests']['shed'].values())

    return {
        'seed': seed,
        'trace': 'bursty(0.5->8 qps, 60s bursts, 4x300s seasons)',
        'reactive': {'shed': sheds(reactive),
                     'lost': reactive['requests']['lost'],
                     'chip_seconds': reactive['chip_seconds'],
                     'slo': reactive['slo']},
        'forecast': {'shed': sheds(forecast),
                     'lost': forecast['requests']['lost'],
                     'chip_seconds': forecast['chip_seconds'],
                     'slo': forecast['slo']},
        'requests': {'arrived': reactive['requests']['arrived'],
                     'completed': forecast['requests']['completed'],
                     'shed': {'reactive': sheds(reactive),
                              'forecast': sheds(forecast)},
                     'lost': max(reactive['requests']['lost'],
                                 forecast['requests']['lost']),
                     'migrated': 0},
        'forecast_sheds_strictly_fewer': sheds(forecast)
                                         < sheds(reactive),
        'events': reactive['events'] + forecast['events'],
        'event_log_sha256': reactive['event_log_sha256'],
        'virtual_s': reactive['virtual_s'] + forecast['virtual_s'],
        'chip_seconds': reactive['chip_seconds']
                        + forecast['chip_seconds'],
    }


def _multi_turn_affinity_runner(scn: 'Scenario', seed: int,
                                policy: Optional[str]
                                ) -> Dict[str, Any]:
    """The round-18 routing comparison: the IDENTICAL multi-turn trace
    over the same 1000-replica fleet under ``queue_depth`` (load-only)
    vs ``prefix_affinity`` (digest routing + stickiness + proactive
    migration). Affinity must win BOTH ways: strictly higher warm-TTFT
    hit rate AND strictly fewer prefix-recompute tokens."""
    del policy     # the policy axis IS the comparison

    def one(policy_name: str) -> Dict[str, Any]:
        kwargs: Dict[str, Any] = dict(scn.sim_kwargs)
        kwargs.setdefault('keep_log', False)
        sim = sim_fleet.FleetSimulator(
            spec=scn.spec_fn(), trace=scn.trace_fn(), seed=seed,
            policy_name=policy_name,
            curve=calibrated_curve(scn.slots), **kwargs)
        return sim.run()

    qd = one('queue_depth')
    aff = one('prefix_affinity')

    def view(rep: Dict[str, Any]) -> Dict[str, Any]:
        return {'ttft_hit_rate': rep['affinity']['ttft_hit_rate'],
                'recompute_tokens': rep['affinity']['recompute_tokens'],
                'warm_hits': rep['affinity']['warm_hits'],
                'outcomes': rep['affinity']['outcomes'],
                'prefix_migrations': rep['affinity']
                                        ['prefix_migrations'],
                'shed': sum(rep['requests']['shed'].values()),
                'lost': rep['requests']['lost'],
                'slo': rep['slo']}

    return {
        'seed': seed,
        'trace': aff['trace'],
        'replicas': aff['replicas'],
        'queue_depth': view(qd),
        'prefix_affinity': view(aff),
        'affinity_beats_queue_depth': {
            'ttft_hit_rate': (aff['affinity']['ttft_hit_rate']
                              > qd['affinity']['ttft_hit_rate']),
            'recompute_tokens': (aff['affinity']['recompute_tokens']
                                 < qd['affinity']['recompute_tokens']),
        },
        'requests': {'arrived': aff['requests']['arrived'],
                     'completed': aff['requests']['completed'],
                     'shed': aff['requests']['shed'],
                     'lost': max(aff['requests']['lost'],
                                 qd['requests']['lost']),
                     'migrated': aff['requests']['migrated']},
        'slo': aff['slo'],
        'events': qd['events'] + aff['events'],
        'event_log_sha256': aff['event_log_sha256'],
        'virtual_s': qd['virtual_s'] + aff['virtual_s'],
        'chip_seconds': qd['chip_seconds'] + aff['chip_seconds'],
    }


SCENARIOS: Dict[str, Scenario] = {}


def _register(s: Scenario) -> None:
    SCENARIOS[s.name] = s


_register(Scenario(
    name='smoke',
    description='Tier-1 smoke: 3 replicas, steady traffic, one mid-run '
                'replica kill; must finish in seconds with zero lost.',
    spec_fn=lambda: _spec(min_replicas=3),
    trace_fn=lambda: sim_traffic.constant(4.0, 120.0),
    fault_rules=[{'kind': 'zone_outage', 'site': 'sim_zone_outage',
                  'at': 4, 'zone': 'z2'}],
    sim_kwargs=dict(provision_s=20.0, provision_jitter=0.0,
                    n_zones=3, drain_grace_s=200.0),
))

_register(Scenario(
    name='spot_storm',
    description='Correlated spot-preemption storm: a forecast+fallback '
                'autoscaled spot fleet loses 3 spot replicas at once, '
                'twice; on-demand backfill + migration keep lost at 0.',
    spec_fn=lambda: _spec(
        min_replicas=4, max_replicas=12, target_qps_per_replica=2.0,
        base_ondemand_fallback_replicas=1,
        dynamic_ondemand_fallback=True, forecast_enabled=True,
        forecast_bucket_seconds=10.0, forecast_season_seconds=300.0,
        forecast_horizon_seconds=60.0),
    trace_fn=lambda: sim_traffic.diurnal(2.0, 14.0, 300.0, 3),
    fault_rules=[{'kind': 'preempt_signal', 'site': 'sim_storm',
                  'at': 12, 'n': 3},
                 {'kind': 'preempt_signal', 'site': 'sim_storm',
                  'at': 40, 'n': 3}],
    sim_kwargs=dict(provision_s=25.0, storm_dt=10.0),
))

_register(Scenario(
    name='zone_outage',
    description='Zone z0 drops: every replica in one of three zones '
                'dies in the same instant; survivors absorb, the '
                'autoscaler replaces, zero lost.',
    spec_fn=lambda: _spec(min_replicas=9, max_replicas=15,
                          target_qps_per_replica=2.0),
    trace_fn=lambda: sim_traffic.constant(12.0, 600.0),
    fault_rules=[{'kind': 'zone_outage', 'site': 'sim_zone_outage',
                  'at': 20, 'zone': 'z0'}],
    sim_kwargs=dict(provision_s=30.0, n_zones=3, storm_dt=10.0),
))

_register(Scenario(
    name='flaky_probes',
    description='Flaky/slow readiness probes (20% injected timeouts): '
                'the grace/terminate escalation must not churn the '
                'fleet or lose requests.',
    spec_fn=lambda: _spec(min_replicas=5, max_replicas=8,
                          target_qps_per_replica=2.0),
    trace_fn=lambda: sim_traffic.constant(8.0, 600.0),
    fault_rules=[{'kind': 'probe_timeout', 'site': 'probe',
                  'prob': 0.2, 'delay_s': 0.05}],
    fault_seed=11,
    sim_kwargs=dict(provision_s=25.0),
))

_register(Scenario(
    name='stragglers',
    description='Two replicas silently degrade 6x (slow HBM / noisy '
                'neighbor): queue-depth routing must absorb them with '
                'bounded SLO damage and zero lost.',
    spec_fn=lambda: _spec(min_replicas=6),
    trace_fn=lambda: sim_traffic.constant(8.0, 600.0),
    fault_rules=[{'kind': 'straggler', 'site': 'sim_straggler',
                  'at': 6, 'factor': 6.0},
                 {'kind': 'straggler', 'site': 'sim_straggler',
                  'at': 12, 'factor': 6.0}],
    sim_kwargs=dict(provision_s=20.0, storm_dt=10.0),
))

_register(Scenario(
    name='gang_churn',
    description='Gang-member churn: follower ranks of 2-host gangs '
                'die mid-run; one dead rank fails the whole gang, the '
                'gang is replaced as a unit, leader in-flight work '
                'migrates, zero lost.',
    spec_fn=lambda: _spec(min_replicas=3, max_replicas=5,
                          target_qps_per_replica=2.0, gang_hosts=2),
    trace_fn=lambda: sim_traffic.constant(4.0, 600.0),
    fault_rules=[{'kind': 'replica_crash', 'site': 'sim_gang_churn',
                  'at': 10, 'rank': 1},
                 {'kind': 'replica_crash', 'site': 'sim_gang_churn',
                  'at': 30, 'rank': 1}],
    sim_kwargs=dict(provision_s=25.0, storm_dt=10.0),
))

_register(Scenario(
    name='gray_failure_storm',
    description='Gray-failure storm: one replica wedges (accepts work '
                'that never finishes, readiness degrades), a NaN '
                'burst evicts in-flight requests retryably, a '
                'byzantine replica answers canaries wrong (quarantined '
                'before a second wrong response), and a preemption '
                'checkpoint is bit-flipped in transit (the replacement '
                'must boot cold, never byte-wrong). Zero lost.',
    spec_fn=lambda: _spec(min_replicas=6, max_replicas=10,
                          target_qps_per_replica=2.0),
    trace_fn=lambda: sim_traffic.constant(8.0, 600.0),
    fault_rules=[
        {'kind': 'wedged_step', 'site': 'sim_gray', 'at': 3},
        {'kind': 'nan_logits', 'site': 'sim_gray', 'at': 8, 'n': 4},
        {'kind': 'byzantine_response', 'site': 'sim_gray', 'at': 12},
        # Advance preemption warning -> the manager fetches the
        # replica's checkpoint -> the kv_wire rule flips one byte of
        # it -> the replacement's warmup refuses the container (400)
        # and boots cold.
        {'kind': 'preempt_signal', 'site': 'preempt_warning',
         'at': 120},
        {'kind': 'kv_corruption', 'site': 'kv_wire', 'at': 1},
    ],
    sim_kwargs=dict(provision_s=25.0, storm_dt=10.0, canary_s=30.0,
                    drain_grace_s=400.0),
))

_register(Scenario(
    name='controller_crash_storm',
    description='Controller crash-safety: a spot storm kills 2 '
                'replicas, the CONTROLLER then dies mid-recovery '
                '(env halted: its drains/launches unwind, its writes '
                'stop), the LB serves stale-while-revalidate for 60 '
                'virtual seconds, and a fresh controller boots with '
                'recover=True — journal replay must ADOPT the '
                'surviving fleet (never relaunch it, never tear a '
                'replica down twice), then a second storm proves the '
                'recovered control plane still heals. Zero lost; '
                'same-seed byte-identical.',
    spec_fn=lambda: _spec(
        min_replicas=6, max_replicas=10, target_qps_per_replica=2.0,
        base_ondemand_fallback_replicas=2,
        dynamic_ondemand_fallback=True),
    trace_fn=lambda: sim_traffic.constant(8.0, 600.0),
    fault_rules=[
        {'kind': 'preempt_signal', 'site': 'sim_storm', 'at': 8,
         'n': 2},
        {'kind': 'controller_crash', 'site': 'sim_controller',
         'at': 10},
        {'kind': 'controller_restart', 'site': 'sim_controller',
         'at': 16},
        {'kind': 'preempt_signal', 'site': 'sim_storm', 'at': 30,
         'n': 2},
    ],
    sim_kwargs=dict(provision_s=25.0, storm_dt=10.0,
                    drain_grace_s=400.0),
))

_register(Scenario(
    name='flash_crowd',
    description='Flash crowd: traffic steps 6x with no seasonal '
                'precedent — only the trend term can chase it; '
                'measures shed depth vs provisioning lead.',
    spec_fn=lambda: _spec(
        min_replicas=2, max_replicas=16, target_qps_per_replica=2.0,
        forecast_enabled=True, forecast_bucket_seconds=10.0,
        forecast_season_seconds=600.0, forecast_horizon_seconds=60.0),
    trace_fn=lambda: sim_traffic.flash_crowd(3.0, 18.0, 240.0, 720.0),
    recovery_covered=False,      # sheds expected; nothing is killed
    sim_kwargs=dict(provision_s=25.0),
))

_register(Scenario(
    name='forecast_vs_reactive',
    description='The PR-10 shed replay as a fleet scenario: identical '
                'bursty trace under reactive vs forecast autoscaling; '
                'forecast must shed strictly fewer.',
    spec_fn=lambda: _spec(min_replicas=1),     # per-variant (runner)
    trace_fn=lambda: sim_traffic.bursty(0.5, 8.0, 60.0, 300.0, 4),
    recovery_covered=False,      # sheds are the measurement
    runner=_forecast_vs_reactive_runner,
))

_register(Scenario(
    name='fleet_1k',
    description='Scale proof: 1000 fixed replicas, ~2000 QPS for 10 '
                'virtual minutes (>1M requests), light storm; the '
                'simulator itself must stay fast and deterministic.',
    spec_fn=lambda: _spec(min_replicas=1000),
    # ~0.9x the fleet's rated capacity (1000 replicas x ~2 req/s):
    # loaded enough that queueing is visible, headroom enough that
    # the zone outage is absorbable.
    trace_fn=lambda: sim_traffic.constant(
        1800.0, 600.0,
        sim_traffic.RequestShape(latency_frac=0.3)),
    policy='round_robin',
    fault_rules=[{'kind': 'zone_outage', 'site': 'sim_zone_outage',
                  'at': 30, 'zone': 'z1'}],
    sim_kwargs=dict(provision_s=30.0, n_zones=10, arrival_dt=0.5,
                    max_chunk=16, keep_log=False, storm_dt=10.0,
                    drain_grace_s=300.0),
))


_register(Scenario(
    name='multi_turn_affinity',
    description='Prefix-affinity routing comparison: one multi-turn '
                'trace (800 sessions, prefix-extending prompts) over '
                'a 1000-replica fleet under queue_depth vs '
                'prefix_affinity; affinity must score a strictly '
                'higher warm-TTFT hit rate AND strictly fewer '
                'prefix-recompute tokens.',
    spec_fn=lambda: _spec(min_replicas=1000),
    trace_fn=lambda: sim_traffic.multi_turn(20.0, 240.0, 800, 192),
    policy='prefix_affinity',
    recovery_covered=False,      # nothing is killed; a measurement
    sim_kwargs=dict(provision_s=20.0, provision_jitter=0.0,
                    n_zones=10, keep_log=False, drain_grace_s=200.0),
    runner=_multi_turn_affinity_runner,
))

_register(Scenario(
    name='slo_burst',
    description='SLO burn-rate drill: a fixed 3-replica fleet with a '
                'declared latency-tier TTFT objective serves 55 '
                'virtual minutes of healthy traffic, then every '
                'replica silently degrades 12x for the final stretch. '
                'The fleet aggregator (fed over /telemetry/summary on '
                'the probe path) must flip the 5-minute burn rate '
                'above 1 while the 1-hour window stays below — the '
                'multi-window page/ticket distinction.',
    spec_fn=lambda: _spec(
        min_replicas=3,
        slos={'latency': {'ttft_ms': 2000.0, 'target': 0.9},
              'throughput': {'ttft_ms': 10000.0, 'target': 0.9}}),
    trace_fn=lambda: sim_traffic.constant(4.0, 3600.0),
    fault_rules=[{'kind': 'straggler', 'site': 'sim_straggler',
                  'at': 330, 'factor': 12.0},
                 {'kind': 'straggler', 'site': 'sim_straggler',
                  'at': 331, 'factor': 12.0},
                 {'kind': 'straggler', 'site': 'sim_straggler',
                  'at': 332, 'factor': 12.0}],
    recovery_covered=False,      # nothing dies; latency IS the drill
    sim_kwargs=dict(provision_s=20.0, provision_jitter=0.0,
                    storm_dt=10.0, keep_log=False,
                    drain_grace_s=300.0),
))

_register(Scenario(
    name='lb_crash',
    description='Horizontal LB tier under fire: 2 LB processes share '
                'the sync feed, multi-turn sessions split between '
                'them by client hash; one LB dies mid-run — its '
                'sticky sessions and probe caches are gone, the '
                'survivor re-forms affinity from the replicas\' '
                'advertised digests, and ZERO requests are lost.',
    spec_fn=lambda: _spec(min_replicas=3, max_replicas=6,
                          target_qps_per_replica=2.0),
    trace_fn=lambda: sim_traffic.multi_turn(4.0, 300.0, 40, 192),
    policy='prefix_affinity',
    fault_rules=[{'kind': 'lb_crash', 'site': 'sim_lb_crash',
                  'at': 12}],
    sim_kwargs=dict(provision_s=20.0, provision_jitter=0.0,
                    n_lbs=2, storm_dt=10.0, drain_grace_s=200.0),
))


def get_scenario(name: str) -> Scenario:
    if name not in SCENARIOS:
        raise ValueError(f'unknown scenario {name!r}; choose from '
                         f'{sorted(SCENARIOS)}')
    return SCENARIOS[name]


def run_scenario(name: str, seed: int = 0,
                 policy: Optional[str] = None,
                 **overrides: Any) -> Dict[str, Any]:
    """Run one named scenario; returns its report dict (the CLI prints
    it as JSON)."""
    return get_scenario(name).run(seed=seed, policy=policy, **overrides)
