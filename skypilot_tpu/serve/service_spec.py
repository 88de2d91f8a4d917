"""Service spec: the ``service:`` section of a task YAML.

Role of reference ``SkyServiceSpec`` (``sky/serve/service_spec.py:18``):
readiness probe + replica policy (fixed count or QPS autoscaling with
optional spot/on-demand mix). TPU-first notes: replicas are whole TPU
slices, so scaling granularity is a slice; the replica port is where the
in-tree model server (``skypilot_tpu.serve.server``) listens.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from skypilot_tpu import exceptions
from skypilot_tpu.utils import schemas


@dataclasses.dataclass
class SkyServiceSpec:
    """Validated service section."""
    readiness_path: str
    initial_delay_seconds: float = 60.0
    readiness_timeout_seconds: float = 15.0
    post_data: Optional[Any] = None
    min_replicas: int = 1
    max_replicas: Optional[int] = None      # None => fixed at min_replicas
    target_qps_per_replica: Optional[float] = None
    upscale_delay_seconds: float = 300.0
    downscale_delay_seconds: float = 1200.0
    base_ondemand_fallback_replicas: int = 0
    dynamic_ondemand_fallback: bool = False
    replica_port: int = 8081
    load_balancing_policy: str = 'round_robin'
    # TLS for the public LB endpoint (reference carries tls on
    # SkyServiceSpec, ``sky/serve/service_spec.py:18``). Paths are
    # resolved on the controller cluster.
    tls_certfile: Optional[str] = None
    tls_keyfile: Optional[str] = None
    # Multi-chip replica parallelism (``parallelism:`` block).
    # 'adaptive' picks (tp, dp) per model size and SLO tier
    # (serve/placement.py — Nitsum-style: latency tier maxes tp for
    # TPOT, throughput tier takes the smallest fitting tp and spends
    # the rest on dp); 'fixed' pins the explicit tp/dp below. The plan
    # reaches replicas as SKYTPU_TP/SKYTPU_DP launch env.
    parallelism_policy: str = 'adaptive'
    chips_per_replica: int = 1
    slo_tier: str = 'latency'
    parallelism_model: Optional[str] = None
    parallelism_quantize: Optional[str] = None
    hbm_per_chip_gb: float = 16.0
    tp: Optional[int] = None
    dp: Optional[int] = None
    # Multi-host gang serving (``parallelism: hosts:``): each replica
    # is a *gang* of this many processes that launch, drain,
    # checkpoint, and die together (serve/gang.py). Rank 0 owns the
    # replica's one routable endpoint; the manager keys every
    # lifecycle action by gang ID. Reaches replicas as the
    # SKYTPU_COORDINATOR/SKYTPU_RANK/SKYTPU_WORLD/SKYTPU_GANG_ID
    # launch env.
    gang_hosts: int = 1
    # Disaggregated prefill/decode serving (``disaggregation:`` block):
    # dedicate this many replicas to each phase; the rest stay
    # colocated. Roles reach replicas as the SKYTPU_ROLE launch env
    # (serve/placement.py::role_for_new_replica assigns them in launch
    # order: prefill pool first, then decode, then colocated).
    disagg_prefill_replicas: int = 0
    disagg_decode_replicas: int = 0
    # Forecast-aware autoscaling (``forecast:`` under ``replica_policy``,
    # serve/forecaster.py): pre-scale ahead of traffic ramps by the
    # learned provisioning lead time instead of reacting after the ramp
    # lands. The knobs are the forecaster's bucket width, season length
    # (diurnal period — or minutes for tests), and the default
    # look-ahead horizon.
    forecast_enabled: bool = False
    forecast_bucket_seconds: float = 10.0
    forecast_season_seconds: float = 600.0
    forecast_horizon_seconds: float = 120.0
    # Per-tier service-level objectives (``slos:`` block): tier name ->
    # {ttft_ms, tpot_ms, shed_rate, target}. The controller's
    # FleetAggregator evaluates 5m/1h burn rates against these
    # (telemetry/fleet.py) and surfaces them in controller status, the
    # LB sync response and ``GET /fleet/metrics``.
    slos: Optional[Dict[str, Dict[str, float]]] = None
    # Multi-tenant LoRA serving (``adapters:`` block): each replica
    # carries a device-resident adapter bank of ``adapter_slots`` rows
    # at rank ``adapter_rank``, lazily loaded by name from
    # ``adapter_dir`` (LRU evict under pressure). Reaches replicas as
    # --adapter-slots/--adapter-dir/--adapter-rank server flags.
    adapter_slots: int = 0
    adapter_dir: Optional[str] = None
    adapter_rank: int = 8

    @property
    def disagg_enabled(self) -> bool:
        return (self.disagg_prefill_replicas > 0
                or self.disagg_decode_replicas > 0)

    def __post_init__(self):
        if not self.readiness_path.startswith('/'):
            raise exceptions.InvalidServiceSpecError(
                f'readiness path must start with "/": {self.readiness_path}')
        if self.max_replicas is not None and \
                self.max_replicas < self.min_replicas:
            raise exceptions.InvalidServiceSpecError(
                f'max_replicas ({self.max_replicas}) < min_replicas '
                f'({self.min_replicas})')
        if self.max_replicas is not None and \
                self.max_replicas > self.min_replicas and \
                self.target_qps_per_replica is None:
            raise exceptions.InvalidServiceSpecError(
                'replica_policy with max_replicas > min_replicas requires '
                'target_qps_per_replica')
        if self.forecast_enabled and not self.autoscaling_enabled:
            raise exceptions.InvalidServiceSpecError(
                'forecast requires autoscaling (target_qps_per_replica '
                'with max_replicas > min_replicas, or no max_replicas '
                'at all = unbounded)')
        if self.forecast_enabled and \
                self.forecast_bucket_seconds <= 0:
            raise exceptions.InvalidServiceSpecError(
                'forecast bucket_seconds must be positive')
        if self.target_qps_per_replica is not None and \
                self.target_qps_per_replica <= 0:
            raise exceptions.InvalidServiceSpecError(
                'target_qps_per_replica must be positive')
        if self.disagg_prefill_replicas < 0 or \
                self.disagg_decode_replicas < 0:
            raise exceptions.InvalidServiceSpecError(
                'disaggregation replica counts must be >= 0')
        if self.disagg_enabled and (self.disagg_prefill_replicas == 0
                                    or self.disagg_decode_replicas == 0):
            raise exceptions.InvalidServiceSpecError(
                'disaggregation needs BOTH prefill_replicas and '
                'decode_replicas >= 1 (a lone pool has nobody to hand '
                'off to/from)')
        if self.adapter_slots < 0:
            raise exceptions.InvalidServiceSpecError(
                f'adapters.slots must be >= 0, got {self.adapter_slots}')
        if self.adapter_rank < 1:
            raise exceptions.InvalidServiceSpecError(
                f'adapters.rank must be >= 1, got {self.adapter_rank}')
        if self.gang_hosts < 1:
            raise exceptions.InvalidServiceSpecError(
                f'parallelism.hosts must be >= 1, got {self.gang_hosts}')
        if self.gang_hosts > 1 and self.disagg_enabled:
            raise exceptions.InvalidServiceSpecError(
                'multi-host gangs and disaggregated prefill/decode '
                'cannot combine (a KV handoff in/out of a gang would '
                'desync its follower ranks); drop one of '
                'parallelism.hosts / disaggregation')
        for tier, obj in (self.slos or {}).items():
            if not isinstance(obj, dict):
                raise exceptions.InvalidServiceSpecError(
                    f'slos.{tier} must be a mapping of objectives')
            target = obj.get('target', 0.99)
            if not 0.0 < float(target) < 1.0:
                raise exceptions.InvalidServiceSpecError(
                    f'slos.{tier}.target must be in (0, 1), got '
                    f'{target}')
            for key in ('ttft_ms', 'tpot_ms'):
                if obj.get(key) is not None and float(obj[key]) <= 0:
                    raise exceptions.InvalidServiceSpecError(
                        f'slos.{tier}.{key} must be positive')
            shed = obj.get('shed_rate')
            if shed is not None and not 0.0 < float(shed) <= 1.0:
                raise exceptions.InvalidServiceSpecError(
                    f'slos.{tier}.shed_rate must be in (0, 1], got '
                    f'{shed}')

    @property
    def autoscaling_enabled(self) -> bool:
        # max_replicas is None with a QPS target = UNBOUNDED
        # autoscaling (the autoscaler clamps only from below); a policy
        # without a QPS target stays fixed at min_replicas.
        return (self.target_qps_per_replica is not None
                and (self.max_replicas is None
                     or self.max_replicas > self.min_replicas))

    @classmethod
    def from_yaml_config(cls, config: Dict[str, Any]) -> 'SkyServiceSpec':
        schemas.validate(config, schemas.SERVICE_SCHEMA, 'service')
        probe = config['readiness_probe']
        if isinstance(probe, str):
            probe = {'path': probe}
        policy = config.get('replica_policy')
        fields: Dict[str, Any] = {
            'readiness_path': probe.get('path', '/'),
            'initial_delay_seconds': float(
                probe.get('initial_delay_seconds', 60.0)),
            'readiness_timeout_seconds': float(
                probe.get('timeout_seconds', 15.0)),
            'post_data': probe.get('post_data'),
            'replica_port': int(config.get('port', 8081)),
            'load_balancing_policy': config.get('load_balancing_policy',
                                                'round_robin'),
        }
        tls = config.get('tls')
        if tls:
            fields.update(tls_certfile=tls.get('certfile'),
                          tls_keyfile=tls.get('keyfile'))
        disagg = config.get('disaggregation')
        if disagg:
            fields.update(
                disagg_prefill_replicas=int(
                    disagg.get('prefill_replicas', 0)),
                disagg_decode_replicas=int(
                    disagg.get('decode_replicas', 0)))
        adapters = config.get('adapters')
        if adapters:
            fields.update(
                adapter_slots=int(adapters.get('slots', 0)),
                adapter_dir=adapters.get('dir'),
                adapter_rank=int(adapters.get('rank', 8)))
        slos = config.get('slos')
        if slos:
            fields['slos'] = {
                str(tier): dict(obj or {})
                for tier, obj in slos.items()}
        par = config.get('parallelism')
        if par:
            fields.update(
                parallelism_policy=par.get('policy', 'adaptive'),
                chips_per_replica=int(par.get('chips_per_replica', 1)),
                slo_tier=par.get('slo_tier', 'latency'),
                parallelism_model=par.get('model'),
                parallelism_quantize=par.get('quantize'),
                hbm_per_chip_gb=float(par.get('hbm_per_chip_gb', 16.0)),
                tp=par.get('tp'), dp=par.get('dp'),
                gang_hosts=int(par.get('hosts', 1)))
        if policy is not None and 'replicas' in config:
            raise exceptions.InvalidServiceSpecError(
                'Give either replicas (fixed) or replica_policy, not both.')
        if policy is not None:
            fields.update(
                min_replicas=int(policy.get('min_replicas', 1)),
                max_replicas=(int(policy['max_replicas'])
                              if 'max_replicas' in policy else None),
                target_qps_per_replica=policy.get('target_qps_per_replica'),
                upscale_delay_seconds=float(
                    policy.get('upscale_delay_seconds', 300.0)),
                downscale_delay_seconds=float(
                    policy.get('downscale_delay_seconds', 1200.0)),
                base_ondemand_fallback_replicas=int(
                    policy.get('base_ondemand_fallback_replicas', 0)),
                dynamic_ondemand_fallback=bool(
                    policy.get('dynamic_ondemand_fallback', False)),
            )
            forecast = policy.get('forecast')
            if forecast:
                if forecast is True:
                    forecast = {}
                fields.update(
                    forecast_enabled=True,
                    forecast_bucket_seconds=float(
                        forecast.get('bucket_seconds', 10.0)),
                    forecast_season_seconds=float(
                        forecast.get('season_seconds', 600.0)),
                    forecast_horizon_seconds=float(
                        forecast.get('horizon_seconds', 120.0)))
        else:
            fields['min_replicas'] = int(config.get('replicas', 1))
        return cls(**fields)

    def to_yaml_config(self) -> Dict[str, Any]:
        probe: Dict[str, Any] = {
            'path': self.readiness_path,
            'initial_delay_seconds': self.initial_delay_seconds,
            'timeout_seconds': self.readiness_timeout_seconds,
        }
        if self.post_data is not None:
            probe['post_data'] = self.post_data
        cfg: Dict[str, Any] = {
            'readiness_probe': probe,
            'port': self.replica_port,
            'load_balancing_policy': self.load_balancing_policy,
        }
        if self.tls_certfile and self.tls_keyfile:
            cfg['tls'] = {'certfile': self.tls_certfile,
                          'keyfile': self.tls_keyfile}
        if self.disagg_enabled:
            cfg['disaggregation'] = {
                'prefill_replicas': self.disagg_prefill_replicas,
                'decode_replicas': self.disagg_decode_replicas,
            }
        if self.gang_hosts > 1:
            cfg['parallelism'] = {'hosts': self.gang_hosts}
        if self.slos:
            cfg['slos'] = {tier: dict(obj)
                           for tier, obj in sorted(self.slos.items())}
        if self.adapter_slots > 0:
            adapters: Dict[str, Any] = {'slots': self.adapter_slots,
                                        'rank': self.adapter_rank}
            if self.adapter_dir:
                adapters['dir'] = self.adapter_dir
            cfg['adapters'] = adapters
        if self.autoscaling_enabled or self.target_qps_per_replica:
            policy: Dict[str, Any] = {
                'min_replicas': self.min_replicas,
                'target_qps_per_replica': self.target_qps_per_replica,
                'upscale_delay_seconds': self.upscale_delay_seconds,
                'downscale_delay_seconds': self.downscale_delay_seconds,
                'base_ondemand_fallback_replicas':
                    self.base_ondemand_fallback_replicas,
                'dynamic_ondemand_fallback': self.dynamic_ondemand_fallback,
            }
            # None = unbounded: the key is simply omitted (writing
            # min_replicas here used to silently freeze an unbounded
            # policy at its floor on round-trip).
            if self.max_replicas is not None:
                policy['max_replicas'] = self.max_replicas
            if self.forecast_enabled:
                policy['forecast'] = {
                    'bucket_seconds': self.forecast_bucket_seconds,
                    'season_seconds': self.forecast_season_seconds,
                    'horizon_seconds': self.forecast_horizon_seconds,
                }
            cfg['replica_policy'] = policy
        else:
            cfg['replicas'] = self.min_replicas
        return cfg
