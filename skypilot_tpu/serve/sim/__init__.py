"""Fleet-scale discrete-event simulator for the serve control plane.

Drives the REAL, unmodified policy objects — the autoscalers +
forecaster, ``placement.role_for_new_replica``, the LB policies, and
the replica manager's launch/probe/drain/checkpoint/warmup/backfill
state machines (behind ``serve/control_env.py``'s simulator-or-live
seam) — against simulated replicas with service curves calibrated from
the repo's BENCH engine numbers, on a virtual clock: 100–1000 replicas
and millions of requests in seconds of wall time, deterministic to the
byte for a fixed seed.

Entry points: :func:`skypilot_tpu.serve.sim.scenarios.run_scenario`
(what the ``skytpu sim`` CLI calls)
and :class:`skypilot_tpu.serve.sim.fleet.FleetSimulator` for custom
harnesses. graftcheck GC117 bans every wall-clock read under this
package — the virtual clock is the only time axis.
"""
from skypilot_tpu.serve.sim.core import EventLoop, SimShutdown
from skypilot_tpu.serve.sim.replica import ServiceCurve, SimReplica
from skypilot_tpu.serve.sim.scenarios import (SCENARIOS, get_scenario,
                                              run_scenario)

__all__ = ['EventLoop', 'SimShutdown', 'ServiceCurve', 'SimReplica',
           'SCENARIOS', 'get_scenario', 'run_scenario']
