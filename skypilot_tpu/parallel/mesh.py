"""Device mesh + logical-axis sharding rules.

This is the TPU-native replacement for the reference's delegated parallelism
(SURVEY.md §2.3/§2.4): instead of exporting torchrun/NCCL env vars for an
external framework, the in-tree engines shard over a `jax.sharding.Mesh` and
let XLA insert ICI/DCN collectives.

Axes (any may be size 1):
  slice : outer data-parallel axis across pod slices (DCN; multislice)
  pp    : pipeline parallel (layer stack split into stages; GPipe
          microbatching in parallel/pipeline.py)
  dp    : data parallel (pure replication of params)
  fsdp  : fully-sharded data parallel (params sharded, gathered per layer)
  sp    : sequence/context parallel (ring attention partitions the sequence)
  tp    : tensor parallel (heads/mlp sharded; collectives per layer)
  ep    : expert parallel (MoE experts sharded)

``ep`` is folded over ``fsdp×sp`` at use-site (MoE layers reshape), keeping
the physical mesh 6-D and collectives on ICI neighbors.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MESH_AXES = ('slice', 'pp', 'dp', 'fsdp', 'sp', 'tp')


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. Product must equal the device count."""
    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1
    num_slices: int = 1
    pp: int = 1

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.num_slices, self.pp, self.dp, self.fsdp, self.sp,
                self.tp)

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    @classmethod
    def auto(cls, num_devices: int, *, num_slices: int = 1,
             tp: Optional[int] = None, sp: int = 1) -> 'MeshSpec':
        """Default: everything not TP/SP goes to FSDP (ZeRO-3-style), the
        dominant TPU training layout. TP defaults to 1 within reason — FSDP
        over fast ICI usually wins until per-chip batch gets tiny."""
        per_slice = num_devices // num_slices
        if num_devices % num_slices:
            raise ValueError(f'{num_devices} devices not divisible into '
                             f'{num_slices} slices')
        tp = tp or 1
        if per_slice % (tp * sp):
            raise ValueError(f'tp*sp={tp * sp} must divide per-slice device '
                             f'count {per_slice}')
        return cls(dp=1, fsdp=per_slice // (tp * sp), sp=sp, tp=tp,
                   num_slices=num_slices)

    @classmethod
    def for_serving(cls, tp: int = 1, dp: int = 1) -> 'MeshSpec':
        """The serving layout: params/KV heads sharded over ``tp``
        (innermost — collectives on nearest-neighbor ICI), the decode
        batch replicated-or-sharded over ``dp``. No fsdp/sp/pp —
        inference keeps whole layers resident and decode reads are
        latency-bound, so the only profitable axes are tensor split
        (TPOT) and batch split (tok/s)."""
        if tp < 1 or dp < 1:
            raise ValueError(f'tp/dp must be >= 1, got tp={tp} dp={dp}')
        return cls(dp=dp, tp=tp)


def spec_from_env(*, tp: Optional[int] = None, sp: int = 1,
                  num_devices: Optional[int] = None) -> MeshSpec:
    """MeshSpec honoring the launch env contract: SKYTPU_NUM_SLICES (set
    by the job driver from the provisioned topology) becomes the DCN
    mesh axis. Falls back to a single slice outside a launched job."""
    import os
    num_slices = int(os.environ.get('SKYTPU_NUM_SLICES', '1') or 1)
    if num_devices is None:
        num_devices = jax.device_count()
    return MeshSpec.auto(num_devices, num_slices=num_slices, tp=tp, sp=sp)


def serving_spec_from_env(*, tp: Optional[int] = None,
                          dp: Optional[int] = None) -> MeshSpec:
    """Serving MeshSpec from the launch env contract: the controller's
    adaptive-TP placement exports ``SKYTPU_TP``/``SKYTPU_DP`` on the
    replica, and explicit args (``--tp/--dp``) override. Absent both,
    tp=dp=1 — the single-chip path stays the default."""
    import os
    if tp is None:
        tp = int(os.environ.get('SKYTPU_TP', '1') or 1)
    if dp is None:
        dp = int(os.environ.get('SKYTPU_DP', '1') or 1)
    return MeshSpec.for_serving(tp=tp, dp=dp)


def serving_mesh(tp: int = 1, dp: int = 1,
                 devices: Optional[Sequence[jax.Device]] = None
                 ) -> Optional[Mesh]:
    """Build the (tp, dp) serving mesh over the first ``tp*dp`` visible
    devices. Returns None for tp=dp=1: the engines' meshless path skips
    sharding entirely (and keeps the Pallas decode kernel eligible), so
    single-chip serving must not pay for an over-general 1-device mesh."""
    spec = MeshSpec.for_serving(tp=tp, dp=dp)
    if spec.num_devices == 1:
        return None
    if devices is None:
        devices = jax.devices()
    if len(devices) < spec.num_devices:
        raise ValueError(
            f'serving mesh tp={tp} x dp={dp} needs {spec.num_devices} '
            f'devices, but only {len(devices)} are visible')
    return make_mesh(spec, devices[:spec.num_devices])


def mesh_axis_sizes(mesh: Optional[Mesh]) -> Dict[str, int]:
    """{axis: size} for every logical mesh axis — the stable-schema
    payload behind the ``skytpu_mesh_shape{axis=...}`` gauges and the
    LB's replica view. All 1s for a meshless (single-chip) engine, so
    the series exist with sane values from the first scrape."""
    if mesh is None:
        return {a: 1 for a in MESH_AXES}
    return {a: int(mesh.shape[a]) for a in MESH_AXES}


def axis_shard_degree(mesh: Optional[Mesh], axes, dim: int) -> int:
    """Effective shard count of a tensor dimension of size ``dim``
    mapped to mesh ``axes`` (a name or tuple), mirroring ``spec_for``'s
    divisibility fallback: trailing axes that do not divide ``dim``
    drop to replication. THE divisor per-shard byte accounting must use
    — sizing with the raw axis product would overstate sharding exactly
    where spec_for silently replicated (e.g. MQA's n_kv_heads < tp)."""
    if mesh is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    keep = tuple(axes)
    while keep and dim % math.prod(mesh.shape[a] for a in keep):
        keep = keep[:-1]
    return math.prod(mesh.shape[a] for a in keep) if keep else 1


_distributed_initialized = False

# Port offset from the gang bus (rank 0's HTTP front end) to the JAX
# distributed-runtime coordinator: the two services share a host but
# not a protocol (gang bus = HTTP long-poll sync; JAX = gRPC).
_GANG_JAX_PORT_OFFSET = 1000


def jax_coordinator_from_url(url: str) -> str:
    """``host:port`` for ``jax.distributed.initialize`` derived from
    the gang's SKYTPU_COORDINATOR HTTP URL (rank 0's model server):
    same host, HTTP port + a fixed offset."""
    import urllib.parse
    parsed = urllib.parse.urlparse(url if '//' in url else f'//{url}')
    host = parsed.hostname or 'localhost'
    port = (parsed.port or 8081) + _GANG_JAX_PORT_OFFSET
    return f'{host}:{port}'


def initialize_gang_distributed(coordinator_url: str, rank: int,
                                world: int, *,
                                timeout_s: float = 120.0) -> bool:
    """Multi-process serving-mesh bootstrap from the gang launch-env
    contract (SKYTPU_COORDINATOR/SKYTPU_RANK/SKYTPU_WORLD — the
    serving twin of the SKYTPU_COORDINATOR_ADDRESS training contract
    above): ``jax.distributed.initialize`` with rank 0's derived gRPC
    address, so ``jax.devices()`` spans every gang process and the
    (tp, dp) serving mesh shards one model across hosts.

    The join is BOUNDED by ``timeout_s`` (graftcheck GC116: no
    unbounded distributed joins — a member that never comes up must
    fail the gang, not hang it). No-op (False) for world <= 1; only
    attempted on multi-host-capable backends — single-process CPU
    serving (the tests) keeps the ``replicated`` data plane, where
    each rank holds a full model copy and lockstep is digest-verified
    by the gang bus instead. Idempotent."""
    global _distributed_initialized
    if world <= 1:
        return False
    if _distributed_initialized:
        return True
    addr = jax_coordinator_from_url(coordinator_url)
    try:
        jax.distributed.initialize(
            coordinator_address=addr, num_processes=world,
            process_id=rank,
            initialization_timeout=int(max(1, timeout_s)))
    except RuntimeError as e:
        # Benign re-init only; a coordinator-connect failure fails
        # LOUDLY — swallowing it would leave a half-alive gang whose
        # ranks each serve a disconnected model shard.
        if 'already initialized' not in str(e).lower():
            raise
    _distributed_initialized = True
    return True


def initialize_distributed_from_env() -> bool:
    """Multi-host bootstrap from the SKYTPU_* env contract: calls
    jax.distributed.initialize(coordinator, num_processes, process_id)
    when launched on a multi-host cluster; no-op (returns False) when
    the contract is absent or single-host. Idempotent — safe to call
    from every Trainer/engine constructor."""
    global _distributed_initialized
    import os
    coord = os.environ.get('SKYTPU_COORDINATOR_ADDRESS')
    n = int(os.environ.get('SKYTPU_NUM_NODES', '1') or 1)
    if not coord or n <= 1:
        return False
    if _distributed_initialized:
        return True
    rank = int(os.environ.get('SKYTPU_NODE_RANK', '0') or 0)
    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=n, process_id=rank)
    except RuntimeError as e:
        # Only the benign re-init case may pass; a coordinator-connect
        # failure must fail LOUDLY — swallowing it would leave every
        # host training a disconnected replica.
        if 'already initialized' not in str(e).lower():
            raise
    _distributed_initialized = True
    return True


def make_mesh(spec: MeshSpec,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the 5-D mesh. Axis order puts `tp` innermost so tensor-parallel
    collectives ride nearest-neighbor ICI links; `slice` outermost so only
    the pure-DP gradient all-reduce crosses DCN (multislice)."""
    if devices is None:
        devices = jax.devices()
    if len(devices) != spec.num_devices:
        raise ValueError(
            f'MeshSpec {spec.shape} needs {spec.num_devices} devices, '
            f'got {len(devices)}')
    arr = np.asarray(devices).reshape(spec.shape)
    return Mesh(arr, MESH_AXES)


# --- Logical axis rules ----------------------------------------------------
# logical axis -> mesh axis (str), tuple of mesh axes, or None (replicated).
LogicalRules = Dict[str, Any]

# Default rules (MaxText-style): params shard embed-dim over fsdp and
# heads/mlp over tp; activations shard batch over all data axes and sequence
# over sp.
DEFAULT_RULES: LogicalRules = {
    'batch': ('slice', 'dp', 'fsdp'),
    'seq': 'sp',
    'embed': 'fsdp',
    'heads': 'tp',
    'kv_heads': 'tp',
    'head_dim': None,
    'mlp': 'tp',
    'vocab': 'tp',
    # Input embedding table: vocab dim unsharded (a tp-sharded table turns
    # the token gather into an SPMD full-rematerialization; the table's
    # memory is carried by the fsdp-sharded embed dim instead).
    'vocab_in': None,
    'expert': ('fsdp', 'sp'),   # ep folded over fsdp×sp
    'norm': None,
    # Layer stack sharded over pipeline stages (no-op at pp=1).
    'layers': 'pp',
}


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[LogicalRules] = None,
             *,
             shape: Optional[Sequence[int]] = None,
             mesh: Optional[Mesh] = None) -> PartitionSpec:
    """Map a tuple of logical axis names to a PartitionSpec.

    Custom ``rules`` are OVERRIDES merged onto DEFAULT_RULES, so a user
    dict doesn't break when the model layer introduces a new logical
    axis (e.g. 'vocab_in'); unknown axes still raise (typo guard).

    When ``shape`` and ``mesh`` are given, the mapping is
    divisibility-aware: mesh axes that do not evenly divide the tensor
    dimension are dropped (trailing-first), falling back to replication.
    This is what lets MQA/GQA models with ``n_kv_heads < tp`` run under
    tensor parallelism — KV heads are replicated over the tp axis instead
    of pjit rejecting the layout (MaxText does the same)."""
    rules = {**DEFAULT_RULES, **rules} if rules else DEFAULT_RULES
    parts = []
    used = set()
    for i, ax in enumerate(logical_axes):
        if ax is None:
            parts.append(None)
            continue
        if ax not in rules:
            raise ValueError(f'No sharding rule for logical axis {ax!r}')
        mesh_ax = rules[ax]
        # Drop mesh axes already used by an earlier dimension (a mesh axis
        # may shard at most one tensor dimension).
        if mesh_ax is None:
            keep = ()
        elif isinstance(mesh_ax, (tuple, list)):
            keep = tuple(a for a in mesh_ax if a not in used)
        else:
            keep = (mesh_ax,) if mesh_ax not in used else ()
        if keep and shape is not None and mesh is not None:
            dim = shape[i]
            while keep and dim % math.prod(
                    mesh.shape[a] for a in keep):
                keep = keep[:-1]
        used.update(keep)
        if not keep:
            parts.append(None)
        elif len(keep) == 1 and not isinstance(rules[ax], (tuple, list)):
            parts.append(keep[0])
        else:
            parts.append(keep)
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def tree_shardings(logical_tree: Any, mesh: Mesh,
                   rules: Optional[LogicalRules] = None,
                   shapes: Optional[Any] = None) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.

    ``shapes`` (optional) is a matching pytree of arrays or
    ShapeDtypeStructs; when given, shardings are divisibility-aware (see
    ``spec_for``)."""
    is_leaf = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    if shapes is None:
        return jax.tree.map(
            lambda axes: NamedSharding(mesh, spec_for(axes, rules)),
            logical_tree, is_leaf=is_leaf)
    return jax.tree.map(
        lambda axes, s: NamedSharding(
            mesh, spec_for(axes, rules, shape=s.shape, mesh=mesh)),
        logical_tree, shapes, is_leaf=is_leaf)


def batch_sharding(mesh: Mesh,
                   rules: Optional[LogicalRules] = None) -> NamedSharding:
    """Sharding for [batch, seq] token arrays."""
    return NamedSharding(mesh, spec_for(('batch', 'seq'), rules))


def data_axis_size(mesh: Mesh) -> int:
    """Global data-parallel degree (batch must be divisible by this)."""
    return (mesh.shape['slice'] * mesh.shape['dp'] * mesh.shape['fsdp'])
