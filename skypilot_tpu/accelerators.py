"""Accelerator registry with first-class TPU slice topology.

The reference treats a TPU pod slice as one "node" with many IPs
(``num_ips_per_node`` hack at ``sky/backends/cloud_vm_ray_backend.py:2550``)
and keeps TPU-type knowledge scattered across ``sky/clouds/utils/gcp_utils.py``
and catalog CSVs. Here slice topology (generation, chip count, hosts,
chips/host, ICI layout) is a first-class, parsed object that every layer —
optimizer, provisioner, backend, trainer — shares.

Naming convention (same strings SkyPilot's catalog uses):
  ``tpu-v4-8``     -> v4,  8 TensorCores  = 4 chips, 1 host
  ``tpu-v5litepod-8`` / ``tpu-v5e-8`` -> v5e, 8 chips, 1 host
  ``tpu-v5p-16``   -> v5p, 16 cores = 8 chips, 2 hosts
  ``tpu-v6e-16``   -> v6e, 16 chips, 2 hosts
Generations v2/v3/v4/v5p name slices by TensorCore count; v5e/v6e by chips.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

from skypilot_tpu import exceptions


@dataclasses.dataclass(frozen=True)
class TpuGeneration:
    """Static facts about one TPU generation."""
    name: str                      # 'v5e'
    names_by_cores: bool           # True: v2/v3/v4/v5p; False: v5e/v6e
    cores_per_chip: int
    chips_per_host: int
    peak_bf16_tflops: float        # per chip
    hbm_gb_per_chip: float
    hbm_bw_gbps: float             # per chip
    default_runtime_version: str
    # max chips in a single slice (pod size)
    max_chips: int
    # GCE machine-type prefix used by the TPU-VM API for this gen
    accelerator_api_type: str      # value for acceleratorType, e.g. 'v5litepod'


TPU_GENERATIONS: Dict[str, TpuGeneration] = {
    'v2': TpuGeneration('v2', True, 2, 4, 23.0, 8.0, 300.0,
                        'tpu-vm-base', 512, 'v2'),
    'v3': TpuGeneration('v3', True, 2, 4, 61.0, 16.0, 450.0,
                        'tpu-vm-base', 2048, 'v3'),
    'v4': TpuGeneration('v4', True, 2, 4, 137.5, 32.0, 615.0,
                        'tpu-vm-v4-base', 8192, 'v4'),
    'v5e': TpuGeneration('v5e', False, 1, 8, 197.0, 16.0, 819.0,
                         'v2-alpha-tpuv5-lite', 256, 'v5litepod'),
    'v5p': TpuGeneration('v5p', True, 2, 4, 459.0, 95.0, 2765.0,
                         'v2-alpha-tpuv5', 17920, 'v5p'),
    'v6e': TpuGeneration('v6e', False, 1, 8, 918.0, 32.0, 1640.0,
                         'v2-alpha-tpuv6e', 256, 'v6e'),
}

# Aliases accepted in user YAML.
_GEN_ALIASES = {
    'v5litepod': 'v5e',
    'v5lite': 'v5e',
    'v5-lite': 'v5e',
    'v6litepod': 'v6e',
}

_TPU_RE = re.compile(r'^tpu[-_]?(v[0-9]+[a-z]*?(?:litepod|lite|p|e)?)[-_]([0-9]+)$',
                     re.IGNORECASE)

# What a JAX device reports as its ``device_kind``, by generation.
_DEVICE_KINDS = {
    'tpu v2': 'v2',
    'tpu v3': 'v3',
    'tpu v4': 'v4',
    'tpu v5 lite': 'v5e',
    'tpu v5e': 'v5e',
    'tpu v5': 'v5p',
    'tpu v5p': 'v5p',
    'tpu v6 lite': 'v6e',
    'tpu v6e': 'v6e',
}


def generation_of_device(device_kind: str) -> Optional[TpuGeneration]:
    """The generation of a JAX device's ``device_kind`` ('TPU v5 lite'),
    or None for a device that is no TPU this module knows (the CPU)."""
    name = _DEVICE_KINDS.get(device_kind.strip().lower())
    return TPU_GENERATIONS[name] if name else None


@dataclasses.dataclass(frozen=True)
class TpuTopology:
    """A fully-resolved TPU slice shape.

    ``num_hosts`` is first-class: the backend runs the user program on every
    host; the trainer builds its mesh from ``num_chips``.
    """
    generation: str          # 'v5e'
    num_chips: int
    num_hosts: int
    chips_per_host: int
    num_cores: int
    name: str                # canonical 'tpu-v5e-8'

    @property
    def gen(self) -> TpuGeneration:
        return TPU_GENERATIONS[self.generation]

    @property
    def is_pod(self) -> bool:
        return self.num_hosts > 1

    @property
    def peak_bf16_tflops(self) -> float:
        return self.gen.peak_bf16_tflops * self.num_chips

    @property
    def hbm_gb(self) -> float:
        return self.gen.hbm_gb_per_chip * self.num_chips

    @property
    def accelerator_type(self) -> str:
        """String for the TPU API ``acceleratorType`` field, e.g. 'v5litepod-8'."""
        gen = self.gen
        count = self.num_cores if gen.names_by_cores else self.num_chips
        return f'{gen.accelerator_api_type}-{count}'

    def mesh_shape_2d(self) -> Tuple[int, int]:
        """A (rows, cols) factorization of num_chips close to square.

        Used for default ICI mesh layout hints; XLA handles the physical
        mapping, we only need a logical factorization.
        """
        n = self.num_chips
        best = (1, n)
        r = 1
        while r * r <= n:
            if n % r == 0:
                best = (r, n // r)
            r += 1
        return best

    def __str__(self) -> str:
        return self.name


def is_tpu(accelerator_name: Optional[str]) -> bool:
    """Mirrors reference ``sky/clouds/utils/gcp_utils.py:29`` predicates."""
    if accelerator_name is None:
        return False
    return accelerator_name.lower().startswith('tpu')


def parse_tpu(accelerator_name: str) -> TpuTopology:
    """Parse 'tpu-v5e-8' / 'tpu-v5litepod-16' / 'tpu-v4-32' into a topology."""
    m = _TPU_RE.match(accelerator_name.strip())
    if not m:
        raise exceptions.InvalidResourcesError(
            f'Cannot parse TPU accelerator name {accelerator_name!r}. '
            f"Expected e.g. 'tpu-v5e-8', 'tpu-v4-8', 'tpu-v5p-16'.")
    gen_raw = m.group(1).lower()
    count = int(m.group(2))
    gen_name = _GEN_ALIASES.get(gen_raw, gen_raw)
    if gen_name not in TPU_GENERATIONS:
        raise exceptions.InvalidResourcesError(
            f'Unknown TPU generation {gen_raw!r} in {accelerator_name!r}. '
            f'Known: {sorted(TPU_GENERATIONS)}')
    gen = TPU_GENERATIONS[gen_name]
    if count <= 0:
        raise exceptions.InvalidResourcesError(
            f'TPU count must be positive: {accelerator_name!r}')
    if gen.names_by_cores:
        if count % gen.cores_per_chip != 0:
            raise exceptions.InvalidResourcesError(
                f'{accelerator_name!r}: {gen_name} slice sizes count '
                f'TensorCores and must be a multiple of {gen.cores_per_chip}.')
        num_cores = count
        num_chips = count // gen.cores_per_chip
    else:
        num_chips = count
        num_cores = count * gen.cores_per_chip
    if num_chips > gen.max_chips:
        raise exceptions.InvalidResourcesError(
            f'{accelerator_name!r} exceeds the max pod size for {gen_name} '
            f'({gen.max_chips} chips).')
    # Valid slice shapes: sub-host slices must evenly divide a host; pod
    # slices must be whole hosts (otherwise num_hosts would be inconsistent
    # and the backend would under-provision the gang).
    if num_chips < gen.chips_per_host:
        if gen.chips_per_host % num_chips != 0:
            raise exceptions.InvalidResourcesError(
                f'{accelerator_name!r}: sub-host slice size must divide '
                f'{gen.chips_per_host} chips/host.')
    elif num_chips % gen.chips_per_host != 0:
        raise exceptions.InvalidResourcesError(
            f'{accelerator_name!r}: slice must be a whole number of hosts '
            f'({gen.chips_per_host} chips/host for {gen_name}).')
    # Hosts: full hosts for slices >= one host; sub-host slices (e.g.
    # v5e-1, v5e-4) run on one shared host.
    num_hosts = max(1, num_chips // gen.chips_per_host)
    chips_per_host = min(num_chips, gen.chips_per_host)
    canonical = f'tpu-{gen_name}-{count}'
    return TpuTopology(generation=gen_name, num_chips=num_chips,
                       num_hosts=num_hosts, chips_per_host=chips_per_host,
                       num_cores=num_cores, name=canonical)


# --- GPU registry (for optimizer comparisons; reference: accelerator_registry)
_CANONICAL_GPUS = {
    'a100': 'A100', 'a100-80gb': 'A100-80GB', 'h100': 'H100',
    'v100': 'V100', 't4': 'T4', 'l4': 'L4', 'p4': 'P4', 'k80': 'K80',
    'a10g': 'A10G', 'l40s': 'L40S',
}


def canonicalize_accelerator_name(name: str) -> str:
    """Canonical accelerator name: TPUs get canonical slice names, GPUs a
    fixed capitalization. Unknown names pass through unchanged (catalog will
    reject them at feasibility time), mirroring the reference's permissive
    registry (``sky/utils/accelerator_registry.py``)."""
    if is_tpu(name):
        return parse_tpu(name).name
    return _CANONICAL_GPUS.get(name.lower(), name)
