"""Exception hierarchy for skypilot_tpu.

Mirrors the role of the reference's ``sky/exceptions.py`` (error classification that
the failover loop keys on), re-designed around TPU provisioning semantics:
queued-resource timeouts and slice preemption are first-class failover signals
(see reference failure classification at
``sky/backends/cloud_vm_ray_backend.py:1031-1086``).
"""
from __future__ import annotations

from typing import List, Optional


class SkyTpuError(Exception):
    """Base class for all framework errors."""


# --- Spec / validation -----------------------------------------------------
class InvalidTaskError(SkyTpuError):
    """Task YAML / Task object failed validation."""


class InvalidResourcesError(SkyTpuError):
    """Resources spec is malformed (unknown accelerator, bad topology...)."""


class InvalidDagError(SkyTpuError):
    """DAG is malformed (cycles, unsupported shape)."""


# --- Optimizer -------------------------------------------------------------
class ResourcesUnavailableError(SkyTpuError):
    """No feasible (cloud, region, zone, type) satisfies the request.

    Carries ``no_failover`` when retrying with different resources is
    pointless (e.g. user pinned a zone that is out of capacity and asked for
    no failover).
    """

    def __init__(self, message: str, no_failover: bool = False):
        super().__init__(message)
        self.no_failover = no_failover


class ResourcesMismatchError(SkyTpuError):
    """Requested resources do not match an existing cluster's resources."""


class NoCloudAccessError(SkyTpuError):
    """No cloud is enabled/credentialed."""


# --- Provisioning ----------------------------------------------------------
class ProvisionError(SkyTpuError):
    """Provisioning failed; carries a blocklist hint for the failover loop."""

    #: Scope the failover should blocklist: 'zone' | 'region' | 'cloud'.
    blocklist_scope: str = 'zone'


class QuotaExceededError(ProvisionError):
    """Cloud quota prevents provisioning in a region; blocklist the region.

    A ProvisionError subclass so the failover loop catches and
    blocklists it rather than crashing the launch."""
    blocklist_scope = 'region'


class InsufficientCapacityError(ProvisionError):
    """Stockout: the zone has no capacity for the slice type."""
    blocklist_scope = 'zone'


class QueuedResourceTimeoutError(ProvisionError):
    """Queued-resource request sat in WAITING/PROVISIONING beyond deadline.

    TPU-specific: the queued-resources API is async accept->provision; a
    too-long queue is treated like a stockout so the optimizer can move on.
    """
    blocklist_scope = 'zone'


class PreemptedDuringProvisionError(ProvisionError):
    """Spot/preemptible slice was reclaimed before setup finished."""
    blocklist_scope = 'zone'


class ClusterOwnerIdentityMismatchError(SkyTpuError):
    """Cluster was created by a different cloud identity."""


class CommandError(SkyTpuError):
    """A remote command failed."""

    def __init__(self, returncode: int, command: str, error_msg: str = '',
                 detailed_reason: Optional[str] = None):
        self.returncode = returncode
        self.command = command
        self.error_msg = error_msg
        self.detailed_reason = detailed_reason
        msg = (f'Command failed with return code {returncode}: {command}'
               + (f'\n{error_msg}' if error_msg else ''))
        super().__init__(msg)


# --- Cluster state ---------------------------------------------------------
class ClusterNotUpError(SkyTpuError):
    """Operation requires an UP cluster."""


class ClusterDoesNotExist(SkyTpuError):
    """Named cluster not found in global state."""


class NotSupportedError(SkyTpuError):
    """Operation not supported for this cloud/cluster (e.g. stop TPU pod)."""


# --- Jobs ------------------------------------------------------------------
class JobNotFoundError(SkyTpuError):
    """Job id not present in the job table."""


class ManagedJobReachedMaxRetriesError(SkyTpuError):
    """Managed job exhausted recovery attempts."""


class ManagedJobStatusError(SkyTpuError):
    """Managed job is in a state that does not allow the operation."""


# --- Serve -----------------------------------------------------------------
class ServeUserTerminatedError(SkyTpuError):
    """Service was torn down by the user while an operation was in flight."""


class ServiceNotFoundError(SkyTpuError):
    """Named service not found."""


class InvalidServiceSpecError(SkyTpuError):
    """Malformed ``service:`` section in a task YAML."""


# --- Storage ---------------------------------------------------------------
class StorageError(SkyTpuError):
    """Base class for storage errors."""


class StorageBucketCreateError(StorageError):
    pass


class StorageBucketGetError(StorageError):
    pass


class StorageBucketDeleteError(StorageError):
    pass


class StorageUploadError(StorageError):
    pass


class StorageModeError(StorageError):
    pass


class StorageSpecError(StorageError):
    pass


# --- Misc ------------------------------------------------------------------
class ApiError(SkyTpuError):
    """Cloud REST API returned an error; wraps status code + body."""

    def __init__(self, message: str, status: Optional[int] = None,
                 reason: Optional[str] = None):
        super().__init__(message)
        self.status = status
        self.reason = reason


class UserRequestRejectedByPolicy(SkyTpuError):
    """Admin policy rejected the request."""


def format_blocklist(resources_list: List) -> str:
    return '\n'.join(f'  - {r}' for r in resources_list)
