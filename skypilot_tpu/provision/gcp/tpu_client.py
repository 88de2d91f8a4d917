"""Minimal REST clients for the GCP TPU and Compute APIs.

Role of reference ``sky/provision/gcp/instance_utils.py`` (GCPTpuVmInstance
``:1191-1607``) and its ``googleapiclient`` discovery stack: here a thin
urllib layer with an injectable ``transport`` callable so the provisioner
is unit-testable without network or credentials (the reference mocks at
the googleapiclient layer in its tests; SURVEY §4 calls for doing better
in-tree).

Transport contract: ``transport(method, url, body_dict_or_None) ->
(status_code, response_dict)``. The default transport attaches a gcloud
access token. HTTP errors are mapped onto the exception classification here so
every caller sees blocklist-scoped ProvisionErrors, not raw HTTP.
"""
from __future__ import annotations

import json
import os
import subprocess
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, Optional, Tuple

from skypilot_tpu import exceptions

TPU_API = 'https://tpu.googleapis.com/v2'
COMPUTE_API = 'https://compute.googleapis.com/compute/v1'

Transport = Callable[[str, str, Optional[Dict[str, Any]]],
                     Tuple[int, Dict[str, Any]]]

# Test hook: factory returning a Transport (see tests/test_gcp_provisioner).
_transport_factory: Optional[Callable[[], Transport]] = None


def set_transport_factory(fn: Optional[Callable[[], Transport]]) -> None:
    global _transport_factory
    _transport_factory = fn


# Access tokens are valid ~1h; cache one for 50 minutes so polling loops
# don't spawn a gcloud subprocess per request.
_token_cache: Dict[str, Any] = {'token': None, 'expires': 0.0}


def _gcloud_access_token() -> str:
    if _token_cache['token'] and time.time() < _token_cache['expires']:
        return _token_cache['token']
    try:
        out = subprocess.run(['gcloud', 'auth', 'print-access-token'],
                             capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise exceptions.NoCloudAccessError(
            f'gcloud not available for GCP auth: {e}') from e
    if out.returncode != 0:
        raise exceptions.NoCloudAccessError(
            f'gcloud auth failed: {out.stderr.strip()}')
    _token_cache['token'] = out.stdout.strip()
    _token_cache['expires'] = time.time() + 50 * 60
    return _token_cache['token']


def _default_transport(method: str, url: str,
                       body: Optional[Dict[str, Any]]
                       ) -> Tuple[int, Dict[str, Any]]:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={'Authorization': f'Bearer {_gcloud_access_token()}',
                 'Content-Type': 'application/json'})
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            payload = resp.read()
            return resp.status, json.loads(payload) if payload else {}
    except urllib.error.HTTPError as e:
        try:
            payload = json.loads(e.read())
        except Exception:  # pylint: disable=broad-except
            payload = {'error': {'message': str(e)}}
        return e.code, payload
    except (urllib.error.URLError, TimeoutError, OSError) as e:
        # Network-level failures must enter the classification too, or they
        # bypass gang cleanup and the failover loop entirely.
        err = exceptions.ProvisionError(
            f'GCP API unreachable ({method} {url.split("?")[0]}): {e}')
        err.blocklist_scope = 'zone'
        raise err from e


def get_transport() -> Transport:
    if _transport_factory is not None:
        return _transport_factory()
    return _default_transport


def _error_message(payload: Dict[str, Any]) -> str:
    err = payload.get('error') or {}
    if isinstance(err, dict):
        return str(err.get('message') or payload)
    return str(err)


def raise_for_status(status: int, payload: Dict[str, Any], *,
                     zone: Optional[str] = None) -> None:
    """Map a GCP error onto the blocklist-scoped exception classification
    (reference error discrimination:
    ``sky/backends/cloud_vm_ray_backend.py:1031-1086``)."""
    if status < 400:
        return
    msg = _error_message(payload)
    lower = msg.lower()
    where = f' in {zone}' if zone else ''
    if status in (401, 403):
        raise exceptions.NoCloudAccessError(
            f'GCP auth/permission error{where}: {msg}')
    if status == 429 or 'quota' in lower:
        err: exceptions.SkyTpuError = exceptions.QuotaExceededError(
            f'GCP quota exceeded{where}: {msg}')
        err.blocklist_scope = 'region'
        raise err
    if ('resource_exhausted' in lower or 'out of capacity' in lower
            or 'stockout' in lower or 'no more capacity' in lower
            or 'not enough resources' in lower):
        err = exceptions.InsufficientCapacityError(
            f'GCP capacity unavailable{where}: {msg}')
        err.blocklist_scope = 'zone'
        raise err
    err = exceptions.ProvisionError(f'GCP API error {status}{where}: {msg}')
    err.blocklist_scope = 'zone'
    raise err


class TpuClient:
    """tpu.googleapis.com v2: nodes + queuedResources + operations."""

    def __init__(self, project: str,
                 transport: Optional[Transport] = None):
        self.project = project
        self.transport = transport or get_transport()

    # ------------------------------------------------------------- urls
    def _zone_url(self, zone: str) -> str:
        return f'{TPU_API}/projects/{self.project}/locations/{zone}'

    # ------------------------------------------------------------ nodes
    def create_node(self, zone: str, node_id: str,
                    body: Dict[str, Any]) -> Dict[str, Any]:
        status, payload = self.transport(
            'POST', f'{self._zone_url(zone)}/nodes?nodeId={node_id}', body)
        raise_for_status(status, payload, zone=zone)
        return payload                      # long-running operation

    def get_node(self, zone: str, node_id: str) -> Optional[Dict[str, Any]]:
        status, payload = self.transport(
            'GET', f'{self._zone_url(zone)}/nodes/{node_id}', None)
        if status == 404:
            return None
        raise_for_status(status, payload, zone=zone)
        return payload

    def list_nodes(self, zone: str) -> list:
        status, payload = self.transport(
            'GET', f'{self._zone_url(zone)}/nodes', None)
        if status == 404:
            return []
        raise_for_status(status, payload, zone=zone)
        return payload.get('nodes', [])

    def delete_node(self, zone: str, node_id: str) -> Optional[Dict]:
        status, payload = self.transport(
            'DELETE', f'{self._zone_url(zone)}/nodes/{node_id}', None)
        if status == 404:
            return None
        raise_for_status(status, payload, zone=zone)
        return payload

    def stop_node(self, zone: str, node_id: str) -> Dict[str, Any]:
        status, payload = self.transport(
            'POST', f'{self._zone_url(zone)}/nodes/{node_id}:stop', {})
        raise_for_status(status, payload, zone=zone)
        return payload

    def start_node(self, zone: str, node_id: str) -> Dict[str, Any]:
        status, payload = self.transport(
            'POST', f'{self._zone_url(zone)}/nodes/{node_id}:start', {})
        raise_for_status(status, payload, zone=zone)
        return payload

    # -------------------------------------------------- queued resources
    def create_queued_resource(self, zone: str, qr_id: str,
                               body: Dict[str, Any]) -> Dict[str, Any]:
        status, payload = self.transport(
            'POST',
            f'{self._zone_url(zone)}/queuedResources?queuedResourceId='
            f'{qr_id}', body)
        raise_for_status(status, payload, zone=zone)
        return payload

    def get_queued_resource(self, zone: str,
                            qr_id: str) -> Optional[Dict[str, Any]]:
        status, payload = self.transport(
            'GET', f'{self._zone_url(zone)}/queuedResources/{qr_id}', None)
        if status == 404:
            return None
        raise_for_status(status, payload, zone=zone)
        return payload

    def list_queued_resources(self, zone: str) -> list:
        status, payload = self.transport(
            'GET', f'{self._zone_url(zone)}/queuedResources', None)
        if status == 404:
            return []
        raise_for_status(status, payload, zone=zone)
        return payload.get('queuedResources', [])

    def delete_queued_resource(self, zone: str,
                               qr_id: str, force: bool = True
                               ) -> Optional[Dict[str, Any]]:
        status, payload = self.transport(
            'DELETE',
            f'{self._zone_url(zone)}/queuedResources/{qr_id}'
            f'?force={"true" if force else "false"}', None)
        if status == 404:
            return None
        raise_for_status(status, payload, zone=zone)
        return payload

    # ------------------------------------------------------- operations
    def get_operation(self, op_name: str) -> Dict[str, Any]:
        status, payload = self.transport(
            'GET', f'{TPU_API}/{op_name.lstrip("/")}', None)
        raise_for_status(status, payload)
        return payload

    def wait_operation(self, op: Dict[str, Any], *, zone: Optional[str],
                       timeout: float) -> Dict[str, Any]:
        """Poll a long-running operation to completion; map its terminal
        error (if any) through raise_for_status."""
        deadline = time.time() + timeout
        while not op.get('done'):
            if time.time() > deadline:
                err = exceptions.ProvisionError(
                    f'GCP operation timed out after {timeout:.0f}s: '
                    f'{op.get("name")}')
                err.blocklist_scope = 'zone'
                raise err
            time.sleep(poll_interval())
            op = self.get_operation(op['name'])
        if 'error' in op:
            code = int(op['error'].get('code', 500))
            # Operation errors carry gRPC-ish codes; normalize to HTTP.
            http = {8: 429, 7: 403, 16: 401}.get(code, 500)
            raise_for_status(http, {'error': op['error']}, zone=zone)
        return op


class ComputeClient:
    """compute.googleapis.com v1: the GCE path (GPU/CPU VMs)."""

    def __init__(self, project: str,
                 transport: Optional[Transport] = None):
        self.project = project
        self.transport = transport or get_transport()

    def _zone_url(self, zone: str) -> str:
        return f'{COMPUTE_API}/projects/{self.project}/zones/{zone}'

    def insert_instance(self, zone: str,
                        body: Dict[str, Any]) -> Dict[str, Any]:
        status, payload = self.transport(
            'POST', f'{self._zone_url(zone)}/instances', body)
        raise_for_status(status, payload, zone=zone)
        return payload

    def get_instance(self, zone: str,
                     name: str) -> Optional[Dict[str, Any]]:
        status, payload = self.transport(
            'GET', f'{self._zone_url(zone)}/instances/{name}', None)
        if status == 404:
            return None
        raise_for_status(status, payload, zone=zone)
        return payload

    def list_instances(self, zone: str) -> list:
        status, payload = self.transport(
            'GET', f'{self._zone_url(zone)}/instances', None)
        if status == 404:
            return []
        raise_for_status(status, payload, zone=zone)
        return payload.get('items', [])

    def delete_instance(self, zone: str, name: str) -> Optional[Dict]:
        status, payload = self.transport(
            'DELETE', f'{self._zone_url(zone)}/instances/{name}', None)
        if status == 404:
            return None
        raise_for_status(status, payload, zone=zone)
        return payload

    def stop_instance(self, zone: str, name: str) -> Dict[str, Any]:
        status, payload = self.transport(
            'POST', f'{self._zone_url(zone)}/instances/{name}/stop', {})
        raise_for_status(status, payload, zone=zone)
        return payload

    def start_instance(self, zone: str, name: str) -> Dict[str, Any]:
        """For TERMINATED (stopped) VMs; SUSPENDED needs resume_instance."""
        status, payload = self.transport(
            'POST', f'{self._zone_url(zone)}/instances/{name}/start', {})
        raise_for_status(status, payload, zone=zone)
        return payload

    def resume_instance(self, zone: str, name: str) -> Dict[str, Any]:
        status, payload = self.transport(
            'POST', f'{self._zone_url(zone)}/instances/{name}/resume', {})
        raise_for_status(status, payload, zone=zone)
        return payload


def poll_interval() -> float:
    return float(os.environ.get('SKYTPU_GCP_POLL', '5'))


def queued_resource_timeout() -> float:
    """How long a queued resource may sit non-ACTIVE before the attempt
    is abandoned and failover moves on ("queued too long" — SURVEY §7
    hard-parts; reference provisions QRs with a wait loop)."""
    return float(os.environ.get('SKYTPU_GCP_QR_TIMEOUT', '900'))
