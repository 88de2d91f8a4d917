"""The sanctioned device->host synchronization points.

Every device->host readback in the compute layer (``inference/``,
``models/``, ``ops/``, ``train/``) goes through :func:`host_sync` (or
:func:`host_block` when only a completion barrier is needed, not a
copy). Two reasons:

- **Auditability**: ``graftcheck``'s AST lint (rule GC202) flags any
  other host-sync spelling (bare ``np.asarray``, ``.item()``,
  ``jax.device_get``, implicit ``float()``) in compute files, and the
  runtime jaxpr auditor (``skypilot_tpu.analysis.jaxpr_audit``) counts
  transfers made outside these helpers as violations — an accidental
  sync inside the decode hot loop becomes a failing test, not a silent
  5.5s TTFT regression.
- **Explicitness**: a call spelled ``host_sync(x)`` tells the reader
  the host is about to stall on device completion; ``np.asarray(x)``
  says nothing.

The helpers are dependency-light: jax is imported lazily so the
orchestration layer can import ``skypilot_tpu.utils`` without the
compute extra installed.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

# Audit hook: the jaxpr auditor installs a recorder here while it
# drives an engine step; ``host_sync``/``host_block`` announce
# themselves so the interceptor can tell a sanctioned readback from an
# accidental one. Thread-local because the serve layer runs engines
# from a dedicated engine thread while tests drive audits from another.
_tls = threading.local()


@contextlib.contextmanager
def _sanctioned():
    prev = getattr(_tls, 'sanctioned', 0)
    _tls.sanctioned = prev + 1
    try:
        yield
    finally:
        _tls.sanctioned = prev


def in_sanctioned_sync() -> bool:
    """True while the current thread is inside host_sync/host_block —
    the jaxpr auditor's transfer interceptor checks this."""
    return getattr(_tls, 'sanctioned', 0) > 0


def host_sync(tree: Any) -> Any:
    """Copy a device array (or pytree of them) to host numpy, blocking
    until the device values are ready.

    This is THE device->host readback point for the compute layer: the
    engines' lagged async-pipeline readback, trainer metrics, and
    checkpoint saves all come through here. Keeping the spelling unique
    lets graftcheck prove the decode hot loop performs no OTHER host
    transfers."""
    with _sanctioned():
        try:
            import jax
        except ImportError:           # host-only tree (tests, tooling)
            import numpy as np
            if isinstance(tree, dict):
                return {k: np.asarray(v) for k, v in tree.items()}
            if isinstance(tree, (list, tuple)):
                return type(tree)(np.asarray(v) for v in tree)
            return np.asarray(tree)
        return jax.device_get(tree)


def host_scalars(tree: Any) -> Any:
    """host_sync + unwrap: every 0-d array in ``tree`` becomes a plain
    Python scalar (the trainer's metrics-logging path — ``float(v)`` on
    a device value is the implicit-sync spelling GC202 bans)."""
    tree = host_sync(tree)

    def scalar(x):
        return x.item() if hasattr(x, 'item') and getattr(
            x, 'ndim', None) == 0 else x
    try:
        import jax
        return jax.tree.map(scalar, tree)
    except ImportError:
        if isinstance(tree, dict):
            return {k: scalar(v) for k, v in tree.items()}
        return scalar(tree)


def host_block(tree: Any) -> Any:
    """Block until every array in ``tree`` has been computed, WITHOUT
    copying it to host (the donation barrier in quantize_params,
    timing fences). Returns ``tree``."""
    with _sanctioned():
        import jax
        return jax.block_until_ready(tree)


def device_upload(tree: Any, sharding: Any = None) -> Any:
    """The sanctioned host->device upload for compute-layer step paths.

    Thin wrapper over ``jax.device_put`` whose NAME carries the
    contract: the operands are freshly built HOST arrays (numpy) —
    never committed device arrays — so the call is a pure h2d copy and
    can NEVER trigger an implicit cross-mesh reshard of device state.
    graftcheck GC113 bans bare ``jax.device_put`` inside ``inference/``
    step functions; placement (construction-time sharding of params and
    caches) stays on ``jax.device_put`` in the sanctioned helpers
    (``prepare_params``, engine ``__init__``).

    ``sharding`` (optional) pre-partitions the upload — matching the
    consuming program's ``in_shardings`` so steady state never inserts
    a resharding collective between upload and use."""
    import jax
    if sharding is None:
        return jax.device_put(tree)
    return jax.device_put(tree, sharding)
