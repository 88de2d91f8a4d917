"""Which device this process runs on, and what it has compiled.

The process entry points (``serve/server.py``, ``train/__main__.py``)
report this, so a process that came up on the CPU cannot pass for one on
the chip, and a caller that stays off JAX (``chip_smoke.py``'s parent)
can read the device from the child that held it.

jax is imported lazily: ``skypilot_tpu.telemetry`` must import without
the compute extra.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional

_BACKEND_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


def device_identity() -> Dict[str, Any]:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them."""
    import jax
    first = jax.devices()[0]
    return {'platform': first.platform, 'device_kind': first.device_kind,
            'device_count': jax.device_count()}


def device_memory() -> list:
    """Each local device's live ``memory_stats()`` (zeros on backends
    that report none, e.g. the CPU)."""
    import jax
    memory = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        memory.append({
            'id': d.id,
            'bytes_in_use': int(stats.get('bytes_in_use', 0)),
            'peak_bytes_in_use': int(stats.get('peak_bytes_in_use', 0)),
            'bytes_limit': int(stats.get('bytes_limit', 0)),
        })
    return memory


def bytes_by_device(tree: Any) -> Dict[str, int]:
    """Stored bytes of a pytree of jax arrays per device id (from the
    shards' shapes: no transfer). Keys are strings: the result is JSON."""
    import jax
    out: Dict[str, int] = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + int(shard.data.nbytes)
    return out


class CompileWatch:
    """Counts every XLA backend compile of the process (jitted programs
    and eager ops alike, persistent-cache hits included) through
    ``jax.monitoring``. Steady-state serving over repeated shapes must
    not move ``count``."""

    def __init__(self) -> None:
        import jax.monitoring
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        del kwargs
        if event != _BACKEND_COMPILE_EVENT:
            return
        with self._lock:
            self.count += 1
            self.seconds += duration

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {'count': self.count,
                    'seconds': round(self.seconds, 3)}


_watch_lock = threading.Lock()
_watch: Optional[CompileWatch] = None


def get_compile_watch() -> CompileWatch:
    """The process-wide watch (listeners cannot be unregistered, so
    there is one, created at first use — call it before the first
    compile that should count)."""
    global _watch
    with _watch_lock:
        if _watch is None:
            _watch = CompileWatch()
        return _watch
