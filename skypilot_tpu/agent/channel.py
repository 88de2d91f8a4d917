"""Persistent agent-RPC channels.

One-shot RPCs pay a remote interpreter start per call (over SSH that is
hundreds of ms; the reference pays the same per codegen-exec,
``sky/skylet/job_lib.py:930``). A channel starts ``python -m
<module> --serve`` on the head ONCE per client session and pipes
line-delimited JSON over its stdin/stdout — status/queue/logs/cancel
sequences then cost one round trip each instead of one interpreter
start each.

Failure model (the channel is an optimization, never a new failure
mode):

- Startup failure (old runtime without ``--serve``, agent not yet
  synced): raises ``ChannelError(sent=False)``; the caller falls back
  to the one-shot exec AND the key is negative-cached for a cooldown so
  every later call doesn't pay failed spawns first.
- Failure BEFORE the request was written: safe to re-establish and
  retry — nothing executed remotely.
- Failure AFTER the request was written (EOF mid-response, read
  timeout): NO retry and NO fallback — the op may have executed, and
  blindly re-sending would double-submit writes like ``queue_job``.
  The error surfaces to the caller (``sent=True``).
- Reads ride a reader thread + queue, so every wait is bounded by
  ``request_timeout`` — a wedged remote handler cannot hold the
  channel lock forever.
"""
from __future__ import annotations

import atexit
import json
import queue as queue_mod
import shlex
import threading
import time
from typing import Dict, Optional, Tuple

from skypilot_tpu import tpu_logging
from skypilot_tpu.agent import rpc as agent_rpc

logger = tpu_logging.init_logger(__name__)

# How long a failed-to-start channel key stays disabled (fall straight
# to one-shot execs) before the channel is attempted again.
_DISABLE_COOLDOWN_S = 120.0


class ChannelError(Exception):
    """The channel could not serve the request.

    ``sent`` is True when the request MAY have reached the remote
    handler — the caller must not re-execute non-idempotent ops."""

    def __init__(self, msg: str, *, sent: bool):
        super().__init__(msg)
        self.sent = sent


class RpcChannel:
    """One persistent ``--serve`` interpreter on a node."""

    def __init__(self, runner, module: str,
                 request_timeout: float = 120.0):
        self._runner = runner
        self._module = module
        self._timeout = request_timeout
        self._proc = None
        self._lines: 'queue_mod.Queue[Optional[str]]' = queue_mod.Queue()
        self._lock = threading.Lock()

    def _start(self) -> None:
        cmd = (f'{shlex.quote(self._runner.remote_python)} '
               f'-m {self._module} --serve')
        self._proc = self._runner.popen_interactive(cmd)
        self._lines = queue_mod.Queue()
        stdout = self._proc.stdout

        def reader(q: 'queue_mod.Queue[Optional[str]]') -> None:
            # Dedicated reader: readline() has no timeout, so waits
            # happen on the queue (bounded) instead of the pipe.
            for line in iter(stdout.readline, ''):
                q.put(line)
            q.put(None)                      # EOF marker

        threading.Thread(target=reader, args=(self._lines,),
                         daemon=True).start()
        # Wait for the ready banner so a failed spawn (e.g. a head
        # running an older runtime whose rpc has no --serve) surfaces
        # here as sent=False, never as a confusing mid-request EOF.
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                line = self._lines.get(timeout=1.0)
            except queue_mod.Empty:
                continue
            if line is None:
                raise ChannelError(
                    f'channel to {self._runner.node_id} died during '
                    f'startup (rc={self._proc.poll()})', sent=False)
            if line.strip() == agent_rpc.READY_LINE:
                return
        raise ChannelError('channel startup: no ready banner',
                           sent=False)

    def _ensure(self) -> None:
        if self._proc is not None and self._proc.poll() is None:
            return
        self.close()
        self._start()

    def _roundtrip(self, request: Dict) -> Dict:
        try:
            self._proc.stdin.write(json.dumps(request) + '\n')
            self._proc.stdin.flush()
        except (OSError, ValueError) as e:
            # Write failed outright — remote never saw the request.
            raise ChannelError(f'channel write failed: {e}',
                               sent=False) from e
        deadline = time.time() + self._timeout
        while True:
            remaining = deadline - time.time()
            if remaining <= 0:
                raise ChannelError(
                    f'channel request timed out after '
                    f'{self._timeout}s', sent=True)
            try:
                line = self._lines.get(timeout=min(remaining, 5.0))
            except queue_mod.Empty:
                continue
            if line is None:
                raise ChannelError('channel EOF mid-request', sent=True)
            if line.startswith(agent_rpc.PAYLOAD_PREFIX):
                return json.loads(line[len(agent_rpc.PAYLOAD_PREFIX):])

    def request(self, request: Dict) -> Dict:
        """One RPC round trip. Re-establishes and retries only when the
        request provably never reached the remote (sent=False);
        anything after the write surfaces as ChannelError(sent=True) —
        the caller decides what re-execution means for the op."""
        with self._lock:
            for attempt in (0, 1):
                try:
                    self._ensure()
                    return self._roundtrip(request)
                except ChannelError as e:
                    if e.sent:
                        self.close()
                        raise
                    self.close()
                    if attempt == 1:
                        raise
                    logger.debug(f'RPC channel retry to '
                                 f'{self._runner.node_id}: {e}')
                except (OSError, ValueError,
                        NotImplementedError) as e:
                    self.close()
                    if attempt == 1:
                        raise ChannelError(str(e), sent=False) from e
                    logger.debug(f'RPC channel retry to '
                                 f'{self._runner.node_id}: {e}')

    def close(self) -> None:
        proc, self._proc = self._proc, None
        if proc is None:
            return
        try:
            if proc.stdin:
                proc.stdin.close()
            proc.terminate()
            proc.wait(timeout=2)
        except Exception as e:  # pylint: disable=broad-except
            logger.debug(f'channel close: terminate failed '
                         f'({type(e).__name__}: {e}); killing')
            try:
                proc.kill()
            except Exception as e2:  # pylint: disable=broad-except
                logger.debug(f'channel close: kill failed too '
                             f'({type(e2).__name__}: {e2})')


_channels: Dict[Tuple, RpcChannel] = {}
_disabled_until: Dict[Tuple, float] = {}
_registry_lock = threading.Lock()


def channel_for(runner, module: str) -> Optional[RpcChannel]:
    """The cached channel for (node, module); None when the runner has
    no interactive transport or the key is in its failure cooldown."""
    try:
        key = runner.channel_key + (module,)
    except (AttributeError, NotImplementedError):
        return None
    with _registry_lock:
        if _disabled_until.get(key, 0) > time.time():
            return None
        ch = _channels.get(key)
        if ch is None:
            ch = RpcChannel(runner, module)
            _channels[key] = ch
        return ch


def disable(runner, module: str,
            cooldown: float = _DISABLE_COOLDOWN_S) -> None:
    """Negative-cache a channel key after a startup failure: later
    calls go straight to the one-shot exec instead of paying failed
    channel spawns first (e.g. a head running an older runtime)."""
    try:
        key = runner.channel_key + (module,)
    except (AttributeError, NotImplementedError):
        return
    with _registry_lock:
        _disabled_until[key] = time.time() + cooldown
        ch = _channels.pop(key, None)
    if ch is not None:
        ch.close()


def close_all() -> None:
    with _registry_lock:
        chans = list(_channels.values())
        _channels.clear()
        _disabled_until.clear()
    for ch in chans:
        ch.close()


atexit.register(close_all)
