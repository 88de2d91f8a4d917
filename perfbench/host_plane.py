"""The host plane of a traced run beside the device's executions.

The program enters a ``jax.profiler.TraceAnnotation`` named
``skytpu:<phase>`` for every phase of its engine loop
(``telemetry/profiler.py``), so a ``--trace 1`` run's ``.xplane.pb`` holds
them on the plane ``/host:CPU`` in the same nanoseconds as the chip's
``XLA Modules`` line. ``trace.py`` reduces the device side; a reader that
needs both sides opens the file here. A program without the annotations
(the parent of the PR that added them) has none to find: every function
then returns an empty list or ``None`` and raises nothing.
"""
from __future__ import annotations

import re
import statistics
from typing import Hashable, List, Optional, Tuple

from perfbench import trace

# start_ns, end_ns, and what identifies the program
Keyed = Tuple[float, float, Hashable]
PREFIX = 'skytpu:'


def load(trace_dir: Optional[str]):
    """``ProfileData`` of the newest trace under ``trace_dir``, or None."""
    path = trace.find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    import jax
    return jax.profiler.ProfileData.from_file(path)


def annotations(data, phase: str) -> List[Keyed]:
    """Every ``skytpu:<phase>`` event of the host planes, by start, with
    the annotation's keyword arguments (the program key) as its key."""
    name = PREFIX + phase
    return sorted((e.start_ns, e.start_ns + e.duration_ns,
                   tuple(sorted(dict(e.stats).items())))
                  for p in data.planes if p.name.startswith('/host:')
                  for ln in p.lines for e in ln.events if e.name == name)


def executions(data, program: str) -> List[Keyed]:
    """Every execution of the jitted ``program`` on the first chip's
    ``XLA Modules`` line, by start; the key is the event's full name,
    ``jit_<program>(<fingerprint>)``: one per compiled program."""
    for p in data.planes:
        if not re.match(r'/device:[A-Za-z]+:\d+$', p.name):
            continue
        found = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for ln in p.lines if ln.name == trace.MODULES_LINE
                       for e in ln.events
                       if trace.program_name(e.name) == program)
        if found:
            return found
    return []


def _one_to_one(pairs) -> bool:
    """Each dispatch key always meets the same execution key, and back."""
    forth, back = {}, {}
    return all(forth.setdefault(d[2], r[2]) == r[2]
               and back.setdefault(r[2], d[2]) == d[2] for d, r in pairs)


def pair_in_order(dispatches: List[Keyed], runs: List[Keyed]
                  ) -> Optional[List[Tuple[Keyed, Keyed]]]:
    """Pair the k-th dispatch with the k-th execution it caused. One
    stream executes in dispatch order, so only the ends need care: the
    first executions in the trace may have been dispatched before the
    trace began (they have no annotation), and the last dispatches'
    executions may lie beyond its end. How many executions to drop at
    the front is read off the program keys: the fewest for which every
    dispatch key meets one and the same compiled program all along the
    trace, and no execution starts before the dispatch that caused it
    returned. None when no such number leaves more than half of the
    trace paired (the match is void)."""
    most = min(len(dispatches), len(runs))
    for drop in range(len(runs)):
        pairs = list(zip(dispatches, runs[drop:]))
        if 2 * len(pairs) <= most:
            break
        if _one_to_one(pairs) and all(r[0] >= d[1] for d, r in pairs):
            return pairs
    return None


def dispatch_lag_ms(data, phase: str, program: str) -> Optional[float]:
    """Median of (device start of an execution of ``program`` - end of
    the ``skytpu:<phase>`` annotation that dispatched it), in ms."""
    pairs = pair_in_order(annotations(data, phase),
                          executions(data, program))
    if pairs is None:
        return None
    return statistics.median(r[0] - d[1] for d, r in pairs) / 1e6
