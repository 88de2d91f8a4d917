"""``.xplane.pb`` -> what the per-layer metrics and ``breakdown`` need.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. On a
TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules``
has one event per execution of a jitted program (named
``jit_<function>(<fingerprint>)``) and its line ``XLA Ops`` one per
device operation. Host threads are lines of the plane ``/host:CPU``.
All times are nanoseconds on one clock.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import statistics
from typing import Dict, List, NamedTuple, Optional, Tuple

MODULES_LINE = 'XLA Modules'
OPS_LINE = 'XLA Ops'
Interval = Tuple[float, float]          # start_ns, end_ns


class Execution(NamedTuple):
    duration_s: float
    inner_loops: int      # ``while`` ops one level inside its top-level ops:
                          # the fused decode steps of a decode_steps call


class Reduced(NamedTuple):
    window_s: float                      # first to last event of any plane
    busy_s: float                        # union of op intervals, mean/chip
    devices: int
    programs: Dict[str, List[Execution]]   # executions on the first chip
    top_ops: List[Tuple[str, float]]     # (name, self seconds), first chip
    idle_gaps: List[Tuple[str, float]]   # (what the host did, s), first chip


def start(trace_dir: str) -> None:
    """Profile into ``trace_dir``: device and host events, no Python
    tracer (it slows the host and swells the file)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    return found[-1] if found else None


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def program_name(event_name: str) -> str:
    """``jit_decode_steps(1234567)`` -> ``decode_steps``."""
    m = re.match(r'(?:jit_)?(.*?)(?:\(\d+\))?$', event_name)
    return m.group(1) if m else event_name


def _lines(plane, name):
    return [ln for ln in plane.lines if ln.name == name]


def _host_label(host_events: List[Tuple[float, float, str]],
                gap: Interval) -> str:
    """The host event that covers most of a gap, or 'unattributed'."""
    best, best_cover = 'unattributed', 0.0
    for s, e, name in host_events:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best if best_cover >= 0.5 * (gap[1] - gap[0]) else 'unattributed'


def nest(ops: List[Tuple[float, float, str]]):
    """Depth and self time of each op of one device line. A ``while`` op
    spans the ops of its body, so totals by name would count them twice:
    self time is an op's duration less its direct children's."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    depth = [0] * len(ops)
    self_ns = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i in order:
        s, e, _ = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        depth[i] = len(stack)
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return depth, self_ns


def short_name(op: str, width: int = 96) -> str:
    """An op as the trace names it, cut before its operand list."""
    return op.split(' fusion(')[0].split(' while(')[0][:width]


def reduce_xplane(path: str, top: int = 10) -> Optional[Reduced]:
    """None when no device plane holds an operation."""
    with open(path, 'rb') as f:
        return reduce_xspace(f.read(), top)


def reduce_xspace(serialized: bytes, top: int = 10) -> Optional[Reduced]:
    import jax
    data = jax.profiler.ProfileData.from_serialized_xspace(serialized)
    device_planes = [p for p in data.planes
                     if re.match(r'/device:[A-Za-z]+:\d+$', p.name)]
    busy, first = [], None
    for plane in device_planes:
        ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for ln in _lines(plane, OPS_LINE) for e in ln.events]
        if not ops:
            continue
        merged = union([(s, e) for s, e, _ in ops])
        busy.append(sum(e - s for s, e in merged))
        if first is None:
            first = (plane, ops, merged)
    if first is None:
        return None
    plane, ops, merged = first
    depth, self_ns = nest(ops)
    loops = sorted(s for (s, _, name), d in zip(ops, depth)
                   if d == 1 and ' while(' in name)
    programs: Dict[str, List[Execution]] = collections.defaultdict(list)
    for ln in _lines(plane, MODULES_LINE):
        for e in ln.events:
            inside = (bisect.bisect_left(loops, e.start_ns + e.duration_ns)
                      - bisect.bisect_left(loops, e.start_ns))
            programs[program_name(e.name)].append(
                Execution(e.duration_ns / 1e9, inside))
    totals: Dict[str, float] = collections.Counter()
    for (_, _, name), ns in zip(ops, self_ns):
        totals[short_name(name)] += ns / 1e9
    host_events, lo, hi = [], merged[0][0], merged[-1][1]
    for p in data.planes:
        for ln in p.lines:
            for e in ln.events:
                if e.duration_ns > 0:
                    lo = min(lo, e.start_ns)
                    hi = max(hi, e.start_ns + e.duration_ns)
                    if p.name.startswith('/host:'):
                        host_events.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name))
    gaps = sorted(((b[0] - a[1], (a[1], b[0]))
                   for a, b in zip(merged, merged[1:])), reverse=True)[:top]
    by_label: Dict[str, float] = collections.Counter()
    for length, gap in gaps:
        by_label[_host_label(host_events, gap)] += length / 1e9
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=statistics.fmean(busy) / 1e9,
        devices=len(busy),
        programs=dict(programs),
        top_ops=sorted(totals.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(by_label.items(), key=lambda kv: -kv[1])[:top])


def per_step_ms(reduced: Reduced, program: str) -> Optional[float]:
    """Median device time of one fused step of ``program``: each
    execution divided by the loops it ran one level inside."""
    per_step = [e.duration_s / e.inner_loops * 1e3
                for e in reduced.programs.get(program, []) if e.inner_loops]
    return statistics.median(per_step) if per_step else None
