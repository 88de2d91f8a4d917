"""What the readers of the engine loop's account share.

The program charges every instant of its engine thread to one cause
(``telemetry/profiler.py``): the innermost open phase, or ``loop`` for the
thread's own code between phases. ``/metrics?format=json`` reports the
running totals as ``loop_<cause>_seconds_total`` beside ``loop_turns_total``
(the loop's turns that had work) in its ``engine_loop`` block; differenced
between the window's two scrapes they give the window's shares and means.
Traced, each phase is a ``skytpu:<cause>`` event on the host plane in the
nanoseconds of the chip's operations, so the chip's idle time can be split
by what the engine thread was doing meanwhile. A program without the
account (the parent of the change that added it) has neither: every
function here then returns None and raises nothing.
"""
from __future__ import annotations

import bisect
import collections
import difflib
import re
from typing import Dict, List, Optional, Tuple

from perfbench import host_plane, moe_window, trace

counter_delta = moe_window.counter_delta
# The causes of a turn handing work to the runtime.
HANDING = ('dispatch', 'upload', 'eager')
# The jitted programs of the serving loop (``XLA Modules`` names): any
# other execution is an eager op.
PROGRAMS = ('decode_steps', 'prefill', 'merge_ring_into_pool', 'verify',
            'fused', 'lambda')
Span = Tuple[float, float, str]          # start_ns, end_ns, cause


# ------------------------------------------------------- the counters
def cause_delta(run, cause: str) -> Optional[float]:
    """Seconds the window charged to ``cause``, or None."""
    return counter_delta(run, f'loop_{cause}_seconds_total')


def _window(run):
    """(clock seconds, seconds waiting for work, working turns) of the
    window, or None where the program keeps no account."""
    clock = counter_delta(run, 'clock_s')
    wait = cause_delta(run, 'wait_work')
    turns = counter_delta(run, 'loop_turns_total')
    if clock is None or wait is None or turns is None or clock <= 0:
        return None
    return clock, wait, turns


def wait_work_share(run) -> Optional[float]:
    """Per cent of the window the engine thread waited for work."""
    window = _window(run)
    return None if window is None else 100.0 * window[1] / window[0]


def turn_ms(run) -> Optional[float]:
    """Mean length of a working turn: the clock not spent waiting for
    work, over the turns that had work."""
    window = _window(run)
    if window is None or not window[2]:
        return None
    clock, wait, turns = window
    return 1e3 * (clock - wait) / turns


def dispatch_wait_ms(run) -> Optional[float]:
    """Host time a working turn spent handing work to the runtime: the
    self seconds of ``dispatch``, ``upload`` and ``eager`` a turn."""
    window = _window(run)
    parts = [cause_delta(run, cause) for cause in HANDING]
    if window is None or not window[2] or None in parts:
        return None
    return 1e3 * sum(parts) / window[2]


def account(run) -> Optional[Dict[str, float]]:
    """Every cause's seconds in the window, or None."""
    end = run['records']['metrics_end'].get('engine_loop') or {}
    causes = [k[len('loop_'):-len('_seconds_total')] for k in end
              if k.startswith('loop_') and k.endswith('_seconds_total')]
    out = {c: cause_delta(run, c) for c in causes}
    if not out or None in out.values():
        return None
    return out


def log_account(run) -> None:
    """One line in the run's log: the window's causes, their sum against
    the clock, and the turns."""
    seconds, window = account(run), _window(run)
    if seconds is None or window is None:
        return
    top = sorted(seconds.items(), key=lambda kv: -kv[1])
    run['ctx'].log(
        f'loop account over the window: causes sum {sum(seconds.values()):.4f}'
        f' s of clock {window[0]:.4f} s, {window[2]:.0f} working turns; '
        + ', '.join(f'{c} {s:.4f}s' for c, s in top if s > 0))


# --------------------------------------------------------- the trace
def _host_lines(data):
    for p in data.planes:
        if p.name.startswith('/host:'):
            yield from p.lines


def engine_line(data) -> Optional[list]:
    """Events of the engine thread (the host line holding the most
    ``skytpu:wait_work`` events) as (start_ns, end_ns, name, stats),
    outer first; None where no line holds one."""
    best, most = None, 0
    for ln in _host_lines(data):
        n = sum(e.name == host_plane.PREFIX + 'wait_work' for e in ln.events)
        if n > most:
            best, most = ln, n
    if best is None:
        return None
    return sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(e.stats)) for e in best.events),
                  key=lambda ev: (ev[0], -ev[1]))


def cause_of(name: str, stats: dict) -> str:
    """``skytpu:eager`` with its site -> ``eager:<site>``."""
    cause = name[len(host_plane.PREFIX):]
    return f'{cause}:{stats["site"]}' if 'site' in stats else cause


def innermost(events) -> List[Span]:
    """The engine thread's time from its first ``skytpu:`` event to its
    last as consecutive pieces, each charged to the innermost span open
    there, or ``loop`` where none is (one thread's spans nest)."""
    spans = [(s, e, cause_of(n, st)) for s, e, n, st in events
             if n.startswith(host_plane.PREFIX) and e > s]
    out: List[Span] = []
    if not spans:
        return out

    def emit(a, b, cause):
        if b > a:
            out.append((a, b, cause))

    stack: List[Span] = []
    t = spans[0][0]
    for s, e, cause in spans:
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            emit(t, top[1], top[2])
            t = max(t, top[1])
        emit(t, s, stack[-1][2] if stack else 'loop')
        t = max(t, s)
        stack.append((s, e, cause))
    while stack:
        top = stack.pop()
        emit(t, top[1], top[2])
        t = max(t, top[1])
    return out


def _busy(plane) -> List[Tuple[float, float]]:
    return trace.union([(e.start_ns, e.start_ns + e.duration_ns)
                        for ln in plane.lines if ln.name == trace.OPS_LINE
                        for e in ln.events])


def _device_planes(data):
    return [p for p in data.planes
            if re.match(r'/device:[A-Za-z]+:\d+$', p.name)]


def idle_by_cause(data) -> Optional[Dict[str, float]]:
    """Seconds the chip was idle (the complement of its operations'
    union, as ``idle_share`` counts it) between the engine thread's first
    and last ``skytpu:`` event, by the cause charged meanwhile; the mean
    over chips. None without such events or operations."""
    line = engine_line(data)
    pieces = innermost(line) if line else []
    busy = [b for b in map(_busy, _device_planes(data)) if b]
    if not pieces or not busy:
        return None
    lo, hi = pieces[0][0], pieces[-1][1]
    out: Dict[str, float] = collections.Counter()
    for merged in busy:
        gaps, t = [], lo
        for s, e in merged:
            if s > t:
                gaps.append((t, min(s, hi)))
            t = max(t, e)
            if t >= hi:
                break
        if t < hi:
            gaps.append((t, hi))
        i = 0
        for a, b in gaps:
            while i < len(pieces) and pieces[i][1] <= a:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < b:
                s, e, cause = pieces[j]
                out[cause] += (min(b, e) - max(a, s)) / 1e9 / len(busy)
                j += 1
    return dict(out)


def host_idle_s(split: Dict[str, float]) -> float:
    """The idle seconds of a split that no wait for work covers."""
    return sum(s for c, s in split.items() if c != 'wait_work')


def _program(name: str) -> str:
    """A program's name as the host and the device both spell it
    (``<lambda>`` is ``jit__lambda`` on the chip)."""
    return re.sub(r'\W', '_', trace.program_name(name)).strip('_')


def _cause_at(pieces: List[Span], starts: List[float], t: float) -> str:
    k = bisect.bisect_right(starts, t) - 1
    return pieces[k][2] if k >= 0 and t < pieces[k][1] else 'outside'


def eager_executions(data) -> Optional[Dict[str, int]]:
    """Executions of eager ops on the first chip (an ``XLA Modules`` event
    that is none of ``PROGRAMS``) by the ``site`` of the ``skytpu:eager``
    span that dispatched them. One stream runs programs in the order the
    engine thread handed them over (its ``PjitFunction(...)`` events), so
    the two sequences of program names are aligned; where they differ
    between two agreeing runs (a squeeze that runs under another module's
    name) with as many on each side, they pair in order. ``unnamed``: an
    execution whose dispatch lies in no eager span; ``inside_its_span``:
    one that started before its span ended; ``unpaired``: one the
    alignment left alone (dispatched before the trace began)."""
    line = engine_line(data)
    planes = _device_planes(data)
    if line is None or not planes:
        return None
    pieces = innermost(line)
    starts = [p[0] for p in pieces]
    spans = sorted((s, e, st.get('site', '')) for s, e, n, st in line
                   if n == host_plane.PREFIX + 'eager')
    handed, outer = [], (0, -1.0, '')
    for s, e, name, _ in line:
        m = re.match(r'PjitFunction\((.*)\)$', name)
        if not m or (name == outer[2] and e <= outer[1]):
            continue                     # the runtime's nested twin
        outer = (s, e, name)
        if _cause_at(pieces, starts, s) != 'first_call':
            handed.append((s, _program(m.group(1))))
    runs = sorted((e.start_ns, _program(e.name)) for ln in planes[0].lines
                  if ln.name == trace.MODULES_LINE for e in ln.events)
    paired = {}
    ops = difflib.SequenceMatcher(
        None, [h[1] for h in handed], [r[1] for r in runs],
        autojunk=False).get_opcodes()
    for tag, a0, a1, b0, b1 in ops:
        # as many dispatches as executions between two agreeing runs: one
        # each, in order, whatever the module calls itself
        if tag == 'equal' or (tag == 'replace' and a1 - a0 == b1 - b0):
            for j in range(b1 - b0):
                paired[b0 + j] = handed[a0 + j][0]
    out: Dict[str, int] = collections.Counter()
    for k, (t, program) in enumerate(runs):
        if program in PROGRAMS:
            continue
        if k not in paired:
            out['unpaired'] += 1
            continue
        i = bisect.bisect_right(spans, (paired[k], float('inf'), '')) - 1
        if i < 0 or spans[i][1] < paired[k] or not spans[i][2]:
            out['unnamed'] += 1
        elif spans[i][1] > t:
            out['inside_its_span'] += 1
        else:
            out[spans[i][2]] += 1
    return dict(out)


def traced_seconds(data) -> Optional[Dict[str, float]]:
    """The engine thread's traced time by innermost cause."""
    line = engine_line(data)
    if line is None:
        return None
    out: Dict[str, float] = collections.Counter()
    for s, e, cause in innermost(line):
        out[cause] += (e - s) / 1e9
    return dict(out)


def runtime_by_cause(data, pattern: str) -> Optional[Dict[str, float]]:
    """Seconds of the runtime's events whose name holds ``pattern`` on
    any host line (``ExecutePrepare`` sits on a line of its own), by the
    engine thread's innermost cause at their start."""
    line = engine_line(data)
    pieces = innermost(line) if line else []
    if not pieces:
        return None
    starts = [p[0] for p in pieces]
    out: Dict[str, float] = collections.Counter()
    for ln in _host_lines(data):
        for e in ln.events:
            if pattern in e.name and pieces[0][0] <= e.start_ns < pieces[-1][1]:
                out[_cause_at(pieces, starts, e.start_ns)] += e.duration_ns / 1e9
    return dict(out)


def host_idle_share(run) -> Optional[float]:
    """Per cent of the traced window the chip idled while the engine
    thread was not waiting for work; logs the whole split."""
    try:
        data = host_plane.load(run.get('trace_dir'))
    except (OSError, RuntimeError):          # no trace JAX can parse
        data = None
    split = idle_by_cause(data) if data is not None else None
    if split is None:
        return None
    log = run['ctx'].log
    log('chip idle between the engine thread\'s first and last span, by '
        'its innermost cause: ' + ', '.join(
            f'{c} {s:.6f}s' for c, s in sorted(split.items(),
                                                key=lambda kv: -kv[1])))
    seconds = traced_seconds(data) or {}
    log('the engine thread\'s traced time by innermost cause: ' + ', '.join(
        f'{c} {s:.6f}s' for c, s in sorted(seconds.items(),
                                            key=lambda kv: -kv[1])))
    eager = eager_executions(data)
    if eager:
        log('eager-op executions on the chip by the site that issued them: '
            + ', '.join(f'{c} {n}' for c, n in sorted(eager.items())))
    prepare = runtime_by_cause(data, 'ExecutePrepare') or {}
    if prepare:
        log('ExecutePrepare on the engine thread by cause: ' + ', '.join(
            f'{c} {s:.6f}s' for c, s in sorted(prepare.items(),
                                                key=lambda kv: -kv[1])))
    return 100.0 * host_idle_s(split) / run['trace'].window_s
