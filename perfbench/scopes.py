"""Device time by ``jax.named_scope``, from the ``.xplane.pb``.

A device operation's event carries its HLO text; the scope path it was
traced under (``jit(decode_steps)/while/body/.../mla_attn/dot_general``)
is a stat of the event's METADATA, named ``tf_op``, which
``jax.profiler.ProfileData`` does not expose. So the few fields needed
are read from the protobuf wire format here (``XSpace.planes`` ->
``XPlane.event_metadata`` / ``stat_metadata``; tensorflow/tsl
``xplane.proto``), skipping the events themselves, and joined to
``ProfileData``'s events by the event name. ``trace.py`` is not edited.

A fusion carries the scope of its root operation, so an operation fused
across a scope's edge is counted on one side of it: a scope's time is
good to a few per cent, not to an operation.
"""
from __future__ import annotations

import gzip
import re
from typing import Dict, Iterator, Optional, Tuple

from perfbench import trace

_CACHE: Dict[str, Dict[Tuple[str, str], float]] = {}


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7f) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a
    length-delimited value is a memoryview-free bytes slice."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f'wire type {wire} in an xplane')
        yield field, wire, val


def _map_value(entry: bytes) -> bytes:
    """The value of a ``map<int64, Message>`` entry."""
    return next((v for f, _, v in _fields(entry) if f == 2), b'')


def op_scopes(xspace: bytes) -> Dict[str, str]:
    """HLO text of an operation -> the scope path it was traced under,
    over the device planes."""
    out: Dict[str, str] = {}
    for f, _, plane in _fields(xspace):
        if f != 1:
            continue
        name, events, stats = '', [], {}
        for pf, _, val in _fields(plane):
            if pf == 2:
                name = val.decode('utf-8', 'replace')
            elif pf == 4:                           # event_metadata
                events.append(_map_value(val))
            elif pf == 5:                           # stat_metadata
                meta = dict((mf, mv) for mf, _, mv in
                            _fields(_map_value(val)) if mf in (1, 2))
                stats[meta.get(1, 0)] = meta.get(2, b'').decode()
        if not re.match(r'/device:[A-Za-z]+:\d+$', name):
            continue
        tf_op = {i for i, n in stats.items() if n == 'tf_op'}
        for meta in events:
            text, path = '', ''
            for mf, _, mv in _fields(meta):
                if mf == 2:
                    text = mv.decode('utf-8', 'replace')
                elif mf == 5:                       # an XStat
                    stat = dict((sf, sv) for sf, _, sv in _fields(mv))
                    if stat.get(1) in tf_op and 5 in stat:
                        path = stat[5].decode('utf-8', 'replace')
            if text and path:
                out[text] = path
    return out


def _read(path: str) -> bytes:
    with (gzip.open(path) if path.endswith('.gz') else open(path, 'rb')) as f:
        return f.read()


def scope_seconds(xplane_path: str, program: str,
                  scope: str) -> Optional[float]:
    """Self time, on the first chip, of the operations of ``program``
    (``decode_steps``, ``prefill``) traced under ``scope``; None where
    the trace names no such operation (a program that has no such
    scope: the parent's)."""
    if xplane_path not in _CACHE:
        import jax
        raw = _read(xplane_path)
        where = op_scopes(raw)
        data = jax.profiler.ProfileData.from_serialized_xspace(raw)
        plane = next((p for p in data.planes
                      if re.match(r'/device:[A-Za-z]+:\d+$', p.name)
                      and any(ln.name == trace.OPS_LINE and
                              next(iter(ln.events), None) is not None
                              for ln in p.lines)), None)
        totals: Dict[Tuple[str, str], float] = {}
        if plane is not None:
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for ln in plane.lines if ln.name == trace.OPS_LINE
                   for e in ln.events]
            _, self_ns = trace.nest(ops)
            for (_, _, text), ns in zip(ops, self_ns):
                path = where.get(text)
                if not path:
                    continue
                prog = re.match(r'jit\((\w+)\)', path)
                for part in set(path.split('/')):
                    key = (prog.group(1) if prog else '', part)
                    totals[key] = totals.get(key, 0.0) + ns / 1e9
        _CACHE[xplane_path] = totals
    return _CACHE[xplane_path].get((program, scope))


def of_run(run, program: str, scope: str) -> Optional[float]:
    """``scope_seconds`` of a traced run's own trace."""
    path = trace.find_xplane(run['trace_dir']) if run.get('trace_dir') \
        else None
    return scope_seconds(path, program, scope) if path else None
