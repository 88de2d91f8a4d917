"""The loop account's readers against hand-made scrapes, and its trace
side against a hand-made ``.xplane.pb``: an XSpace encoded here field by
field and read back through ``jax.profiler.ProfileData`` as a run's is. A
parent's scrapes and traces, which hold no account, give ``None``."""
import os
import types

import pytest

from perfbench import loop_account
from perfbench.run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = os.path.join(os.path.dirname(HERE), 'layer_metrics')
NEW = ('host_idle_share', 'wait_work_share', 'loop_turn_ms',
       'dispatch_wait_ms', 'compile_in_window_s')


def reader(name):
    return load_module(os.path.join(READERS, name + '.py'))


def scrape(clock_s, turns, **causes):
    loop = {'clock_s': clock_s, 'lock_held_seconds_total': 0.0,
            'loop_turns_total': turns}
    for cause in ('loop', 'wait_work', 'dispatch', 'upload', 'eager',
                  'first_call', 'readback'):
        loop[f'loop_{cause}_seconds_total'] = causes.get(cause, 0.0)
    return {'engine_loop': loop}


def run_with(start, end, **extra):
    logged = []
    ctx = types.SimpleNamespace(log=logged.append)
    return dict({'records': {'metrics_start': start, 'metrics_end': end},
                 'ctx': ctx}, **extra), logged


def window():
    """51 s of clock: 20 s waiting, 100 working turns, 1.5 s handing work
    over (0.9 + 0.2 + 0.4), 0.25 s compiling."""
    a = scrape(1000.0, 40, loop=3.0, wait_work=50.0, dispatch=2.0,
               upload=1.0, eager=0.5, first_call=30.0, readback=8.0)
    b = scrape(1051.0, 140, loop=5.0, wait_work=70.0, dispatch=2.9,
               upload=1.2, eager=0.9, first_call=30.25, readback=35.25)
    return run_with(a, b)


def test_counter_readers_read_the_window():
    run, logged = window()
    assert reader('wait_work_share').read(run) == pytest.approx(
        100 * 20 / 51)
    assert reader('loop_turn_ms').read(run) == pytest.approx(
        1e3 * (51 - 20) / 100)
    assert reader('dispatch_wait_ms').read(run) == pytest.approx(
        1e3 * 1.5 / 100)
    assert reader('compile_in_window_s').read(run) == pytest.approx(0.25)
    # the account's line: its causes against the clock
    assert logged and 'causes sum 51.0000 s of clock 51.0000 s' in \
        logged[0], logged


def test_no_turn_no_means():
    a = scrape(0.0, 7, wait_work=1.0)
    b = scrape(30.0, 7, wait_work=31.0)
    run, _ = run_with(a, b)
    assert reader('wait_work_share').read(run) == pytest.approx(100.0)
    assert reader('loop_turn_ms').read(run) is None
    assert reader('dispatch_wait_ms').read(run) is None


@pytest.mark.parametrize('name', NEW)
def test_a_parent_reads_none(name):
    """The parent's ``engine_loop`` block has no loop account, and its
    trace no ``skytpu:wait_work`` span."""
    old = {'engine_loop': {'clock_s': 5.0, 'lock_held_seconds_total': 1.0,
                           'decode_substeps_total': 3}}
    new = {'engine_loop': {'clock_s': 9.0, 'lock_held_seconds_total': 2.0,
                           'decode_substeps_total': 8}}
    run, logged = run_with(old, new, trace_dir=None)
    assert reader(name).read(run) is None
    run, _ = run_with({}, {}, trace_dir=None)
    assert reader(name).read(run) is None
    assert not logged


@pytest.mark.parametrize('name', NEW)
def test_reader_declares_what_benchmark_json_says(name):
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           'BENCHMARK.json'), encoding='utf-8') as f:
        entry = next(m for m in json.load(f)['per_layer']
                     if m['name'] == name)
    mod = reader(name)
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry['layer'], entry['unit'], entry['moves'], entry['source'])
    assert mod.CELLS == entry['workloads']


# ------------------------------------------------------- a synthetic trace
def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    """One protobuf field: an int as a varint, bytes/str/message length
    delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(pid, name, lines):
    """``lines``: {line name: [(name, start_ns, end_ns, {stat: str})]}.
    XPlane: id 1, name 2, lines 3, event_metadata 4, stat_metadata 5;
    XLine: id 1, name 2, timestamp_ns 3, events 4; XEvent: metadata_id 1,
    offset_ps 2, duration_ps 3, stats 4; XStat: metadata_id 1, str_value
    5; metadata maps are entries of key 1 and value 2."""
    event_ids, stat_ids = {}, {}
    body = _field(1, pid) + _field(2, name)
    for lid, (line_name, events) in enumerate(lines.items(), 1):
        line = _field(1, lid) + _field(2, line_name) + _field(3, 0)
        for ev_name, start, end, stats in events:
            mid = event_ids.setdefault(ev_name, len(event_ids) + 1)
            ev = (_field(1, mid) + _field(2, start * 1000)
                  + _field(3, (end - start) * 1000))
            for key, val in stats.items():
                sid = stat_ids.setdefault(key, len(stat_ids) + 1)
                ev += _field(4, _field(1, sid) + _field(5, val))
            line += _field(4, ev)
        body += _field(3, line)
    for ev_name, mid in event_ids.items():
        body += _field(4, _field(1, mid) + _field(2, _field(1, mid)
                                                  + _field(2, ev_name)))
    for key, sid in stat_ids.items():
        body += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                  + _field(2, key)))
    return body


def xspace(host_lines, ops, modules):
    return (_field(1, _plane(1, '/host:CPU', host_lines))
            + _field(1, _plane(2, '/device:TPU:0',
                               {'XLA Ops': ops, 'XLA Modules': modules})))


def span(cause, start, end, **stats):
    return ('skytpu:' + cause, start, end, stats)


# The engine thread, ns: waits 0-100; a turn 100-400 whose decode enqueue
# (150-300) holds an eager split (160-170), an upload (170-180), a
# dispatch (180-290) and an eager slice (292-296); route 350-380; waits
# 400-600. Each program handed over is a ``PjitFunction``. The chip runs
# 185-330 and 620-700, so of the engine thread's 0-600 it idles 0-185 and
# 330-600.
HOST = {'engine': [
    span('wait_work', 0, 100),
    span('decode_enqueue', 150, 300, horizon='8'),
    span('eager', 160, 170, site='rng_split'),
    ('PjitFunction(_threefry_split)', 161, 163, {}),
    span('upload', 170, 180),
    span('dispatch', 180, 290),
    ('PjitFunction(decode_steps)', 181, 289, {}),
    ('TpuExecutable::ExecutePrepare', 182, 288, {}),
    span('eager', 292, 296, site='token_slice'),
    ('PjitFunction(dynamic_slice)', 293, 295, {}),
    span('route_events', 350, 380),
    span('wait_work', 400, 600)],
    'handler': [('skytpu:readback', 0, 700, {})]}
OPS = [('fusion.1', 185, 330, {}), ('fusion.2', 620, 700, {})]
MODULES = [('jit__threefry_split(11)', 175, 184, {}),
           ('jit_decode_steps(22)', 185, 330, {}),
           ('jit_dynamic_slice(33)', 620, 700, {})]


def traced(tmp_path, host, ops=OPS, modules=MODULES):
    folder = tmp_path / 'plugins' / 'profile' / 'run'
    folder.mkdir(parents=True)
    (folder / 'host.xplane.pb').write_bytes(xspace(host, ops, modules))
    import jax
    data = jax.profiler.ProfileData.from_file(
        str(folder / 'host.xplane.pb'))
    return str(tmp_path), data


def test_innermost_cause_of_each_instant(tmp_path):
    _, data = traced(tmp_path, HOST)
    line = loop_account.engine_line(data)
    assert [ev[2] for ev in line][:2] == ['skytpu:wait_work',
                                         'skytpu:decode_enqueue']
    pieces = loop_account.innermost(line)
    assert pieces == [
        (0, 100, 'wait_work'), (100, 150, 'loop'),
        (150, 160, 'decode_enqueue'), (160, 170, 'eager:rng_split'),
        (170, 180, 'upload'), (180, 290, 'dispatch'),
        (290, 292, 'decode_enqueue'), (292, 296, 'eager:token_slice'),
        (296, 300, 'decode_enqueue'), (300, 350, 'loop'),
        (350, 380, 'route_events'), (380, 400, 'loop'),
        (400, 600, 'wait_work')]


def test_idle_split_and_host_idle_share(tmp_path):
    trace_dir, data = traced(tmp_path, HOST)
    split = loop_account.idle_by_cause(data)
    want = {'wait_work': 100e-9 + 200e-9, 'loop': 50e-9 + 20e-9 + 20e-9,
            'decode_enqueue': 10e-9, 'eager:rng_split': 10e-9,
            'upload': 10e-9, 'dispatch': 5e-9, 'route_events': 30e-9}
    assert split.keys() == want.keys()
    for cause, s in want.items():
        assert split[cause] == pytest.approx(s), cause
    run, logged = run_with({}, {}, trace_dir=trace_dir,
                           trace=types.SimpleNamespace(window_s=700e-9))
    host_idle = reader('host_idle_share').read(run)
    assert host_idle == pytest.approx(100 * 155e-9 / 700e-9)
    # never above idle_share: 1 - busy / window
    assert host_idle <= 100 * (1 - 225 / 700)
    text = '\n'.join(logged)
    assert 'wait_work 0.000000' in text and 'eager-op executions' in text
    # the eager executions (the split at 175, the slice at 620), each by
    # the site of the span its dispatch lies in
    assert loop_account.eager_executions(data) == {'rng_split': 1,
                                                   'token_slice': 1}
    assert 'ExecutePrepare on the engine thread by cause: dispatch' in text
    assert 'traced time by innermost cause: wait_work 0.000000' in text


def test_eager_execution_outside_a_span_or_before_its_end(tmp_path):
    """An eager op handed over between spans is ``unnamed``; one that
    ran before its span ended is counted apart. The device's first
    execution came from before the trace: the alignment drops it."""
    host = {'engine': HOST['engine'] + [
        ('PjitFunction(squeeze)', 310, 312, {})]}
    _, data = traced(tmp_path, host, modules=[
        ('jit_prefill(5)', 2, 90, {}),
        ('jit__threefry_split(11)', 165, 168, {}),
        ('jit_decode_steps(22)', 185, 330, {}),
        ('jit_dynamic_slice(33)', 332, 334, {}),
        ('jit_squeeze(7)', 335, 340, {})])
    assert loop_account.eager_executions(data) == {
        'inside_its_span': 1, 'token_slice': 1, 'unnamed': 1}


def test_a_parents_trace_reads_none(tmp_path):
    host = {'engine': [span('decode_enqueue', 150, 300, horizon='8'),
                       span('dispatch', 180, 290)]}
    trace_dir, data = traced(tmp_path, host)
    assert loop_account.engine_line(data) is None
    assert loop_account.idle_by_cause(data) is None
    run, logged = run_with({}, {}, trace_dir=trace_dir,
                           trace=types.SimpleNamespace(window_s=1.0))
    assert reader('host_idle_share').read(run) is None and not logged


def test_real_trace_of_a_parent_reads_none():
    """The CPU trace kept beside these tests, from a program before the
    account: no ``skytpu:wait_work``, so nothing to read."""
    import gzip
    import jax
    with gzip.open(os.path.join(HERE, 'data', 'tiny.xplane.pb.gz')) as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    assert loop_account.idle_by_cause(data) is None
    assert loop_account.eager_executions(data) is None
