"""The readers of PR 26 against hand-made ``run`` dicts, and the pairing of
dispatch annotations with device executions (``host_plane.py``) against
hand-made lines. A run of a program without the new block, counters or
annotations (the parent) gives ``None`` everywhere and raises nothing."""
import os

import pytest

from perfbench import host_plane
from perfbench.run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = os.path.join(os.path.dirname(HERE), 'layer_metrics')
STAGE_OF = {'sched_wait_p95_ms': 'sched_wait',
            'prefill_span_p95_ms': 'prefill',
            'first_token_lag_p95_ms': 'first_token_lag',
            'emit_first_p95_ms': 'emit_first'}


def reader(name):
    return load_module(os.path.join(READERS, name + '.py'))


def run_with(start=None, end=None, **extra):
    return dict({'records': {'metrics_start': start or {},
                             'metrics_end': end or {}}}, **extra)


def loop(clock_s, held, substeps, rows):
    return {'engine_loop': {
        'clock_s': clock_s, 'lock_held_seconds_total': held,
        'lock_wait_seconds_total': 0.0,
        'decode_substeps_total': substeps, 'decode_live_rows_total': rows}}


@pytest.mark.parametrize('name', sorted(STAGE_OF))
def test_stage_reader_takes_its_stage_p95(name):
    stages = {s: {'p50': 1.0 + i, 'p95': 10.0 * (i + 1), 'n': 7}
              for i, s in enumerate(STAGE_OF.values())}
    mod = reader(name)
    assert mod.read(run_with(end={'ttft_stages': stages})) == \
        stages[STAGE_OF[name]]['p95']
    # no request through the stage yet, or a program without the block
    stages[STAGE_OF[name]]['n'] = 0
    assert mod.read(run_with(end={'ttft_stages': stages})) is None
    assert mod.read(run_with(end={'queue_wait_ms_median': 3.0})) is None


def test_engine_lock_held_share_is_held_over_the_scrapes_clock():
    mod = reader('engine_lock_held_share')
    run = run_with(start=loop(100.0, 2.0, 0, 0), end=loop(151.5, 50.925, 0, 0))
    assert mod.read(run) == pytest.approx(100 * 48.925 / 51.5)
    assert mod.read(run_with()) is None
    assert mod.read(run_with(start=loop(5.0, 0, 0, 0),
                             end=loop(5.0, 0, 0, 0))) is None


def test_decode_live_rows_mean_is_rows_over_substeps():
    mod = reader('decode_live_rows_mean')
    run = run_with(start=loop(0, 0, 800, 1600), end=loop(51, 0, 4000, 32320))
    assert mod.read(run) == pytest.approx(30720 / 3200)
    assert mod.read(run_with(start=loop(0, 0, 8, 8),
                             end=loop(1, 0, 8, 8))) is None
    assert mod.read(run_with()) is None


def test_dispatch_lag_reader_without_a_trace():
    mod = reader('prefill_dispatch_lag_ms')
    assert mod.read(run_with(trace_dir=None)) is None
    assert mod.read(run_with(trace_dir=os.path.join(HERE, 'no_such'))) is None


MS = 1_000_000                  # ns
A, B = (('pages', 2), ('prompts', 1)), (('pages', 4), ('prompts', 2))
FA, FB = 'jit_prefill(111)', 'jit_prefill(222)'


def lags_ms(pairs):
    return [(r[0] - d[1]) / MS for d, r in pairs]


def test_pairing_in_dispatch_order():
    disp = [(10 * MS, 12 * MS, A), (100 * MS, 103 * MS, B),
            (200 * MS, 202 * MS, A)]
    runs = [(60 * MS, 95 * MS, FA), (150 * MS, 185 * MS, FB),
            (290 * MS, 330 * MS, FA)]
    assert lags_ms(host_plane.pair_in_order(disp, runs)) == [48, 47, 88]


def test_pairing_drops_executions_dispatched_before_the_trace():
    """The first two executions were dispatched before the trace began.
    The second of them even starts AFTER the first annotation returned,
    so the clock alone would pair it: its program does not fit the keys
    all along. The last dispatch's execution lies beyond the trace."""
    disp = [(100 * MS, 103 * MS, A), (200 * MS, 202 * MS, B),
            (300 * MS, 301 * MS, A), (400 * MS, 401 * MS, B)]
    runs = [(5 * MS, 40 * MS, FB), (110 * MS, 145 * MS, FB),
            (150 * MS, 185 * MS, FA), (290 * MS, 330 * MS, FB),
            (390 * MS, 425 * MS, FA)]
    pairs = host_plane.pair_in_order(disp, runs)
    assert pairs == list(zip(disp[:3], runs[2:]))
    assert lags_ms(pairs) == [47, 88, 89]


def test_pairing_is_void_when_nothing_fits():
    # an execution starts before its dispatch returned, at every offset
    disp = [(100 * MS, 103 * MS, A), (200 * MS, 260 * MS, A)]
    runs = [(150 * MS, 185 * MS, FA), (250 * MS, 290 * MS, FA)]
    assert host_plane.pair_in_order(disp, runs) is None
    assert host_plane.pair_in_order([], runs) is None
    assert host_plane.pair_in_order(disp, []) is None


# ------------------------------------------------------ the recorded trace
@pytest.fixture(scope='module')
def recorded():
    """``data/chat_dispatch.xplane.pb.gz``: the traced 3.96 s of a
    ``qwen2-7b.chat`` run on the v5e (seed 2147484101; my chip run, PR
    26), cut to the ``skytpu:`` events of the host plane and the chip's
    ``XLA Modules`` line."""
    import gzip

    import jax
    with gzip.open(os.path.join(HERE, 'data',
                                'chat_dispatch.xplane.pb.gz')) as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


def test_recorded_trace_holds_the_loops_phases_and_named_programs(recorded):
    phases = {e.name for p in recorded.planes if p.name.startswith('/host:')
              for ln in p.lines for e in ln.events}
    assert phases == {'skytpu:' + name for name in (
        'lock_wait', 'fill_engine', 'readback', 'admit', 'admit_upload',
        'prefill_chunk', 'admit_token_merge', 'decode_enqueue',
        'route_events')}
    for program, count in (('prefill', 44), ('decode_steps', 24),
                           ('merge_ring_into_pool', 23), ('_unknown', 0)):
        assert len(host_plane.executions(recorded, program)) == count


def test_recorded_dispatch_lag(recorded):
    """The first four prefill executions of the trace precede the first
    annotation (dispatched before the trace began); the last two
    dispatches' executions lie beyond its end."""
    disp = host_plane.annotations(recorded, 'prefill_chunk')
    runs = host_plane.executions(recorded, 'prefill')
    assert (len(disp), len(runs)) == (42, 44)
    assert sum(r[0] < disp[0][0] for r in runs) == 4
    assert dict(disp[0][2]) == {'prompts': 1, 'pages': 1, 'width': 128}
    pairs = host_plane.pair_in_order(disp, runs)
    assert pairs == list(zip(disp[:40], runs[4:]))
    assert len({d[2] for d, _ in pairs}) == 8       # program keys met
    assert host_plane.dispatch_lag_ms(
        recorded, 'prefill_chunk', 'prefill') == pytest.approx(133.2317,
                                                                abs=1e-3)
    lags = lags_ms(pairs)
    assert 99 < min(lags) and max(lags) < 306
    # a trace that begins later: more executions precede its first
    # annotation, some of them after that annotation returned
    later = host_plane.pair_in_order(disp[7:], runs)
    assert later == pairs[7:]
    # no annotations (the parent's trace), or another program's
    assert host_plane.dispatch_lag_ms(recorded, 'no_such_phase',
                                      'prefill') is None
    assert host_plane.dispatch_lag_ms(recorded, 'prefill_chunk',
                                      'no_such_program') is None
