"""What the readers of a dense serving cell share: the pool's tokens
over the window's ``/metrics`` samples (``loadgen.py`` takes them every
0.25 s of a traced run) and the decode steps the traced part holds.
Every function returns None where there is nothing to read, and never
raises."""
import statistics

from perfbench import host_plane, trace


def tokens_used(run):
    """``kv_pool_tokens_used`` of every sample: page-granular."""
    return [s['kv_pool_tokens_used']
            for s in run['records'].get('samples', [])
            if 'error' not in s and 'kv_pool_tokens_used' in s]


def live_tokens_mean(run):
    used = tokens_used(run)
    return statistics.fmean(used) if used else None


def _pair(dispatches, runs):
    """The k-th dispatch with the k-th execution it caused, as
    ``host_plane.pair_in_order`` does, but an execution need only start
    after its dispatch BEGAN: the annotation also covers the dispatch of
    the ring merge, and on an idle chip the steps start before it ends."""
    most = min(len(dispatches), len(runs))
    for drop in range(len(runs)):
        pairs = list(zip(dispatches, runs[drop:]))
        if 2 * len(pairs) <= most:
            break
        if host_plane._one_to_one(pairs) and all(
                r[0] >= d[0] for d, r in pairs):
            return pairs
    return None


def decode_step_ms(run):
    """Median device time of one fused decode step. The steps of an
    execution are the ``horizon`` its dispatch was tagged with (the
    ``skytpu:decode_enqueue`` annotation, paired in dispatch order),
    where the trace holds such a pairing; else the loops one level
    inside it (``trace.per_step_ms``). The loops alone miscount a
    looped model's short calls: under pool pressure the engine halves
    the horizon, XLA unrolls a 2-step horizon loop, and the four pass
    loops of each step are then counted as four steps (a share of the
    roofline read 260 % so; my chip run, PR 35)."""
    data = host_plane.load(run.get('trace_dir'))
    pairs = None if data is None else _pair(
        [d for d in host_plane.annotations(data, 'decode_enqueue')
         if 'horizon' in dict(d[2])],
        host_plane.executions(data, 'decode_steps'))
    if not pairs:
        return trace.per_step_ms(run['trace'], 'decode_steps')
    return statistics.median(
        (r[1] - r[0]) / 1e6 / int(dict(d[2])['horizon']) for d, r in pairs)


def traced_steps(run):
    """Fused decode steps of ALL the ``decode_steps`` executions traced:
    their device time over the step's."""
    step_ms = decode_step_ms(run)
    if not step_ms:
        return None
    return sum(e.duration_s for e in
               run['trace'].programs.get('decode_steps', [])) * 1e3 / step_ms
