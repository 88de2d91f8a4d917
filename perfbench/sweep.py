"""Find a serving mix's knee, once, on the chip: one server, one window
for each offered rate, rising. Not part of a run: its result is written
into the traffic file as a number, by hand.

    python3 perfbench/sweep.py --workload <cell> --rates 4,5,6 --seconds 20

The knee is the highest rate at which the mix's ``knee.share_meeting`` of
the requests have TTFT and TPOT within its limits and the backlog at the
window's end is no deeper than at its middle.
"""
import time
T_START = time.time()

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import statistics               # noqa: E402
import sys                      # noqa: E402
import types                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [os.path.dirname(HERE)] + [
    p for p in sys.path if os.path.abspath(p or '.') != HERE]

from perfbench import run as run_mod  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=20)
    ap.add_argument('--seed', type=int, default=1)
    args = ap.parse_args()
    _, cell, config, mix = run_mod.find_cell(args.workload)
    run_mod.use_compile_cache()
    workdir = os.path.join(run_mod.REPO, '.perfbench_work', 'sweep')
    os.makedirs(workdir, exist_ok=True)
    ctx = types.SimpleNamespace(
        root=HERE, cell=cell, mix=mix, seed=args.seed, workdir=workdir,
        config=config, t_start=T_START, log=run_mod.log)
    serve = run_mod.load_module(os.path.join(HERE, 'runners', 'serve.py'))
    _, _, srv, watch = serve.setup(ctx)
    knee = mix['knee']
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(',')):
            rec = serve.run_window(ctx, srv, watch, seed=args.seed + i,
                                   seconds=args.seconds, rate_per_s=rate,
                                   sample=True)
            out = serve.reduce_window(rec, lambda _msg: None)
            meet = sum(t <= knee['ttft_limit_ms'] and p <= knee[
                'tpot_limit_ms'] for t, p in zip(out['ttft_ms'],
                                                 out['tpot_ms']))
            depth = [(s['t'], s['queue_depth'] + s['active_slots'])
                     for s in rec['samples'] if 'error' not in s]
            mid_t = rec['t0'] + rec['seconds'] / 2
            mid = [d for t, d in depth if abs(t - mid_t) <= 1.5]
            end = [d for t, d in depth if t >= rec['t0'] + rec['seconds']
                   - 3.0]
            print(json.dumps({
                'rate_per_s': rate, 'attempted': out['attempted'],
                'failed': out['failed'],
                'share_meeting': round(meet / out['attempted'], 3),
                'ttft_p50_ms': round(out['ttft_p50_ms'], 1),
                'ttft_p95_ms': round(out['ttft_p95_ms'], 1),
                'tpot_p50_ms': round(out['tpot_p50_ms'], 2),
                'tpot_p95_ms': round(out['tpot_p95_ms'], 2),
                'out_tok_s': round(out['out_tok_s'], 1),
                'in_system_mid': round(statistics.fmean(mid), 1),
                'in_system_end': round(statistics.fmean(end), 1),
                'compiles_in_window': rec['compiles_in_window'],
                'compiles_by_drain': rec['compiles_by_drain'],
                'unwarmed': rec['unwarmed'],
                'late_max_ms': round(max((r['sent'] - r['due']) * 1e3
                                         for r in rec['requests']), 1),
                'preemptions': rec['metrics_end']['kv_pool_preemptions'],
            }), flush=True)
    finally:
        srv.stop()
    return 0


if __name__ == '__main__':
    sys.exit(main())
