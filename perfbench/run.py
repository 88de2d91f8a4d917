"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name, never by an edit
here: the cell in ``BENCHMARK.json``, its configuration in
``configs/<config>.json``, its traffic mix in ``traffic/<mix>.json`` (which
names the runner), and the per-layer metrics by listing
``layer_metrics/``. The last line of standard output is the result; every
line before it is for the reader. Without a TPU, or on a device that
``peaks.json`` does not know, it exits non-zero and prints no result.
"""
import time
T_START = time.time()           # set-up counts from here

import argparse                 # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402
import types                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
# The repo's root in place of this directory: perfbench.* and the program
# import from there, and perfbench/trace.py cannot shadow the stdlib's.
sys.path[:] = [REPO] + [p for p in sys.path if os.path.abspath(p or '.')
                        not in (HERE, REPO)]


def log(msg: str) -> None:
    print(f'[perfbench +{time.time() - T_START:6.1f}s] {msg}', flush=True)


def load_json(*parts):
    with open(os.path.join(*parts), encoding='utf-8') as f:
        return json.load(f)


def load_module(path: str):
    name = 'perfbench_' + os.path.basename(path)[:-3].replace('.', '_')
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(workload: str):
    """(benchmark, cell, configuration, traffic mix) by the cell's name;
    the cell is None when BENCHMARK.json has no such workload."""
    bench = load_json(REPO, 'BENCHMARK.json')
    cell = next((w for w in bench['workloads'] if w['name'] == workload),
                None)
    if cell is None:
        return bench, None, None, None
    entry = next(c for c in bench['configs'] if c['name'] == cell['config'])
    return (bench, cell, load_json(REPO, entry['file']),
            load_json(HERE, 'traffic', cell['traffic'] + '.json'))


def use_compile_cache() -> None:
    """Where the machine says, else a fixed path in the checkout (the
    path is part of the cache's key). Call before JAX is imported."""
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR',
                          os.path.join(REPO, '.perfbench_cache', 'jax'))
    import jax
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)


def device_info():
    """The device as JAX reports it (``telemetry/device.py``)."""
    from skypilot_tpu.telemetry import device as device_lib
    ident = device_lib.device_identity()
    return {'platform': ident['platform'], 'kind': ident['device_kind'],
            'count': ident['device_count']}


def memory_peak_bytes() -> int:
    from skypilot_tpu.telemetry import device as device_lib
    return max(max(m['peak_bytes_in_use'], m['bytes_in_use'])
               for m in device_lib.device_memory())


def layer_readers(cell_name: str):
    """Every reader under ``layer_metrics/`` that lists this cell."""
    folder = os.path.join(HERE, 'layer_metrics')
    for fname in sorted(os.listdir(folder)):
        if fname.endswith('.py'):
            mod = load_module(os.path.join(folder, fname))
            if mod.CELLS is None or cell_name in mod.CELLS:
                yield fname[:-3], mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    bench, cell, config, mix = find_cell(args.workload)
    if cell is None:
        print(f'unknown workload {args.workload!r}', file=sys.stderr)
        return 2
    use_compile_cache()
    device = device_info()
    peaks = load_json(HERE, 'peaks.json')['devices']
    if device['platform'] != 'tpu' or device['count'] < cell['chips']:
        print(f'needs {cell["chips"]} TPU chip(s), found {device}',
              file=sys.stderr)
        return 3
    if device['kind'] not in peaks:
        print(f'device kind {device["kind"]!r} is not in peaks.json',
              file=sys.stderr)
        return 3
    log(f'cell {cell["name"]} seed {args.seed} seconds {args.seconds} '
        f'trace {args.trace} on {device}; compile cache '
        f'{os.environ["JAX_COMPILATION_CACHE_DIR"]}')

    workdir = os.path.join(REPO, '.perfbench_work', cell['name'])
    os.makedirs(workdir, exist_ok=True)
    ctx = types.SimpleNamespace(
        root=HERE, cell=cell, config=config, mix=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        workdir=workdir, peak=peaks[device['kind']], device=device,
        log=log)
    runner = load_module(os.path.join(HERE, 'runners',
                                      mix['runner'] + '.py'))
    run = runner.run(ctx)
    run['ctx'] = ctx

    out_device = dict(device, count=cell['chips'],
                      memory_peak_bytes=memory_peak_bytes())
    result = {'correct': bool(run['correct']),
              'attempted': int(run['attempted']),
              'failed': int(run['failed']), 'metrics': {},
              'device': out_device}
    if not args.trace:
        for m in bench['end_to_end']:
            if cell['name'] in m.get('workloads', [cell['name']]):
                result['metrics'][m['name']] = {
                    'value': float(run[m['name']]), 'unit': m['unit']}
    else:
        from perfbench import trace as trace_lib
        xplane = trace_lib.find_xplane(run['trace_dir'])
        run['trace'] = (trace_lib.reduce_xplane(xplane)
                        if xplane else None)
        if run['trace'] is None:
            print('the traced run holds no device operation',
                  file=sys.stderr)
            return 4
        tr = run['trace']
        by_program = sorted(((sum(e.duration_s for e in ex), len(ex), name)
                             for name, ex in tr.programs.items()),
                            reverse=True)[:6]
        log('device time by program in the traced part: ' + ', '.join(
            f'{name} {s:.3f}s/{n}' for s, n, name in by_program))
        out_device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result['breakdown'] = {
            'device_ops': [[n, s] for n, s in tr.top_ops],
            'idle_gaps': [[n, s] for n, s in tr.idle_gaps]}
        for name, mod in layer_readers(cell['name']):
            value = mod.read(run)
            if value is not None:
                result['metrics'][name] = {'value': float(value),
                                           'unit': mod.UNIT}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
