"""The ``graftcheck`` console entry point.

Subcommands:

- ``graftcheck lint [paths...]`` (default when omitted) — AST lint;
  exit 1 on violations not covered by the baseline or inline
  suppressions. ``--update-baseline`` rewrites the baseline from the
  current violations (review before committing).
- ``graftcheck audit [--preset paged|paged-spec|telemetry|kv-int8|
  llama|...]`` (``jaxpr_audit.PRESETS``) —
  runtime jaxpr audit of the engine's hot loops, including the
  speculative propose→verify→commit steady state and the int8-KV
  (``kv_cache_dtype='int8'`` over bf16 weights) quantize-on-write path
  (requires jax); exit 1 on unsanctioned host transfers, steady-state
  recompiles, callback primitives, float64 promotions, or byte-budget
  violations.
- ``graftcheck costmodel [--preset ...]`` — static per-dispatch cost
  attribution (HBM bytes by operand class, FLOPs, collectives) for a
  preset's captured steady-state dispatches, checked against the
  preset's declared byte budget.
- ``graftcheck rules`` — list the rule set.

``lint``, ``audit`` and ``costmodel`` all take ``--json`` for
machine-readable output (schema: docs/analysis.md#graftcheck-json).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional


def _violation_json(v) -> Dict[str, Any]:
    return {'rule': v.rule, 'path': v.path, 'line': v.line,
            'col': v.col, 'func': v.func, 'message': v.message,
            'source': v.source}


def _cmd_lint(args: argparse.Namespace) -> int:
    from skypilot_tpu.analysis import lint
    baseline = lint.load_baseline(args.baseline)
    new, old = lint.lint_paths(args.paths or None, baseline=baseline)
    if args.update_baseline:
        path = lint.write_baseline(new + old, args.baseline)
        print(f'graftcheck: baseline with {len(new) + len(old)} '
              f'fingerprint(s) written to {path}')
        return 0
    if getattr(args, 'json', False):
        print(json.dumps({
            'ok': not new,
            'violations': [_violation_json(v) for v in
                           sorted(new, key=lambda v: (v.path, v.line))],
            'baselined': len(old),
        }, indent=1, sort_keys=True))
        return 1 if new else 0
    for v in sorted(new, key=lambda v: (v.path, v.line)):
        print(v.format())
    stale = baseline - {v.fingerprint for v in old}
    if stale and args.verbose:
        print(f'note: {len(stale)} baseline entr(ies) no longer match '
              'any violation — prune with --update-baseline')
    print(f'graftcheck lint: {len(new)} violation(s), '
          f'{len(old)} baselined')
    return 1 if new else 0


def _split_presets(names: List[str]):
    """(local, remote) preset split: multi-device presets run in a
    re-exec'd subprocess with a forced virtual CPU device count when
    this process is short on devices (the env must be set before jax
    initializes — this process's backend is already pinned)."""
    import jax

    from skypilot_tpu.analysis import jaxpr_audit
    local = [n for n in names
             if jax.device_count()
             >= jaxpr_audit.MULTI_DEVICE_PRESETS.get(n, 1)]
    return local, [n for n in names if n not in local]


def _reexec(subcmd: str, remote: List[str],
            want_json: bool) -> 'subprocess.CompletedProcess':
    import os
    import subprocess

    from skypilot_tpu.analysis import jaxpr_audit
    n_dev = max(jaxpr_audit.MULTI_DEVICE_PRESETS[n] for n in remote)
    env = dict(os.environ)
    env['XLA_FLAGS'] = (env.get('XLA_FLAGS', '') +
                        f' --xla_force_host_platform_device_count='
                        f'{n_dev}').strip()
    env['JAX_PLATFORMS'] = 'cpu'
    cmd = [sys.executable, '-m', 'skypilot_tpu.analysis.cli',
           subcmd] + [x for n in remote for x in ('--preset', n)]
    if want_json:
        cmd.append('--json')
        return subprocess.run(cmd, env=env, capture_output=True,
                              text=True)
    print(f'graftcheck {subcmd}: re-exec for {remote} on a '
          f'{n_dev}-device virtual CPU mesh')
    return subprocess.run(cmd, env=env)


def _cmd_audit(args: argparse.Namespace) -> int:
    from skypilot_tpu.analysis import jaxpr_audit
    try:
        import jax  # noqa: F401
    except ImportError:
        print('graftcheck audit requires jax (the compute extra)')
        return 2
    want_json = getattr(args, 'json', False)
    names = args.preset or list(jaxpr_audit.DEFAULT_PRESETS)
    local, remote = _split_presets(names)
    rc = 0
    reports: List[Dict[str, Any]] = []
    for rep in jaxpr_audit.run_presets(local) if local else []:
        if want_json:
            reports.append(rep.to_json())
        else:
            print(rep.format())
        if not rep.ok():
            rc = 1
    if remote:
        proc = _reexec('audit', remote, want_json)
        rc = rc or proc.returncode
        if want_json:
            try:
                reports.extend(json.loads(proc.stdout)['reports'])
            except (json.JSONDecodeError, KeyError):
                reports.append({'name': f're-exec {remote}',
                                'ok': False,
                                'error': proc.stderr[-2000:]})
                rc = rc or 1
    if want_json:
        print(json.dumps({'ok': rc == 0, 'reports': reports},
                         indent=1, sort_keys=True))
    return rc


def _cmd_costmodel(args: argparse.Namespace) -> int:
    from skypilot_tpu.analysis import costmodel, jaxpr_audit
    try:
        import jax  # noqa: F401
    except ImportError:
        print('graftcheck costmodel requires jax (the compute extra)')
        return 2
    want_json = getattr(args, 'json', False)
    names = args.preset or list(jaxpr_audit.DEFAULT_PRESETS)
    local, remote = _split_presets(names)
    rc = 0
    presets: Dict[str, Any] = {}
    for name in local:
        costs, violations = costmodel.preset_costs(name)
        if violations:
            rc = 1
        if want_json:
            presets[name] = {
                'dispatches': {k: c.to_json()
                               for k, c in costs.items()},
                'byte_budget': costmodel.budget_for(name) or {},
                'violations': violations,
            }
            continue
        print(f'=== costmodel [{name}] ===')
        if not costs:
            print('  (no dispatch captured)')
        for _label, cost in sorted(costs.items()):
            print(cost.format_table())
        for v in violations:
            print(f'  BYTE BUDGET: {v}')
    if remote:
        proc = _reexec('costmodel', remote, want_json)
        rc = rc or proc.returncode
        if want_json:
            try:
                presets.update(json.loads(proc.stdout)['presets'])
            except (json.JSONDecodeError, KeyError):
                presets[f're-exec {remote}'] = {
                    'violations': [proc.stderr[-2000:]]}
                rc = rc or 1
    if want_json:
        print(json.dumps({'ok': rc == 0, 'presets': presets},
                         indent=1, sort_keys=True))
    return rc


def _cmd_rules(_args: argparse.Namespace) -> int:
    from skypilot_tpu.analysis import rules as rules_lib
    for rule, desc in sorted(rules_lib.RULES.items()):
        print(f'{rule}  {desc}')
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog='graftcheck',
        description='skypilot-tpu static analysis + jaxpr audit')
    sub = parser.add_subparsers(dest='cmd')

    p_lint = sub.add_parser('lint', help='AST lint (GC1xx/GC2xx rules)')
    p_lint.add_argument('paths', nargs='*',
                        help='files/dirs (default: the whole package)')
    p_lint.add_argument('--baseline', default=None,
                        help='baseline file (default: '
                             'graftcheck.baseline at the repo root)')
    p_lint.add_argument('--update-baseline', action='store_true',
                        help='rewrite the baseline from current '
                             'violations')
    p_lint.add_argument('--json', action='store_true',
                        help='machine-readable output')
    p_lint.add_argument('-v', '--verbose', action='store_true')

    p_audit = sub.add_parser('audit',
                             help='runtime jaxpr audit of engine hot '
                                  'loops (requires jax)')
    # Choices come from the preset registry (importable without jax)
    # so new presets are runnable from the CLI the day they land.
    from skypilot_tpu.analysis import jaxpr_audit
    p_audit.add_argument('--preset', action='append',
                         choices=sorted(jaxpr_audit.PRESETS),
                         help='repeatable; default: '
                              'jaxpr_audit.DEFAULT_PRESETS')
    p_audit.add_argument('--json', action='store_true',
                         help='machine-readable output')

    p_cost = sub.add_parser('costmodel',
                            help='static per-dispatch byte/FLOP/'
                                 'collective attribution (requires '
                                 'jax)')
    p_cost.add_argument('--preset', action='append',
                        choices=sorted(jaxpr_audit.PRESETS),
                        help='repeatable; default: all default audit '
                             'presets')
    p_cost.add_argument('--json', action='store_true',
                        help='machine-readable output')

    sub.add_parser('rules', help='list the rule set')

    args = parser.parse_args(argv)
    if args.cmd == 'audit':
        return _cmd_audit(args)
    if args.cmd == 'costmodel':
        return _cmd_costmodel(args)
    if args.cmd == 'rules':
        return _cmd_rules(args)
    if args.cmd is None:
        args = parser.parse_args(['lint'] + (argv or []))
    return _cmd_lint(args)


if __name__ == '__main__':
    sys.exit(main())
