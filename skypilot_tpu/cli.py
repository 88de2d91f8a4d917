"""Command-line interface: ``skytpu`` / ``python -m skypilot_tpu.cli``.

Role of reference ``sky/cli.py`` (5.5k LoC of click commands): the same
verb surface — launch/exec/status/start/stop/down/autostop/queue/logs/
cancel/check/cost-report/optimize, plus the ``jobs`` and ``serve``
subcommand groups and the accelerator-catalog browser (``show-tpus``,
the TPU-first counterpart of ``sky show-gpus`` ``sky/cli.py:3085``).
Every command is a thin shell over the SDK in ``skypilot_tpu.core`` /
``execution`` / ``jobs.core`` / ``serve.core`` — the CLI owns parsing,
confirmation prompts, and table rendering only.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import click

import skypilot_tpu as sky
from skypilot_tpu import exceptions
from skypilot_tpu.task import Task


# ------------------------------------------------------------------ helpers
def _fmt_table(rows: List[List[str]], headers: List[str]) -> str:
    widths = [len(h) for h in headers]
    for row in rows:
        for i, cell in enumerate(row):
            widths[i] = max(widths[i], len(str(cell)))
    fmt = '  '.join(f'{{:<{w}}}' for w in widths)
    lines = [fmt.format(*headers)]
    lines += [fmt.format(*[str(c) for c in row]) for row in rows]
    return '\n'.join(lines)


def _fmt_age(ts: Optional[float]) -> str:
    if not ts:
        return '-'
    secs = max(0, time.time() - ts)
    for unit, div in (('d', 86400), ('h', 3600), ('m', 60)):
        if secs >= div:
            return f'{int(secs // div)}{unit} ago'
    return f'{int(secs)}s ago'


def _load_task(entrypoint: Optional[str],
               env: Tuple[str, ...] = (),
               name: Optional[str] = None) -> Task:
    """YAML path -> Task; no entrypoint -> empty (provision-only) task.

    --env overrides are merged into the YAML's ``envs:`` BEFORE the Task
    is constructed, so ``${VAR}`` interpolation anywhere in the config
    (resources, workdir, file_mounts — not just run/setup) sees the
    overridden values."""
    overrides = {}
    for item in env:
        if '=' not in item:
            raise click.UsageError(f'--env must be KEY=VALUE, got {item!r}')
        k, v = item.split('=', 1)
        overrides[k] = v
    if entrypoint is None:
        task = Task(name=name or 'sky-cmd')
        if overrides:
            task.update_envs(overrides)
    else:
        import os

        import yaml
        with open(os.path.expanduser(entrypoint), encoding='utf-8') as f:
            config = yaml.safe_load(f) or {}
        if overrides:
            envs = dict(config.get('envs') or {})
            envs.update(overrides)
            config['envs'] = envs
        task = Task.from_yaml_config(config)
    if name:
        task.name = name
    return task


def _confirm(message: str, yes: bool) -> None:
    if not yes:
        click.confirm(message, abort=True)


@click.group()
@click.version_option(sky.__version__, '--version', '-v')
def cli():
    """skypilot_tpu: run, manage, and serve workloads on TPU slices."""


# ----------------------------------------------------------------- clusters
@cli.command()
@click.argument('entrypoint', required=False, type=click.Path(exists=True))
@click.option('--cluster', '-c', default=None, help='Cluster name.')
@click.option('--dryrun', is_flag=True, help='Print the plan; launch nothing.')
@click.option('--yes', '-y', is_flag=True, help='Skip confirmation.')
@click.option('--detach-run', '-d', is_flag=True,
              help='Submit and return; do not stream job logs.')
@click.option('--idle-minutes-to-autostop', '-i', type=int, default=None,
              help='Autostop after this many idle minutes.')
@click.option('--down', is_flag=True,
              help='Autostop tears the cluster DOWN instead of stopping.')
@click.option('--retry-until-up', is_flag=True,
              help='Keep retrying across zones/regions until provisioned.')
@click.option('--no-setup', is_flag=True, help='Skip the setup phase.')
@click.option('--env', multiple=True, metavar='KEY=VALUE',
              help='Override task env vars (repeatable).')
def launch(entrypoint, cluster, dryrun, yes, detach_run,
           idle_minutes_to_autostop, down, retry_until_up, no_setup, env):
    """Launch a task YAML on a new or existing cluster."""
    task = _load_task(entrypoint, env)
    if not dryrun:
        _confirm(f'Launching task on cluster {cluster or "<new>"}. Proceed?',
                 yes)
    job_id, handle = sky.launch(
        task, cluster_name=cluster, dryrun=dryrun,
        detach_run=detach_run, stream_logs=not detach_run,
        idle_minutes_to_autostop=idle_minutes_to_autostop, down=down,
        retry_until_up=retry_until_up, no_setup=no_setup)
    if dryrun:
        return
    if job_id is not None:
        click.echo(f'Job submitted (id: {job_id}) on cluster '
                   f'{handle.cluster_name}.')


@cli.command(name='exec')
@click.argument('entrypoint', type=click.Path(exists=True))
@click.option('--cluster', '-c', required=True, help='Target cluster.')
@click.option('--detach-run', '-d', is_flag=True)
@click.option('--env', multiple=True, metavar='KEY=VALUE')
def exec_(entrypoint, cluster, detach_run, env):
    """Run a task on an existing cluster (skips provision/setup)."""
    task = _load_task(entrypoint, env)
    job_id, _ = getattr(sky, 'exec')(task, cluster,
                                     detach_run=detach_run)
    click.echo(f'Job submitted (id: {job_id}) on cluster {cluster}.')


@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--refresh', '-r', is_flag=True,
              help='Reconcile against the cloud before printing.')
def status(clusters, refresh):
    """Show clusters (reference ``sky status``)."""
    records = sky.status(list(clusters) or None, refresh=refresh)
    if not records:
        click.echo('No existing clusters.')
        return
    rows = []
    for r in records:
        handle = r.get('handle')
        res = (str(handle.launched_resources)
               if handle is not None and
               getattr(handle, 'launched_resources', None) is not None
               else '-')
        autostop = f"{r['autostop']}m" if r.get('autostop', -1) >= 0 else '-'
        rows.append([r['name'], _fmt_age(r.get('launched_at')), res,
                     r['status'].value, autostop])
    click.echo(_fmt_table(rows, ['NAME', 'LAUNCHED', 'RESOURCES', 'STATUS',
                                 'AUTOSTOP']))


@cli.command()
@click.argument('cluster')
@click.option('--idle-minutes-to-autostop', '-i', type=int, default=None)
@click.option('--retry-until-up', is_flag=True)
def start(cluster, idle_minutes_to_autostop, retry_until_up):
    """Restart a stopped cluster."""
    sky.start(cluster, idle_minutes_to_autostop=idle_minutes_to_autostop,
              retry_until_up=retry_until_up)
    click.echo(f'Cluster {cluster} started.')


@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--all', '-a', 'stop_all', is_flag=True)
@click.option('--yes', '-y', is_flag=True)
def stop(clusters, stop_all, yes):
    """Stop cluster(s) (preserves disk; billing stops for TPU time)."""
    names = _select_clusters(clusters, stop_all, 'stop')
    _confirm(f'Stopping {len(names)} cluster(s): {", ".join(names)}. '
             'Proceed?', yes)
    for name in names:
        sky.stop(name)
        click.echo(f'Cluster {name} stopped.')


@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--all', '-a', 'down_all', is_flag=True)
@click.option('--yes', '-y', is_flag=True)
def down(clusters, down_all, yes):
    """Tear down cluster(s)."""
    names = _select_clusters(clusters, down_all, 'down')
    _confirm(f'Tearing down {len(names)} cluster(s): {", ".join(names)}. '
             'Proceed?', yes)
    for name in names:
        sky.down(name)
        click.echo(f'Cluster {name} terminated.')


_CONTROLLER_CLUSTERS = ('skytpu-jobs-controller', 'skytpu-serve-controller')


def _select_clusters(clusters, select_all: bool, verb: str) -> List[str]:
    if select_all:
        # Control-plane clusters are excluded from --all (killing them
        # orphans managed jobs / serve state); name them explicitly to
        # act on them — same contract as the reference's `sky down -a`.
        names = [r['name'] for r in sky.status()
                 if r['name'] not in _CONTROLLER_CLUSTERS]
        if not names:
            click.echo('No existing clusters.')
            raise SystemExit(0)
        return names
    if not clusters:
        raise click.UsageError(f'Specify cluster(s) to {verb}, or --all.')
    return list(clusters)


@cli.command()
@click.argument('cluster')
@click.option('--idle-minutes', '-i', type=int, default=5,
              help='Idle minutes before autostop.')
@click.option('--down', is_flag=True,
              help='Tear down instead of stopping when idle.')
@click.option('--cancel', is_flag=True, help='Disable autostop.')
def autostop(cluster, idle_minutes, down, cancel):
    """Arm (or cancel) idle autostop on a cluster."""
    sky.autostop(cluster, -1 if cancel else idle_minutes, down=down)
    if cancel:
        click.echo(f'Autostop cancelled on {cluster}.')
    else:
        click.echo(f'{cluster}: autostop after {idle_minutes} idle '
                   f'minute(s) ({"down" if down else "stop"}).')


@cli.command()
@click.argument('cluster')
def queue(cluster):
    """Show a cluster's job queue."""
    jobs = sky.queue(cluster)
    if not jobs:
        click.echo(f'No jobs on {cluster}.')
        return
    rows = [[j['job_id'], j.get('name') or '-',
             _fmt_age(j.get('submitted_at')), j['status']]
            for j in jobs]
    click.echo(_fmt_table(rows, ['ID', 'NAME', 'SUBMITTED', 'STATUS']))


@cli.command()
@click.argument('cluster')
@click.argument('job_id', type=int)
@click.option('--no-follow', is_flag=True, help='Print and exit.')
def logs(cluster, job_id, no_follow):
    """Tail a job's logs."""
    sky.tail_logs(cluster, job_id, follow=not no_follow)


@cli.command()
@click.argument('cluster')
@click.argument('job_ids', nargs=-1, type=int)
@click.option('--all', '-a', 'cancel_all', is_flag=True)
@click.option('--yes', '-y', is_flag=True)
def cancel(cluster, job_ids, cancel_all, yes):
    """Cancel job(s) on a cluster."""
    if not cancel_all and not job_ids:
        raise click.UsageError('Specify job id(s) or --all.')
    _confirm(f'Cancelling {"ALL jobs" if cancel_all else str(job_ids)} on '
             f'{cluster}. Proceed?', yes)
    if cancel_all:
        sky.cancel(cluster, all=True)
    else:
        for jid in job_ids:
            sky.cancel(cluster, jid)
    click.echo('Cancelled.')


@cli.command(name='cost-report')
def cost_report():
    """Estimated cost per (live or historical) cluster."""
    report = sky.cost_report()
    if not report:
        click.echo('No clusters.')
        return
    rows = [[r['name'],
             f"{r.get('duration_hours', 0):.2f}h",
             f"${r.get('cost_per_hour', 0):.2f}",
             f"${r.get('total_cost', 0):.2f}"] for r in report]
    click.echo(_fmt_table(rows, ['NAME', 'DURATION', '$/HR (est.)',
                                 'TOTAL COST (est.)']))
    click.echo('Note: dollar amounts are ESTIMATES from the checked-in '
               'catalog\n(approximate list prices); billing truth lives '
               'with your cloud provider.')


@cli.command()
def check():
    """Probe cloud credentials; list enabled clouds."""
    from skypilot_tpu import check as check_lib
    enabled = check_lib.check()
    if enabled:
        click.echo('Enabled clouds: ' + ', '.join(enabled))
    else:
        click.echo('No clouds enabled.')


@cli.command(name='show-tpus')
@click.option('--cloud', default='gcp')
@click.option('--all', '-a', 'show_all', is_flag=True,
              help='Include GPU/CPU instance types.')
def show_tpus(cloud, show_all):
    """Browse the accelerator catalog (TPU-first ``sky show-gpus``)."""
    from skypilot_tpu.catalog import catalog
    entries = catalog.get_catalog(cloud)
    rows = []
    for e in entries:
        if not show_all and not e.is_tpu:
            continue
        rows.append([e.instance_type, e.accelerator_name or '-',
                     e.accelerator_count or '-', e.region,
                     f'${e.price:.2f}',
                     f'${e.spot_price:.2f}' if e.spot_price else '-'])
    if not rows:
        click.echo('No catalog entries.')
        return
    click.echo(_fmt_table(
        rows, ['INSTANCE', 'ACCELERATOR', 'COUNT', 'REGION', '$/HR',
               'SPOT $/HR']))


@cli.command()
@click.argument('entrypoint', type=click.Path(exists=True))
@click.option('--env', multiple=True, metavar='KEY=VALUE')
def optimize(entrypoint, env):
    """Print the optimizer's plan for a task YAML without launching."""
    task = _load_task(entrypoint, env)
    sky.launch(task, dryrun=True)


# --------------------------------------------------------------------- jobs
@cli.group()
def jobs():
    """Managed jobs: launch-with-recovery on preemptible capacity."""


@jobs.command(name='launch')
@click.argument('entrypoint', type=click.Path(exists=True))
@click.option('--name', '-n', default=None)
@click.option('--yes', '-y', is_flag=True)
@click.option('--env', multiple=True, metavar='KEY=VALUE')
def jobs_launch(entrypoint, name, yes, env):
    """Submit a managed job (controller monitors + recovers it)."""
    task = _load_task(entrypoint, env, name=name)
    _confirm('Submitting managed job. Proceed?', yes)
    job_id = sky.jobs.launch(task, name=name)
    click.echo(f'Managed job submitted (id: {job_id}).')


@jobs.command(name='queue')
def jobs_queue():
    """List managed jobs."""
    try:
        records = sky.jobs.queue()
    except exceptions.ClusterNotUpError:
        records = []                      # no controller -> no jobs yet
    if not records:
        click.echo('No managed jobs.')
        return
    rows = [[r['job_id'], r.get('name') or '-',
             _fmt_age(r.get('submitted_at')), r['status'],
             r.get('recovery_count', 0)] for r in records]
    click.echo(_fmt_table(rows, ['ID', 'NAME', 'SUBMITTED', 'STATUS',
                                 'RECOVERIES']))


@jobs.command(name='cancel')
@click.argument('job_id', type=int)
@click.option('--yes', '-y', is_flag=True)
def jobs_cancel(job_id, yes):
    """Cancel a managed job (tears its task cluster down)."""
    _confirm(f'Cancelling managed job {job_id}. Proceed?', yes)
    ok = sky.jobs.cancel(job_id)
    click.echo('Cancelled.' if ok else 'Job not found or already terminal.')


@jobs.command(name='logs')
@click.argument('job_id', type=int)
@click.option('--no-follow', is_flag=True)
def jobs_logs(job_id, no_follow):
    """Stream a managed job's controller log."""
    if no_follow:
        click.echo(sky.jobs.logs(job_id))
    else:
        sky.jobs.tail_logs(job_id, follow=True)


# -------------------------------------------------------------------- bench
@cli.group()
def bench():
    """Benchmark a task across candidate resources (``sky bench``)."""


@bench.command(name='launch')
@click.argument('entrypoint', type=click.Path(exists=True))
@click.option('--benchmark', '-b', 'bench_name', required=True)
@click.option('--candidate', 'candidates', multiple=True, required=True,
              metavar='YAML_DICT',
              help='Candidate resources as YAML, e.g. '
                   '"{cloud: gcp, tpu: v5e-8}" (repeatable).')
@click.option('--yes', '-y', is_flag=True)
@click.option('--env', multiple=True, metavar='KEY=VALUE')
def bench_launch(entrypoint, bench_name, candidates, yes, env):
    """Launch the task once per candidate resource."""
    import yaml as yaml_lib

    from skypilot_tpu import Resources
    from skypilot_tpu.benchmark import benchmark_utils
    task = _load_task(entrypoint, env)
    res = [Resources.from_yaml_config(yaml_lib.safe_load(c))
           for c in candidates]
    _confirm(f'Launching benchmark {bench_name!r} on {len(res)} '
             'candidate(s). Proceed?', yes)
    clusters = benchmark_utils.launch_benchmark(task, res, bench_name)
    click.echo(f'Benchmark {bench_name!r} launched on: '
               f'{", ".join(clusters)}')


@bench.command(name='show')
@click.argument('bench_name')
def bench_show(bench_name):
    """Show per-candidate status/duration/cost."""
    from skypilot_tpu.benchmark import benchmark_utils
    rows = benchmark_utils.summary(bench_name)
    table = [[r['cluster'], r['resources'], r['status'],
              f"{r['duration_s']:.1f}s" if r['duration_s'] else '-',
              f"${r['cost']:.4f}" if r['cost'] else '-'] for r in rows]
    click.echo(_fmt_table(table, ['CLUSTER', 'RESOURCES', 'STATUS',
                                  'DURATION', 'COST']))


@bench.command(name='down')
@click.argument('bench_name')
@click.option('--yes', '-y', is_flag=True)
def bench_down(bench_name, yes):
    """Tear down a benchmark's clusters."""
    from skypilot_tpu.benchmark import benchmark_utils
    _confirm(f'Tearing down benchmark {bench_name!r}. Proceed?', yes)
    benchmark_utils.teardown(bench_name)
    click.echo(f'Benchmark {bench_name!r} removed.')


@bench.command(name='list')
def bench_list():
    """List benchmarks."""
    from skypilot_tpu.benchmark import benchmark_utils
    names = benchmark_utils.list_benchmarks()
    click.echo('\n'.join(names) if names else 'No benchmarks.')


# -------------------------------------------------------------------- serve
@cli.group()
def serve():
    """Autoscaled serving: replicas behind a load balancer."""


@serve.command(name='up')
@click.argument('entrypoint', type=click.Path(exists=True))
@click.option('--service-name', '-n', default=None)
@click.option('--yes', '-y', is_flag=True)
@click.option('--env', multiple=True, metavar='KEY=VALUE')
def serve_up(entrypoint, service_name, yes, env):
    """Spin up a service from a task YAML with a ``service:`` section."""
    task = _load_task(entrypoint, env)
    _confirm(f'Starting service {service_name or task.name!r}. Proceed?',
             yes)
    result = sky.serve.up(task, service_name=service_name)
    click.echo(f"Service {result['name']!r} endpoint: {result['endpoint']}")


@serve.command(name='update')
@click.argument('entrypoint', type=click.Path(exists=True))
@click.option('--service-name', '-n', required=True)
@click.option('--yes', '-y', is_flag=True)
@click.option('--env', multiple=True, metavar='KEY=VALUE')
def serve_update(entrypoint, service_name, yes, env):
    """Blue-green update: new replicas launch with the new task; old
    ones drain once enough new replicas are ready."""
    task = _load_task(entrypoint, env)
    _confirm(f'Updating service {service_name!r}. Proceed?', yes)
    result = sky.serve.update(task, service_name)
    click.echo(f"Service {service_name!r} updating to "
               f"v{result['version']}.")


@serve.command(name='status')
@click.argument('service_names', nargs=-1)
def serve_status(service_names):
    """Show services and their replicas."""
    try:
        services = sky.serve.status(list(service_names) or None)
    except exceptions.ClusterNotUpError:
        services = []                     # no controller -> no services
    if not services:
        click.echo('No services.')
        return
    rows = [[s['name'], s['status'], s.get('version', 1),
             sum(1 for r in s['replicas'] if r['status'] == 'READY'),
             len(s['replicas']), s['endpoint']] for s in services]
    click.echo(_fmt_table(rows, ['NAME', 'STATUS', 'VERSION', 'READY',
                                 'REPLICAS', 'ENDPOINT']))
    for s in services:
        if not s['replicas']:
            continue
        click.echo(f"\nReplicas of {s['name']}:")
        rrows = [[r['replica_id'], r['cluster_name'], r['status'],
                  r.get('url') or '-'] for r in s['replicas']]
        click.echo(_fmt_table(rrows, ['ID', 'CLUSTER', 'STATUS', 'URL']))


@serve.command(name='down')
@click.argument('service_name')
@click.option('--purge', '-p', is_flag=True,
              help='Best-effort cleanup even if the controller is gone.')
@click.option('--yes', '-y', is_flag=True)
def serve_down(service_name, purge, yes):
    """Tear down a service and its replicas."""
    _confirm(f'Tearing down service {service_name!r}. Proceed?', yes)
    sky.serve.down(service_name, purge=purge)
    click.echo(f'Service {service_name!r} torn down.')


@serve.command(name='logs')
@click.argument('service_name')
@click.option('--no-follow', is_flag=True)
def serve_logs(service_name, no_follow):
    """Stream a service's controller/LB log."""
    sky.serve.tail_logs(service_name, follow=not no_follow)


@cli.command(name='model-server')
@click.option('--model', default='tiny',
              help='Preset config name (random weights).')
@click.option('--model-path', default=None,
              help='HF checkpoint dir (real weights + tokenizer).')
@click.option('--quantize', default=None,
              type=click.Choice(['int8', 'int4']),
              help='Weight quantization: int8 halves the decode '
                   'weight stream (KV cache follows via '
                   '--kv-cache-dtype auto); int4 packs two codes per '
                   'byte with fused dequant — half the streamed bytes '
                   'again on top of int8 (KV stays int8).')
@click.option('--tp', type=int, default=None,
              help='Tensor-parallel degree (shard weights + KV heads '
                   'over tp chips; ~linear decode TPOT win). Default: '
                   'SKYTPU_TP env, else 1.')
@click.option('--dp', type=int, default=None,
              help='Data-parallel degree (decode batch over chip '
                   'groups; aggregate tok/s). Default: SKYTPU_DP env, '
                   'else 1.')
@click.option('--kv-cache-dtype', default=None,
              type=click.Choice(['bf16', 'int8']),
              help='KV cache storage dtype; default follows --quantize. '
                   'int8 halves decode KV HBM traffic and ~doubles '
                   'paged pool token capacity.')
@click.option('--page-size', type=int, default=None,
              help='Paged-cache page granularity (tokens; auto).')
@click.option('--prefill-chunk-tokens', type=int, default=None,
              help='Chunked-prefill chunk width (0 = monolithic).')
@click.option('--decode-priority-ratio', type=float, default=None,
              help='Decode share of the interleaved token budget.')
@click.option('--decode-steps-per-call', type=int, default=None,
              help='Multi-step on-device decode: fuse EXACTLY this '
                   'many decode steps (with on-device sampling) into '
                   'each jitted call — per-step dispatch, readback '
                   'and sampling host-syncs amortize k x. Default: '
                   'adaptive horizon.')
@click.option('--prefill-w8a8', is_flag=True,
              help='int8 activations on the compute-bound prefill.')
@click.option('--speculate-k', type=int, default=0,
              help='Speculative decoding: propose up to K tokens per '
                   'verify step via prompt-lookup (n-gram) matching '
                   '(0 = off). Greedy outputs are identical to vanilla '
                   'decode; sampling keeps the output distribution.')
@click.option('--slo-tier-default', default='latency',
              type=click.Choice(['latency', 'throughput']),
              help='SLO tier for requests that declare none '
                   '(per-request: "slo_tier" body field or X-SLO-Tier '
                   'header). latency = interactive TTFT contract; '
                   'throughput = batch tokens/s contract.')
@click.option('--max-queue-tokens', type=int, default=None,
              help='Per-tier admission bound in work tokens; overflow '
                   'is shed with HTTP 429 + Retry-After instead of '
                   'queueing. Default: 2x KV pool token capacity.')
@click.option('--latency-admit-frac', type=float, default=0.7,
              help='Share of admitted work tokens reserved for the '
                   'latency tier while both tiers are backlogged.')
@click.option('--drain-deadline-s', type=float, default=30.0,
              help='Graceful-drain deadline: POST /drain stops '
                   'admission (retryable 503 + Retry-After) and lets '
                   'in-flight requests finish before teardown.')
@click.option('--step-watchdog-s', type=float, default=None,
              help='Wedge-watchdog deadline (seconds) on each engine '
                   'step: a step stuck longer flips /readiness to a '
                   'degraded 503 and fails in-flight requests over '
                   '(retryable). Default: SKYTPU_STEP_WATCHDOG_S env, '
                   'else 120; 0 disables.')
@click.option('--fault-spec', default=None,
              help='Deterministic fault-injection spec (JSON or '
                   '@/path; default SKYTPU_FAULT_SPEC env var).')
@click.option('--role', default=None,
              type=click.Choice(['colocated', 'prefill', 'decode']),
              help='Disaggregated-serving phase role: prefill workers '
                   'hand each finished prefill\'s KV (int8 stays int8 '
                   'on the wire) to a decode worker via POST '
                   '/kv/ingest and relay its token stream; decode '
                   'workers run high-batch decode without prefill '
                   'stalls. Default: SKYTPU_ROLE env, else colocated.')
@click.option('--handoff-targets', default=None,
              help='Comma-separated decode-worker base URLs a prefill '
                   'replica may hand off to when no router supplied '
                   'X-Handoff-Target (picked by live KV-pool '
                   'headroom). Default: SKYTPU_HANDOFF_TARGETS env.')
@click.option('--checkpoint-path', default=None,
              help='Local prefix-cache checkpoint file (default: '
                   'SKYTPU_KV_CHECKPOINT_PATH env). A drain/preemption '
                   'warning persists hot prefix chains here; a '
                   '(re)booting server warms its cache from the file '
                   'before declaring readiness.')
@click.option('--gang-rank', type=int, default=None,
              help='Multi-host gang rank (0 = leader: HTTP front end '
                   '+ scheduler; >0 = follower loop replaying the '
                   'leader\'s op log). Default: SKYTPU_RANK env.')
@click.option('--gang-world', type=int, default=None,
              help='Gang size (processes per replica; 1 = not a '
                   'gang). Default: SKYTPU_WORLD env.')
@click.option('--gang-coordinator', default=None,
              help='Rank 0\'s base URL (the gang bus; required on '
                   'nonzero ranks). Default: SKYTPU_COORDINATOR env.')
@click.option('--gang-id', default=None,
              help='Shared gang identity (the replica manager\'s unit '
                   'of drain/checkpoint/teardown). Default: '
                   'SKYTPU_GANG_ID env.')
@click.option('--max-batch', type=int, default=8)
@click.option('--max-seq', type=int, default=1024)
@click.option('--port', type=int, default=8081)
def model_server(model, model_path, quantize, tp, dp,
                 kv_cache_dtype, page_size, prefill_chunk_tokens,
                 decode_priority_ratio, decode_steps_per_call,
                 prefill_w8a8, speculate_k,
                 slo_tier_default, max_queue_tokens, latency_admit_frac,
                 drain_deadline_s, step_watchdog_s, fault_spec, role,
                 handoff_targets, checkpoint_path, gang_rank,
                 gang_world, gang_coordinator, gang_id, max_batch,
                 max_seq, port):
    """Run the in-tree replica model server on this host (the process
    a service task's ``run`` command starts on each replica; same
    knobs as ``python -m skypilot_tpu.serve.server``). With
    ``--gang-world N`` the replica is a gang of N processes: rank 0
    serves HTTP, nonzero ranks run follower loops and the whole gang
    launches, drains, checkpoints, and dies together."""
    from skypilot_tpu.serve import gang as gang_lib
    gang_spec = gang_lib.GangSpec.from_env(
        rank=gang_rank, world=gang_world, coordinator=gang_coordinator,
        gang_id=gang_id)
    if gang_spec.is_gang and not gang_spec.is_leader:
        import argparse
        from skypilot_tpu.serve import server as server_lib
        click.echo(f'Gang follower rank {gang_spec.rank}/'
                   f'{gang_spec.world} -> {gang_spec.coordinator}')
        server_lib.run_follower(gang_spec, argparse.Namespace(
            model=model, model_path=model_path, quantize=quantize,
            tp=tp, dp=dp,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            prefill_w8a8=prefill_w8a8,
            prefill_chunk_tokens=prefill_chunk_tokens,
            decode_priority_ratio=decode_priority_ratio,
            decode_steps_per_call=decode_steps_per_call,
            speculate_k=speculate_k, fault_spec=fault_spec,
            max_batch=max_batch, max_seq=max_seq))
        return
    from skypilot_tpu.serve.server import ModelServer
    server = ModelServer(model, max_batch=max_batch, max_seq=max_seq,
                         port=port, model_path=model_path,
                         quantize=quantize, tp=tp, dp=dp,
                         kv_cache_dtype=kv_cache_dtype,
                         page_size=page_size,
                         prefill_w8a8=prefill_w8a8,
                         prefill_chunk_tokens=prefill_chunk_tokens,
                         decode_priority_ratio=decode_priority_ratio,
                         decode_steps_per_call=decode_steps_per_call,
                         speculate_k=speculate_k,
                         slo_tier_default=slo_tier_default,
                         max_queue_tokens=max_queue_tokens,
                         latency_admit_frac=latency_admit_frac,
                         drain_deadline_s=drain_deadline_s,
                         fault_spec=fault_spec,
                         role=role,
                         handoff_targets=(handoff_targets.split(',')
                                          if handoff_targets else None),
                         checkpoint_path=checkpoint_path,
                         gang=gang_spec,
                         step_watchdog_s=step_watchdog_s)
    click.echo(f'Model server on :{port} '
               f'(speculate_k={speculate_k}, '
               f'tp={server.tp}, dp={server.dp}, role={server.role}, '
               f'gang_world={server.gang.world})')
    server.start(block=True)


# --------------------------------------------------------------- storage
@cli.group()
def storage():
    """Managed storage buckets (reference ``sky storage``,
    ``sky/cli.py:3474``)."""


@storage.command(name='ls')
def storage_ls():
    """List managed storage objects."""
    from skypilot_tpu import global_state
    records = global_state.get_storage()
    if not records:
        click.echo('No existing storage.')
        return
    rows = []
    for r in records:
        h = r.get('handle') or {}
        rows.append([r['name'],
                     ','.join(h.get('stores', [])) or '-',
                     str(h.get('source') or '-'),
                     _fmt_age(r.get('launched_at')),
                     r['status'].value])
    click.echo(_fmt_table(rows, ['NAME', 'STORE', 'SOURCE', 'CREATED',
                                 'STATUS']))


@storage.command(name='delete')
@click.argument('names', nargs=-1)
@click.option('--all', '-a', 'delete_all', is_flag=True)
@click.option('--yes', '-y', is_flag=True)
def storage_delete(names, delete_all, yes):
    """Delete managed storage (bucket contents included)."""
    from skypilot_tpu import global_state
    from skypilot_tpu.data import storage as storage_lib
    records = global_state.get_storage()
    if delete_all:
        targets = [r['name'] for r in records]
    else:
        targets = list(names)
    if not targets:
        click.echo('No storage to delete.')
        return
    if not yes:
        click.confirm(f'Delete storage: {", ".join(targets)}?', abort=True)
    by_name = {r['name']: r for r in records}
    for name in targets:
        rec = by_name.get(name)
        if rec is None:
            click.echo(f'Storage {name!r} not found.')
            continue
        h = rec.get('handle') or {}
        stores = [storage_lib.StoreType.from_str(s)
                  for s in h.get('stores', [])] or None
        obj = storage_lib.Storage(name=name, source=h.get('source'),
                                  stores=stores)
        obj.delete()
        click.echo(f'Storage {name!r} deleted.')


# ------------------------------------------------------------ telemetry
@cli.group()
def telemetry():
    """Unified telemetry: metrics registry, request traces, profiler."""


@telemetry.command(name='dump')
@click.option('--url', default=None, metavar='http://HOST:PORT',
              help='Fetch a running server\'s /metrics instead of this '
                   'process\'s registry (model server, dashboard — any '
                   'endpoint speaking the telemetry exposition).')
@click.option('--format', 'fmt', default='prom',
              type=click.Choice(['prom', 'json']),
              help='Prometheus text exposition (default) or JSON.')
@click.option('--debug-requests', is_flag=True,
              help='With --url: dump /debug/requests (completed '
                   'request span timelines) instead of /metrics.')
@click.option('--fleet', 'fleet_view', is_flag=True,
              help='With --url (a controller): dump the aggregated '
                   'fleet plane (GET /fleet/metrics) instead of the '
                   'per-process /metrics.')
@click.option('--trace', 'trace_id', default=None, metavar='TRACE_ID',
              help='With --url (a controller): dump one assembled '
                   'cross-process trace (GET /fleet/trace/<id>); '
                   'combine with --chrome-trace PATH to write it as a '
                   'chrome://tracing file instead.')
@click.option('--chrome-trace', default=None, metavar='PATH',
              help='Also export this process\'s completed request '
                   'traces as a chrome://tracing file (or, with '
                   '--trace, the fetched fleet trace).')
def telemetry_dump(url, fmt, debug_requests, fleet_view, trace_id,
                   chrome_trace):
    """Dump telemetry: the local process registry, or a remote
    server's /metrics, /debug/requests, or a controller's fleet
    plane (/fleet/metrics, /fleet/trace/<id>)."""
    import urllib.request

    from skypilot_tpu import telemetry as telemetry_lib
    if debug_requests and not url:
        raise click.UsageError('--debug-requests requires --url')
    if (fleet_view or trace_id) and not url:
        raise click.UsageError('--fleet/--trace require --url '
                               '(a controller URL)')
    if url:
        base = url.rstrip('/')
        if trace_id:
            suffix = '?format=chrome' if chrome_trace else ''
            with urllib.request.urlopen(
                    f'{base}/fleet/trace/{trace_id}{suffix}',
                    timeout=10) as r:
                body = r.read().decode()
            if chrome_trace:
                with open(chrome_trace, 'w', encoding='utf-8') as f:
                    f.write(body)
                click.echo(f'chrome trace: {chrome_trace}')
            else:
                click.echo(body)
            return
        if debug_requests:
            path = '/debug/requests'
        elif fleet_view:
            path = ('/fleet/metrics?format=json' if fmt == 'json'
                    else '/fleet/metrics')
        elif fmt == 'json':
            path = '/metrics?format=json'
        else:
            path = '/metrics'
        with urllib.request.urlopen(base + path, timeout=10) as r:
            click.echo(r.read().decode())
        return
    reg = telemetry_lib.get_registry()
    if fmt == 'json':
        import json as json_lib
        click.echo(json_lib.dumps(reg.render_json(), indent=2))
    else:
        click.echo(reg.render_prometheus(), nl=False)
    if chrome_trace:
        out = telemetry_lib.export_chrome_trace(chrome_trace)
        click.echo(f'chrome trace: {out or "no completed traces"}')


# ---------------------------------------------------------------- fleet
@cli.group()
def fleet():
    """Fleet observability plane: aggregated metrics, SLO burn rates,
    and assembled cross-process request traces from a controller."""


def _fleet_get(url: str, path: str):
    import json as json_lib
    import urllib.request
    with urllib.request.urlopen(url.rstrip('/') + path,
                                timeout=10) as r:
        return json_lib.loads(r.read().decode())


_CONTROLLER_URL_OPT = click.option(
    '--url', required=True, metavar='http://HOST:PORT',
    help='Controller URL (the process serving /fleet/metrics).')


@fleet.command(name='top')
@_CONTROLLER_URL_OPT
def fleet_top(url):
    """Fleet at a glance: scraped sources, per-tier traffic and
    latency, SLO attainment and burn."""
    data = _fleet_get(url, '/fleet/metrics?format=json')

    def gauge(name, default=0.0):
        series = (data.get(name) or {}).get('series') or []
        return series[0].get('value', default) if series else default

    click.echo(f'sources   {int(gauge("skytpu_fleet_sources"))}')
    click.echo(f'scrapes   '
               f'{int(gauge("skytpu_fleet_scrapes_total"))}')
    click.echo(f'traces    {int(gauge("skytpu_fleet_traces"))}')
    rows = []
    for entry in (data.get('skytpu_request_ttft_ms') or {}) \
            .get('series') or []:
        tier = (entry.get('labels') or {}).get('tier', '-')
        count = int(entry.get('count', 0))
        mean = entry.get('sum', 0.0) / count if count else 0.0
        rows.append((tier, count, mean))
    if rows:
        click.echo(f'{"TIER":12s} {"REQUESTS":>10s} '
                   f'{"TTFT_MEAN_MS":>13s}')
        for tier, count, mean in sorted(rows):
            click.echo(f'{tier:12s} {count:10d} {mean:13.1f}')
    slo = data.get('_slo') or {}
    for tier, vals in sorted(slo.items()):
        burns = ' '.join(
            f'burn_{k.split("_", 1)[1]}={v:.2f}'
            for k, v in sorted(vals.items()) if k.startswith('burn_'))
        click.echo(f'slo {tier:12s} '
                   f'attainment={vals.get("attainment", 1.0):.4f} '
                   f'{burns}')


@fleet.command(name='slo')
@_CONTROLLER_URL_OPT
def fleet_slo(url):
    """Per-tier SLO burn rates and attainment, as JSON."""
    import json as json_lib
    data = _fleet_get(url, '/fleet/metrics?format=json')
    click.echo(json_lib.dumps(data.get('_slo') or {}, indent=2))


@fleet.command(name='trace')
@_CONTROLLER_URL_OPT
@click.argument('trace_id', required=False)
@click.option('--chrome', default=None, metavar='PATH',
              help='Write the assembled trace as a chrome://tracing '
                   'file instead of printing JSON.')
def fleet_trace(url, trace_id, chrome):
    """Show one assembled multi-process trace (or, with no TRACE_ID,
    list the ids the controller holds)."""
    import json as json_lib
    if not trace_id:
        data = _fleet_get(url, '/fleet/traces')
        for tid in data.get('traces') or []:
            click.echo(tid)
        return
    suffix = '?format=chrome' if chrome else ''
    try:
        data = _fleet_get(url, f'/fleet/trace/{trace_id}{suffix}')
    except Exception as e:  # urllib HTTPError on unknown id
        raise click.ClickException(
            f'trace {trace_id!r} not found at {url}: {e}')
    if chrome:
        with open(chrome, 'w', encoding='utf-8') as f:
            json_lib.dump(data, f)
        click.echo(f'chrome trace: {chrome}')
        return
    click.echo(json_lib.dumps(data, indent=2))


# ------------------------------------------------------------------- lb
@cli.command(name='lb')
@click.option('--controller-url', required=True, metavar='URL',
              help='Controller to sync the replica set (and the LB '
                   'peer ring) from.')
@click.option('--port', required=True, type=int,
              help='Port this LB listens on.')
@click.option('--policy', default='prefix_affinity',
              type=click.Choice(['round_robin', 'least_load',
                                 'queue_depth', 'phase_aware',
                                 'prefix_affinity']),
              help='Load-balancing policy for this LB process.')
@click.option('--lb-id', default=None, metavar='NAME',
              help='Stable identity in the consistent-hash ring '
                   '(default: SKYTPU_LB_ID env or a random id).')
@click.option('--advertise-url', default=None, metavar='URL',
              help='URL peer LBs reach this LB at for idempotency-key '
                   'handoff (default: http://127.0.0.1:<port>).')
def lb(controller_url, port, policy, lb_id, advertise_url):
    """Run one load balancer of a horizontal LB tier.

    Every LB started against the same controller registers on the
    sync feed and joins the consistent-hash ring: session/idempotency
    keys get exactly one owner, affinity survives any single LB
    crash, and a replayed request answered via one LB is deduped at
    every other (docs/serving.md "A horizontal LB tier").
    """
    import signal
    import threading

    from skypilot_tpu.serve import load_balancer as lb_lib
    balancer = lb_lib.SkyServeLoadBalancer(
        controller_url=controller_url, port=port, policy_name=policy,
        lb_id=lb_id, advertise_url=advertise_url)
    balancer.start()
    click.echo(f'LB {balancer.lb_id} serving on port {port} '
               f'(policy {policy}); Ctrl-C to stop.')
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    balancer.stop()


# ------------------------------------------------------------------ sim
@cli.command()
@click.option('--scenario', '-s', default='smoke', metavar='NAME',
              help='Chaos scenario to run (see --list).')
@click.option('--seed', default=0, type=int,
              help='Determinism seed: same seed, byte-identical event '
                   'log (the report carries its SHA-256).')
@click.option('--policy', default=None,
              type=click.Choice(['round_robin', 'least_load',
                                 'queue_depth', 'phase_aware',
                                 'prefix_affinity']),
              help='Override the scenario\'s LB policy (the REAL '
                   'policy object routes every simulated request).')
@click.option('--list', 'list_scenarios', is_flag=True,
              help='List the scenario library and exit.')
@click.option('--event-log', default=None, metavar='PATH',
              help='Also write the full event log to PATH (lines of '
                   '"<t>|<kind>|<detail>"; its SHA-256 is the '
                   'determinism fingerprint in the report).')
def sim(scenario, seed, policy, list_scenarios, event_log):
    """Fleet-scale control-plane simulation: drive the REAL
    autoscaler/forecaster/placement/LB-policy/drain machinery through
    failure storms at up to 1000 simulated replicas and millions of
    requests in seconds of wall time (docs/simulation.md).

    Prints the scenario report as JSON: SLO attainment per tier, shed/
    lost/migrated counts (lost MUST be 0 in recovery-covered
    scenarios), recovery p50/p90, chip-seconds, and the event-log
    SHA-256 (same seed => byte-identical log).
    """
    import json as json_lib
    import logging as logging_lib

    from skypilot_tpu.serve.sim import scenarios as sim_scenarios
    # The control plane narrates every launch/drain/READY at INFO — a
    # 1000-replica storm would drown the JSON report (and corrupt
    # stdout for pipelines). Warnings still surface.
    logging_lib.getLogger('skytpu').setLevel(logging_lib.ERROR)
    if list_scenarios:
        for name in sorted(sim_scenarios.SCENARIOS):
            scn = sim_scenarios.SCENARIOS[name]
            click.echo(f'{name:22s} {scn.description}')
        return
    try:
        scn = sim_scenarios.get_scenario(scenario)
    except ValueError as e:
        raise click.UsageError(str(e))
    keep = {'keep_log': True} if event_log and scn.runner is None \
        else {}
    if scn.runner is None:
        fleet = scn.build(seed=seed, policy=policy, **keep)
        report = fleet.run()
        report['scenario'] = scn.name
        report['recovery_covered'] = scn.recovery_covered
        if event_log:
            with open(event_log, 'w', encoding='utf-8') as f:
                f.write(fleet.event_log())
            report['event_log_path'] = event_log
    else:
        report = scn.run(seed=seed, policy=policy)
        if event_log:
            raise click.UsageError(
                '--event-log is not supported for comparison '
                f'scenarios ({scenario})')
    click.echo(json_lib.dumps(report, indent=2))
    if report.get('recovery_covered') and \
            report['requests'].get('lost', 0) > 0:
        raise SystemExit(
            f'LOST {report["requests"]["lost"]} request(s) in a '
            'recovery-covered scenario — the zero-lost contract is '
            'broken')


@cli.command()
@click.option('--port', default=8500, help='Port to serve the dashboard.')
@click.option('--no-browser', is_flag=True, hidden=True)
def dashboard(port, no_browser):
    """Serve the live jobs/serve/cluster dashboard
    (reference ``sky/jobs/dashboard/``)."""
    del no_browser
    from skypilot_tpu import dashboard as dash
    click.echo(f'Dashboard: http://127.0.0.1:{port} (Ctrl-C to stop)')
    dash.serve_forever(port)


def main() -> None:
    import sys

    from skypilot_tpu.usage import usage_lib
    usage_lib.record('cli', argv=sys.argv[1:2])   # command name only
    try:
        cli(standalone_mode=True)
    except exceptions.SkyTpuError as e:       # pragma: no cover - passthru
        raise SystemExit(f'Error: {e}')


if __name__ == '__main__':
    main()
