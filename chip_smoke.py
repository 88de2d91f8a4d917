#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the two process entry points a user calls, at published widths and
on random weights made from a seed, and checks what comes out against the
plain XLA reference on the same chip:

  serve   ``python -m skypilot_tpu.serve.server --model-path <llama3-8b
          synthetic HF checkpoint, 16 of 32 layers> --quantize int8
          --max-batch 32 --max-seq 2048`` answers
          requests over HTTP (chunked prefill, prefix cache, Pallas paged
          decode), reports the device and the path it resolved, and does
          not recompile over repeated shapes.
  score   the tokens the server produced, teacher-forced through the plain
          XLA forward of the same int8 weights: each must be the
          reference's choice or within tolerance of it.
  check   the same weights cut to 4 layers: flash-attention logits against
          the XLA reference, and the paged engine (gather / Pallas bf16 /
          Pallas int8) against the reference's teacher-forced logits.
  train   ``python -m skypilot_tpu.train --model llama3-1b --seq 2048``
          takes 5 steps, writes an orbax checkpoint, and a second process
          resumes from it for 2 more.

``--chips 4`` runs, instead, only what exists across chips and what it is
compared with: the server at ``--tp 4`` scored by a one-chip reference,
and the trainer over fsdp=4 against a one-chip twin.

The parent never imports jax: a chip belongs to one process at a time, so
every phase is a child process, run in turn, whose output goes to a log
file under ``chiprun_out/chip_smoke/`` and never to the parent's stdout.
The parent reads each child's device from what the child reports. The
last line of stdout is the one JSON object of the contract; it is printed
only when every phase passed on a TPU, and the exit code is 0 only then.

``--rehearse`` runs the same control flow at a tiny size wherever JAX
puts it (the CPU, with ``JAX_PLATFORMS=cpu``). It can find a wrong path
or argument before chip time is spent; it never prints the result line
and always exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, '.bench_cache', 'chip_smoke')
LOGS = os.path.join(REPO, 'chiprun_out', 'chip_smoke')
SEED = 0
# The contract allows 1200 s, compilation included.
TIME_LIMIT_S = 1150.0

# Published widths; depth is the only thing ever cut (``reduced``).
# llama3-8b is served at 16 of its 32 layers: on the one-chip machine files
# live in the same 40 GiB as the processes, and at full depth the fp16
# checkpoint (16 GB), its int8 cache (8.6 GB) and the server that loads
# them (17 GB of host buffers before it quantizes) do not fit together.
REAL = {
    'serve_model': 'llama3-8b', 'serve_layers': 16, 'published_layers': 32,
    'check_layers': 4,
    'max_batch': 32, 'max_seq': 2048,
    'prefix': 256, 'tails': (64, 96, 128, 80), 'gen': 24, 'burst': 8,
    'check_seq': 512,
    'train_model': 'llama3-1b', 'train_batch': 2, 'train_seq': 2048,
    # Four chips: batch 4 divides over fsdp=4, and at seq 2048 it does
    # not fit the one-chip twin (17.9 GB by the compiler's count).
    'train4_batch': 4, 'train4_seq': 1024,
}
TINY = {
    'serve_model': 'tiny', 'serve_layers': 2, 'published_layers': 2,
    'check_layers': 2,
    'max_batch': 4, 'max_seq': 128,
    'prefix': 32, 'tails': (8, 12, 16, 10), 'gen': 8, 'burst': 4,
    'check_seq': 64,
    'train_model': 'tiny', 'train_batch': 4, 'train_seq': 64,
    'train4_batch': 4, 'train4_seq': 64,
}

# A child restricted to one chip of a four-chip host, by the TPU runtime's
# own device-visibility variables.
ONE_CHIP_ENV = {
    'TPU_VISIBLE_CHIPS': '0',
    'TPU_CHIPS_PER_PROCESS_BOUNDS': '1,1,1', 'TPU_PROCESS_BOUNDS': '1,1,1',
}
# The rehearsal's stand-in, on the CPU's virtual devices.
ONE_CPU_ENV = {'XLA_FLAGS': '--xla_force_host_platform_device_count=1'}


# Absolute tolerances on logits of unit scale (rms-normed hidden state
# times fan-in-scaled unembedding), per sqrt(layer): rounding errors of
# independent layers add in quadrature. Set from the dtypes before any
# chip run; a wrong mask, scale or page moves logits by O(1).
#  bf16: two programs that round the same bf16 values in another order
#        (8 bits kept; 8 * 2^-8 leaves a factor of a few).
#  int8_kv: one side also stores K/V rows as int8 (scale = row
#        absmax/127: about as coarse as bf16 again).
#  int8_weights: one side's weights are int8 (per-channel absmax/127 of a
#        normal weight: ~1% per weight, seven matmuls a layer, and the
#        largest of 128k logit errors is ~5 sigma: 0.215 at 4 layers on the
#        CPU before any chip run).
LOGIT_TOL_PER_SQRT_LAYER = {'bf16': 1 / 32, 'int8_kv': 1 / 16,
                            'int8_weights': 5 / 32}


def logit_tolerance(n_layers: int, kind: str) -> float:
    return LOGIT_TOL_PER_SQRT_LAYER[kind] * math.sqrt(n_layers)


class SmokeError(Exception):
    pass


class Smoke:
    """The parent: starts children in turn, reads what they report, and
    holds the assertions. Touches neither jax nor the compute packages."""

    def __init__(self, chips: int, rehearse: bool):
        self.chips = chips
        self.rehearse = rehearse
        self.sz = TINY if rehearse else REAL
        self.t0 = time.monotonic()
        self.procs: list = []
        self.chip_only: list = []        # rehearsal: what only a chip shows
        self.device = None               # from the child that held the chip
        self.reduced: dict = {}
        self.corpus_written = False
        if self.sz['serve_layers'] != self.sz['published_layers']:
            self.reduced[self.sz['serve_model']] = {
                'n_layers': self.sz['serve_layers'],
                'published': self.sz['published_layers']}
        os.makedirs(WORK, exist_ok=True)
        shutil.rmtree(LOGS, ignore_errors=True)
        os.makedirs(LOGS)

    # ------------------------------------------------------------ plumbing
    def elapsed(self) -> float:
        return time.monotonic() - self.t0

    def remaining(self) -> float:
        left = TIME_LIMIT_S - self.elapsed()
        if left <= 0:
            raise SmokeError(f'out of time: {self.elapsed():.0f}s elapsed '
                             f'of {TIME_LIMIT_S:.0f}s')
        return left

    def say(self, msg: str) -> None:
        """An earlier line of stdout."""
        print(f'[smoke {self.elapsed():7.1f}s] {msg}', flush=True)

    def require(self, cond: bool, msg: str) -> None:
        if not cond:
            raise SmokeError(msg)

    def require_chip(self, cond: bool, msg: str) -> None:
        """An assertion only a chip can meet. A rehearsal notes it and
        goes on (and can never pass); a real run stops here."""
        if cond:
            return
        if not self.rehearse:
            raise SmokeError(msg)
        self.chip_only.append(msg)

    def child_env(self, extra=None) -> dict:
        env = dict(os.environ)
        # Placed from outside where the variable is set; else a fixed path
        # in the checkout (the path is part of the cache key).
        env.setdefault('JAX_COMPILATION_CACHE_DIR',
                       os.path.join(REPO, '.bench_cache', 'jax_cache'))
        env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
        env['PYTHONUNBUFFERED'] = '1'
        env.update(extra or {})
        return env

    def start(self, name: str, argv: list, env_extra=None):
        """Start a child whose stdout and stderr go to a log file."""
        log_path = os.path.join(LOGS, f'{name}.log')
        with open(log_path, 'w', encoding='utf-8') as log:
            proc = subprocess.Popen(
                argv, cwd=REPO, env=self.child_env(env_extra), stdout=log,
                stderr=subprocess.STDOUT, stdin=subprocess.DEVNULL,
                start_new_session=True)
        proc.smoke_name, proc.smoke_log = name, log_path
        self.procs.append(proc)
        return proc

    def log_tail(self, proc, n: int = 40) -> str:
        with open(proc.smoke_log, encoding='utf-8', errors='replace') as f:
            return ''.join(f.readlines()[-n:])

    def finish(self, proc, timeout=None) -> None:
        """Wait for a child that ends by itself; it must exit 0."""
        try:
            rc = proc.wait(timeout=min(timeout or 1e9, self.remaining()))
        except subprocess.TimeoutExpired:
            raise SmokeError(f'{proc.smoke_name}: still running after its '
                             f'time; log tail:\n{self.log_tail(proc)}')
        self.require(rc == 0, f'{proc.smoke_name}: exit code {rc}; log '
                              f'tail:\n{self.log_tail(proc)}')

    def stop(self, proc) -> None:
        """Stop a child that is still running (its whole session)."""
        # SIGINT first: python then runs its exit handlers and jax lets
        # go of the chip in good order.
        for sig, grace in ((signal.SIGINT, 20), (signal.SIGTERM, 10),
                           (signal.SIGKILL, 10)):
            if proc.poll() is not None:
                return
            try:
                os.killpg(proc.pid, sig)
                proc.wait(timeout=grace)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                continue

    def stop_all(self) -> None:
        for proc in self.procs:
            self.stop(proc)

    def run_child(self, name: str, mode: str, args: list, env_extra=None,
                  timeout=None) -> dict:
        """One of this file's own child modes, run to its end; returns
        the JSON it wrote."""
        out = os.path.join(WORK, f'{name}.json')
        if os.path.exists(out):
            os.remove(out)
        proc = self.start(name, [sys.executable, os.path.abspath(__file__),
                                 '--child', mode, '--out', out] + args,
                          env_extra)
        self.finish(proc, timeout)
        with open(out, encoding='utf-8') as f:
            return json.load(f)

    def note_device(self, who: str, device: dict, count: int) -> None:
        """Every child that needs the chip says where it ran."""
        self.say(f'{who}: device {json.dumps(device)}')
        self.require_chip(device['platform'] == 'tpu',
                          f'{who} ran on {device["platform"]!r}, not on '
                          f'a TPU')
        self.require_chip(device['device_count'] == count,
                          f'{who} saw {device["device_count"]} device(s), '
                          f'expected {count}')

    # -------------------------------------------------------------- inputs
    def synth(self, model: str, layers: int) -> str:
        path = os.path.join(WORK, f'{model}-L{layers}-seed{SEED}')
        t = time.monotonic()
        # Host-only work: this child is kept off the chip.
        self.run_child(f'synth-{model}-L{layers}', 'synth',
                       ['--model', model, '--layers', str(layers),
                        '--path', path], {'JAX_PLATFORMS': 'cpu'})
        self.say(f'synthetic HF checkpoint {model} x {layers} layers, '
                 f'seed {SEED}: {time.monotonic() - t:.1f}s -> {path}')
        return path

    def prompts(self) -> list:
        """Token-id prompts: one shared prefix (whole pages, so the
        prefix cache can hit) and distinct tails, each longer than one
        prefill chunk."""
        rng = random.Random(SEED)
        vocab = 200 if self.rehearse else 100000
        prefix = [rng.randrange(3, vocab) for _ in range(self.sz['prefix'])]
        return [prefix + [rng.randrange(3, vocab) for _ in range(n)]
                for n in self.sz['tails']]

    def corpus(self) -> str:
        """A seeded text corpus with word statistics to learn."""
        path = os.path.join(WORK, f'corpus-seed{SEED}.txt')
        if self.corpus_written:
            return path
        self.corpus_written = True
        rng = random.Random(SEED)
        words = [''.join(rng.choice('abcdefghijklmnopqrstuvwxyz')
                         for _ in range(rng.randint(2, 9)))
                 for _ in range(500)]
        weights = [1.0 / (i + 1) for i in range(len(words))]     # Zipf
        text = ' '.join(rng.choices(words, weights, k=60000))
        with open(path, 'w', encoding='utf-8') as f:
            f.write(text)
        return path

    # --------------------------------------------------------------- serve
    def serve(self, ckpt: str, tp: int) -> list:
        """The server child over HTTP. Returns [(prompt, tokens)] for the
        reference to score."""
        sz = self.sz
        port = free_port()
        base = f'http://127.0.0.1:{port}'
        argv = [sys.executable, '-m', 'skypilot_tpu.serve.server',
                '--model-path', ckpt, '--quantize', 'int8',
                '--max-batch', str(sz['max_batch']),
                '--max-seq', str(sz['max_seq']), '--port', str(port)]
        if tp > 1:
            argv += ['--tp', str(tp)]
        name = f'serve-tp{tp}'
        self.say(f'{name}: ' + ' '.join(argv[1:]))
        t_start = time.monotonic()
        server = self.start(name, argv)
        ready = self.wait_ready(server, base)
        t_ready = time.monotonic() - t_start
        metrics = http_json('GET', f'{base}/metrics?format=json')
        device, engine = metrics['device'], metrics['engine']
        self.note_device(name, {k: device[k] for k in
                                ('platform', 'device_kind',
                                 'device_count')}, self.chips)
        self.require(ready['device']['platform'] == device['platform'],
                     '/readiness and /metrics disagree on the platform')
        self.say(f'{name}: ready in {t_ready:.1f}s (load + quantize + '
                 f'warm-up), {metrics["compiles"]["count"]} compiles, '
                 f'{metrics["compiles"]["seconds"]}s in the compiler')
        self.say(f'{name}: engine {json.dumps(engine)}')
        self.say(f'{name}: memory_stats {json.dumps(device["memory"])}')
        self.say(f'{name}: attention: prefill {engine["prefill_attn"]} '
                 f'(cached_attention: the paged prefill never takes the '
                 f'flash kernel), decode {engine["decode_impl"]}'
                 + (' in interpret mode' if engine['decode_interpret']
                    else ''))
        self.require(not engine['decode_interpret'] or self.rehearse,
                     'the decode kernel runs in interpret mode')
        if tp == 1:
            self.require_chip(engine['decode_impl'] == 'pallas',
                              f'decode_impl is {engine["decode_impl"]!r}, '
                              f"not 'pallas'")
        else:
            # paged.py keeps any mesh on the XLA gather path: reported,
            # not changed here (ROADMAP A4/A7).
            self.require(engine['decode_impl'] == 'gather',
                         f'decode_impl under a mesh is '
                         f'{engine["decode_impl"]!r}')
            self.check_spread(name, engine, device, tp)
        self.require_chip(engine['pool_auto_sized'],
                          'the KV pool was not sized from live '
                          'memory_stats')
        self.require(metrics['kv_cache_dtype'] == 'int8',
                     f'KV cache is {metrics["kv_cache_dtype"]}, not int8')

        prompts, gen = self.prompts(), sz['gen']
        # Pass 1, cold: two /v1/completions (the second streamed), two
        # /generate; then a concurrent burst for batched decode.
        answered = [self.completion(base, prompts[0], gen),
                    self.completion(base, prompts[1], gen, stream=True)]
        served = [(p, self.generate(base, p, gen)) for p in prompts[2:]]
        burst = [None] * sz['burst']

        def one(i):
            burst[i] = self.generate(base, prompts[i % len(prompts)], gen)

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(sz['burst'])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=self.remaining())
        self.require(all(b is not None and len(b) == gen for b in burst),
                     f'burst: not every request answered with {gen} tokens')
        self.say(f'{name}: {len(answered) + len(served) + len(burst)} '
                 f'requests answered with {gen} tokens each '
                 f'({len(prompts[0])}-{max(map(len, prompts))}-token '
                 f'prompts, shared {sz["prefix"]}-token prefix)')
        # Passes 2 and 3, one request at a time: every full page is in
        # the prefix cache after pass 1, so 2 and 3 run the same shapes.
        # Pass 3 must compile nothing and answer what pass 2 answered.
        pass2 = [self.generate(base, p, gen) for p in prompts]
        warm = http_json('GET', f'{base}/metrics?format=json')
        pass3 = [self.generate(base, p, gen) for p in prompts]
        after = http_json('GET', f'{base}/metrics?format=json')
        self.require(pass2 == pass3, 'the same request on the same warm '
                                     'server gave different tokens')
        for key, a, b in (
                ('XLA compiles', warm['compiles']['count'],
                 after['compiles']['count']),
                ('jit first calls', warm['engine']['jit_first_calls'],
                 after['engine']['jit_first_calls'])):
            self.say(f'{name}: {key}: {a} after warm-up, {b} after the '
                     f'repeat pass')
            self.require(a == b, f'{key} grew over repeated shapes: '
                                 f'{a} -> {b}')
        self.require(after['requests_served'] >= 2 * len(prompts),
                     'requests_served does not count the requests')
        self.say(f'{name}: tpot_ms_median {after["tpot_ms_median"]} '
                 f'ttft_ms_median {after["ttft_ms_median"]} over '
                 f'{after["requests_served"]} requests, compiles '
                 f'included: not a benchmark')

        drain = http_json('POST', f'{base}/drain', {})
        self.require(drain.get('draining') is True, f'/drain: {drain}')
        deadline = time.monotonic() + min(60, self.remaining())
        while not http_json('GET', f'{base}/drain').get('drained'):
            self.require(time.monotonic() < deadline, 'never drained')
            time.sleep(0.2)
        code, _ = http_status('POST', f'{base}/generate',
                              {'prompt': prompts[0], 'max_new_tokens': 1})
        self.require(code == 503, f'a drained server answered {code}')
        self.stop(server)
        self.say(f'{name}: drained and stopped')
        return served + list(zip(prompts, pass2))

    def wait_ready(self, server, base: str) -> dict:
        while True:
            self.require(server.poll() is None,
                         f'{server.smoke_name} exited with '
                         f'{server.returncode} before it was ready; log '
                         f'tail:\n{self.log_tail(server)}')
            self.remaining()
            try:
                code, body = http_status('GET', f'{base}/readiness',
                                         timeout=5)
            except OSError:
                code, body = None, {}          # not listening yet
            if code == 200:
                return body
            self.require(body.get('status') != 'failed',
                         f'{server.smoke_name} failed: {body}; log '
                         f'tail:\n{self.log_tail(server)}')
            time.sleep(1.0)

    def completion(self, base: str, prompt: list, gen: int,
                   stream: bool = False) -> int:
        payload = {'prompt': prompt, 'max_tokens': gen, 'temperature': 0,
                   'eos_id': None, 'stream': stream}
        if not stream:
            body = http_json('POST', f'{base}/v1/completions', payload,
                             timeout=self.remaining())
            n = body['usage']['completion_tokens']
            self.require(body['usage']['prompt_tokens'] == len(prompt),
                         'prompt_tokens does not match the prompt')
        else:
            events = http_sse(f'{base}/v1/completions', payload,
                              timeout=self.remaining())
            self.require(events and events[-1] == '[DONE]',
                         f'stream did not end in [DONE]: {events[-2:]}')
            chunks = [json.loads(e) for e in events[:-1]]
            self.require(chunks[-1]['choices'][0]['finish_reason']
                         == 'length', f'stream ended with {chunks[-1]}')
            n = len(chunks) - 1                  # the terminal chunk
        self.require(n == gen, f'/v1/completions (stream={stream}) '
                               f'answered {n} tokens, asked {gen}')
        return n

    def generate(self, base: str, prompt: list, gen: int) -> list:
        body = http_json('POST', f'{base}/generate',
                         {'prompt': prompt, 'max_new_tokens': gen},
                         timeout=self.remaining())
        self.require(len(body['tokens']) == gen,
                     f'/generate answered {len(body["tokens"])} tokens, '
                     f'asked {gen}')
        return body['tokens']

    def check_spread(self, name: str, engine: dict, device: dict,
                     tp: int) -> None:
        """Weights and KV pool sit on ``tp`` devices, not all on the
        first: from the arrays' shards and from each device's own
        memory_stats."""
        for what, by_dev in engine['bytes_by_device'].items():
            total = sum(by_dev.values())
            self.say(f'{name}: {what} bytes by device {json.dumps(by_dev)}')
            self.require(len(by_dev) == tp,
                         f'{what} sits on {len(by_dev)} device(s), not '
                         f'{tp}')
            self.require(max(by_dev.values()) < 0.5 * total,
                         f'{what}: one device holds half or more')
        in_use = [m['bytes_in_use'] for m in device['memory']]
        self.require_chip(len(in_use) == tp and min(in_use) > 0
                          and max(in_use) < 1.5 * min(in_use),
                          f'memory_stats bytes_in_use uneven over '
                          f'devices: {in_use}')

    # ------------------------------------------------------ score / check
    def score(self, name: str, ckpt: str, layers: int, served: list,
              env_extra=None) -> None:
        """The served tokens against the plain XLA forward of the same
        int8 weights on one chip."""
        tol = logit_tolerance(layers, 'int8_kv')
        req = os.path.join(WORK, f'{name}-requests.json')
        with open(req, 'w', encoding='utf-8') as f:
            json.dump([{'prompt': p, 'tokens': t} for p, t in served], f)
        out = self.run_child(name, 'score',
                             ['--path', ckpt, '--requests', req,
                              '--seq', str(self.sz['check_seq'])],
                             env_extra)
        self.note_device(name, out['device'], 1)
        self.say(f'{name}: load {out["load_s"]}s, compile+run '
                 f'{out["run_s"]}s; attention {out["attention"]} '
                 f'(reference_attention, no cache); '
                 f'{out["agree"]}/{out["positions"]} served tokens are '
                 f'the reference argmax; worst deficit '
                 f'{out["max_deficit"]:.4f} logits (tolerance {tol:.4f}: '
                 f'int8 KV at {layers} layers); median top-2 margin '
                 f'{out["median_margin"]:.4f}')
        self.require(out['finite'], 'reference logits are not finite')
        self.require(out['max_deficit'] <= tol,
                     f'{name}: a served token lies {out["max_deficit"]:.4f}'
                     f' logits below the reference choice (tolerance '
                     f'{tol:.4f})')

    def check(self, ckpt: str, layers: int) -> None:
        """Kernels against the XLA reference at a depth of a few layers:
        logits, and the engine's greedy tokens. Every comparison is
        printed before any of them fails the phase."""
        out = self.run_child('check', 'check',
                             ['--path', ckpt,
                              '--seq', str(self.sz['check_seq']),
                              '--gen', str(self.sz['gen'])])
        self.note_device('check', out['device'], 1)
        tol = {kind: logit_tolerance(layers, kind)
               for kind in LOGIT_TOL_PER_SQRT_LAYER}
        self.say(f'check: {layers} layers, logit tolerances '
                 f'{json.dumps({k: round(v, 4) for k, v in tol.items()})}; '
                 f'{out["compiles"]["count"]} compiles, '
                 f'{out["compiles"]["seconds"]}s in the compiler')
        failed = []
        flash = out['flash']
        self.say(f'check: forward attention {flash["attention"]} '
                 f'(kernel in the program: {flash["kernel_in_hlo"]}) vs '
                 f'reference_attention: max |dlogit| '
                 f'{flash["max_abs_diff"]:.4f} over '
                 f'{flash["positions"]} positions x vocab')
        if flash['max_abs_diff'] > tol['bf16']:
            failed.append(f'flash logits off by {flash["max_abs_diff"]:.4f}')
        self.say(f'check: int8 weights vs bf16 (what quantization costs), '
                 f'first-token logits: max |dlogit| '
                 f'{out["int8_first_token_diff"]:.4f}')
        if out['int8_first_token_diff'] > tol['int8_weights']:
            failed.append('int8-weight first-token logits out of tolerance')
        for var in out['engines']:
            self.say(f'check: engine {var["name"]}: decode_impl '
                     f'{var["decode_impl"]} kv {var["kv_cache_dtype"]} '
                     f'pool_auto_sized {var["pool_auto_sized"]}, against '
                     f'the {var["reference"]} reference: '
                     f'{var["agree"]}/{var["positions"]} greedy tokens are '
                     f'its argmax, worst deficit {var["max_deficit"]:.4f} '
                     f'(tolerance {tol[var["tolerance"]]:.4f})')
            if var['max_deficit'] > tol[var['tolerance']]:
                failed.append(f'engine {var["name"]}: a token lies '
                              f'{var["max_deficit"]:.4f} logits below the '
                              f'reference choice')
        self.require(not failed, 'check: ' + '; '.join(failed))
        self.require_chip(flash['attention'] == 'flash'
                          and flash['kernel_in_hlo'],
                          'the forward pass did not take the flash kernel')
        for var in out['engines']:
            if var['name'] != 'reference':
                self.require_chip(var['decode_impl'] == 'pallas',
                                  f'engine {var["name"]} decoded through '
                                  f'{var["decode_impl"]!r}')

    # --------------------------------------------------------------- train
    def train(self, name: str, steps: int, batch: int, seq: int,
              ckpt_dir=None, env_extra=None, count: int = 1) -> dict:
        """The trainer child, to its end. Returns its first line and its
        losses by step."""
        argv = [sys.executable, '-m', 'skypilot_tpu.train',
                '--model', self.sz['train_model'], '--data', self.corpus(),
                '--batch', str(batch), '--seq', str(seq),
                '--steps', str(steps), '--lr', '1e-4',
                '--warmup-steps', '1', '--log-every', '1',
                '--mu-dtype', 'bfloat16']
        if ckpt_dir:
            argv += ['--ckpt-dir', ckpt_dir, '--save-every', '1000']
        self.say(f'{name}: ' + ' '.join(argv[1:]))
        t = time.monotonic()
        proc = self.start(name, argv, env_extra)
        self.finish(proc)
        with open(proc.smoke_log, encoding='utf-8') as f:
            lines = [ln.strip() for ln in f]
        first = next((ln for ln in lines if ln.startswith('[train] {')),
                     None)
        self.require(first is not None, f'{name}: no device line')
        head = json.loads(first[len('[train] '):])
        self.note_device(name, head['device'], count)
        steps_out = [json.loads(ln) for ln in lines if ln.startswith('{')]
        losses = {s['step']: s['loss'] for s in steps_out}
        self.say(f'{name}: mesh {json.dumps(head["mesh"])}, attention '
                 f'{head["attention"]}'
                 + (' (head_dim 64 is below the flash kernel\'s 128 '
                    'tiling)' if head['attention'] == 'xla'
                    and not self.rehearse else '')
                 + f'; losses {json.dumps(losses)}; '
                 f'{time.monotonic() - t:.1f}s in all; '
                 + next((ln for ln in lines if ln.startswith(
                     '[train] memory')), 'no memory line'))
        self.require(all(math.isfinite(v) for v in losses.values()),
                     f'{name}: a loss is not finite')
        self.require(f'[train] done at step {steps}' in lines,
                     f'{name}: did not reach step {steps}')
        return {'head': head, 'losses': losses, 'lines': lines}

    def train_and_resume(self) -> None:
        sz = self.sz
        ckpt_dir = os.path.join(WORK, 'train_ckpt')
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        first = self.train('train', 5, sz['train_batch'], sz['train_seq'],
                           ckpt_dir)
        losses = first['losses']
        self.require(sorted(losses) == [1, 2, 3, 4, 5],
                     f'train: steps logged {sorted(losses)}')
        # Each step sees a new batch, so "flat" allows 2%.
        self.require(losses[5] <= losses[1] * 1.02,
                     f'train: loss rose from {losses[1]} to {losses[5]}')
        again = self.train('train-resume', 7, sz['train_batch'],
                           sz['train_seq'], ckpt_dir)
        self.require(any(ln.startswith('[train] resumed from ')
                         and ln.endswith(' at step 5')
                         for ln in again['lines']),
                     'train-resume: did not resume at step 5')
        self.require(sorted(again['losses']) == [6, 7],
                     f'train-resume: steps logged '
                     f'{sorted(again["losses"])}')
        self.require(again['losses'][7] <= losses[1] * 1.02,
                     'train-resume: loss above where training began')
        shutil.rmtree(ckpt_dir)

    # -------------------------------------------------------------- phases
    def probe(self, name: str, env_extra, count: int) -> dict:
        out = self.run_child(name, 'device', [], env_extra, timeout=300)
        self.note_device(name, out['device'], count)
        return out['device']

    def one_chip(self) -> None:
        sz = self.sz
        self.device = self.probe('device', None, 1)
        ckpt = self.synth(sz['serve_model'], sz['serve_layers'])
        served = self.serve(ckpt, tp=1)
        self.score('score', ckpt, sz['serve_layers'], served)
        # Files cost the machine the same memory as processes do: each
        # phase's checkpoint goes as soon as the phase is over.
        shutil.rmtree(ckpt)
        ckpt = self.synth(sz['serve_model'], sz['check_layers'])
        self.check(ckpt, sz['check_layers'])
        shutil.rmtree(ckpt)
        self.train_and_resume()

    def four_chips(self) -> None:
        sz = self.sz
        self.device = self.probe('device', None, 4)
        one_chip_env = ONE_CPU_ENV if self.rehearse else ONE_CHIP_ENV
        self.probe('device-one-chip-twin', one_chip_env, 1)
        ckpt = self.synth(sz['serve_model'], sz['serve_layers'])
        served = self.serve(ckpt, tp=4)
        self.score('score-one-chip-twin', ckpt, sz['serve_layers'], served,
                   one_chip_env)
        shutil.rmtree(ckpt)
        batch, seq = sz['train4_batch'], sz['train4_seq']
        if (batch, seq) != (sz['train_batch'], sz['train_seq']):
            self.reduced['train4'] = {'batch': batch, 'seq': seq}
        four = self.train('train-fsdp4', 3, batch, seq, count=4)
        self.require(four['head']['mesh']['fsdp'] == 4,
                     f'train-fsdp4: mesh {four["head"]["mesh"]}')
        twin = self.train('train-one-chip-twin', 3, batch, seq,
                          env_extra=one_chip_env)
        # Step 1 is computed before any update: same seed, same data,
        # only the reduction order differs. Later steps are reported.
        tol = 2.0 ** -8 * four['losses'][1]
        diffs = {s: round(abs(four['losses'][s] - twin['losses'][s]), 4)
                 for s in sorted(four['losses'])}
        self.say(f'train: |loss fsdp=4 - loss one chip| by step '
                 f'{json.dumps(diffs)} (step-1 tolerance {tol:.4f})')
        self.require(diffs[1] <= tol, f'step-1 loss differs by {diffs[1]}')

    def run(self) -> int:
        self.say(f'chips {self.chips}, seed {SEED}, sizes '
                 f'{json.dumps(self.sz)}')

        def on_term(signum, frame):
            raise SmokeError(f'signal {signum}')

        signal.signal(signal.SIGTERM, on_term)
        try:
            if self.chips == 4:
                self.four_chips()
            else:
                self.one_chip()
        except SmokeError as e:
            print(f'[smoke] FAILED after {self.elapsed():.0f}s: {e}',
                  file=sys.stderr, flush=True)
            return 1
        finally:
            self.stop_all()
        self.say(f'reduced: {json.dumps(self.reduced)}')
        self.say(f'all phases passed in {self.elapsed():.0f}s; logs in '
                 f'{os.path.relpath(LOGS, REPO)}/')
        if self.rehearse or self.chip_only:
            print('[smoke] a rehearsal is not a chip run; what only a chip '
                  'can show:\n  ' + '\n  '.join(self.chip_only or ['-']),
                  file=sys.stderr, flush=True)
            return 1
        print(json.dumps({'ok': True, 'device': {
            'platform': self.device['platform'],
            'kind': self.device['device_kind'],
            'count': self.device['device_count']}}), flush=True)
        return 0


# ------------------------------------------------------------------- HTTP
def free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def http_status(method: str, url: str, payload=None, timeout=60):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={'Content-Type': 'application/json'})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            return e.code, json.loads(body)
        except ValueError:
            return e.code, {'raw': body.decode(errors='replace')}


def http_json(method: str, url: str, payload=None, timeout=60) -> dict:
    code, body = http_status(method, url, payload, timeout)
    if code != 200:
        raise SmokeError(f'{method} {url} -> {code}: {body}')
    return body


def http_sse(url: str, payload: dict, timeout=60) -> list:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method='POST',
        headers={'Content-Type': 'application/json'})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return [ln[len('data: '):].strip() for ln in
                r.read().decode().splitlines() if ln.startswith('data: ')]


# --------------------------------------------------------------- children
# Everything below runs in a child process and may import jax.
def child_device(args) -> dict:
    from skypilot_tpu.telemetry import device as device_lib
    return {'device': device_lib.device_identity()}


def child_synth(args) -> dict:
    import dataclasses

    from skypilot_tpu.models import configs, synth
    cfg = dataclasses.replace(configs.get_config(args.model),
                              n_layers=args.layers)
    synth.write_synthetic_hf_checkpoint(args.path, cfg, seed=SEED)
    return {'path': args.path}


def _scorer(cfg, seq: int):
    """jit: teacher-forced reference logits of one padded sequence,
    reduced on the device to what the comparison needs."""
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import llama

    @jax.jit
    def score(params, tokens, chosen):
        logits, _ = llama.forward(params, tokens[None], cfg,
                                  attn_impl='xla')
        logits = logits[0]
        top2 = jax.lax.top_k(logits, 2)[0]
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)
        return (top2[:, 0] - picked[:, 0], top2[:, 0] - top2[:, 1],
                jnp.all(jnp.isfinite(logits)))

    def run(params, prompt, tokens):
        """(deficit, margin) per generated token: how far below the
        reference's best logit the generated token lies, and the
        reference's top-2 margin there."""
        import numpy as np
        n, m = len(prompt), len(tokens)
        assert n + m <= seq, (n, m, seq)
        padded = np.zeros(seq, np.int32)
        padded[:n + m] = list(prompt) + list(tokens)
        chosen = np.zeros(seq, np.int32)
        chosen[n - 1:n - 1 + m] = tokens      # position i predicts i + 1
        deficit, margin, finite = jax.device_get(
            score(params, jnp.asarray(padded), jnp.asarray(chosen)))
        return (deficit[n - 1:n - 1 + m], margin[n - 1:n - 1 + m],
                bool(finite))

    return run


def _summary(deficits, margins) -> dict:
    import numpy as np
    deficits, margins = np.concatenate(deficits), np.concatenate(margins)
    return {'positions': int(deficits.size),
            'agree': int((deficits <= 0).sum()),
            'max_deficit': float(deficits.max()),
            'median_margin': float(np.median(margins))}


def child_score(args) -> dict:
    from skypilot_tpu.models import weights
    from skypilot_tpu.telemetry import device as device_lib
    device_lib.get_compile_watch()
    t = time.monotonic()
    cfg, params = weights.load_checkpoint(args.path, quantize='int8')
    load_s = round(time.monotonic() - t, 1)
    with open(args.requests, encoding='utf-8') as f:
        requests = json.load(f)
    t = time.monotonic()
    run = _scorer(cfg, args.seq)
    deficits, margins, finite = [], [], True
    for r in requests:
        d, m, ok = run(params, r['prompt'], r['tokens'])
        deficits.append(d)
        margins.append(m)
        finite = finite and ok
    return dict(_summary(deficits, margins), finite=finite,
                device=device_lib.device_identity(), attention='xla',
                load_s=load_s, run_s=round(time.monotonic() - t, 1))


def child_check(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.inference.paged import PagedInferenceEngine
    from skypilot_tpu.models import llama, quantization, weights
    from skypilot_tpu.ops.attention import flash_selected
    from skypilot_tpu.telemetry import device as device_lib
    watch = device_lib.get_compile_watch()
    cfg, params = weights.load_checkpoint(args.path)          # bf16
    seq, gen = args.seq, args.gen
    rng = random.Random(SEED + 1)
    vocab = min(cfg.vocab_size, 100000)
    shared = [rng.randrange(3, vocab) for _ in range(seq // 2)]
    prompts = [shared + [rng.randrange(3, vocab) for _ in range(n)]
               for n in (seq // 8, seq // 4)]

    # (1) The forward pass with the attention 'auto' picks here (flash on
    # the chip at this length and head width) against reference_attention.
    tokens = np.zeros((len(prompts), seq), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    tokens = jnp.asarray(tokens)
    takes_flash = flash_selected('auto', seq, cfg.head_dim)

    def forward(impl):
        return jax.jit(lambda p, t: llama.forward(p, t, cfg,
                                                  attn_impl=impl)[0])

    ref_logits = forward('xla')(params, tokens)
    auto = forward('auto')
    auto_logits = auto(params, tokens)
    hlo = auto.lower(params, tokens).compile().as_text()
    valid = np.zeros((len(prompts), seq), bool)
    for i, p in enumerate(prompts):
        valid[i, :len(p)] = True
    diff = np.abs(np.asarray(auto_logits) - np.asarray(ref_logits))
    flash = {'attention': 'flash' if takes_flash else 'xla',
             'kernel_in_hlo': 'tpu_custom_call' in hlo,
             'max_abs_diff': float(diff[valid].max()),
             'positions': int(valid.sum())}

    # (2) int8 weights against bf16, at the first-token position.
    last = np.array([len(p) - 1 for p in prompts])
    rows = np.arange(len(prompts))
    params8 = quantization.quantize_params(params, mode='int8')
    int8_logits = forward('xla')(params8, tokens)
    int8_diff = float(np.abs(
        np.asarray(int8_logits)[rows, last]
        - np.asarray(ref_logits)[rows, last]).max())
    del auto_logits, int8_logits, diff

    # (3) The paged engine against the teacher-forced reference of the
    # same weights: the plain XLA paths, then what a chip resolves by
    # itself (Pallas decode; int8 weights with int8 KV as served).
    run = _scorer(cfg, seq)
    engines = []
    for name, kw, ref_params, tolerance in (
            ('reference', dict(decode_impl='gather', quantize=None,
                               kv_cache_dtype='bf16'), params, 'bf16'),
            ('bf16', dict(quantize=None, kv_cache_dtype='bf16'),
             params, 'bf16'),
            ('int8-as-served', dict(kv_cache_dtype='int8'),
             params8, 'int8_kv')):
        eng = PagedInferenceEngine(cfg, ref_params, max_batch=4,
                                   max_seq=seq, **kw)
        rids = [eng.add_request(list(p), max_new_tokens=gen)
                for p in prompts]
        done = eng.run_to_completion()
        deficits, margins = [], []
        for p, rid in zip(prompts, rids):
            out = done[rid].output
            assert len(out) == gen, (name, len(out))
            d, m, _ = run(ref_params, p, out)
            deficits.append(d)
            margins.append(m)
        path = eng.resolved_path()
        engines.append(dict(
            _summary(deficits, margins), name=name, tolerance=tolerance,
            reference='int8-weight' if ref_params is params8 else 'bf16',
            decode_impl=path['decode_impl'],
            kv_cache_dtype=eng.kv_cache_dtype,
            pool_auto_sized=path['pool_auto_sized']))
        del eng, done
    return {'device': device_lib.device_identity(), 'flash': flash,
            'int8_first_token_diff': int8_diff, 'engines': engines,
            'compiles': watch.stats()}


CHILDREN = {'device': child_device, 'synth': child_synth,
            'score': child_score, 'check': child_check}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--chips', type=int, default=1, choices=[1, 4],
                        help='4: only the four-chip path and what it is '
                             'compared with')
    parser.add_argument('--rehearse', action='store_true',
                        help='tiny sizes on whatever device JAX finds; '
                             'never passes')
    parser.add_argument('--child', choices=sorted(CHILDREN),
                        help=argparse.SUPPRESS)
    for flag in ('--out', '--model', '--path', '--requests'):
        parser.add_argument(flag, help=argparse.SUPPRESS)
    for flag in ('--layers', '--seq', '--gen'):
        parser.add_argument(flag, type=int, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        result = CHILDREN[args.child](args)
        with open(args.out, 'w', encoding='utf-8') as f:
            json.dump(result, f)
        return 0
    return Smoke(args.chips, args.rehearse).run()


if __name__ == '__main__':
    sys.exit(main())
