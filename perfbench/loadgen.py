"""The load generator: a process of its own that never imports JAX.

It reads a spec (a JSON file named on the command line), builds the
schedule with ``traffic.py``, warms the HTTP path, and on ``go`` sends
every request when it is DUE, whether or not earlier ones have finished
(open loop). It timestamps what it receives and writes its records to the
spec's ``out`` path. The benchmark's process turns the records into
metrics (``runners/serve.py``): nothing here is a metric yet.

Timestamp arithmetic after ``bench.py`` ``_serving_http_measure``; unlike
it, latency counts from when a request was due (not from the send), the
lateness of the sends is reported, and the clients do not share the
server's interpreter lock.
"""
from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
import urllib.request

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
from perfbench import traffic  # noqa: E402


def stream_generate(host, port, body, deadline, t_lo, t_hi):
    """POST /generate with stream: true; returns what was seen, by the
    client's clock: first and last token times, tokens, tokens inside
    [t_lo, t_hi), and an error string or None."""
    rec = {'sent': time.time(), 'first': None, 'last': None, 'tokens': 0,
           'tokens_in_window': 0, 'error': None, 'status': None}
    conn = http.client.HTTPConnection(host, port, timeout=max(
        1.0, deadline - time.time()))
    try:
        conn.connect()
        sock = conn.sock     # the response takes the connection over
        conn.request('POST', '/generate', body,
                     {'Content-Type': 'application/json'})
        resp = conn.getresponse()
        rec['status'] = resp.status
        if resp.status != 200:
            rec['error'] = f'HTTP {resp.status}'
            return rec
        while True:
            left = deadline - time.time()
            if left <= 0:
                rec['error'] = 'not finished at the deadline'
                return rec
            sock.settimeout(left)
            line = resp.readline()
            if not line:
                rec['error'] = rec['error'] or 'stream ended without done'
                return rec
            if not line.startswith(b'data:'):
                continue
            now = time.time()
            ev = json.loads(line[5:])
            if 'token' in ev:
                if rec['first'] is None:
                    rec['first'] = now
                rec['last'] = now
                rec['tokens'] += 1
                if t_lo <= now < t_hi:
                    rec['tokens_in_window'] += 1
            elif 'error' in ev:
                rec['error'] = str(ev['error'])
                return rec
            elif ev.get('done'):
                return rec
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec['error'] = f'{type(e).__name__}: {e}'
        return rec
    finally:
        conn.close()


def body_of(prompt, output_tokens):
    return json.dumps({'prompt': prompt, 'max_new_tokens': output_tokens,
                       'stream': True, 'temperature': 0.0}).encode()


def sample_metrics(base, t_hi, period, samples):
    """/metrics?format=json every ``period`` s until the window's end."""
    keys = ('active_slots', 'max_batch', 'queue_depth',
            'kv_pool_preemptions', 'kv_pool_tokens_used',
            'queue_wait_ms_median', 'prefill_inflight')
    while time.time() < t_hi:
        t = time.time()
        try:
            with urllib.request.urlopen(base + '/metrics?format=json',
                                        timeout=5) as r:
                m = json.load(r)
            samples.append(dict({k: m[k] for k in keys}, t=t))
        except (OSError, ValueError, KeyError) as e:
            samples.append({'t': t, 'error': f'{type(e).__name__}: {e}'})
        time.sleep(max(0.0, period - (time.time() - t)))


def main() -> int:
    with open(sys.argv[1], encoding='utf-8') as f:
        spec = json.load(f)
    host, port = spec['host'], spec['port']
    base = f'http://{host}:{port}'
    seconds, grace = spec['seconds'], spec['grace_s']
    reqs = traffic.schedule(spec['mix'], spec['seed'], seconds,
                            spec.get('rate_per_s'))
    bodies = [body_of(traffic.prompt_ids(r, spec['vocab_size']),
                      r.output_tokens) for r in reqs]
    # Warm the HTTP path (handler threads, SSE) with the mix's smallest
    # request; the programs were warmed before the server came up.
    small = traffic.Request(0.0, spec['mix']['prompt_tokens']['min'],
                            spec['mix']['output_tokens']['min'], 1)
    warm_body = body_of(traffic.prompt_ids(small, spec['vocab_size']),
                        small.output_tokens)
    far = time.time() + 600
    for _ in range(spec['warmup_requests']):
        rec = stream_generate(host, port, warm_body, far, 0, 0)
        if rec['error'] or rec['tokens'] != small.output_tokens:
            print(json.dumps({'event': 'warmup_failed', 'rec': rec}),
                  flush=True)
            return 1
    print(json.dumps({'event': 'ready', 'requests': len(reqs)}), flush=True)
    if sys.stdin.readline().strip() != 'go':
        return 1
    t0 = time.time() + 0.2
    t_hi = t0 + seconds
    print(json.dumps({'event': 'start', 't0': t0}), flush=True)

    records = [None] * len(reqs)
    samples = []
    threads = []
    if spec['sample_period_s']:
        th = threading.Thread(target=sample_metrics, args=(
            base, t_hi, spec['sample_period_s'], samples))
        th.start()
        threads.append(th)

    def one(i):
        records[i] = stream_generate(host, port, bodies[i], t_hi + grace,
                                     t0, t_hi)

    for i, r in enumerate(reqs):
        wait = t0 + r.due_s - time.time()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(i,))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    out = {'t0': t0, 'seconds': seconds, 'samples': samples, 'requests': [
        dict(rec, due=t0 + r.due_s, prompt_tokens=r.prompt_tokens,
             output_tokens=r.output_tokens)
        for r, rec in zip(reqs, records)]}
    with open(spec['out'], 'w', encoding='utf-8') as f:
        json.dump(out, f)
    print(json.dumps({'event': 'done'}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
