"""The training runner: ``Trainer.step`` over ``train/data.py``'s
``TokenStream`` + ``packed_batches`` of a seeded corpus, in the loop of
``train/__main__.py``: the loss is read back every ``log_every`` steps,
and the window starts and ends on such a readback.

Timing shape after ``bench.py`` ``_train_step_bench`` (warm-up step, timed
steps ended by a readback); the data, the configuration and the peaks are
the benchmark's own.
"""
from __future__ import annotations

import math
import os
import statistics
import time

import numpy as np

from perfbench import trace, weights

FIRST_LOSS_ABOVE_LN_VOCAB = (-0.2, 1.0)


def write_corpus(path: str, seed: int, n_bytes: int) -> None:
    """Seeded text-like bytes with a skewed (Zipf) unigram: lowercase
    words, spaces and line ends. The byte tokenizer maps them to ids
    below 256."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    alphabet = np.frombuffer(b' etaoinshrdlcumwfgypbvkjxqz\n', np.uint8)
    p = 1.0 / np.arange(1, len(alphabet) + 1)
    data = rng.choice(alphabet, size=n_bytes, p=p / p.sum())
    with open(path, 'wb') as f:
        f.write(data.tobytes())


def run(ctx):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models.configs import ModelConfig
    from skypilot_tpu.ops.attention import flash_selected
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.telemetry import device as device_lib
    from skypilot_tpu.train.data import TokenStream, packed_batches
    from skypilot_tpu.train.trainer import TrainConfig, Trainer
    from skypilot_tpu.utils.host import host_scalars

    watch = device_lib.get_compile_watch()
    mix, job = ctx.mix, ctx.config['training']
    cfg = ModelConfig(**ctx.config['model'])
    batch, seq, log_every = job['batch'], mix['seq'], mix['log_every']
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshSpec(fsdp=job.get('fsdp', 1), tp=job.get('tp', 1)),
        jax.devices()[:ctx.cell['chips']])
    trainer = Trainer(
        cfg, mesh=mesh,
        train_config=TrainConfig(
            learning_rate=job['learning_rate'],
            warmup_steps=job['warmup_steps'],
            total_steps=job['total_steps'], mu_dtype=job['mu_dtype'],
            attn_impl=job['attn_impl']))
    ctx.log(f'trainer: mesh {mesh_lib.mesh_axis_sizes(trainer.mesh)}, '
            f'attention '
            f'{"flash" if flash_selected(job["attn_impl"], seq, cfg.head_dim) else "xla"}'
            f', batch {batch} x seq {seq}')
    state = trainer.init(weights.seed_key(ctx.seed))

    corpus = os.path.join(ctx.workdir, 'corpus.txt')
    write_corpus(corpus, ctx.seed, mix['corpus_bytes'])
    stream = TokenStream(corpus, vocab_size=cfg.vocab_size)
    batches = packed_batches(stream, batch=batch, seq=seq)

    waits = []

    def step(state):
        t = time.perf_counter()
        b = {k: jnp.asarray(v) for k, v in next(batches).items()}
        waits.append(time.perf_counter() - t)
        return trainer.step(state, b)

    # Warm-up: the step program, and the first loss for ``correct``.
    state, metrics = step(state)
    first_loss = host_scalars(metrics)['loss']
    ctx.log(f'first step loss {first_loss:.4f} (ln vocab '
            f'{math.log(cfg.vocab_size):.4f}), compiles {watch.count}')
    t = time.time()
    for _ in range(log_every - 1):
        state, metrics = step(state)
    host_scalars(metrics)
    chunk_s = (time.time() - t) * log_every / (log_every - 1)
    waits.clear()

    compiles_start = watch.count
    t0 = time.time()
    setup_s = t0 - ctx.t_start
    trace_dir = os.path.join(ctx.workdir, 'trace') if ctx.trace else None
    tracing, steps, t_end = False, 0, t0
    window_losses = []
    while t_end - t0 < ctx.seconds:
        # The traced part is the window's last readback interval: the
        # one that, at the pace so far, ends past the window.
        if (trace_dir and not tracing
                and t_end - t0 + 1.1 * chunk_s >= ctx.seconds):
            trace.start(trace_dir)
            tracing = True
        for _ in range(log_every):
            state, metrics = step(state)
        window_losses.append(host_scalars(metrics)['loss'])
        steps += log_every
        chunk_s, t_end = time.time() - t_end, time.time()
    if tracing:
        jax.profiler.stop_trace()
    elapsed = t_end - t0
    ctx.log(f'window: {steps} steps in {elapsed:.3f}s, losses '
            f'{[round(x, 4) for x in window_losses]}, compiles inside '
            f'{watch.count - compiles_start}')

    finite = all(math.isfinite(x) for x in [first_loss] + window_losses)
    ln_v = math.log(cfg.vocab_size)
    lo, hi = FIRST_LOSS_ABOVE_LN_VOCAB
    correct = (finite and lo <= first_loss - ln_v <= hi
               and statistics.fmean(window_losses[-5:]) < first_loss)
    return {
        'correct': correct, 'attempted': steps,
        'failed': sum(not math.isfinite(x) for x in window_losses)
        * log_every,
        'setup_s': setup_s,
        'train_tok_s': steps * batch * seq / elapsed / ctx.cell['chips'],
        'data_wait_s': list(waits), 'trace_dir': trace_dir,
        'seq': seq, 'first_loss': first_loss,
    }
