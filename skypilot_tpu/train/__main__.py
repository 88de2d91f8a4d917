"""``python -m skypilot_tpu.train`` — train a model on a text corpus.

The in-tree counterpart of the reference's training recipes (which shell
out to torchrun/HF scripts, e.g. ``llm/llama-3_1-finetuning/lora.yaml``):
one command that tokenizes/packs a corpus, builds the sharded trainer,
and runs with automatic checkpoint-resume — the managed-jobs recovery
contract (relaunch on a fresh cluster with the same mounted checkpoint
bucket resumes exactly where training stopped, SURVEY §5 checkpoint/
resume).

Example (and ``examples/train_llama_job.yaml``):

    python -m skypilot_tpu.train --model llama3-1b --data gs://bkt/corpus \
        --batch 8 --seq 2048 --steps 5000 --ckpt-dir /ckpt/llama \
        --save-every 500
"""
from __future__ import annotations

import argparse
import json
import os
import time

from skypilot_tpu.utils.host import host_scalars


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(prog='python -m skypilot_tpu.train')
    parser.add_argument('--model', default='tiny',
                        help='preset config name (models/configs.py)')
    parser.add_argument('--data', required=True,
                        help='corpus: text file/dir/glob or gs:// URI')
    parser.add_argument('--tokenizer', default=None,
                        help='HF tokenizer dir (default: byte tokenizer)')
    parser.add_argument('--batch', type=int, default=8,
                        help='per-host batch size')
    parser.add_argument('--seq', type=int, default=512)
    parser.add_argument('--steps', type=int, default=100,
                        help='total optimizer steps (training stops at '
                             'this step, including restored progress)')
    parser.add_argument('--lr', type=float, default=3e-4)
    parser.add_argument('--warmup-steps', type=int, default=100)
    parser.add_argument('--ckpt-dir', default=None,
                        help='checkpoint dir (orbax); auto-resumes if a '
                             'checkpoint exists — the managed-jobs '
                             'MOUNT-bucket recovery contract')
    parser.add_argument('--save-every', type=int, default=500)
    parser.add_argument('--from-pretrained', default=None,
                        help='HF checkpoint dir to fine-tune from')
    parser.add_argument('--lora-rank', type=int, default=0,
                        help='> 0 enables LoRA fine-tuning: the base is '
                             'frozen, only low-rank adapters train '
                             '(models/lora.py)')
    parser.add_argument('--lora-alpha', type=float, default=16.0)
    parser.add_argument('--lora-targets', default='wq,wk,wv,wo',
                        help='comma-separated projections to adapt')
    parser.add_argument('--adapter-out', default=None,
                        help='where to save the final adapter-only '
                             'checkpoint (LoRA runs)')
    parser.add_argument('--tp', type=int, default=None)
    parser.add_argument('--sp', type=int, default=1)
    parser.add_argument('--attn-impl', default='auto')
    parser.add_argument('--mu-dtype', default='float32')
    parser.add_argument('--log-every', type=int, default=10)
    args = parser.parse_args(argv)

    import jax

    from skypilot_tpu.models import configs
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train.data import TokenStream, packed_batches
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    cfg = configs.get_config(args.model)
    if args.lora_rank > 0:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, lora_rank=args.lora_rank, lora_alpha=args.lora_alpha,
            lora_targets=tuple(
                t.strip() for t in args.lora_targets.split(',') if t))
    trainer = Trainer(
        cfg,
        mesh_spec=(mesh_lib.spec_from_env(tp=args.tp, sp=args.sp)
                   if (args.tp or args.sp > 1) else None),
        train_config=TrainConfig(learning_rate=args.lr,
                                 warmup_steps=args.warmup_steps,
                                 total_steps=args.steps,
                                 attn_impl=args.attn_impl,
                                 mu_dtype=args.mu_dtype))

    # First line: where this runs and which attention the step traces
    # (a trainer that came up on the CPU must not pass for one on the
    # chip). chip_smoke.py reads it.
    from skypilot_tpu.ops.attention import flash_selected
    from skypilot_tpu.telemetry import device as device_lib
    attention = args.attn_impl
    if attention in ('auto', 'xla', 'flash'):
        attention = ('flash' if flash_selected(args.attn_impl, args.seq,
                                               cfg.head_dim) else 'xla')
    print('[train] ' + json.dumps({
        'device': device_lib.device_identity(),
        'mesh': mesh_lib.mesh_axis_sizes(trainer.mesh),
        'attention': attention}), flush=True)

    data_axis = mesh_lib.data_axis_size(trainer.mesh)
    if args.batch % data_axis:
        raise SystemExit(
            f'--batch {args.batch} must be divisible by the mesh data-'
            f'parallel degree {data_axis} (slice*dp*fsdp); pick a '
            f'multiple or reduce the mesh with --tp/--sp')

    # ---- state: restore > fine-tune > fresh ----
    start_step = 0
    state = None
    latest = _latest_checkpoint(args.ckpt_dir)
    if latest is not None:
        state = trainer.restore_checkpoint(latest)
        start_step = int(state.step)
        print(f'[train] resumed from {latest} at step {start_step}',
              flush=True)
    elif args.from_pretrained:
        state = trainer.init_from_pretrained(args.from_pretrained)
        print(f'[train] initialized from {args.from_pretrained}',
              flush=True)
    else:
        state = trainer.init(jax.random.PRNGKey(0))

    # ---- data: deterministic resume = start at the restored step ----
    stream = TokenStream(args.data,
                         load_tokenizer_or_none(args.tokenizer,
                                                cfg.vocab_size))
    # Per-process rank: under a multi-host launch each host feeds its
    # own stride of the stream (jax process == dp shard of the batch).
    it = packed_batches(stream, batch=args.batch, seq=args.seq,
                        dp_rank=jax.process_index(),
                        dp_size=jax.process_count(),
                        start_step=start_step)

    t0 = time.time()
    last_logged = start_step
    for step in range(start_step, args.steps):
        state, metrics = trainer.step(state, _to_jnp(next(it)))
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            # The readback is what waits for the device: taken before
            # the clock is read, or tok_s times the enqueue.
            m = host_scalars(metrics)   # explicit readback (GC202)
            dt = time.time() - t0
            window = step + 1 - last_logged     # actual steps elapsed
            print(json.dumps({
                'step': step + 1,
                'loss': round(m['loss'], 4),
                'accuracy': round(m['accuracy'], 4),
                'tok_s': round(args.batch * args.seq * window
                               / max(dt, 1e-9), 1),
            }), flush=True)
            t0 = time.time()
            last_logged = step + 1
        if (args.ckpt_dir and args.save_every
                and (step + 1) % args.save_every == 0
                and step + 1 < args.steps):
            _save(trainer, state, args.ckpt_dir)
    if args.ckpt_dir:
        _save(trainer, state, args.ckpt_dir)
    if args.lora_rank > 0 and args.adapter_out:
        trainer.save_adapter(os.path.abspath(args.adapter_out), state)
        print(f'[train] adapter saved: {args.adapter_out}', flush=True)
    print('[train] memory ' + json.dumps(device_lib.device_memory()),
          flush=True)
    print(f'[train] done at step {int(state.step)}', flush=True)


def load_tokenizer_or_none(path, vocab_size):
    from skypilot_tpu.models.tokenizer import load_tokenizer
    return load_tokenizer(path, model_vocab_size=vocab_size)


def _to_jnp(batch):
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _save(trainer, state, ckpt_dir: str) -> None:
    """Write step-addressed orbax checkpoints + a LATEST pointer.
    Step-addressed dirs make the save atomic from the reader's side: the
    pointer flips only after orbax finishes."""
    step = int(state.step)
    path = os.path.abspath(os.path.join(ckpt_dir, f'step_{step}'))
    trainer.save_checkpoint(path, state)
    tmp = os.path.join(ckpt_dir, 'LATEST.tmp')
    with open(tmp, 'w', encoding='utf-8') as f:
        f.write(f'step_{step}')
    os.replace(tmp, os.path.join(ckpt_dir, 'LATEST'))
    print(f'[train] checkpoint saved: {path}', flush=True)


def _latest_checkpoint(ckpt_dir):
    if not ckpt_dir:
        return None
    pointer = os.path.join(ckpt_dir, 'LATEST')
    if not os.path.exists(pointer):
        return None
    with open(pointer, encoding='utf-8') as f:
        name = f.read().strip()
    path = os.path.abspath(os.path.join(ckpt_dir, name))
    return path if os.path.isdir(path) else None


if __name__ == '__main__':
    main()
