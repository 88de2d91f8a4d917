"""pjit trainer: FSDP/TP/SP-sharded training step for the in-tree models.

Reference parity: the reference launches external trainers (HF+PyTorch/XLA at
``examples/tpu/v6e/train.py``, torchtune at ``llm/llama-3_1-finetuning``);
this module IS the trainer, built on the standard TPU recipe:

- One jitted train step: loss (fp32 logits CE) -> grad -> optax update,
  with in/out shardings derived from the model's logical axes, so FSDP is
  "params sharded over fsdp; XLA all-gathers per layer and reduce-scatters
  grads" — no wrapper classes.
- Per-layer rematerialization via the model's ``remat='block'`` policy.
- bf16 params/activations, fp32 optimizer moments (cast on update).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from skypilot_tpu.models import llama
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.utils.host import host_scalars


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip_norm: float = 1.0
    attn_impl: str = 'auto'
    moe_aux_weight: float = 0.01
    # Adam first-moment dtype: bfloat16 halves optimizer memory with
    # negligible quality impact (the noisy moment tolerates it; the
    # variance stays fp32) — lets ~1B-param models train on one 16GB chip.
    mu_dtype: str = 'float32'
    # LoRA weight decay (applied to adapter leaves when the model config
    # has lora_rank > 0; the frozen base takes no updates at all, so
    # tc.weight_decay never touches it). 0.0 is the standard choice.
    lora_weight_decay: float = 0.0


def make_optimizer(tc: TrainConfig,
                   weight_decay: Optional[float] = None
                   ) -> optax.GradientTransformation:
    # Clamp warmup below the step budget: optax requires positive decay
    # span (a short --steps run with the default warmup would crash).
    warmup = min(tc.warmup_steps, max(tc.total_steps - 1, 0))
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=tc.learning_rate,
        warmup_steps=warmup, decay_steps=tc.total_steps,
        end_value=tc.learning_rate * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip_norm),
        optax.adamw(schedule, b1=tc.b1, b2=tc.b2,
                    weight_decay=(tc.weight_decay if weight_decay is None
                                  else weight_decay),
                    mu_dtype=jnp.dtype(tc.mu_dtype)),
    )


def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            attn_impl: str = 'auto', moe_aux_weight: float = 0.01
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal LM loss (+ MoE load-balancing aux).

    batch: inputs [b,s], targets [b,s], mask [b,s]."""
    logits, _, aux = llama.forward(params, batch['inputs'], cfg,
                                   attn_impl=attn_impl, return_aux=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tgt = batch['targets']
    token_ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    mask = batch.get('mask')
    if mask is None:
        mask = jnp.ones_like(tgt, jnp.float32)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = -(token_ll * mask).sum() / denom
    loss = ce + moe_aux_weight * aux
    metrics = {
        'loss': ce,
        'moe_aux_loss': aux,
        'tokens': mask.sum(),
        'accuracy': ((jnp.argmax(logits, -1) == tgt) * mask).sum() / denom,
    }
    return loss, metrics


class Trainer:
    """Owns the mesh, sharded state, and the compiled train step."""

    def __init__(self, cfg: ModelConfig,
                 mesh_spec: Optional[mesh_lib.MeshSpec] = None,
                 train_config: Optional[TrainConfig] = None,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[mesh_lib.LogicalRules] = None):
        self.cfg = cfg
        self.tc = train_config or TrainConfig()
        if mesh is None:
            # Multi-host launched jobs: join the jax.distributed gang
            # BEFORE reading device_count, else each host builds a
            # disconnected local mesh. No-op outside a launched job.
            mesh_lib.initialize_distributed_from_env()
            # Default spec honors the launch env contract (multi-slice
            # jobs set SKYTPU_NUM_SLICES; standalone use sees 1 slice).
            spec = mesh_spec or mesh_lib.spec_from_env()
            mesh = mesh_lib.make_mesh(spec)
        self.mesh = mesh
        self.rules = rules or mesh_lib.DEFAULT_RULES
        # LoRA configs train ONLY the adapter subtree: grads, updates,
        # and optimizer moments are adapter-sized (the memory win that
        # makes fine-tuning a 7B on one chip possible); the base is
        # frozen bit-for-bit.
        self._lora = cfg.lora_enabled
        self.optimizer = make_optimizer(
            self.tc, weight_decay=(self.tc.lora_weight_decay
                                   if self._lora else None))

        self._params_shape = jax.eval_shape(
            functools.partial(llama.init_params, cfg=cfg),
            jax.random.PRNGKey(0))
        self.param_shardings = mesh_lib.tree_shardings(
            llama.param_logical_axes(cfg), mesh, self.rules,
            shapes=self._params_shape)
        if self._lora:
            self._trainable_shape = self._params_shape['layers']['lora']
            self._trainable_shardings = \
                self.param_shardings['layers']['lora']
        else:
            self._trainable_shape = self._params_shape
            self._trainable_shardings = self.param_shardings
        self.state_shardings = self._state_shardings()
        self.batch_sharding = mesh_lib.batch_sharding(mesh, self.rules)

        self._init_jit = jax.jit(
            self._init_fn, out_shardings=self.state_shardings)
        self._step_jit = jax.jit(
            self._step_fn,
            in_shardings=(self.state_shardings,
                          {'inputs': self.batch_sharding,
                           'targets': self.batch_sharding,
                           'mask': self.batch_sharding}),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,))

    # ---------------- sharding derivation ----------------
    def _state_shardings(self) -> TrainState:
        """Derive opt_state shardings: any subtree with the same structure
        as the TRAINABLE tree (full params, or the LoRA adapter subtree)
        gets that tree's shardings (adam mu/nu); everything else is
        replicated (scalars like count)."""
        trainable_shape = self._trainable_shape
        opt_shape = jax.eval_shape(self.optimizer.init, trainable_shape)
        trainable_treedef = jax.tree.structure(trainable_shape)
        replicated = NamedSharding(self.mesh, PartitionSpec())

        def map_opt(node):
            if jax.tree.structure(node) == trainable_treedef:
                return self._trainable_shardings
            return jax.tree.map(lambda _: replicated, node)

        opt_shardings = jax.tree.map(
            map_opt, opt_shape,
            is_leaf=lambda n: (jax.tree.structure(n) == trainable_treedef
                               if not isinstance(n, jax.ShapeDtypeStruct)
                               else True))
        return TrainState(step=replicated, params=self.param_shardings,
                          opt_state=opt_shardings)

    # ---------------- init / step ----------------
    def _init_fn(self, rng: jax.Array) -> TrainState:
        params = llama.init_params(rng, self.cfg)
        opt_state = self.optimizer.init(self._trainable(params))
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt_state)

    def _trainable(self, params):
        from skypilot_tpu.models import lora as lora_lib
        return lora_lib.split_lora(params) if self._lora else params

    def _step_fn(self, state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if self._lora:
            from skypilot_tpu.models import lora as lora_lib

            def lora_loss(lora_tree, batch):
                return loss_fn(lora_lib.with_lora(state.params, lora_tree),
                               batch, self.cfg, self.tc.attn_impl,
                               self.tc.moe_aux_weight)

            trainable = lora_lib.split_lora(state.params)
            (_, metrics), grads = jax.value_and_grad(
                lora_loss, has_aux=True)(trainable, batch)
            updates, new_opt = self.optimizer.update(grads, state.opt_state,
                                                     trainable)
            new_params = lora_lib.with_lora(
                state.params, optax.apply_updates(trainable, updates))
        else:
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch, self.cfg,
                                       self.tc.attn_impl,
                                       self.tc.moe_aux_weight)
            updates, new_opt = self.optimizer.update(grads, state.opt_state,
                                                     state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics['grad_norm'] = optax.global_norm(grads)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt), metrics

    def init(self, rng: jax.Array) -> TrainState:
        with jax.set_mesh(self.mesh):
            return self._init_jit(rng)

    def init_from_pretrained(self, path: str) -> TrainState:
        """Start training from an HF checkpoint (fine-tuning entry):
        params come from the checkpoint (sharded per the param rules),
        optimizer state is fresh. Under LoRA the checkpoint carries no
        adapters — fresh ones are initialized (delta starts at 0)."""
        from skypilot_tpu.models import weights
        params = weights.load_hf_params(path, self.cfg)
        if self._lora and 'lora' not in params['layers']:
            from skypilot_tpu.models import lora as lora_lib
            params = lora_lib.with_lora(
                params,
                lora_lib.init_lora_layers(jax.random.PRNGKey(0), self.cfg))
        params = jax.device_put(params, self.param_shardings)

        def init_opt(p):
            return TrainState(step=jnp.zeros((), jnp.int32), params=p,
                              opt_state=self.optimizer.init(
                                  self._trainable(p)))

        with jax.set_mesh(self.mesh):
            return jax.jit(init_opt,
                           out_shardings=self.state_shardings)(params)

    def step(self, state: TrainState, batch: Dict[str, jax.Array]
             ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        if 'mask' not in batch:
            batch = dict(batch,
                         mask=jnp.ones_like(batch['targets'], jnp.float32))
        with jax.set_mesh(self.mesh):
            return self._step_jit(state, batch)

    def fit(self, state: TrainState, data_iter, num_steps: int,
            callbacks=None) -> TrainState:
        """Drive ``num_steps`` steps with callback instrumentation
        (``skypilot_tpu.callbacks``) — the hook the benchmark subsystem
        reads step timing from."""
        from skypilot_tpu.callbacks.base import BaseCallback, CallbackList
        if isinstance(callbacks, CallbackList):
            cbs = callbacks
        elif isinstance(callbacks, BaseCallback):
            cbs = CallbackList([callbacks])
        else:
            cbs = CallbackList(callbacks)
        for _ in range(num_steps):
            batch = next(data_iter)
            step_no = int(state.step)
            cbs.on_step_begin(step_no)
            state, metrics = self.step(state, batch)
            # Block so the timer measures compute, not dispatch.
            metrics = host_scalars(metrics)
            cbs.on_step_end(step_no, metrics)
        cbs.on_train_end()
        return state

    # ---------------- checkpointing ----------------
    def save_checkpoint(self, path: str, state: TrainState) -> None:
        """Orbax checkpoint (async-capable); the managed-jobs recovery
        contract re-mounts the same bucket path and calls restore."""
        import orbax.checkpoint as ocp
        ckpt = ocp.StandardCheckpointer()
        ckpt.save(path, state, force=True)
        ckpt.wait_until_finished()

    def restore_checkpoint(self, path: str,
                           like: Optional[TrainState] = None) -> TrainState:
        import orbax.checkpoint as ocp
        ckpt = ocp.StandardCheckpointer()
        if like is None:
            like = jax.eval_shape(self._init_fn, jax.random.PRNGKey(0))
            like = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                like, self.state_shardings)
        return ckpt.restore(path, like)

    # ---------------- LoRA adapter checkpoints ----------------
    def save_adapter(self, path: str, state: TrainState) -> None:
        """Adapter-only checkpoint: the LoRA subtree, megabytes instead
        of the base's gigabytes (the artifact a fine-tuning job ships)."""
        from skypilot_tpu.models import lora as lora_lib
        if not self._lora:
            raise ValueError('save_adapter requires a LoRA config '
                             '(cfg.lora_rank > 0)')
        import orbax.checkpoint as ocp
        ckpt = ocp.StandardCheckpointer()
        ckpt.save(path, lora_lib.split_lora(state.params), force=True)
        ckpt.wait_until_finished()
        # Sidecar metadata: rank is recoverable from the tree, but a
        # wrong lora_alpha at serve time would silently mis-scale the
        # fold — record the full adapter config so load can validate.
        import json
        with open(self._adapter_meta_path(path), 'w',
                  encoding='utf-8') as f:
            json.dump({'lora_rank': self.cfg.lora_rank,
                       'lora_alpha': self.cfg.lora_alpha,
                       'lora_targets': list(self.cfg.lora_targets)}, f)

    @staticmethod
    def _adapter_meta_path(path: str) -> str:
        return path.rstrip('/') + '.lora.json'

    def load_adapter(self, path: str, state: TrainState) -> TrainState:
        """Swap a saved adapter into an existing state (base unchanged);
        optimizer moments are NOT restored — use restore_checkpoint to
        resume training exactly."""
        from skypilot_tpu.models import lora as lora_lib
        if not self._lora:
            raise ValueError('load_adapter requires a LoRA config '
                             '(cfg.lora_rank > 0)')
        import json
        import os
        meta_path = self._adapter_meta_path(path)
        if os.path.exists(meta_path):
            with open(meta_path, encoding='utf-8') as f:
                meta = json.load(f)
            mine = {'lora_rank': self.cfg.lora_rank,
                    'lora_alpha': self.cfg.lora_alpha,
                    'lora_targets': list(self.cfg.lora_targets)}
            if meta != mine:
                raise ValueError(
                    f'adapter at {path} was trained with {meta}, but '
                    f'this trainer is configured with {mine}; a '
                    f'mismatched alpha/rank would silently mis-scale '
                    f'the fold')
        import orbax.checkpoint as ocp
        ckpt = ocp.StandardCheckpointer()
        like = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            self._trainable_shape, self._trainable_shardings)
        adapter = ckpt.restore(path, like)
        return state._replace(
            params=lora_lib.with_lora(state.params, adapter))
