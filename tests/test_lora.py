"""LoRA fine-tuning tests (virtual 8-device CPU mesh).

Reference capability anchor: ``llm/llama-3_1-finetuning/lora.yaml``
(torchtune LoRA recipe); here the adapters are in-tree (models/lora.py)
and trained by the pjit trainer with a frozen base.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs, llama, lora
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.train.trainer import TrainConfig, Trainer

pytestmark = pytest.mark.slow

TINY_LORA = dataclasses.replace(
    configs.TINY, lora_rank=4, lora_alpha=8.0,
    lora_targets=('wq', 'wk', 'wv', 'wo', 'w_gate', 'w_up', 'w_down'))


def _batch(rng, b=8, s=16, vocab=250):
    toks = jax.random.randint(rng, (b, s + 1), 0, vocab)
    return {'inputs': toks[:, :-1].astype(jnp.int32),
            'targets': toks[:, 1:].astype(jnp.int32)}


class TestAdapterMath:

    def test_zero_init_delta(self):
        """b = 0 at init => adapted forward == base forward exactly."""
        base = llama.init_params(jax.random.PRNGKey(0), configs.TINY)
        adapted = llama.init_params(jax.random.PRNGKey(0), TINY_LORA)
        toks = jnp.arange(16, dtype=jnp.int32)[None, :] % 250
        lb, _ = llama.forward(base, toks, configs.TINY)
        la, _ = llama.forward(adapted, toks, TINY_LORA)
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(la))

    def test_merge_matches_unmerged(self):
        """After perturbing b, merged weights reproduce the low-rank
        path (the serving contract). fp32 so the comparison is tight —
        in bf16 the fold adds one rounding of (W + delta)."""
        f32 = dataclasses.replace(TINY_LORA, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), f32)
        lt = lora.split_lora(params)
        keys = iter(jax.random.split(jax.random.PRNGKey(7), 20))
        lt = jax.tree.map(
            lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape,
                                                   x.dtype), lt)
        params = lora.with_lora(params, lt)
        toks = jnp.arange(16, dtype=jnp.int32)[None, :] % 250
        unmerged, _ = llama.forward(params, toks, f32)
        mcfg, mparams = lora.merge(f32, params)
        assert mcfg.lora_rank == 0
        assert 'lora' not in mparams['layers']
        merged, _ = llama.forward(mparams, toks, mcfg)
        np.testing.assert_allclose(np.asarray(unmerged),
                                   np.asarray(merged), atol=1e-4)
        # and the delta is genuinely nonzero
        f32_base = dataclasses.replace(configs.TINY, dtype=jnp.float32)
        base_only, _ = llama.forward(
            llama.init_params(jax.random.PRNGKey(0), f32_base),
            toks, f32_base)
        assert not np.allclose(np.asarray(merged), np.asarray(base_only),
                               atol=1e-3)

    def test_moe_mlp_targets_rejected(self):
        bad = dataclasses.replace(configs.TINY_MOE, lora_rank=4,
                                  lora_targets=('wq', 'w_up'))
        with pytest.raises(ValueError, match='dense FFN'):
            lora.resolve_targets(bad)

    def test_unknown_target_rejected(self):
        bad = dataclasses.replace(configs.TINY, lora_rank=4,
                                  lora_targets=('wx',))
        with pytest.raises(ValueError, match='unknown LoRA target'):
            lora.resolve_targets(bad)


class TestLoraTraining:

    def test_base_frozen_adapters_move_loss_drops(self):
        trainer = Trainer(TINY_LORA,
                          mesh_spec=mesh_lib.MeshSpec(dp=8),
                          train_config=TrainConfig(learning_rate=5e-2,
                                                   warmup_steps=2,
                                                   total_steps=40,
                                                   attn_impl='xla'))
        state = trainer.init(jax.random.PRNGKey(0))
        base_before = jax.tree.map(
            np.asarray, {k: v for k, v in state.params['layers'].items()
                         if k != 'lora'})
        embed_before = np.asarray(state.params['embed'])
        rng = jax.random.PRNGKey(1)
        batch = _batch(rng)                    # one batch: overfit it
        first = last = None
        for _ in range(30):
            state, metrics = trainer.step(state, batch)
            last = float(metrics['loss'])
            if first is None:
                first = last
        assert last < first * 0.9, (first, last)
        # Base exactly untouched (bit-for-bit), adapters moved.
        np.testing.assert_array_equal(embed_before,
                                      np.asarray(state.params['embed']))
        for k, v in base_before.items():
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(a,
                                                           np.asarray(b)),
                v, state.params['layers'][k])
        b_leaf = np.asarray(state.params['layers']['lora']['wq']['b'])
        assert np.abs(b_leaf).max() > 0

    def test_optimizer_state_is_adapter_sized(self):
        trainer = Trainer(TINY_LORA, mesh_spec=mesh_lib.MeshSpec(dp=8),
                          train_config=TrainConfig(attn_impl='xla'))
        state = trainer.init(jax.random.PRNGKey(0))
        opt_elems = sum(x.size for x in jax.tree.leaves(state.opt_state)
                        if hasattr(x, 'size'))
        param_elems = sum(x.size for x in jax.tree.leaves(state.params))
        lora_elems = sum(
            x.size for x in jax.tree.leaves(
                lora.split_lora(state.params)))
        # mu + nu (+ a few scalars): ~2x the adapters, nowhere near 2x
        # the full params.
        assert opt_elems < 2 * lora_elems + 64
        assert opt_elems < param_elems

    def test_tp_mesh_step_matches_dp_mesh(self):
        tc = TrainConfig(learning_rate=1e-2, warmup_steps=1,
                         total_steps=10, attn_impl='xla')
        batch = _batch(jax.random.PRNGKey(3))
        losses = []
        for spec in (mesh_lib.MeshSpec(dp=8),
                     mesh_lib.MeshSpec(tp=2, fsdp=2, dp=2)):
            trainer = Trainer(TINY_LORA, mesh_spec=spec, train_config=tc)
            state = trainer.init(jax.random.PRNGKey(0))
            state, m = trainer.step(state, batch)
            state, m = trainer.step(state, batch)
            losses.append(float(m['loss']))
        assert abs(losses[0] - losses[1]) < 1e-3, losses

    def test_adapter_checkpoint_roundtrip(self, tmp_path):
        trainer = Trainer(TINY_LORA, mesh_spec=mesh_lib.MeshSpec(dp=8),
                          train_config=TrainConfig(learning_rate=5e-2,
                                                   warmup_steps=1,
                                                   total_steps=10,
                                                   attn_impl='xla'))
        state = trainer.init(jax.random.PRNGKey(0))
        state, _ = trainer.step(state, _batch(jax.random.PRNGKey(4)))
        trainer.save_adapter(str(tmp_path / 'adapter'), state)
        fresh = trainer.init(jax.random.PRNGKey(9))
        restored = trainer.load_adapter(str(tmp_path / 'adapter'), fresh)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            lora.split_lora(state.params),
            lora.split_lora(restored.params))
        # base of `fresh` untouched by the adapter swap
        np.testing.assert_array_equal(
            np.asarray(fresh.params['embed']),
            np.asarray(restored.params['embed']))
        # sidecar metadata guards against a mis-configured serve-side
        # trainer (wrong alpha would silently mis-scale the fold)
        wrong = Trainer(dataclasses.replace(TINY_LORA, lora_alpha=999.0),
                        mesh_spec=mesh_lib.MeshSpec(dp=8),
                        train_config=TrainConfig(attn_impl='xla'))
        with pytest.raises(ValueError, match='mis-scale'):
            wrong.load_adapter(str(tmp_path / 'adapter'),
                               wrong.init(jax.random.PRNGKey(0)))


class TestLoraServing:

    def test_engine_auto_merges(self):
        """The engine accepts a LoRA param tree and serves its merged
        model: what it emits is the choice of the plain forward of the
        offline-merged weights."""
        import greedy_oracle
        from skypilot_tpu.inference.paged import PagedInferenceEngine
        params = llama.init_params(jax.random.PRNGKey(0), TINY_LORA)
        lt = lora.split_lora(params)
        keys = iter(jax.random.split(jax.random.PRNGKey(7), 20))
        lt = jax.tree.map(
            lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape,
                                                   x.dtype), lt)
        params = lora.with_lora(params, lt)
        mcfg, mparams = lora.merge(TINY_LORA, params)

        eng = PagedInferenceEngine(TINY_LORA, params, max_batch=2,
                                   max_seq=64, attn_impl='xla')
        assert eng.cfg.lora_rank == 0
        (out,) = greedy_oracle.greedy(eng, [[1, 2, 3, 4]], 5)
        assert len(out) == 5
        greedy_oracle.assert_agrees(mcfg, mparams, [1, 2, 3, 4], out,
                                    what='auto-merged LoRA')

    def test_stock_config_with_adapters_rejected(self):
        """A trainer checkpoint served with the stock base config must
        fail loudly, not fold with a guessed (wrong) scale."""
        params = llama.init_params(jax.random.PRNGKey(0), TINY_LORA)
        with pytest.raises(ValueError, match='lora_rank'):
            lora.merge(configs.TINY, params)
        wrong_rank = dataclasses.replace(TINY_LORA, lora_rank=8)
        with pytest.raises(ValueError, match='adapter rank'):
            lora.merge(wrong_rank, params)

    def test_merge_rejects_quantized_base(self):
        from skypilot_tpu.models import quantization
        params = llama.init_params(jax.random.PRNGKey(0), TINY_LORA)
        qparams = quantization.quantize_params(params)
        with pytest.raises(ValueError, match='int8'):
            lora.merge(TINY_LORA, qparams)
