"""Ulysses (head-scatter) sequence parallelism: exact equivalence with
single-device attention on the virtual CPU mesh, GQA/MQA handling, and
the trainer integration (attn_impl='ulysses')."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops.attention import reference_attention
from skypilot_tpu.ops.ulysses import ulysses_attention
from skypilot_tpu.parallel import mesh as mesh_lib

pytestmark = pytest.mark.slow

jax.config.update('jax_platforms', 'cpu')


def _mesh(sp):
    spec = mesh_lib.MeshSpec(dp=1, fsdp=8 // sp // 1, sp=sp, tp=1)
    return mesh_lib.make_mesh(spec, jax.devices()[:8])


def _rand(b, s, h, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, s, h, d), jnp.float32),
            jax.random.normal(ks[2], (b, s, h, d), jnp.float32))


@pytest.mark.parametrize('causal', [True, False])
def test_matches_reference(causal):
    b, s, h, d = 2, 64, 8, 16
    q, k, v = _rand(b, s, h, d)
    mesh = _mesh(sp=4)
    with jax.set_mesh(mesh):
        out = ulysses_attention(q, k, v, mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gqa_grouping_preserved():
    b, s, h, hkv, d = 2, 32, 8, 4, 16
    q, _, _ = _rand(b, s, h, d)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    k = jax.random.normal(ks[0], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    mesh = _mesh(sp=4)            # hkv % sp == 0: grouped form survives
    with jax.set_mesh(mesh):
        out = ulysses_attention(q, k, v, mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_mqa_expands_kv():
    b, s, h, d = 2, 32, 8, 16
    q, _, _ = _rand(b, s, h, d)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    k = jax.random.normal(ks[0], (b, s, 1, d), jnp.float32)
    v = jax.random.normal(ks[1], (b, s, 1, d), jnp.float32)
    mesh = _mesh(sp=4)            # hkv=1 < sp: expansion path
    with jax.set_mesh(mesh):
        out = ulysses_attention(q, k, v, mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_rejects_indivisible_heads():
    b, s, h, d = 2, 32, 6, 16
    q, k, v = _rand(b, s, h, d)
    mesh = _mesh(sp=4)
    with pytest.raises(ValueError, match='n_heads'):
        with jax.set_mesh(mesh):
            ulysses_attention(q, k, v, mesh, causal=True)


def test_trainer_attn_impl_ulysses():
    """Training with attn_impl='ulysses' on an sp mesh converges like
    the xla path (same loss after one step on identical data)."""
    from skypilot_tpu.models import configs
    from skypilot_tpu.train.trainer import TrainConfig, Trainer
    spec = mesh_lib.MeshSpec(dp=1, fsdp=2, sp=2, tp=2)
    mesh = mesh_lib.make_mesh(spec, jax.devices()[:8])
    losses = {}
    for impl in ('xla', 'ulysses'):
        tr = Trainer(configs.TINY, mesh=mesh,
                     train_config=TrainConfig(warmup_steps=1,
                                              total_steps=4,
                                              attn_impl=impl))
        state = tr.init(jax.random.PRNGKey(0))
        data = {'inputs': jnp.ones((4, 32), jnp.int32),
                'targets': jnp.ones((4, 32), jnp.int32)}
        _, metrics = tr.step(state, data)
        losses[impl] = float(metrics['loss'])
    assert abs(losses['xla'] - losses['ulysses']) < 1e-3, losses


def test_custom_scale_honored():
    b, s, h, d = 2, 32, 8, 16
    q, k, v = _rand(b, s, h, d, seed=4)
    mesh = _mesh(sp=4)
    with jax.set_mesh(mesh):
        out = ulysses_attention(q, k, v, mesh, causal=True, scale=2.0)
    ref = reference_attention(q, k, v, causal=True, scale=2.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
