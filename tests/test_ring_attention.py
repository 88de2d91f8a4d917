"""Ring attention on the virtual 8-device CPU mesh: exactness vs the
single-device reference, GQA, causal/non-causal, and the trainer
integration the SURVEY §5 long-context mandate asks for."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs
from skypilot_tpu.ops.attention import reference_attention
from skypilot_tpu.ops.ring_attention import ring_attention
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.train.trainer import TrainConfig, Trainer

pytestmark = pytest.mark.slow


def _mesh(sp: int, dp: int = 1) -> jax.sharding.Mesh:
    spec = mesh_lib.MeshSpec(dp=dp, fsdp=8 // (sp * dp), sp=sp, tp=1)
    return mesh_lib.make_mesh(spec)


def _rand_qkv(b=4, s=32, h=4, hkv=4, d=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize('sp', [2, 4])
@pytest.mark.parametrize('causal', [True, False])
def test_matches_reference(sp, causal):
    mesh = _mesh(sp)
    q, k, v = _rand_qkv()
    ref = reference_attention(q, k, v, causal=causal)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gqa_grouped_heads():
    mesh = _mesh(sp=4)
    q, k, v = _rand_qkv(h=8, hkv=2)
    ref = reference_attention(q, k, v, causal=True)
    with jax.set_mesh(mesh):
        out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sp1_falls_back_to_reference():
    mesh = _mesh(sp=1)
    q, k, v = _rand_qkv()
    with jax.set_mesh(mesh):
        out = ring_attention(q, k, v, mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sharded_inputs_inside_jit():
    """The real call pattern: sharded global arrays, ring inside jit."""
    mesh = _mesh(sp=4, dp=2)
    q, k, v = _rand_qkv(b=4, s=64)
    qs = jax.device_put(q, jax.sharding.NamedSharding(
        mesh, mesh_lib.spec_for(('batch', 'seq', 'heads', 'head_dim'))))
    ref = reference_attention(q, k, v, causal=True)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=True))(qs, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


class TestTrainerIntegration:

    def _loss_after_step(self, attn_impl: str, sp: int) -> float:
        cfg = dataclasses.replace(configs.TINY, remat='none')
        trainer = Trainer(
            cfg,
            mesh_spec=mesh_lib.MeshSpec(dp=1, fsdp=8 // (sp * 2), sp=sp,
                                        tp=2),
            train_config=TrainConfig(warmup_steps=1, total_steps=4,
                                     attn_impl=attn_impl))
        state = trainer.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        data = rng.randint(0, 250, size=(8, 33))
        batch = {'inputs': jnp.asarray(data[:, :-1], jnp.int32),
                 'targets': jnp.asarray(data[:, 1:], jnp.int32)}
        _, metrics = trainer.step(state, batch)
        return float(metrics['loss'])

    def test_ring_training_matches_xla_attention(self):
        """Same data, same init: ring-attention loss == xla-path loss.
        This is the 'seq: sp rule backed by a real kernel path' check —
        the trainer accepts sp>1 with exact attention semantics."""
        loss_ring = self._loss_after_step('ring', sp=2)
        loss_xla = self._loss_after_step('xla', sp=2)
        assert abs(loss_ring - loss_xla) < 2e-2, (loss_ring, loss_xla)


class TestZigzag:
    """Balanced causal ring (VERDICT r4 task 6)."""

    @pytest.mark.parametrize('sp', [2, 4, 8])
    def test_zigzag_matches_reference(self, sp):
        mesh = _mesh(sp)
        q, k, v = _rand_qkv(s=32 * (sp // 2) if sp > 2 else 32)
        ref = reference_attention(q, k, v, causal=True)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=True, layout='zigzag',
                block_impl='einsum'))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_zigzag_gradients_match(self):
        mesh = _mesh(2)
        q, k, v = _rand_qkv()

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, causal=True,
                                          layout='zigzag',
                                          block_impl='einsum') ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v,
                                               causal=True) ** 2)

        with jax.set_mesh(mesh):
            g_ring = jax.jit(jax.grad(loss_ring, (0, 1, 2)))(q, k, v)
        g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b, name in zip(g_ring, g_ref, 'qkv'):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f'd{name}')

    def test_schedule_balanced_within_one_block(self):
        """The asserted balance property: zigzag per-rank cost is
        rank-independent; contiguous spreads 0.5 .. sp-0.5."""
        from skypilot_tpu.ops.ring_attention import ring_schedule_cost
        for sp in (2, 4, 8, 16):
            zig = [ring_schedule_cost(sp, r, 'zigzag')
                   for r in range(sp)]
            con = [ring_schedule_cost(sp, r, 'contiguous')
                   for r in range(sp)]
            assert max(zig) - min(zig) <= 1.0, (sp, zig)
            assert max(zig) - min(zig) == 0.0          # exactly even
            assert max(con) - min(con) == sp - 1
            # total work conserved (same attention, same FLOPs)
            np.testing.assert_allclose(sum(zig), sum(con))


class TestFlashBlockBody:
    """Pallas flash kernel as the per-block ring body (interpret mode
    on the CPU mesh; VERDICT r4 task 6)."""

    @pytest.mark.parametrize('layout', ['contiguous', 'zigzag'])
    def test_flash_body_matches_einsum_body(self, layout):
        mesh = _mesh(2)
        # 128-aligned halves + d=128 so the kernel tiles.
        q, k, v = _rand_qkv(b=4, s=512, h=2, hkv=2, d=128)
        with jax.set_mesh(mesh):
            ref = jax.jit(lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=True, layout=layout,
                block_impl='einsum'))(q, k, v)
            out = jax.jit(lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=True, layout=layout,
                block_impl='flash'))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_flash_body_gradients(self):
        """Backward re-derives via the einsum reference (custom_vjp):
        grads match the dense reference."""
        mesh = _mesh(2)
        q, k, v = _rand_qkv(b=4, s=512, h=2, hkv=2, d=128)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, causal=True,
                                          layout='zigzag',
                                          block_impl='flash') ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v,
                                               causal=True) ** 2)

        with jax.set_mesh(mesh):
            g_ring = jax.jit(jax.grad(loss_ring, (0, 1, 2)))(q, k, v)
        g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b, name in zip(g_ring, g_ref, 'qkv'):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3,
                                       err_msg=f'd{name}')
