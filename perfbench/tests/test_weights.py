"""The int8 tree made leaf by leaf is the tree the program would make,
the engine takes it, and the hand-over fails loudly without its seam."""
import jax
import pytest

from perfbench import weights
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, llama, quantization

CFG = configs.get_config('tiny-qwen')
SEED = 2**31 + 17


@pytest.fixture(scope='module')
def tree():
    return weights.make_int8_tree(CFG, SEED)


def test_structure_and_dtypes_of_quantize_params_of_init_params(tree):
    want = quantization.quantize_params(
        llama.init_params(jax.random.PRNGKey(0), CFG))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), tree)
            == jax.tree.map(lambda a: (a.shape, a.dtype), want))
    assert quantization.quantized_mode(tree) == 'int8'


def test_seeded_and_fan_in_scaled(tree):
    again = weights.make_int8_tree(CFG, SEED)
    other = weights.make_int8_tree(CFG, SEED + 1)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), tree, again)
    assert all(jax.tree.leaves(same))
    assert not bool((tree['layers']['wq'].int8
                     == other['layers']['wq'].int8).all())
    w = quantization.deq(tree['layers']['w_gate']).astype('float32')
    assert float(w.std()) == pytest.approx(CFG.dim ** -0.5, rel=0.05)
    # layers differ from each other: one random block is not reused
    assert not bool((w[0] == w[1]).all())


def test_engine_accepts_it(tree):
    engine = PagedInferenceEngine(CFG, params=tree, max_batch=2, max_seq=64)
    assert engine.kv_cache_dtype == 'int8'
    engine.add_request([1, 2, 3], max_new_tokens=4)
    (done,) = engine.run_to_completion().values()
    assert len(done.output) == 4


def test_hand_over_fails_loudly_without_build_engine(monkeypatch):
    from perfbench.run import load_module
    import os
    serve = load_module(os.path.join(os.path.dirname(__file__), '..',
                                     'runners', 'serve.py'))
    from skypilot_tpu.serve import server as server_mod
    with serve.hand_over(lambda **kw: 'engine'):
        assert server_mod.build_engine('tiny', max_batch=1,
                                       max_seq=8) == 'engine'
    monkeypatch.delattr(server_mod, 'build_engine')
    with pytest.raises(RuntimeError, match='build_engine'):
        with serve.hand_over(lambda **kw: 'engine'):
            pass
