"""The files GLM-4.7-Flash's cell brings: the benchmark's own copy of the
reference against the program's, the on-device weight maker against
``init_params``, the roofline counts against the configuration's
arithmetic (ISSUE 30's table), and ``correct``'s decision against five
wrong mathematics at a small size."""
import dataclasses
import json
import math
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from perfbench import roofline_glm, weights_glm  # noqa: E402
from perfbench.reference import glm4_moe_lite as bench_ref  # noqa: E402
from perfbench.run import load_module  # noqa: E402
from skypilot_tpu.models import configs, llama  # noqa: E402
from skypilot_tpu.models.reference import glm4_moe_lite as prog_ref  # noqa: E402

serve_ref = load_module(os.path.join(REPO, 'perfbench', 'runners',
                                     'serve_ref.py'))


def model_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != 'dtype'}


@pytest.fixture(scope='module')
def tiny():
    cfg = dataclasses.replace(configs.TINY_GLM, dtype=jnp.dtype('float32'))
    return cfg, weights_glm.make_tree(cfg, 2**31 + 5)


def test_the_two_copies_of_the_reference_agree(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    a, chosen_a = prog_ref.forward(params, tokens, cfg, q_block=5)
    b, chosen_b = bench_ref.forward(params, tokens, model_dict(cfg),
                                    q_block=24, rows=np.arange(24),
                                    wrap=jax.jit)
    np.testing.assert_array_equal(np.asarray(chosen_a), np.asarray(chosen_b))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                               atol=2e-5)      # jit reorders float32 sums
    with open(prog_ref.__file__, 'rb') as f, \
            open(bench_ref.__file__, 'rb') as g:
        assert f.read() == g.read()             # one text, two homes


def test_weights_are_init_params_shaped_seeded_and_fan_in_scaled():
    cfg = configs.TINY_GLM
    tree = weights_glm.make_tree(cfg, 2**31 + 7)
    want = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for got, exp in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert (got.shape, got.dtype) == (exp.shape, exp.dtype)
    again = weights_glm.make_tree(cfg, 2**31 + 7)
    other = weights_glm.make_tree(cfg, 2**31 + 8)
    flat = jax.tree_util.tree_leaves_with_path(tree)
    for (path, leaf), same, diff in zip(flat, jax.tree.leaves(again),
                                        jax.tree.leaves(other)):
        name = path[-1].key
        x = np.asarray(leaf, np.float32)
        np.testing.assert_array_equal(x, np.asarray(same, np.float32))
        if name.endswith('norm'):
            assert (x == 1).all()
            continue
        assert not np.array_equal(x, np.asarray(diff, np.float32))
        if name == 'router_bias':
            assert 0.5 < x.std() / weights_glm.ROUTER_BIAS_STD < 1.5
            continue
        layer_shape = leaf.shape[1:] if 'layers' in path[0].key \
            else leaf.shape
        fan = weights_glm.fan_in(name, layer_shape)
        assert abs(x.std() * math.sqrt(fan) - 1) < 0.1, (name, fan)
    # the layers of a stack differ from each other
    gate = np.asarray(tree['layers']['experts']['w_gate'], np.float32)
    assert not np.array_equal(gate[0], gate[1])
    assert not np.array_equal(gate[0, 0], gate[0, 1])


def test_roofline_counts_match_the_configuration_arithmetic():
    with open(os.path.join(REPO, 'perfbench', 'configs',
                           'glm-4.7-flash.json'), encoding='utf-8') as f:
        m = json.load(f)['model']
    r = roofline_glm
    assert r.mla_params(m) == (2048 * 768 + 768 * 5120 + 2048 * 576
                               + 512 * 8960 + 5120 * 2048) == 21_757_952
    assert r.expert_params(m) == 3 * 2048 * 1536 == 9_437_184
    assert r.expert_params(m) * r.BYTES == 18_874_368          # 18.87 MB
    assert round(r.expert_layer_fixed_params(m) / 1e6, 1) == 31.3
    assert round((r.expert_layer_fixed_params(m)
                  + 64 * r.expert_params(m)) / 1e6, 1) == 635.3
    assert round(r.dense_layer_params(m) / 1e6, 2) == 84.67
    assert round(r.total_params(m) / 1e9, 3) == 5.166
    assert round(r.total_params(m) * r.BYTES / 1e9, 2) == 10.33
    assert r.kv_token_bytes(m) == 8 * 576 * 2 == 9216
    # a step at 8 live rows (25.8 experts a layer) and with all 64 read
    assert round(r.decode_step_bytes(m, 25.8, 0) / 1e9, 2) == 4.65
    assert round(r.decode_step_bytes(m, 64, 0) / 1e9, 1) == 9.7
    assert r.prefill_pair_flops(m) == 20 * (2 * 256 + 2 * 256) == 20480
    assert r.mla_decode_flops(m, 1, 0) == 8 * 20 * (2 * 576 + 2 * 512)
    # the program's own count agrees (norms: 4 a layer + 1)
    cfg = configs.ModelConfig(**m)
    norms = 8 * (2 * 2048 + 768 + 512) + 2048
    assert cfg.num_params == r.total_params(m) + norms
    assert cfg.kv_spec.row_values * cfg.n_layers * 2 == r.kv_token_bytes(m)


# ---------------------------------------------------------------- correct
def _int8_round(tree):
    def rt(a):
        if a.ndim < 2 or a.shape[-1] <= 8:
            return a
        af = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(af), axis=-2, keepdims=True) / 127.0
        return (jnp.round(af / scale) * scale).astype(a.dtype)
    return jax.tree.map(rt, tree)


def _routing(kind):
    def routing(layer, x, cfg):
        k = bench_ref._get(cfg, 'n_experts_per_token')
        logits = x @ bench_ref._f32(layer['router'])
        scores = (jax.nn.softmax(logits, -1) if kind == 'softmax'
                  else jax.nn.sigmoid(logits))
        biased = scores + bench_ref._f32(layer['router_bias'])
        _, chosen = jax.lax.top_k(biased, k)
        w = jnp.take_along_axis(
            biased if kind == 'bias_in_weights' else scores, chosen, -1)
        w = w / jnp.sum(w, -1, keepdims=True) * bench_ref._get(
            cfg, 'routed_scaling_factor')
        return chosen.astype(jnp.int32), w
    return routing


@pytest.fixture(scope='module')
def served(tiny):
    """What the program serves at the small size: greedy tokens of four
    prompts through the paged engine, in one batch."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    cfg, params = tiny
    eng = PagedInferenceEngine(cfg, params=params, max_batch=4,
                               max_seq=128, page_size=8, chunk=16)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (30, 9, 50, 21)]
    ids = [eng.add_request(p, max_new_tokens=40) for p in prompts]
    done = eng.run_to_completion(horizon=4)
    return prompts, [done[i].output for i in ids]


# The runner's rule at this size: the float32 program reads 0 and the
# mildest wrong mathematics (int8 weights, the bias in the weights) reads
# a worst deficit of 0.57 and a mean of 0.012 over three seeds; the
# limits lie between.
TINY_LIMITS = dict(worst=0.3, mean=0.005)


@pytest.mark.parametrize('variant', [
    'right', 'int8_tree', 'no_shared_expert', 'no_scaling', 'softmax',
    'bias_in_weights'])
def test_correct_refuses_wrong_mathematics(tiny, served, variant):
    """The served tokens scored against the reference hold both limits;
    against a reference that rounds the tree to int8, leaves out the
    shared expert or the 1.8, takes softmax for sigmoid or weighs with
    the bias, they break at least one."""
    cfg, params = tiny
    prompts, outputs = served
    model, routing = model_dict(cfg), bench_ref.routing
    if variant == 'int8_tree':
        params = _int8_round(params)
    elif variant == 'no_shared_expert':
        params = jax.tree.map(lambda a: a, params)
        params['layers']['shared']['w_down'] = jnp.zeros_like(
            params['layers']['shared']['w_down'])
    elif variant == 'no_scaling':
        model = dict(model, routed_scaling_factor=1.0)
    elif variant != 'right':
        routing = _routing(variant)
    with mock.patch.object(serve_ref, 'PAD_TO', 16), \
            mock.patch.object(bench_ref, 'routing', routing):
        scored = [serve_ref.deficits(bench_ref, params, model, p, t)
                  for p, t in zip(prompts, outputs)]
    deficit = np.concatenate([d for d, _ in scored])
    finite = all(ok for _, ok in scored)
    holds = serve_ref.within_limits(deficit, finite, **TINY_LIMITS)
    assert holds == (variant == 'right'), (variant, deficit.max(),
                                           deficit.mean())
