"""HF checkpoint import: logits parity against ``transformers`` (torch
CPU) for Llama/GQA, Gemma (MQA + tied embeddings + gelu + norm+1), and
Mixtral (MoE), plus save/load round-trip and tokenizer behavior.

The reference serves *real* HF checkpoints through external engines
(``llm/llama-3/llama3.yaml:109``); this proves our in-tree engine computes
the same function as the HF reference implementation for those layouts.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import configs, llama, weights
from skypilot_tpu.models.tokenizer import (ByteTokenizer, load_tokenizer)

# Compile-heavy (jit of full models): slow tier — the fast sweep is
# the orchestration layer (SURVEY §4 offline tier analog).
pytestmark = pytest.mark.slow

jax.config.update('jax_platforms', 'cpu')


def _save_hf_model(model, path):
    model.save_pretrained(path, safe_serialization=True)


def _our_logits(path, tokens):
    cfg, params = weights.load_checkpoint(path, dtype=jnp.float32)
    logits, _ = llama.forward(params, jnp.asarray(tokens), cfg)
    return np.asarray(logits, np.float32), cfg


def _hf_logits(model, tokens):
    import torch
    with torch.no_grad():
        out = model(torch.tensor(tokens))
    return out.logits.float().numpy()


def _assert_close(ours, theirs, atol=2e-3):
    err = np.abs(ours - theirs).max()
    assert err < atol, f'max |logit diff| = {err}'


@pytest.fixture(scope='module')
def torch_seed():
    import torch
    torch.manual_seed(0)


def test_llama_gqa_logits_parity(tmp_path, torch_seed):
    from transformers import LlamaConfig, LlamaForCausalLM
    hf_cfg = LlamaConfig(
        vocab_size=97, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=8, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False)
    model = LlamaForCausalLM(hf_cfg).eval()
    path = str(tmp_path / 'llama')
    _save_hf_model(model, path)

    tokens = np.random.RandomState(0).randint(0, 97, (2, 17))
    ours, cfg = _our_logits(path, tokens)
    assert cfg.n_kv_heads == 2 and not cfg.tie_embeddings
    _assert_close(ours, _hf_logits(model, tokens))


def test_gemma_mqa_logits_parity(tmp_path, torch_seed):
    from transformers import GemmaConfig, GemmaForCausalLM
    hf_cfg = GemmaConfig(
        vocab_size=89, hidden_size=48, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=64, rope_theta=10000.0,
        rms_norm_eps=1e-6, hidden_act='gelu_pytorch_tanh',
        hidden_activation='gelu_pytorch_tanh')
    model = GemmaForCausalLM(hf_cfg).eval()
    path = str(tmp_path / 'gemma')
    _save_hf_model(model, path)

    tokens = np.random.RandomState(1).randint(0, 89, (2, 11))
    ours, cfg = _our_logits(path, tokens)
    assert cfg.tie_embeddings and cfg.norm_plus_one and cfg.scale_embeddings
    assert cfg.head_dim == 16  # explicit head_dim != dim//n_heads
    _assert_close(ours, _hf_logits(model, tokens))


def test_mixtral_moe_logits_parity(tmp_path, torch_seed):
    from transformers import MixtralConfig, MixtralForCausalLM
    hf_cfg = MixtralConfig(
        vocab_size=71, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    model = MixtralForCausalLM(hf_cfg).eval()
    path = str(tmp_path / 'mixtral')
    _save_hf_model(model, path)

    tokens = np.random.RandomState(2).randint(0, 71, (1, 13))
    cfg, params = weights.load_checkpoint(path, dtype=jnp.float32)
    assert cfg.is_moe and cfg.n_experts == 4
    # Our MoE uses GShard capacity-limited dispatch: with a generous
    # capacity factor no tokens are dropped and it matches HF's exact
    # (ungated-capacity) routing.
    import dataclasses
    cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    logits, _ = llama.forward(params, jnp.asarray(tokens), cfg)
    _assert_close(np.asarray(logits, np.float32),
                  _hf_logits(model, tokens), atol=5e-3)


def test_save_load_roundtrip(tmp_path):
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path / 'rt')
    weights.save_hf_checkpoint(path, cfg, params)
    cfg2, params2 = weights.load_checkpoint(path, dtype=cfg.dtype)
    assert cfg2.dim == cfg.dim and cfg2.n_kv_heads == cfg.n_kv_heads
    tok = np.arange(24).reshape(1, 24) % cfg.vocab_size
    l1, _ = llama.forward(params, jnp.asarray(tok), cfg)
    l2, _ = llama.forward(params2, jnp.asarray(tok), cfg2)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), atol=2e-2)


def test_byte_tokenizer_roundtrip():
    tk = ByteTokenizer()
    ids = tk.encode('hello, TPU!')
    assert ids[0] == tk.bos_id
    assert tk.decode(ids) == 'hello, TPU!'
    assert tk.vocab_size == 258


def test_byte_tokenizer_invalid_utf8_sanitizes_at_boundary():
    """decode keeps surrogateescape internally (string-stop matching
    round-trips arbitrary generated bytes), but sanitize_text must
    strip the lone surrogates before they reach a JSON body — strict
    client-side parsers reject \\udcXX escapes."""
    from skypilot_tpu.models.tokenizer import sanitize_text
    tk = ByteTokenizer()
    text = tk.decode([0x80, 0xFF, ord('a')])     # invalid UTF-8 bytes
    # Internal round trip is byte-faithful...
    assert text.encode('utf-8', 'surrogateescape') == b'\x80\xffa'
    with pytest.raises(UnicodeEncodeError):
        text.encode('utf-8')                      # ...but not JSON-safe
    clean = sanitize_text(text)
    clean.encode('utf-8')                         # wire-safe now
    assert clean.endswith('a') and '�' in clean
    # Valid text passes through untouched.
    assert sanitize_text('héllo') == 'héllo'


def test_load_tokenizer_fallback(tmp_path):
    assert isinstance(load_tokenizer(str(tmp_path)), ByteTokenizer)
    assert isinstance(load_tokenizer(None), ByteTokenizer)


def test_hf_tokenizer_from_file(tmp_path):
    # Build a minimal valid tokenizer.json (WordLevel) via the tokenizers
    # lib, then load through our wrapper.
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    vocab = {'<s>': 0, '</s>': 1, 'hello': 2, 'tpu': 3}
    tk = Tokenizer(WordLevel(vocab, unk_token='</s>'))
    tk.pre_tokenizer = Whitespace()
    tk.save(str(tmp_path / 'tokenizer.json'))
    (tmp_path / 'tokenizer_config.json').write_text(json.dumps(
        {'bos_token': '<s>', 'eos_token': '</s>'}))
    our = load_tokenizer(str(tmp_path))
    ids = our.encode('hello tpu')
    assert ids == [0, 2, 3]
    assert our.eos_id == 1


def _serve_checkpoint(tmp_path, port_base, **server_kwargs):
    """Save a TINY checkpoint, boot a ModelServer on it, wait for
    readiness. Returns (server, port); caller must server.stop()."""
    import time as time_mod
    import urllib.request
    from skypilot_tpu.serve.server import ModelServer
    from skypilot_tpu.utils import common_utils

    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path / 'ckpt')
    weights.save_hf_checkpoint(path, cfg, params)
    port = common_utils.find_free_port(port_base)
    server = ModelServer(max_batch=2, max_seq=64, port=port,
                         model_path=path, **server_kwargs)
    server.start(block=False)
    deadline = time_mod.time() + 60
    ready = False
    while time_mod.time() < deadline:
        try:
            with urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/readiness', timeout=5) as r:
                ready = r.status == 200
                break
        except Exception:
            time_mod.sleep(0.3)
    if not ready:
        server.stop()
        raise AssertionError('server never became ready')
    return server, port


@pytest.mark.slow
def test_server_serves_real_checkpoint_text(tmp_path):
    """E2e: ModelServer --model-path serves a saved checkpoint and
    answers a TEXT prompt with decoded text (the reference's real-model
    serving recipes, in-tree)."""
    import urllib.request
    server, port = _serve_checkpoint(tmp_path, 18200)
    try:
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate',
            data=json.dumps({'prompt': 'hello tpu',
                             'max_new_tokens': 4}).encode(),
            headers={'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert 'text' in out and isinstance(out['text'], str)
        assert len(out['tokens']) > 0
    finally:
        server.stop()


def test_trainer_init_from_pretrained(tmp_path):
    from skypilot_tpu.train.trainer import Trainer
    cfg = configs.TINY
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    path = str(tmp_path / 'ckpt')
    weights.save_hf_checkpoint(path, cfg, params)

    tr = Trainer(cfg)
    state = tr.init_from_pretrained(path)
    assert int(state.step) == 0
    # Params match the checkpoint (post fp32 round-trip).
    got = np.asarray(jnp.asarray(state.params['layers']['wq'], jnp.float32))
    want = np.asarray(jnp.asarray(params['layers']['wq'], jnp.float32))
    np.testing.assert_allclose(got, want, atol=2e-2)
    # And one train step runs.
    batch = {
        'inputs': jnp.zeros((8, 16), jnp.int32),
        'targets': jnp.zeros((8, 16), jnp.int32),
    }
    state2, metrics = tr.step(state, batch)
    assert np.isfinite(metrics['loss'])


def test_qwen2_qkv_bias_logits_parity(tmp_path, torch_seed):
    from transformers import Qwen2Config, Qwen2ForCausalLM
    hf_cfg = Qwen2Config(
        vocab_size=83, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    model = Qwen2ForCausalLM(hf_cfg).eval()
    path = str(tmp_path / 'qwen2')
    _save_hf_model(model, path)

    tokens = np.random.RandomState(5).randint(0, 83, (2, 13))
    ours, cfg = _our_logits(path, tokens)
    assert cfg.qkv_bias
    _assert_close(ours, _hf_logits(model, tokens))


def test_qwen2_save_load_roundtrip(tmp_path):
    cfg = configs.TINY_QWEN
    params = llama.init_params(jax.random.PRNGKey(2), cfg)
    # nonzero biases so the roundtrip actually tests them
    params['layers']['bq'] = params['layers']['bq'] + 0.1
    path = str(tmp_path / 'rtq')
    weights.save_hf_checkpoint(path, cfg, params)
    cfg2, params2 = weights.load_checkpoint(path, dtype=cfg.dtype)
    assert cfg2.qkv_bias
    tok = np.arange(24).reshape(1, 24) % cfg.vocab_size
    l1, _ = llama.forward(params, jnp.asarray(tok), cfg)
    l2, _ = llama.forward(params2, jnp.asarray(tok), cfg2)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), atol=2e-2)


def test_ouro_save_load_roundtrip(tmp_path):
    """``model_type: ouro`` (``total_ut_steps``, ``input_layernorm_2``,
    ``post_attention_layernorm_2``, ``model.early_exit_gate``) written
    and read back: the same configuration, tree and logits."""
    cfg = configs.TINY_OURO
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    # norms off one and a gate bias off zero, so the round trip tests them
    for name in ('attn_post_norm', 'ffn_post_norm'):
        params['layers'][name] = params['layers'][name] * jnp.linspace(
            0.5, 1.5, cfg.dim)
    params['exit_gate']['b'] = params['exit_gate']['b'] + 0.25
    path = str(tmp_path / 'rto')
    weights.save_hf_checkpoint(path, cfg, params)
    with open(os.path.join(path, 'config.json'), encoding='utf-8') as f:
        hf = json.load(f)
    assert hf['model_type'] == 'ouro' and hf['total_ut_steps'] == 3
    cfg2, params2 = weights.load_checkpoint(path, dtype=cfg.dtype)
    assert cfg2 == dataclasses.replace(cfg, name='ouro', remat='block')
    assert jax.tree.structure(params2) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    tok = np.arange(24).reshape(1, 24) % cfg.vocab_size
    l1, _, e1 = llama.forward(params, jnp.asarray(tok), cfg,
                              return_exit=True)
    l2, _, e2 = llama.forward(params2, jnp.asarray(tok), cfg2,
                              return_exit=True)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


@pytest.mark.slow
def test_server_int8_quantized_serving(tmp_path):
    """ModelServer --quantize int8 serves a checkpoint with int8
    weights + KV cache."""
    import urllib.request
    server, port = _serve_checkpoint(tmp_path, 18300, quantize='int8')
    try:
        assert server.engine.cache.quantized
        req = urllib.request.Request(
            f'http://127.0.0.1:{port}/generate',
            data=json.dumps({'prompt': [1, 2, 3],
                             'max_new_tokens': 4}).encode(),
            headers={'Content-Type': 'application/json'})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert len(out['tokens']) == 4
    finally:
        server.stop()


class TestSynthAndInt8Cache:
    """Synthetic checkpoint generator + host-side int8 load + cache
    (the 7B bench path, VERDICT r4 task 1)."""

    def test_synth_checkpoint_loads_and_caches(self, tmp_path):
        import numpy as np

        from skypilot_tpu.models import configs, synth, weights
        p = synth.write_synthetic_hf_checkpoint(str(tmp_path / 'ck'),
                                                configs.TINY)
        assert p == synth.write_synthetic_hf_checkpoint(  # idempotent
            str(tmp_path / 'ck'), configs.TINY)
        cfg, q1 = weights.load_checkpoint(p, quantize='int8')
        assert cfg.dim == configs.TINY.dim
        assert os.path.exists(os.path.join(p, '.int8_cache.bin'))
        _, q2 = weights.load_checkpoint(p, quantize='int8')  # via cache
        flat1 = dict(weights._flatten_leaves(q1))
        flat2 = dict(weights._flatten_leaves(q2))
        assert set(flat1) == set(flat2)
        for k in flat1:
            assert flat1[k].dtype == flat2[k].dtype, k
            np.testing.assert_array_equal(
                np.asarray(flat1[k], np.float32),
                np.asarray(flat2[k], np.float32), err_msg=k)

    def test_host_quantize_matches_device_quantize(self, tmp_path):
        """weights._host_quantize and quantization._quantize_array agree
        bit-for-bit (same rounded-scale contract)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from skypilot_tpu.models import quantization, weights
        w = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (64, 32),
                                         jnp.bfloat16))
        host = weights._host_quantize(np.asarray(w, np.float32), (0,),
                                      jnp.bfloat16)
        dev = quantization._quantize_array(jnp.asarray(w), (0,))
        np.testing.assert_array_equal(
            np.asarray(host.scale, np.float32),
            np.asarray(dev.scale, np.float32))
        codes_equal = (np.asarray(host.int8) == np.asarray(dev.int8))
        assert codes_equal.mean() > 0.999, codes_equal.mean()
