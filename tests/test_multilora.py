"""Multi-LoRA adapter bank: bank math, registry, engine contracts.

The correctness contracts pinned here (ISSUE 20):

- a zero-adapter slot is BYTE-identical to the base model — an engine
  built with a bank produces the same greedy stream as one without;
- a bank-served adapter matches its offline-merged reference
  (``W += scale * A @ B``) token-for-token under greedy decoding at
  fp32, on both engines, including the chunked-prefill path and the
  multi-step / speculative decode compositions;
- constrained decoding (satellite: per-slot vocab masks) only ever
  emits allowed tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import adapters as adapters_lib
import greedy_oracle
from skypilot_tpu.inference.paged import PagedInferenceEngine
from skypilot_tpu.models import configs, llama, multilora

CFG = configs.TINY


def _rand_tree(cfg, rank, targets, seed, sigma=0.2):
    """Trainer-format adapter tree (lora.split_lora layout: the layer
    axis LEADS every a/b leaf)."""
    rng = np.random.default_rng(seed)
    L = cfg.n_layers
    tree = {}
    for t in targets:
        a_shape, b_shape = multilora.target_shapes(cfg, t, rank)
        tree[t] = {
            'a': rng.normal(0.0, sigma, (L,) + a_shape).astype(np.float32),
            'b': rng.normal(0.0, sigma, (L,) + b_shape).astype(np.float32),
        }
    return tree

# Offline merge folds: W += scale * (A contracted with B) per target,
# stacked over the leading layer axis.
_MERGE_EINSUM = {
    'wq': 'ldr,lrhk->ldhk', 'wk': 'ldr,lrhk->ldhk',
    'wv': 'ldr,lrhk->ldhk', 'wo': 'lhkr,lrd->lhkd',
    'w_gate': 'ldr,lrf->ldf', 'w_up': 'ldr,lrf->ldf',
    'w_down': 'lfr,lrd->lfd',
}


def _merged_params(params, tree, scale):
    """The offline-merged reference: base params with the adapter's
    delta folded into the target weights (same fold lora.merge does)."""
    layers = dict(params['layers'])
    for t, ab in tree.items():
        w = layers[t]
        delta = jnp.einsum(_MERGE_EINSUM[t],
                           jnp.asarray(ab['a'], jnp.float32),
                           jnp.asarray(ab['b'], jnp.float32))
        layers[t] = (w.astype(jnp.float32)
                     + float(scale) * delta).astype(w.dtype)
    out = dict(params)
    out['layers'] = layers
    return out


# --------------------------------------------------------------- units

class TestBankMath:

    def test_default_targets(self):
        assert multilora.default_targets(CFG) == (
            'wq', 'wk', 'wv', 'wo', 'w_gate', 'w_up', 'w_down')
        moe = dataclasses.replace(CFG, n_experts=4)
        assert multilora.default_targets(moe) == ('wq', 'wk', 'wv', 'wo')
        with pytest.raises(ValueError, match='dense FFN'):
            multilora.init_bank(moe, 2, 4, targets=('wq', 'w_gate'))

    def test_init_bank_shapes(self):
        bank = multilora.init_bank(CFG, 3, 4)
        L = CFG.n_layers
        assert bank['scale'].shape == (L, 3)
        assert bank['scale'].dtype == jnp.float32
        a_shape, b_shape = multilora.target_shapes(CFG, 'wq', 4)
        assert bank['wq']['a'].shape == (L, 3) + a_shape
        assert bank['wq']['b'].shape == (L, 3) + b_shape
        assert multilora.bank_slots(bank) == 3
        assert multilora.bank_targets(bank) == \
            multilora.default_targets(CFG)
        flat = jax.tree.leaves(bank)
        assert all(not np.asarray(leaf).any() for leaf in flat)
        with pytest.raises(ValueError):
            multilora.init_bank(CFG, 0, 4)
        with pytest.raises(ValueError, match='unknown'):
            multilora.init_bank(CFG, 2, 4, targets=('w_bogus',))

    def test_adjusted_zero_slot_is_bit_exact(self):
        bank = multilora.init_bank(CFG, 2, 4, dtype=jnp.float32)
        tree = _rand_tree(CFG, 4, ('wq',), seed=0)
        row = multilora.adapter_row_from_tree(
            CFG, tree, 4, 1.0, targets=multilora.bank_targets(bank))
        bank = multilora.set_bank_row(bank, row, jnp.asarray(0, jnp.int32))
        ml = jax.tree.map(lambda v: v[0], bank)      # one layer's slice
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(2, 3, CFG.dim)), jnp.float32)
        head_dim = CFG.dim // CFG.n_heads
        base = jnp.asarray(
            rng.normal(size=(2, 3, CFG.n_heads, head_dim)), jnp.float32)
        idx = jnp.asarray([-1, 0], jnp.int32)
        out = multilora.adjusted(ml, 'wq', x, base, idx)
        # idx=-1 row: bitwise-identical base (where-select, not +0).
        assert np.array_equal(np.asarray(out[0]), np.asarray(base[0]))
        # idx=0 row: the adapter delta actually lands.
        assert not np.array_equal(np.asarray(out[1]), np.asarray(base[1]))
        # No-bank / no-idx / untracked-target short-circuits return base.
        assert multilora.adjusted(None, 'wq', x, base, idx) is base
        assert multilora.adjusted(ml, 'wq', x, base, None) is base
        ml_no_wq = {k: v for k, v in ml.items() if k != 'wq'}
        assert multilora.adjusted(ml_no_wq, 'wq', x, base, idx) is base

    def test_set_and_clear_bank_row(self):
        bank = multilora.init_bank(CFG, 2, 4)
        targets = multilora.bank_targets(bank)
        tree = _rand_tree(CFG, 4, targets, seed=1)
        row = multilora.adapter_row_from_tree(
            CFG, tree, 4, 2.5, targets=targets)
        bank = multilora.set_bank_row(bank, row, jnp.asarray(1, jnp.int32))
        got_a = np.asarray(bank['wq']['a'][:, 1].astype(jnp.float32))
        want_a = np.asarray(
            jnp.asarray(row['wq']['a']).astype(bank['wq']['a'].dtype)
            .astype(jnp.float32))
        assert np.array_equal(got_a, want_a)
        assert np.allclose(np.asarray(bank['scale'][:, 1]), 2.5)
        # Slot 0 untouched.
        assert not np.asarray(bank['wq']['a'][:, 0]).any()
        bank = multilora.clear_bank_row(bank, jnp.asarray(1, jnp.int32))
        assert all(not np.asarray(leaf).any()
                   for leaf in jax.tree.leaves(bank))

    def test_adapter_row_pads_and_rejects(self):
        targets = multilora.default_targets(CFG)
        # rank 2 adapter into a rank-4 bank: zero-padded factor columns.
        tree = _rand_tree(CFG, 2, ('wq',), seed=2)
        row = multilora.adapter_row_from_tree(
            CFG, tree, 4, 1.0, targets=targets)
        assert row['wq']['a'].shape[-1] == 4
        assert not row['wq']['a'][..., 2:].any()
        assert not row['wq']['b'][:, 2:].any()
        assert row['wq']['a'][..., :2].any()
        # Targets the adapter lacks are zero rows (no-op slots).
        assert not row['w_up']['a'].any()
        assert np.array_equal(
            row['scale'], np.full((CFG.n_layers,), 1.0, np.float32))
        # Rank above the bank rank is a hard error.
        big = _rand_tree(CFG, 8, ('wq',), seed=3)
        with pytest.raises(ValueError, match='exceeds bank rank'):
            multilora.adapter_row_from_tree(CFG, big, 4, 1.0,
                                            targets=targets)
        # Layer-count mismatch is a hard error.
        wrong = {'wq': {'a': tree['wq']['a'][:1], 'b': tree['wq']['b'][:1]}}
        with pytest.raises(ValueError, match='layers'):
            multilora.adapter_row_from_tree(CFG, wrong, 4, 1.0,
                                            targets=targets)

    def test_save_load_roundtrip(self, tmp_path):
        tree = _rand_tree(CFG, 4, ('wq', 'w_down'), seed=4)
        path = str(tmp_path / 'ad.npz')
        multilora.save_adapter(path, CFG, tree, scale=0.75)
        got, scale = multilora.load_adapter(path)
        assert scale == 0.75
        assert set(got) == {'wq', 'w_down'}
        for t in got:
            assert np.array_equal(got[t]['a'], tree[t]['a'])
            assert np.array_equal(got[t]['b'], tree[t]['b'])
        # Default scale is the config's alpha/rank fold scale.
        path2 = str(tmp_path / 'ad2.npz')
        multilora.save_adapter(path2, CFG, tree)
        _, scale2 = multilora.load_adapter(path2)
        assert scale2 == pytest.approx(CFG.lora_alpha / 4)


class TestGrammar:

    def test_json_mode_mask(self):
        mask = adapters_lib.compile_grammar('json', 256, eos_id=200)
        assert mask.shape == (256,) and mask.dtype == np.bool_
        for ch in '{}[]":, \t\n0123456789truefalsenull':
            assert mask[ord(ch)], ch
        assert not mask[0] and not mask[0x7F]
        assert mask[200]          # eos always allowed to terminate

    def test_id_list_and_bool_masks(self):
        mask = adapters_lib.compile_grammar([5, 9], 256, eos_id=7)
        assert sorted(np.nonzero(mask)[0].tolist()) == [5, 7, 9]
        arr = np.zeros(256, bool)
        arr[3] = True
        mask = adapters_lib.compile_grammar(arr, 256, eos_id=4)
        assert sorted(np.nonzero(mask)[0].tolist()) == [3, 4]
        assert not arr[4]         # input mask not mutated

    def test_grammar_errors(self):
        assert adapters_lib.compile_grammar(None, 256) is None
        with pytest.raises(ValueError, match='unknown grammar'):
            adapters_lib.compile_grammar('regex', 256)
        with pytest.raises(ValueError, match='empty'):
            adapters_lib.compile_grammar([], 256)
        with pytest.raises(ValueError, match='out of vocab'):
            adapters_lib.compile_grammar([256], 256)
        with pytest.raises(ValueError, match='shape'):
            adapters_lib.compile_grammar(np.zeros(8, bool), 256)


# ------------------------------------------------------------ registry

@pytest.fixture(scope='module')
def base_params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


def _registry_engine(base_params, tmp_dir=None, slots=2):
    eng = PagedInferenceEngine(CFG, base_params, max_batch=2, max_seq=64,
                          attn_impl='xla', adapter_slots=slots,
                          adapter_rank=4,
                          adapter_dir=tmp_dir, telemetry=False)
    return eng, eng.adapters


class TestRegistry:

    def test_lru_load_and_evict(self, base_params):
        _, reg = _registry_engine(base_params)
        targets = reg.targets
        for i in range(3):
            reg.register(f'ad{i}', _rand_tree(CFG, 4, targets, seed=i),
                         scale=1.0)
        reg.acquire('ad0'); reg.release('ad0')
        reg.acquire('ad1'); reg.release('ad1')
        assert reg.loaded() == ['ad0', 'ad1']
        # Bank is full and unpinned: ad2 evicts the coldest (ad0).
        reg.acquire('ad2'); reg.release('ad2')
        assert reg.loaded() == ['ad1', 'ad2']
        assert reg.loads_total == 3 and reg.evictions_total == 1
        # LRU hit: no new load, ad1 becomes hottest.
        slot = reg.acquire('ad1'); reg.release('ad1')
        assert slot == reg.slot_of('ad1')
        assert reg.loads_total == 3
        assert reg.loaded() == ['ad2', 'ad1']
        st = reg.stats()
        assert st['slots'] == 2 and st['used'] == 2 and st['free'] == 0
        assert st['rank'] == 4 and st['loads_total'] == 3
        assert st['evictions_total'] == 1 and st['last_load_ms'] >= 0.0

    def test_pins_block_eviction(self, base_params):
        _, reg = _registry_engine(base_params)
        for i in range(3):
            reg.register(f'ad{i}', _rand_tree(CFG, 4, reg.targets, seed=i),
                         scale=1.0)
        reg.acquire('ad0')
        reg.acquire('ad1')
        # Both slots pinned by live requests: retryable full error.
        with pytest.raises(adapters_lib.AdapterBankFullError):
            reg.acquire('ad2')
        reg.release('ad0')
        reg.acquire('ad2')    # now evicts the unpinned ad0
        assert reg.loaded() == ['ad1', 'ad2']
        assert reg.stats()['pinned'] == {'ad1': 1, 'ad2': 1}

    def test_bad_checkpoint_leaks_no_slot(self, base_params):
        """A rejected row (over-rank here) must fail BEFORE a slot is
        taken: repeated requests for a bad adapter must neither exhaust
        the bank nor evict healthy adapters as collateral."""
        _, reg = _registry_engine(base_params)
        reg.register('good', _rand_tree(CFG, 4, reg.targets, seed=0),
                     scale=1.0)
        reg.acquire('good'); reg.release('good')
        reg.register('fat', _rand_tree(CFG, 8, reg.targets, seed=1),
                     scale=1.0)
        for _ in range(3):             # more attempts than slots
            with pytest.raises(ValueError, match='exceeds bank rank'):
                reg.acquire('fat')
        assert reg.loaded() == ['good']
        assert reg.evictions_total == 0
        assert reg.stats()['free'] == 1
        # The bank stays fully serviceable.
        reg.register('ad2', _rand_tree(CFG, 4, reg.targets, seed=2),
                     scale=1.0)
        reg.acquire('good'); reg.release('good')
        reg.acquire('ad2'); reg.release('ad2')
        assert reg.loaded() == ['good', 'ad2']

    def test_unknown_and_illegal_names(self, base_params):
        _, reg = _registry_engine(base_params)
        with pytest.raises(ValueError, match='unknown adapter'):
            reg.acquire('nope')
        for bad in ('../evil', 'a/b', '', '.hidden'):
            with pytest.raises(ValueError, match='illegal|unknown'):
                reg.acquire(bad)
        with pytest.raises(ValueError):
            reg.register('a/b', _rand_tree(CFG, 4, ('wq',), seed=0))

    def test_adapter_dir_checkpoint_source(self, base_params, tmp_path):
        tree = _rand_tree(CFG, 4, ('wq', 'wo'), seed=5)
        multilora.save_adapter(str(tmp_path / 'disk1.npz'), CFG, tree,
                               scale=1.25)
        _, reg = _registry_engine(base_params, tmp_dir=str(tmp_path))
        slot = reg.acquire('disk1')
        assert reg.slot_of('disk1') == slot
        bank = reg.engine.params['layers']['mlora']
        assert np.allclose(np.asarray(bank['scale'][:, slot]), 1.25)
        assert np.asarray(
            bank['wq']['a'][:, slot].astype(jnp.float32)).any()


# ----------------------------------------------- engine contracts (slow)

def _make_engine(cfg, params, **kw):
    kw.setdefault('page_size', 8)
    return PagedInferenceEngine(cfg, params, max_batch=2, max_seq=128,
                                attn_impl='xla', **kw)


CFG32 = dataclasses.replace(CFG, dtype=jnp.float32)


@pytest.fixture(scope='module')
def adapter_setup():
    """fp32 config + params + one random adapter and its offline-merged
    reference params: the bank path ``x@W + s*(x@A)@B`` is held to the
    plain forward of the merged ``x@(W + s*A@B)`` (``greedy_oracle``)."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG32)
    tree = _rand_tree(CFG32, 4, multilora.default_targets(CFG32), seed=11)
    scale = 0.5
    merged = _merged_params(params, tree, scale)
    return params, tree, scale, merged


@pytest.mark.slow
class TestEngineContracts:

    def test_zero_adapter_stream_identical_to_base(self):
        """An engine carrying an (empty) bank is indistinguishable from
        one without (its rows add exact zeros): same greedy stream,
        request by request."""
        prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
        outs = {}
        for label, extra in (('base', {}),
                             ('bank', {'adapter_slots': 2,
                                       'adapter_rank': 4})):
            params = llama.init_params(jax.random.PRNGKey(0), CFG)
            eng = _make_engine(CFG, params, **extra)
            outs[label] = greedy_oracle.greedy(eng, prompts, 8)
        assert outs['bank'] == outs['base'], outs

    def test_adapter_matches_offline_merged(self, adapter_setup):
        """Bank-served adapter emits the choices of the offline-merged
        reference — while a base request sharing the SAME batch stays
        equal to the plain engine (zero-slot purity in a mixed batch:
        exact zeros added)."""
        params, tree, scale, merged = adapter_setup
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        n = 8

        eng = _make_engine(CFG32, params, adapter_slots=2, adapter_rank=4)
        eng.adapters.register('acme', tree, scale=scale)
        rid_a = eng.add_request(prompt, max_new_tokens=n, adapter='acme')
        rid_b = eng.add_request(prompt, max_new_tokens=n)
        done = eng.run_to_completion(horizon=4)
        got_adapter = done[rid_a].output
        got_base = done[rid_b].output

        (want_base,) = greedy_oracle.greedy(_make_engine(CFG32, params),
                                            [prompt], n)
        assert len(got_adapter) == n
        greedy_oracle.assert_agrees(CFG32, merged, prompt, got_adapter,
                                    what='bank adapter')
        assert got_base == want_base, (got_base, want_base)
        # The adapter is actually live (its delta moved the stream).
        assert got_adapter != got_base

    def test_adapter_matches_merged_chunked_prefill(self, adapter_setup):
        """Same contract through the chunked-prefill path: adapter rows
        gather in every prefill chunk."""
        params, tree, scale, merged = adapter_setup
        prompt = ([3, 1, 4, 1, 5, 9, 2, 6] * 5)[:38]
        n = 6

        eng = _make_engine(CFG32, params, prefill_chunk_tokens=16,
                           adapter_slots=2, adapter_rank=4)
        eng.adapters.register('acme', tree, scale=scale)
        (got,) = greedy_oracle.greedy(eng, [prompt], n, adapter='acme')
        assert len(got) == n
        greedy_oracle.assert_agrees(CFG32, merged, prompt, got,
                                    what='bank adapter, chunked prefill')

    def test_adapter_composes_with_multistep_and_spec(self, adapter_setup):
        """decode_steps_per_call and speculate_k emit the merged
        reference's choices, as the single-step adapter stream does
        (the bank rides inside the k-step fused scan and the in-scan
        spec verify)."""
        params, tree, scale, merged = adapter_setup
        prompt = [3, 1, 4, 1, 5]
        n = 8

        for label, extra in (('single', {}),
                             ('multistep', {'decode_steps_per_call': 2}),
                             ('spec', {'speculate_k': 2})):
            eng = _make_engine(CFG32, params, adapter_slots=2,
                               adapter_rank=4, **extra)
            eng.adapters.register('acme', tree, scale=scale)
            (out,) = greedy_oracle.greedy(eng, [prompt], n,
                                          adapter='acme')
            assert len(out) == n
            greedy_oracle.assert_agrees(CFG32, merged, prompt, out,
                                        what=label)

    def test_grammar_constrains_output(self):
        """Satellite: per-slot vocab logit masks. A JSON-mode request
        only ever emits tokens from the JSON-mode set; an id-list
        grammar only emits listed ids — while an unconstrained request
        in the SAME batch is unaffected."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG)
        plain = _make_engine(CFG, params)
        rid = plain.add_request([3, 1, 4], max_new_tokens=8)
        free_want = plain.run_to_completion(horizon=4)[rid].output

        eng = _make_engine(CFG, params)
        rid_json = eng.add_request([3, 1, 4], max_new_tokens=8,
                                   grammar='json')
        rid_free = eng.add_request([3, 1, 4], max_new_tokens=8)
        done = eng.run_to_completion(horizon=4)
        allowed = adapters_lib.compile_grammar('json', CFG.vocab_size)
        assert all(allowed[t] for t in done[rid_json].output), \
            done[rid_json].output
        assert done[rid_free].output == free_want

        eng2 = _make_engine(CFG, params)
        rid = eng2.add_request([3, 1, 4], max_new_tokens=8,
                               grammar=[5, 9])
        out = eng2.run_to_completion(horizon=4)[rid].output
        assert out and set(out) <= {5, 9}, out

    def test_grammar_composes_with_adapter(self, adapter_setup):
        """One request can carry BOTH an adapter and a grammar: the
        mask applies on top of the adapter-shifted logits."""
        params, tree, scale, _ = adapter_setup
        eng = _make_engine(CFG32, params, adapter_slots=2, adapter_rank=4)
        eng.adapters.register('acme', tree, scale=scale)
        rid = eng.add_request([3, 1, 4], max_new_tokens=8,
                              adapter='acme', grammar=[5, 9, 17])
        out = eng.run_to_completion(horizon=4)[rid].output
        assert out and set(out) <= {5, 9, 17}, out
