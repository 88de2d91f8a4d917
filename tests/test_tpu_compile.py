"""The main path's Pallas kernels, compiled for a described TPU v5e at
Llama-3-8B widths (32 Q / 8 KV heads x 128, page 128, 32 layers, 48
slots) — with no chip attached.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a slice off the tiling, too much fast memory, a
layout Mosaic has no rule for. These compiles can, at about two
seconds each, so they guard every later PR at no chip time. A compile
that passes is not a chip run: ``chip_smoke.py`` is.

The topology is described inside a fixture of this file only (one
process at a time may load the TPU's library, and every xdist worker
imports every test file), and everything compiles in the test's own
process.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.inference import paged
from skypilot_tpu.models import configs, kda, llama, quantization
from skypilot_tpu.ops import flash_attention as fa
from skypilot_tpu.ops import paged_attention as pa

CFG = configs.LLAMA3_8B
SLOTS, PAGE, N_PAGES, TABLE_P, RING = 48, 128, 64, 16, 32


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    # These compiles are written to the persistent compile cache but
    # cannot be read back without a chip; keep them out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', prev)
    compilation_cache.reset_cache()


def _pool(kv_dtype, sharding):
    """(pool_k, pool_v, k_scale, v_scale) shapes of a stacked paged pool
    in ``kv_dtype`` ('bf16' | 'int8' | 'int4': packed uint8 nibbles)."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    hkv, d = CFG.n_kv_heads, CFG.head_dim
    dtype, dc = {'bf16': (jnp.bfloat16, d), 'int8': (jnp.int8, d),
                 'int4': (jnp.uint8, d // 2)}[kv_dtype]
    pool = s((CFG.n_layers, N_PAGES, hkv, PAGE, dc), dtype)
    scale = (None if kv_dtype == 'bf16' else
             s((CFG.n_layers, N_PAGES, hkv, PAGE), jnp.float32))
    return pool, pool, scale, scale


def _decode_operands(sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    hq, hkv, d = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    return {
        'q': s((SLOTS, hq, d), jnp.bfloat16),
        'self': s((SLOTS, hkv, d), jnp.bfloat16),
        'ring': s((SLOTS, RING, hkv, d), jnp.bfloat16),
        'table': s((SLOTS, TABLE_P), jnp.int32),
        'lens': s((SLOTS,), jnp.int32),
    }


def _compile_per_layer(kv_dtype, pages_per_block, sharding):
    """``decode_impl='pallas'``'s kernel, layer as a traced index."""
    ops = _decode_operands(sharding)
    pool_k, pool_v, ks, vs = _pool(kv_dtype, sharding)

    def fn(q, pool_k, pool_v, table, lens, ks, vs):
        return pa.paged_decode_attention(
            q, pool_k, pool_v, table, lens, ks, vs, layer=jnp.int32(3),
            pages_per_block=pages_per_block)

    return jax.jit(fn).lower(ops['q'], pool_k, pool_v, ops['table'],
                             ops['lens'], ks, vs).compile()


def _compile_fused(kv_dtype, sharding):
    """``decode_impl='cross_layer'``'s fused-merge kernel."""
    ops = _decode_operands(sharding)
    pool_k, pool_v, ks, vs = _pool(kv_dtype, sharding)

    def fn(q, k_self, v_self, ring_k, ring_v, pool_k, pool_v, table,
           lens, ks, vs):
        return pa.paged_decode_attention_fused(
            q, k_self, v_self, ring_k, ring_v, jnp.int32(5), pool_k,
            pool_v, table, lens, ks, vs, layer=jnp.int32(3))

    return jax.jit(fn).lower(
        ops['q'], ops['self'], ops['self'], ops['ring'], ops['ring'],
        pool_k, pool_v, ops['table'], ops['lens'], ks, vs).compile()


def _has_kernel(compiled) -> bool:
    return 'tpu_custom_call' in compiled.as_text()


def test_flash_forward_and_backward_compile(one_chip):
    """Training/prefill flash at [1, 2048, 32 -> 8, 128]: the forward
    kernel and both backward kernels."""
    def s(heads):
        return jax.ShapeDtypeStruct((1, 2048, heads, CFG.head_dim),
                                    jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    fwd = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
    assert _has_kernel(fwd.lower(s(CFG.n_heads), s(CFG.n_kv_heads),
                                 s(CFG.n_kv_heads)).compile())
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    assert _has_kernel(bwd.lower(s(CFG.n_heads), s(CFG.n_kv_heads),
                                 s(CFG.n_kv_heads)).compile())


@pytest.mark.parametrize('kv_dtype', ['bf16', 'int8'])
@pytest.mark.parametrize('pages_per_block', [1, 4])
def test_paged_decode_attention_compiles(one_chip, kv_dtype,
                                         pages_per_block):
    assert _has_kernel(_compile_per_layer(kv_dtype, pages_per_block,
                                          one_chip))


@pytest.mark.parametrize('kv_dtype', ['bf16', 'int8'])
def test_paged_decode_attention_fused_compiles(one_chip, kv_dtype):
    assert _has_kernel(_compile_fused(kv_dtype, one_chip))


def test_layer_scan_gathers_pages_without_copying_a_pool_layer(one_chip):
    """The paged prefill's read at the chat cell's size (Qwen2-7B: 28
    layers, a 1451-page int8 pool, 4 KV heads): a layer scan that calls
    ``_gather_layer`` with a traced layer must hand the gather the
    stacked pool itself. Slicing the layer out first compiled to a
    ``dynamic-slice_bitcast_fusion`` with an ``s8[1451,4,128,128]``
    output — a copy of the layer's whole pool in every layer step, 19 %
    of the prefill program on the chip (``PERF.md``, PR 28)."""
    n_layers, n_pages, hkv, slots, pages = 28, 1451, 4, 2, 4

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(pool_k, pool_v, ks, vs, table):
        def layer_body(acc, li):
            ck, sck = paged._gather_layer(pool_k, ks, li, table)
            cv, scv = paged._gather_layer(pool_v, vs, li, table)
            return acc + jnp.sum(ck * sck, 1) + jnp.sum(cv * scv, 1), None
        acc = jnp.zeros((slots, hkv, CFG.head_dim), jnp.float32)
        return jax.lax.scan(layer_body, acc, jnp.arange(n_layers))[0]

    pool = s((n_layers, n_pages, hkv, PAGE, CFG.head_dim), jnp.int8)
    scale = s((n_layers, n_pages, hkv, PAGE), jnp.float32)
    compiled = jax.jit(fn).lower(pool, pool, scale, scale,
                                 s((slots, pages), jnp.int32)).compile()
    assert f's8[{n_pages},{hkv},{PAGE},{CFG.head_dim}]' not in \
        compiled.as_text()
    # The compiler counts a loop's body once, int8 tiles as padded
    # words: it read 11.7 MB here, and 404.8 MB with the slice.
    gathered = n_layers * 2 * slots * pages * hkv * PAGE * (
        CFG.head_dim + 4)
    assert compiled.cost_analysis()['bytes accessed'] < 4 * gathered


def test_one_page_prefill_chunk_leaves_the_pool_in_place(one_chip):
    """The whole prefill-chunk program of the chat cell (Qwen2-7B, int8
    weights, a 1451-page int8 pool) for ONE prompt of ONE page, chunk
    128: the shape at which a one-row gather became a dynamic-slice
    fused into the p.v dot, and the dot's transposed layout a copy of
    the whole V pool at the program's start — 2.66 GB of temp beside
    13.7 GB of arguments on a 16.9 GB chip (``PERF.md``, PR 28). Only
    the real program shows it: a scan with a plain consumer does not."""
    cfg = configs.QWEN2_7B

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda key: quantization.quantize_params(
            llama.init_params(key, cfg=cfg)), jax.random.PRNGKey(0)))
    cache = shaped(jax.eval_shape(lambda: paged.PagedKVCache.create(
        cfg, n_pages=1451, page_size=PAGE, kv_dtype='int8')))

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, table_p, tokens, lengths, valid, want):
        return paged.paged_prefill_chunk(params, cache, table_p, tokens,
                                         lengths, valid, want, cfg)

    compiled = prefill.lower(params, cache, vec(1, 1), vec(1, 128),
                             vec(1), vec(1), vec(1)).compile()
    pool_layer = cache.pool_k.size // cfg.n_layers          # 95.1 MB
    assert compiled.memory_analysis().temp_size_in_bytes < pool_layer


@pytest.mark.parametrize('kernel', ['per_layer', 'fused'])
def test_packed_int4_pool_is_refused_by_mosaic(one_chip, kernel):
    """Recorded, not wished for: both kernels unpack a packed int4 pool's
    nibbles with a reshape Mosaic has no layout rule for
    (``infer-vector-layout: unsupported shape cast``), though interpret
    mode runs them. Flip this to a plain compile when ROADMAP A4 gives
    the int4 pool a layout that keeps 128 lanes."""
    with pytest.raises(Exception, match='unsupported shape cast'):
        if kernel == 'per_layer':
            _compile_per_layer('int4', 1, one_chip)
        else:
            _compile_fused('int4', one_chip)


@pytest.mark.parametrize('decode_impl', ['pallas', 'cross_layer'])
def test_engine_refuses_int4_kv_kernels_on_tpu(monkeypatch, decode_impl):
    """What the refusal above means for a user: on the TPU backend an
    explicit kernel path over an int4 pool is an error at engine
    construction, not a crash at the first decode. The backend is
    steered here, in the test; off the TPU the interpret-mode
    combination keeps working (tests/test_kv_round2.py)."""
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with pytest.raises(ValueError, match="kv_cache_dtype='int4'"):
        PagedInferenceEngine(configs.TINY, max_batch=2, max_seq=64,
                             n_pages=8, page_size=8,
                             kv_cache_dtype='int4',
                             decode_impl=decode_impl)


@pytest.mark.parametrize('rows', [128, 4096])    # a decode step (32 slots
def test_grouped_expert_matmuls_compile_at_glm_widths(   # x top-4), a chunk
        one_chip, monkeypatch, rows):
    """The dropless expert layer of GLM-4.7-Flash at its published widths
    (64 experts of 2048 x 1536 in a stack of 7 layers, bf16), through the
    kernel and not interpret mode; the whole stacked weights are the
    kernel's operand (no layer of them is sliced out as a copy), and the
    compiled text holds the kernel three times."""
    from skypilot_tpu.models import latent_moe
    cfg = configs.GLM_4_7_FLASH
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    L, E, d, f, k = 7, cfg.n_routed_experts, cfg.dim, cfg.moe_ffn_dim, \
        cfg.n_experts_per_token

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    experts = {'w_gate': s((L, E, d, f), jnp.bfloat16),
               'w_up': s((L, E, d, f), jnp.bfloat16),
               'w_down': s((L, E, f, d), jnp.bfloat16)}
    T = rows // k

    def fn(experts, layer, x, chosen, w, live):
        return latent_moe.routed_experts(experts, layer, x, chosen, w,
                                         live, cfg)

    compiled = jax.jit(fn).lower(
        experts, s((), jnp.int32), s((T, d), jnp.bfloat16),
        s((T, k), jnp.int32), s((T, k), jnp.float32),
        s((T,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 3
    # nothing expert-stack sized is copied: temps stay far under one
    # layer's experts (604 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


# ------------------------------------------- the latent paged decode kernel
GLM_SLOTS, GLM_PAGES = 32, 2818         # glm-4.7-flash.longctx's engine


def _glm_cfg():
    """GLM-4.7-Flash as the cell cuts it: 8 of its layers."""
    import dataclasses
    return dataclasses.replace(configs.GLM_4_7_FLASH, n_layers=8)


def _glm_cache(one_chip, cfg):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: paged.PagedKVCache.create(
            cfg, n_pages=GLM_PAGES, page_size=PAGE)))


@pytest.mark.parametrize('table_p', [64, 128])   # contexts to 8k and to 16k
def test_latent_paged_decode_attention_compiles(one_chip, table_p):
    """The kernel at the cell's widths (32 slots, page 128, 20 heads,
    ``kv_lora_rank`` 512, rope 64 two tokens to a lane row), one page a
    loop iteration (the block the code keeps): Mosaic takes the one-hot
    spread of the packed rope page and the lane mask."""
    from skypilot_tpu.ops import latent_paged_attention as lpa
    cfg = _glm_cfg()
    cache = _glm_cache(one_chip, cfg)
    assert cache.pool_v.shape[3:] == (64, 128)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q_lat, q_rope, pool_c, pool_r, table, lens, layer):
        return lpa.latent_paged_decode_attention(
            q_lat, q_rope, pool_c, pool_r, table, lens, layer=layer,
            scale=0.0625)

    compiled = jax.jit(fn).lower(
        s((GLM_SLOTS, cfg.n_heads, cfg.kv_lora_rank), jnp.bfloat16),
        s((GLM_SLOTS, cfg.n_heads, cfg.qk_rope_head_dim), jnp.bfloat16),
        cache.pool_k, cache.pool_v, s((GLM_SLOTS, table_p), jnp.int32),
        s((GLM_SLOTS,), jnp.int32), s((), jnp.int32)).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def _result_sizes(hlo_text):
    """{opcode: elements of its largest result} over the instructions
    of an optimized HLO module."""
    import math
    import re
    sizes = {}
    for line in hlo_text.splitlines():
        m = re.match(r'\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z\-]+)\(', line)
        if not m:
            continue
        for dims in re.findall(r'[a-z]+[0-9]*\[([0-9,]+)\]', m.group(1)):
            n = math.prod(int(d) for d in dims.split(','))
            sizes[m.group(2)] = max(sizes.get(m.group(2), 0), n)
    return sizes


@pytest.mark.parametrize('decode_impl', ['pallas', 'gather'])
def test_latent_decode_program_reads_no_page_it_does_not_need(
        one_chip, monkeypatch, decode_impl):
    """The whole latent ``paged_decode_horizon`` of the ``longctx`` cell
    (GLM-4.7-Flash cut to 8 layers, a 2818-page pool, 32 slots, a
    64-page bucket) in the manner of
    ``test_layer_scan_gathers_pages_without_copying_a_pool_layer``: with
    the kernel no operation of the optimized HLO makes anything as large
    as the rope rows of one gathered bucket, let alone of a layer of a
    pool; only parameters and loop plumbing are that large. The
    ``'gather'`` fallback is the witness that the check sees what it is
    after: its gathers, transposes and fusions are. 0.31 GB of temp with
    the gather, under 0.05 GB without (compiler, PR 31)."""
    cfg = _glm_cfg()
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    table_p, horizon = 64, 8
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda key: llama.init_params(key, cfg),
                       jax.random.PRNGKey(0)))
    cache = _glm_cache(one_chip, cfg)

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, cache, table, tokens, lengths, active):
        return paged.paged_decode_horizon(
            params, cache, table, tokens, lengths, cfg, horizon=horizon,
            active=active, decode_impl=decode_impl)

    compiled = jax.jit(decode).lower(
        params, cache, vec((GLM_SLOTS, table_p)), vec((GLM_SLOTS,)),
        vec((GLM_SLOTS,)), vec((GLM_SLOTS,), jnp.bool_)).compile()
    text = compiled.as_text()
    gathered_rope = GLM_SLOTS * table_p * PAGE * cfg.qk_rope_head_dim
    assert gathered_rope < cache.pool_v.size // cfg.n_layers
    # Weights are as large; nothing computes one: a fusion of that size
    # is a bitcast of the output head.
    plumbing = {'parameter', 'get-tuple-element', 'tuple', 'while',
                'bitcast', 'fusion'}
    made = {op: n for op, n in _result_sizes(text).items()
            if op not in plumbing and n >= gathered_rope}
    temp = compiled.memory_analysis().temp_size_in_bytes
    if decode_impl == 'pallas':
        # the dense layer's and the expert layers' scans: 2 + 3 kernels
        assert text.count('tpu_custom_call') == 5
        assert not made, made
        assert f'[{GLM_PAGES},' not in text.replace(
            f'[{cfg.n_layers},{GLM_PAGES},', '')
        assert temp < 50e6
    else:
        assert 'gather' in made and temp > 250e6


# ------------------------------------------------ row writes into the pools
def _compile_row_write(one_chip, cfg, cache, program, slots, table_p,
                       kv_dtype='bf16'):
    """One of the two programs that write rows into ``cache``'s pools,
    compiled with the cache donated: the ring merge of ``slots`` x 8
    steps, or the row write that ends a 1 x 256 prefill chunk."""
    spec, layers = cfg.kv_spec, cfg.n_cache_layers

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def rows(n_slots, n, quantized):
        shape = (layers, n_slots, n, spec.heads)
        if quantized:
            pair = (s(shape + (spec.k_dim,), jnp.int8),
                    s(shape + (1,), jnp.float32))
            return pair, pair
        return (s(shape + (spec.k_dim,), jnp.bfloat16),
                s(shape + (spec.v_dim,), jnp.bfloat16))

    if program == 'merge_ring_into_pool':    # quantizes the ring itself
        target = paged.merge_ring_into_pool
        operands = (*rows(slots, 8, False), s((slots, table_p)),
                    s((slots,)), s((slots,), jnp.bool_))
    else:
        target = paged.merge_rows_into_pool
        operands = (*rows(1, 256, kv_dtype == 'int8'), s((1, table_p)),
                    s((1,)), s((1,)))
    # A function of its own for each case: ``jit`` caches the trace by
    # function and shapes, and a patched form differs in neither.
    return jax.jit(lambda *args: target(*args), donate_argnums=(0,)
                   ).lower(cache, *operands).compile()


def _assert_live_unit_loop_in_place(compiled, cache, moved=(),
                                    temp_limit=4e6):
    """One ``while`` (the loop over live units) whose scatters are the
    pools', every pool aliased in place, under 4 MB of temp, and nothing
    made as large as the smallest pool but by those scatters (and by
    the opcodes ``moved``, which the caller accounts for)."""
    import math
    import re
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    pools = jax.tree.leaves(cache)
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    assert mem.temp_size_in_bytes < temp_limit
    assert text.count(' while(') == 1
    smallest = min(p.size for p in pools)
    scattered = []
    for line in text.splitlines():
        m = re.match(r'\s*(?:ROOT )?%?[\w.\-]+ = [a-z0-9]+\[([0-9,]+)\]'
                     r'\S* scatter\(', line)
        if m:
            scattered.append(math.prod(map(int, m.group(1).split(','))))
    big = [n for n in scattered if n >= smallest]
    assert len(big) == len(pools), scattered
    # the others order the live units: a few hundred flags at the most
    assert all(n < 1024 for n in scattered if n < smallest), scattered
    in_place = {'parameter', 'get-tuple-element', 'tuple', 'bitcast',
                'scatter', 'fusion', 'while', *moved}
    made = {op: n for op, n in _result_sizes(text).items()
            if op not in in_place and n >= smallest}
    assert not made, made


@pytest.mark.parametrize('form', ['whole_lane_rows', 'windowed'])
@pytest.mark.parametrize('program', ['merge_ring_into_pool',
                                     'prefill_chunk_row_write'])
def test_latent_row_writes_hold_no_serial_row_loop_and_no_pool_copy(
        one_chip, monkeypatch, program, form):
    """The two programs that write rows into the ``longctx`` cell's pools
    (8 layers, 2818 pages of 128, latent 512 + rope 64 two tokens to a
    lane row): the ring merge of 32 slots x 8 steps, and the row write
    that ends a 1 x 256 prefill chunk. Written as whole lane rows, a
    unit's rope rows are one ``scatter``: the one ``while`` is the loop
    over live units (PR 36; its unit here is the slot's whole run), its
    scatters are the two pools', nothing is made that is as large as the
    rope pool (369 MB), next to no temp, both pools aliased in place.
    The 2-D windowed scatter that PR 33 replaced compiled to a serial
    ``while`` of one trip a rope row (``s32[rows,2]`` indices; 2,048 rows
    a call then), 6-7.6 ms of an 8.1 ms merge and of a 23.4 ms chunk on
    the chip (``PERF.md``, PR 33): it is the witness that this test can
    see such a loop, now nested in the unit loop."""
    cfg = _glm_cfg()
    cache = _glm_cache(one_chip, cfg)
    if form == 'windowed':
        from test_lane_packed_rows import windowed_scatter
        monkeypatch.setattr(paged, '_scatter_rows_lane_packed',
                            windowed_scatter)
    compiled = _compile_row_write(one_chip, cfg, cache, program,
                                  GLM_SLOTS, 64)
    text = compiled.as_text()
    serial_rows = 's32[%d,2]' % (cfg.n_layers * (
        8 if program == 'merge_ring_into_pool' else 256))
    if form == 'windowed':
        assert text.count(' while(') == 2 and serial_rows in text
        pools = (cache.pool_k.size + cache.pool_v.size) * 2      # bytes
        assert compiled.memory_analysis().alias_size_in_bytes >= pools
        return
    assert serial_rows not in text
    _assert_live_unit_loop_in_place(compiled, cache)


def _cell_cache(one_chip, cell):
    """(cfg, cache shapes, slots, page-table bucket, KV dtype) of a GQA
    serving cell's engine, as ``PERF.md`` §4 has them."""
    cfg, n_pages, kv_dtype, slots, table_p = {
        'ouro-2.6b.reason': (configs.OURO_2_6B, 41, 'bf16', 16, 4),
        'qwen2-7b.chat': (configs.QWEN2_7B, 1451, 'int8', 64, 16),
    }[cell]
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: paged.PagedKVCache.create(
            cfg, n_pages=n_pages, page_size=PAGE, kv_dtype=kv_dtype)))
    return cfg, cache, slots, table_p, kv_dtype


@pytest.mark.parametrize('program', ['merge_ring_into_pool',
                                     'prefill_chunk_row_write'])
@pytest.mark.parametrize('cell', ['ouro-2.6b.reason', 'qwen2-7b.chat'])
def test_live_row_writes_alias_every_pool_at_the_cells_shapes(
        one_chip, cell, program):
    """The loop over live units at ``reason``'s shapes (two bf16 pools
    of 192 cache layers x 41 pages = 8.25 GB; a ring of 16 slots x 8
    steps, a 1 x 256 chunk) and at chat's (int8 codes + two f32 scale
    pools of 1451 pages = 5.49 GB; a ring of 64 slots x 8): every pool
    aliased in place, under 4 MB of temp, nothing as large as a pool
    made (compiler, PR 36)."""
    cfg, cache, slots, table_p, kv_dtype = _cell_cache(one_chip, cell)
    if cell == 'ouro-2.6b.reason':
        assert cache.pool_k.shape == (192, 41, 16, PAGE, 128)
    compiled = _compile_row_write(one_chip, cfg, cache, program, slots,
                                  table_p, kv_dtype)
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        8.25e9 if cell == 'ouro-2.6b.reason' else 5.49e9)
    # Chat's chunk, whose unit is the slot's whole run: the compiler
    # holds one f32 scale pool (83 MB, 1.5 % of the pools' bytes) in the
    # fast memory across its scatter, as the parent's one-scatter
    # program did: a move between memory spaces, in place in the
    # program's own memory; no code pool moves. And the trip's slice of
    # the rows is a copy of them: 7.6 MB of int8 codes and scales.
    moved, temp_limit = (), 4e6
    if (cell, program) == ('qwen2-7b.chat', 'prefill_chunk_row_write'):
        moved = ('copy-start', 'copy-done', 'slice-start', 'slice-done',
                 'custom-call')       # ConcatBitcast of the slices
        temp_limit = 4e6 + 7.6e6
    _assert_live_unit_loop_in_place(compiled, cache, moved, temp_limit)
    made = _result_sizes(compiled.as_text())
    assert all(made.get(op, 0) < cache.pool_k.size for op in moved)


def test_a_token_window_written_by_update_slice_relays_a_whole_pool(
        one_chip, monkeypatch):
    """The witness that the check above can see a copy: a unit written as
    ``dynamic_update_slice`` windows of one token, [L, 1, hkv, 1, d],
    looks cheaper than the flat scatter and makes layout assignment
    relay a pool through 4.13 GB of temp at ``reason``'s shapes
    (compiler, PR 36; ``_scatter_rows``' docstring)."""
    from jax import lax

    def update_slices(pool, rows, flat_idx):
        page = pool.shape[3]
        for j in range(rows.shape[2]):
            tok = flat_idx[0, j]
            pool = lax.dynamic_update_slice(
                pool, rows[:, 0, j][:, None, :, None].astype(pool.dtype),
                (0, tok // page, 0, tok % page, 0))
        return pool

    monkeypatch.setattr(paged, '_scatter_rows', update_slices)
    cfg, cache, slots, table_p, _ = _cell_cache(one_chip,
                                                'ouro-2.6b.reason')
    compiled = _compile_row_write(one_chip, cfg, cache,
                                  'merge_ring_into_pool', slots, table_p)
    one_pool = cache.pool_k.size * 2
    assert compiled.memory_analysis().temp_size_in_bytes >= one_pool
    with pytest.raises(AssertionError):
        _assert_live_unit_loop_in_place(compiled, cache)


# ------------------------------------------------ a looped decoder's decode
def test_looped_decode_program_keeps_the_pass_loop_and_one_kernel(
        one_chip, monkeypatch):
    """The whole ``paged_decode_horizon`` of the ``ouro-2.6b.reason``
    cell (48 layers x 4 passes, 192 cache layers, 16 slots, a 41-page
    pool of 201 MB pages): three nested loops (horizon, passes, layers:
    the pass loop is not unrolled into 192 layers), ONE paged kernel
    whose ``layer`` is pass * 48 + layer, and nothing as large as a
    cache layer of the pool made beside the ring (0.40 GB) and the
    step's transients (1.21 GB of temp; compiler, PR 35)."""
    cfg = configs.OURO_2_6B
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    slots, n_pages, table_p, horizon = 16, 41, 4, 16

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda key: llama.init_params(key, cfg), jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(lambda: paged.PagedKVCache.create(
        cfg, n_pages=n_pages, page_size=PAGE)))
    assert cache.pool_k.shape == (192, n_pages, 16, PAGE, 128)

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, cache, table, tokens, lengths, active):
        return paged.paged_decode_horizon(
            params, cache, table, tokens, lengths, cfg, horizon=horizon,
            active=active, decode_impl='pallas')

    compiled = jax.jit(decode).lower(
        params, cache, vec((slots, table_p)), vec((slots,)),
        vec((slots,)), vec((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 1
    assert text.count(' while(') == 3
    mem = compiled.memory_analysis()
    ring = 2 * 192 * slots * horizon * 16 * 128 * 2
    assert mem.output_size_in_bytes == pytest.approx(ring, rel=0.01)
    assert mem.temp_size_in_bytes < 1.5e9


# --------------------------- a recurrent state beside the pool (PR 37)
def _solar_operands(one_chip, slots, n_pages=2000):
    cfg = configs.SOLAR_OPEN2_250B

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda key: kda.serving_layout(llama.init_params(key, cfg)),
        jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(lambda: paged.PagedKVCache.create(
        cfg, n_pages=n_pages, page_size=PAGE)))
    rec = shapes(jax.eval_shape(
        lambda: paged.RecurrentState.create(cfg, slots)))
    assert cache.pool_k.shape == (1, n_pages, 8, PAGE, 128)
    assert rec.state.shape == (3, slots, 64, 128, 128)
    assert rec.conv.shape == (3, slots, 3, 3 * 8192)
    return cfg, params, cache, rec


def _assert_projections_read_in_place(text, cfg):
    """A KDA layer's q/k/v weight reaches its dot where it lies: no
    dynamic-slice (fused or not) makes a copy as large as one
    projection matrix, and the traced KDA body holds ONE projection dot
    (``serving_layout``'s 2-D ``wqkv``). The 3-D leaves were sliced out
    of the scanned stack into fast memory first, three copies of 67 MB
    a layer a step (``constant_dynamic-slice_fusion``; compiler, PR 38)."""
    import math
    import re
    matrix = cfg.dim * cfg.kda_heads * cfg.kda_head_dim
    copies = [m.group(1) for m in re.finditer(
        r'%(\S*dynamic-slice\S*) = \(?\w+\[([\d,]*)\]', text)
        if math.prod(int(n) for n in m.group(2).split(',') if n) >= matrix]
    assert copies == []
    dots = [re.search(r'kda_mix/(bsd,dn->bsn|bsd,dhk->bshk)/', line)
            for line in text.splitlines()
            if re.search(r' (convolution|dot)\(', line)]
    assert [d.group(1) for d in dots if d] == ['bsd,dn->bsn']


@pytest.mark.parametrize('slots', [32, 64])
def test_hybrid_decode_program_advances_the_state_in_place(
        one_chip, monkeypatch, slots):
    """The whole ``paged_decode_horizon`` of the
    ``solar-open2-250b.longgen`` cell (one period [GQA, KDA, KDA, KDA],
    40 of 320 experts held, 32 slots; and 64): the donated recurrent
    state (13,025,280 B a slot: 417 MB at 32 slots, 834 MB at 64) is
    aliased to its output, so no second copy of it exists; the loops are
    the horizon, the period and the run of 3 KDA layers (ONE traced KDA
    body); one paged kernel (the GQA layer), 2 x 3 grouped expert
    matmuls and the KDA state kernel; each KDA layer's q/k/v read in
    place by one dot. 0.19 GB of temp at 32 slots (compiler, PR 37)."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    cfg, params, cache, rec = _solar_operands(one_chip, slots)

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, cache, table, tokens, lengths, active, rec):
        return paged.paged_decode_horizon(
            params, cache, table, tokens, lengths, cfg, horizon=8,
            active=active, decode_impl='pallas', rec=rec)

    compiled = jax.jit(decode, donate_argnames=('rec',)).lower(
        params, cache, vec((slots, 8)), vec((slots,)), vec((slots,)),
        vec((slots,), jnp.bool_), rec=rec).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    state_bytes = slots * 13_025_280
    assert mem.alias_size_in_bytes == state_bytes
    ring = 2 * slots * 8 * 8 * 128 * 2
    assert mem.output_size_in_bytes == pytest.approx(
        state_bytes + ring, rel=0.01)
    assert mem.temp_size_in_bytes < 0.45e9 * slots / 32
    assert text.count('tpu_custom_call') == 8
    assert text.count(' while(') <= 5
    _assert_projections_read_in_place(text, cfg)


def test_hybrid_prefill_chunk_carries_the_state_in_place(one_chip,
                                                         monkeypatch):
    """``paged_prefill_chunk`` of the same cell, 4 prompts x 256 tokens
    over an 8-page bucket: pools and recurrent state are all aliased to
    their outputs (1.05 GB + 0.42 GB; the 4 rows' states are taken out,
    advanced through the chunked delta rule and scattered back), and
    the transients stay under 0.7 GB (0.49 GB; compiler, PR 37); the
    q/k/v weights are read in place, as in decode."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    slots, n, table_p, chunk = 32, 4, 8, 256
    cfg, params, cache, rec = _solar_operands(one_chip, slots)

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def prefill(params, cache, table, tokens, lengths, valid, want,
                rec=None, slot_ids=None):
        return paged.paged_prefill_chunk(
            params, cache, table, tokens, lengths, valid, want, cfg,
            rec=rec, slot_ids=slot_ids)

    compiled = jax.jit(prefill, donate_argnums=(1,),
                       donate_argnames=('rec',)).lower(
        params, cache, vec((n, table_p)), vec((n, chunk)), vec((n,)),
        vec((n,)), vec((n,)), rec=rec, slot_ids=vec((n,))).compile()
    mem = compiled.memory_analysis()
    held = (2 * cache.pool_k.size * 2) + slots * 13_025_280
    assert mem.alias_size_in_bytes == held
    assert mem.output_size_in_bytes == pytest.approx(held, rel=0.001)
    assert mem.temp_size_in_bytes < 0.7e9
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 6
    _assert_projections_read_in_place(text, cfg)


def test_hybrid_chunk_batches_count_the_recurrent_mixers_terms(
        one_chip, monkeypatch):
    """``_chunk_batch_cap`` at the cell's sizes. A piece's attention
    scores and expert rows alone would admit 16 prompts a chunk at up to
    4 pages (1.75 GB counted), but that program holds 2.22 GB, over the
    2.2 GB the cap exists to keep: its peak is the KDA mixers' float32
    terms, 131 MB a 256-token piece (compiler, PR 37). With them counted
    the cell forms 8 prompts at up to 4 pages and 4 beyond, 18 prefill
    programs in all, and the largest of them stays inside the budget
    (1.1 GB)."""
    import types
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    engine_cls = paged.PagedInferenceEngine
    slots, chunk = 32, 256
    cfg, params, cache, rec = _solar_operands(one_chip, slots)
    eng = types.SimpleNamespace(
        cfg=cfg, chunk=chunk, page=PAGE,
        _ATTN_SCORE_BYTES=engine_cls._ATTN_SCORE_BYTES,
        _RECURRENT_TOKEN_ARRAYS=engine_cls._RECURRENT_TOKEN_ARRAYS)
    budget = engine_cls._CHUNK_TRANSIENT_BUDGET

    def prompts_max(pages):
        piece = engine_cls._chunk_piece_bytes(eng, pages)
        return max(n for n in engine_cls._PREFILL_N_BUCKETS
                   if n * piece <= budget)

    assert {p: prompts_max(p) for p in (1, 2, 4, 8, 16)} == {
        1: 8, 2: 8, 4: 8, 8: 4, 16: 4}
    dense = types.SimpleNamespace(**{**vars(eng), 'cfg': configs.QWEN2_7B})
    assert engine_cls._chunk_piece_bytes(dense, 4) == int(
        4 * 28 * chunk * PAGE * 4.5)         # a dense model's: as it was

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def prefill(params, cache, table, tokens, lengths, valid, want,
                rec=None, slot_ids=None):
        return paged.paged_prefill_chunk(
            params, cache, table, tokens, lengths, valid, want, cfg,
            rec=rec, slot_ids=slot_ids)

    def temp_bytes(n, table_p):
        return jax.jit(prefill, donate_argnums=(1,),
                       donate_argnames=('rec',)).lower(
            params, cache, vec((n, table_p)), vec((n, chunk)), vec((n,)),
            vec((n,)), vec((n,)), rec=rec, slot_ids=vec((n,))
        ).compile().memory_analysis().temp_size_in_bytes

    assert temp_bytes(8, 4) < 1.3e9
    assert temp_bytes(16, 4) > budget


# ------------------------------------------- a lone prompt's wide piece
@pytest.mark.parametrize('cell,table_p', [('longctx', 64), ('longgen', 16)])
def test_wide_piece_holds_what_its_four_prompt_batch_holds(
        one_chip, monkeypatch, cell, table_p):
    """A 1024-token piece of one prompt at the longest context's page
    bucket of each routed cell (8k tokens of GLM-4.7-Flash, 2k of Solar
    Open 2) compiles for the chip, and its transients are those of the
    4-prompt x 256 batch that ``_chunk_piece_bytes`` counts it as, give
    or take the larger in-piece attention (0.80 against 0.77 GB and 0.82
    against 0.62 GB, compiled for a described v5e): far inside the 2.2 GB
    budget."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    if cell == 'longgen':
        cfg, params, cache, rec = _solar_operands(one_chip, 32, 4100)
    else:
        cfg, rec = _glm_cfg(), None
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip),
            jax.eval_shape(lambda key: llama.init_params(key, cfg),
                           jax.random.PRNGKey(0)))
        cache = _glm_cache(one_chip, cfg)

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def prefill(params, cache, table, tokens, lengths, valid, want,
                rec=None, slot_ids=None):
        return paged.paged_prefill_chunk(
            params, cache, table, tokens, lengths, valid, want, cfg,
            rec=rec, slot_ids=slot_ids)

    def temp_bytes(n, width):
        state = {} if rec is None else dict(rec=rec, slot_ids=vec((n,)))
        return jax.jit(prefill, donate_argnums=(1,),
                       donate_argnames=('rec',)).lower(
            params, cache, vec((n, table_p)), vec((n, width)), vec((n,)),
            vec((n,)), vec((n,)), **state
        ).compile().memory_analysis().temp_size_in_bytes

    wide, batch = temp_bytes(1, 1024), temp_bytes(4, 256)
    assert wide < batch + 0.3e9
    assert wide < 0.5 * paged.PagedInferenceEngine._CHUNK_TRANSIENT_BUDGET


# ----------------------------------- Mamba layers beside GQA layers
def _jamba_operands(one_chip, slots, n_pages=4000):
    cfg = configs.JAMBA2_3B

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda key: llama.init_params(key, cfg), jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(lambda: paged.PagedKVCache.create(
        cfg, n_pages=n_pages, page_size=PAGE)))
    rec = shapes(jax.eval_shape(
        lambda: paged.RecurrentState.create(cfg, slots)))
    assert cache.pool_k.shape == (2, n_pages, 1, PAGE, 128)
    assert rec.state.shape == (26, slots, 1, 16, 5120)
    assert rec.conv.shape == (26, slots, 3, 5120)
    return cfg, params, cache, rec


def _stack_slice_copies(text, cfg):
    """Arrays of several layers of a stacked matrix that are not a whole
    stack (26 Mamba layers, 2 GQA layers): the period scan reads a
    dense-FFN stack where it lies. (A period's slice of a scanned stack was a 13-layer copy
    of each leaf, [2, 13, 2560, 10240] and its kin, 2.1 GB of temp a
    decode program.)"""
    import re
    d, f, i = cfg.dim, cfg.ffn_dim, cfg.mamba_inner
    mats = '|'.join(f'{a},{b}' for a, b in ((d, 2 * i), (d, f), (f, d),
                                            (i, d)))
    return sorted({m.group(0) for m in re.finditer(
        rf'bf16\[((?:\d+,)*)({mats})\]', text)
        if m.group(1) not in ('', '1,', '2,', '26,')})   # the stacks


def test_mamba_decode_program_advances_the_state_in_place(one_chip,
                                                          monkeypatch):
    """The whole ``paged_decode_horizon`` of the ``jamba2-3b.docreason``
    cell at 64 slots: the donated state and conv tail (9,318,400 B a
    slot, 596 MB) are aliased to the output; three kernels (the paged
    GQA kernel over 20 query heads on one KV head, and the Mamba state
    kernel in each of the period's two Mamba runs); the loops are the
    horizon, the period, the runs of Mamba layers; no copy of a
    stacked weight; 0.14 GB of temp."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    slots = 64
    cfg, params, cache, rec = _jamba_operands(one_chip, slots)

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, cache, table, tokens, lengths, active, rec):
        return paged.paged_decode_horizon(
            params, cache, table, tokens, lengths, cfg, horizon=8,
            active=active, decode_impl='pallas', rec=rec)

    compiled = jax.jit(decode, donate_argnames=('rec',)).lower(
        params, cache, vec((slots, 16)), vec((slots,)), vec((slots,)),
        vec((slots,), jnp.bool_), rec=rec).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes == slots * 9_318_400
    assert mem.temp_size_in_bytes < 0.3e9
    assert text.count('tpu_custom_call') == 3
    assert 'mamba_recurrent_step' in text
    assert _stack_slice_copies(text, cfg) == []


def test_mamba_prefill_chunk_runs_the_scan_kernel(one_chip, monkeypatch):
    """``paged_prefill_chunk`` of the same cell, 8 prompts x 256 tokens
    over a 16-page bucket: the selective scan is the Pallas kernel (one
    a traced Mamba run), pools and state are aliased to their outputs,
    and the transients stay under 0.8 GB (0.57 GB)."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    slots, n, table_p, chunk = 64, 8, 16, 256
    cfg, params, cache, rec = _jamba_operands(one_chip, slots)

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def prefill(params, cache, table, tokens, lengths, valid, want,
                rec=None, slot_ids=None):
        return paged.paged_prefill_chunk(
            params, cache, table, tokens, lengths, valid, want, cfg,
            rec=rec, slot_ids=slot_ids)

    compiled = jax.jit(prefill, donate_argnums=(1,),
                       donate_argnames=('rec',)).lower(
        params, cache, vec((n, table_p)), vec((n, chunk)), vec((n,)),
        vec((n,)), vec((n,)), rec=rec, slot_ids=vec((n,))).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    held = 2 * cache.pool_k.size * 2 + slots * 9_318_400
    assert mem.alias_size_in_bytes == held
    assert mem.temp_size_in_bytes < 0.8e9
    assert text.count('tpu_custom_call') == 2
    assert 'mamba_scan' in text
    assert _stack_slice_copies(text, cfg) == []


def test_mamba_chunk_batches_count_the_mixers_terms(one_chip, monkeypatch):
    """``_chunk_batch_cap`` at the ``docreason`` cell's sizes counts a
    Mamba mixer's float32 terms, 5 x 5,120 values a token: 32 prompts a
    chunk up to 8 pages of context, 16 up to 32, 8 at 64 (a 8k
    context). The programs at those edges hold what the count says
    (1.65, 1.85, 1.60 GB against 1.59, 1.93, 1.72 counted), and 32
    prompts at 16 pages, which the count refuses, would hold 2.31 GB,
    past the 2.2 GB budget."""
    import types
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    engine_cls = paged.PagedInferenceEngine
    slots, chunk = 64, 256
    cfg, params, cache, rec = _jamba_operands(one_chip, slots)
    eng = types.SimpleNamespace(
        cfg=cfg, chunk=chunk, page=PAGE,
        _ATTN_SCORE_BYTES=engine_cls._ATTN_SCORE_BYTES,
        _RECURRENT_TOKEN_ARRAYS=engine_cls._RECURRENT_TOKEN_ARRAYS,
        _MAMBA_TOKEN_ARRAYS=engine_cls._MAMBA_TOKEN_ARRAYS)
    budget = engine_cls._CHUNK_TRANSIENT_BUDGET

    def prompts_max(pages):
        piece = engine_cls._chunk_piece_bytes(eng, pages)
        return max(n for n in engine_cls._PREFILL_N_BUCKETS
                   if n * piece <= budget)

    assert {p: prompts_max(p) for p in (1, 8, 16, 32, 64)} == {
        1: 32, 8: 32, 16: 16, 32: 16, 64: 8}

    def vec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def prefill(params, cache, table, tokens, lengths, valid, want,
                rec=None, slot_ids=None):
        return paged.paged_prefill_chunk(
            params, cache, table, tokens, lengths, valid, want, cfg,
            rec=rec, slot_ids=slot_ids)

    def temp_bytes(n, table_p):
        return jax.jit(prefill, donate_argnums=(1,),
                       donate_argnames=('rec',)).lower(
            params, cache, vec((n, table_p)), vec((n, chunk)), vec((n,)),
            vec((n,)), vec((n,)), rec=rec, slot_ids=vec((n,))
        ).compile().memory_analysis().temp_size_in_bytes

    for n, pages in ((32, 8), (16, 32), (8, 64)):
        counted = n * engine_cls._chunk_piece_bytes(eng, pages)
        assert temp_bytes(n, pages) < min(1.1 * counted, budget)
    assert temp_bytes(32, 16) > budget
