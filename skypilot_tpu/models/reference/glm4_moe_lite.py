"""Plain reference of GLM-4.7-Flash's forward pass (``glm4_moe_lite``:
zai-org/GLM-4.7-Flash ``config.json``; the DeepSeek-V3 block): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, a loop
over layers and a loop over experts, no cache, no kernel, no batching.
It takes the program's parameter tree (``models/latent_moe.py``) in any
dtype and upcasts each leaf where it is used, one expert at a time, so a
bf16 tree that fills the chip can be scored beside itself.

``cfg`` is a ``ModelConfig`` or the ``model`` object of a benchmark
configuration file (the same field names).

Departures from the published description, each noted at its line:
the multi-token-prediction block is not run; the rotary pairing is
half-split; queries are processed ``q_block`` rows at a time.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _get(cfg, key):
    return cfg[key] if isinstance(cfg, dict) else getattr(cfg, key)


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def rope(x, positions, theta):
    """x [s, ..., d] rotated over all d dims, pairs (i, i + d/2).
    Departure (``assumed``): the published code may interleave the
    pairs instead; on random weights that is a fixed permutation of the
    columns of ``wq_b`` and ``wkv_a``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angles = positions.astype(F32)[:, None] * freqs            # [s, d/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def attention(layer, x, *, cfg, q_block=None):
    """x [s, d] -> [s, d]: MLA in its expanded form, causal."""
    dn, dr = _get(cfg, 'qk_nope_head_dim'), _get(cfg, 'qk_rope_head_dim')
    r, eps = _get(cfg, 'kv_lora_rank'), _get(cfg, 'norm_eps')
    theta = _get(cfg, 'rope_theta')
    s = x.shape[0]
    pos = jnp.arange(s)
    c_q = rms_norm(x @ _f32(layer['wq_a']), layer['q_norm'], eps)
    q = jnp.einsum('sq,qhk->shk', c_q, _f32(layer['wq_b']))
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, theta)
    kv = x @ _f32(layer['wkv_a'])
    c_kv = rms_norm(kv[:, :r], layer['kv_norm'], eps)
    k_rope = rope(kv[:, r:], pos, theta)            # shared by the heads
    kv_up = jnp.einsum('sr,rhk->shk', c_kv, _f32(layer['wkv_b']))
    k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
    outs = []
    # Departure: queries in blocks of q_block rows, so that the scores of
    # an 8k-token request fit; each row's softmax is whole.
    for q0 in range(0, s, q_block or s):
        q1 = min(s, q0 + (q_block or s))
        score = (jnp.einsum('qhd,khd->hqk', q_nope[q0:q1], k_nope)
                 + jnp.einsum('qhd,kd->hqk', q_rope[q0:q1], k_rope)
                 ) / jnp.sqrt(F32(dn + dr))
        mask = pos[None, q0:q1, None] >= pos[None, None, :]
        p = jax.nn.softmax(jnp.where(mask, score, -jnp.inf), axis=-1)
        outs.append(jnp.einsum('hqk,khd->qhd', p, v))
    out = jnp.concatenate(outs, 0)
    return jnp.einsum('shv,hvd->sd', out, _f32(layer['wo']))


def routing(layer, x, cfg):
    """x [s, d] -> (chosen [s, k] int32, weights [s, k]): sigmoid scores;
    top-k of score + correction bias (``noaux_tc``; ``n_group`` =
    ``topk_group`` = 1, so no group stage); the chosen scores, without
    the bias, renormalised and scaled."""
    k = _get(cfg, 'n_experts_per_token')
    scores = jax.nn.sigmoid(x @ _f32(layer['router']))
    _, chosen = jax.lax.top_k(scores + _f32(layer['router_bias']), k)
    w = jnp.take_along_axis(scores, chosen, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * _get(cfg, 'routed_scaling_factor')
    return chosen.astype(jnp.int32), w


def expert_term(x, w_gate, w_up, w_down, weight):
    """One expert's share of the result: every row through the expert,
    times the row's weight for it (0 where it was not chosen)."""
    return swiglu(x, w_gate, w_up, w_down) * weight[:, None]


def routed_ffn(layer, x, cfg, fns):
    chosen, w = fns['routing'](layer, x)
    y = jnp.zeros_like(x)
    ex = layer['experts']
    for e in range(_get(cfg, 'n_routed_experts')):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
        y = y + fns['expert_term'](x, ex['w_gate'][e], ex['w_up'][e],
                                   ex['w_down'][e], weight)
    sh = layer['shared']
    return y + fns['swiglu'](x, sh['w_gate'], sh['w_up'],
                             sh['w_down']), chosen


def layer_forward(layer, x, cfg, fns):
    """One pre-norm residual layer; a routed layer has ``experts``.
    Returns (x, chosen experts [s, k] or None)."""
    eps = _get(cfg, 'norm_eps')
    attn_in = {k: layer[k] for k in ('wq_a', 'q_norm', 'wq_b', 'wkv_a',
                                     'kv_norm', 'wkv_b', 'wo')}
    x = x + fns['attention'](attn_in, rms_norm(x, layer['attn_norm'], eps))
    h = rms_norm(x, layer['ffn_norm'], eps)
    if 'experts' in layer:
        y, chosen = routed_ffn(layer, h, cfg, fns)
        return x + y, chosen
    return x + fns['swiglu'](h, layer['w_gate'], layer['w_up'],
                             layer['w_down']), None


def forward(params, tokens, cfg, *, q_block=None, rows=None,
            wrap=lambda fn: fn):
    """tokens [s] int -> (logits [s or len(rows), vocab] float32, chosen
    experts [routed layers, s, k]). ``rows`` keeps only those positions'
    logits (the whole [s, vocab] of an 8k-token request is 5.7 GB).
    ``wrap`` may compile the per-block functions (the benchmark passes
    ``jax.jit``; it changes no mathematics). Departure: the
    multi-token-prediction block (``num_nextn_predict_layers`` 1) is a
    draft head outside the forward pass and is not run."""
    import functools
    fns = {
        'attention': wrap(functools.partial(attention, cfg=cfg,
                                            q_block=q_block)),
        'routing': wrap(lambda layer, x: routing(
            {k: layer[k] for k in ('router', 'router_bias')}, x, cfg)),
        'expert_term': wrap(expert_term),
        'swiglu': wrap(swiglu),
        'unembed': wrap(lambda x, w: x @ _f32(w)),
    }

    def layer_of(stack, i):
        return jax.tree.map(lambda a: a[i], stack)

    with jax.default_matmul_precision('highest'):
        x = _f32(jnp.asarray(params['embed'])[jnp.asarray(tokens)])
        chosen = []
        n_dense = _get(cfg, 'n_dense_layers')
        for i in range(_get(cfg, 'n_layers')):
            layer = (layer_of(params['dense_layers'], i) if i < n_dense
                     else layer_of(params['layers'], i - n_dense))
            x, picked = layer_forward(layer, x, cfg, fns)
            if picked is not None:
                chosen.append(picked)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, params['final_norm'], _get(cfg, 'norm_eps'))
        logits = fns['unembed'](x, params['unembed'])
    return logits, jnp.stack(chosen)
