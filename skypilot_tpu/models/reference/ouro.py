"""Plain reference of Ouro's forward pass (``ouro``: ByteDance/Ouro-2.6B
``config.json`` and ``modeling_ouro.py``; arXiv:2510.25741): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, a
Python loop over passes and layers, no cache, no kernel, no batching. It
takes the program's parameter tree (``models/llama.py``) in any dtype
and upcasts each leaf where it is used, so a bf16 tree that fills the
chip can be scored beside itself.

    h = E[token]
    for t in 0 .. total_ut_steps - 1:        the SAME layers in every t
      for l in 0 .. layers - 1:
        a = rms(h, g1);  q, k, v = a Wq, a Wk, a Wv;  rope(q), rope(k)
        o = softmax(q K^T / sqrt(head_dim), causal) V     K, V of (t, l)
        h = h + rms(o Wo, g2)                 a norm on the branch's OUTPUT
        m = rms(h, g3);  f = (silu(m Wgate) * (m Wup)) Wdown
        h = h + rms(f, g4)
      h = rms(h, g_final)          after EVERY pass; it enters the next
      lam[t] = sigmoid(h . w_exit + b_exit)
    logits = h W_head              early_exit_threshold 1: the last pass

``cfg`` is a ``ModelConfig`` or the ``model`` object of a benchmark
configuration file (the same field names).

Departures from the published code, each noted at its line: the rotary
pairing is half-split; queries are processed ``q_block`` rows at a time;
grouped heads are expanded by repetition (the published model has as
many KV heads as heads).
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


def post_norm(x, w, eps):
    """The norm on a branch's output (``input_layernorm_2``,
    ``post_attention_layernorm_2``)."""
    return rms_norm(x, w, eps)


def pass_norm(x, w, eps, t, n_loops):
    """The model's one final norm, applied after pass ``t`` of
    ``n_loops``: after every pass."""
    del t, n_loops
    return rms_norm(x, w, eps)


def kv_pass(t):
    """The pass whose keys and values pass ``t`` attends to: its own."""
    return t


def rope(x, positions, theta):
    """x [s, h, d] rotated over all d dims, pairs (i, i + d/2).
    Departure (``assumed``): the published ``rotate_half`` pairs the same
    way; were it interleaved, on random weights that is a fixed
    permutation of the columns of ``wq`` and ``wk``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angles = positions.astype(F32)[:, None] * freqs            # [s, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def qkv(layer, a, *, theta):
    """a [s, d] normed -> roped q [s, h, k], roped k and v [s, hkv, k];
    no bias."""
    pos = jnp.arange(a.shape[0])
    q = jnp.einsum('sd,dhk->shk', a, _f32(layer['wq']))
    k = jnp.einsum('sd,dhk->shk', a, _f32(layer['wk']))
    v = jnp.einsum('sd,dhk->shk', a, _f32(layer['wv']))
    return rope(q, pos, theta), rope(k, pos, theta), v


def attention(q, k, v, wo, *, q_block=None):
    """Causal softmax attention over one (pass, layer)'s keys and
    values, and the output projection: [s, d]."""
    s, h, hd = q.shape
    # Departure: grouped KV heads repeated up to the heads (a no-op at
    # the published 16 = 16).
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    pos = jnp.arange(s)
    outs = []
    # Departure: queries in blocks of q_block rows; each row's softmax
    # is whole.
    for q0 in range(0, s, q_block or s):
        q1 = min(s, q0 + (q_block or s))
        score = jnp.einsum('qhd,khd->hqk', q[q0:q1], k) / jnp.sqrt(F32(hd))
        mask = pos[None, q0:q1, None] >= pos[None, None, :]
        p = jax.nn.softmax(jnp.where(mask, score, -jnp.inf), axis=-1)
        outs.append(jnp.einsum('hqk,khd->qhd', p, v))
    out = jnp.concatenate(outs, 0)
    return jnp.einsum('shk,hkd->sd', out, _f32(wo))


def exit_pdf(lam):
    """[passes, s] gate sigmoids -> the distribution over the pass a
    token leaves at: p[t] = lam[t] * prod_{s<t}(1 - lam[s]) for t < last,
    and the last pass takes what is left."""
    stay, out = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        out.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(out + [stay])


def forward(params, tokens, cfg, *, q_block=None, rows=None,
            wrap=lambda fn: fn):
    """tokens [s] int -> (logits [s or len(rows), vocab] float32, exit
    pdf [passes, s or len(rows)]). ``rows`` keeps only those positions'
    logits and pdf. ``wrap`` may compile the per-block functions (the
    benchmark passes ``jax.jit``; it changes no mathematics)."""
    import functools
    eps, n_loops = _get(cfg, 'norm_eps'), _get(cfg, 'n_loops')
    fns = {
        'qkv': wrap(functools.partial(qkv, theta=_get(cfg, 'rope_theta'))),
        'attention': wrap(functools.partial(attention, q_block=q_block)),
        'swiglu': wrap(swiglu),
        'unembed': wrap(lambda x, w: x @ _f32(w)),
    }
    layers = params['layers']
    with jax.default_matmul_precision('highest'):
        x = _f32(jnp.asarray(params['embed'])[jnp.asarray(tokens)])
        kv, lam = {}, []
        for t in range(n_loops):
            for i in range(_get(cfg, 'n_layers')):
                layer = jax.tree.map(lambda a: a[i], layers)
                a = rms_norm(x, layer['attn_norm'], eps)
                q, k, v = fns['qkv'](
                    {n: layer[n] for n in ('wq', 'wk', 'wv')}, a)
                kv[t, i] = (k, v)
                kv.pop((t - 2, i), None)        # two passes' worth held
                x = x + post_norm(
                    fns['attention'](q, *kv[kv_pass(t), i], layer['wo']),
                    layer['attn_post_norm'], eps)
                m = rms_norm(x, layer['ffn_norm'], eps)
                x = x + post_norm(
                    fns['swiglu'](m, layer['w_gate'], layer['w_up'],
                                  layer['w_down']),
                    layer['ffn_post_norm'], eps)
            x = pass_norm(x, params['final_norm'], eps, t, n_loops)
            gate = params['exit_gate']
            lam.append(jax.nn.sigmoid(x @ _f32(gate['w'])
                                      + _f32(gate['b'])[0]))
        lam = jnp.stack(lam)
        if rows is not None:
            x, lam = x[jnp.asarray(rows)], lam[:, jnp.asarray(rows)]
        logits = fns['unembed'](x, params['unembed'])
    return logits, exit_pdf(lam)
