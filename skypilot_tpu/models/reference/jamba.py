"""Plain reference of Jamba's forward pass (``jamba``:
ai21labs/AI21-Jamba2-3B ``config.json``; the state-space layers are
Mamba-1, arXiv:2312.00752, as HF ``JambaMambaMixer.slow_forward`` has
them; the layout is Jamba's, arXiv:2403.19887): float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, a Python loop over
layers, the scan token by token (a ``lax.scan`` over time inside a scan
over blocks of ``SCAN_BLOCK`` tokens), no cache, no kernel, no batching.
It takes the program's parameter tree (``models/mamba.py``) in any dtype
and upcasts each leaf where it is used.

    x = E[token]
    for l in 0 .. layers - 1:        attention where l % period == offset
      h = rms(x, g1)
      GQA:   q, k, v = h Wq, h Wk, h Wv           NO rotary, no bias
             x = x + softmax(q K^T / sqrt(head_dim), causal) V Wo
      Mamba: [x_; z] = h W_in
             x~ = SiLU(b_conv + sum_j w_j x_{t-3+j})       causal, 4 taps
             [d; B; C] = x~ W_x;  d, B, C = rms(d), rms(B), rms(C)
             dt = softplus(d W_dt + b_dt);  A = -exp(A_log)     [I], [I, N]
             S_t = exp(dt_t A) * S_{t-1} + (dt_t x~_t) B_t^T,  S_0 = 0   [I, N]
             y_t = S_t C_t + D * x~_t
             x = x + (y * SiLU(z)) W_out
      m = rms(x, g2)
      x = x + SwiGLU(m)                                    dense, every layer
    logits = rms(x, g_final) E^T                           tied

``cfg`` is a ``ModelConfig`` or the ``model`` object of a benchmark
configuration file (the same field names).

Departures from the published description, each noted at its line: the
scan's state is float32 (HF keeps it in the model's dtype); queries of
the GQA layers are processed ``q_block`` rows at a time; the one KV head
is repeated up to the query heads; the tree stores ``A_log`` as [N, I]
(transposed here to HF's [I, N]). A list passed as ``states`` receives
each Mamba layer's state in the program's layout, [1, N, I].
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
SCAN_BLOCK = 256        # tokens whose decays are formed at once


def _get(cfg, key, default=None):
    if isinstance(cfg, dict):
        return cfg.get(key, default)
    return getattr(cfg, key, default)


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def gqa(layer, a, *, q_block=None):
    """a [s, d] normed -> the attention branch [s, d]: no positional
    encoding, no bias, no gate."""
    s = a.shape[0]
    pos = jnp.arange(s)
    q = jnp.einsum('sd,dhk->shk', a, _f32(layer['wq']))
    k = jnp.einsum('sd,dhk->shk', a, _f32(layer['wk']))
    v = jnp.einsum('sd,dhk->shk', a, _f32(layer['wv']))
    h, hd = q.shape[1:]
    # Departure: the KV head repeated up to the query heads.
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    outs = []
    # Departure: queries in blocks of q_block rows; each row's softmax
    # is whole.
    for q0 in range(0, s, q_block or s):
        q1 = min(s, q0 + (q_block or s))
        score = jnp.einsum('qhd,khd->hqk', q[q0:q1], k) / jnp.sqrt(F32(hd))
        mask = pos[None, q0:q1, None] >= pos[None, None, :]
        p = jax.nn.softmax(jnp.where(mask, score, -jnp.inf), axis=-1)
        outs.append(jnp.einsum('hqk,khd->qhd', p, v))
    return jnp.einsum('shk,hkd->sd', jnp.concatenate(outs, 0),
                      _f32(layer['wo']))


def causal_conv(x, w, bias):
    """x [s, c], w [taps, c] (the last row multiplies the current
    token), bias [c] or None: b + sum_j w[taps-1-j] x[t-j], zeros before
    the start; then SiLU."""
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), F32), x])
    y = sum(padded[j:j + s] * w[j] for j in range(taps))
    return jax.nn.silu(y if bias is None else y + bias)


def state_dtype():
    """The scan's state precision (departure: HF keeps the model's)."""
    return F32


def selective_scan(x, dt, A, B, C):
    """x, dt [s, I]; A [I, N]; B, C [s, N] -> (y [s, I] without the skip,
    the state after the last token [I, N]). The decays and writes of
    ``SCAN_BLOCK`` tokens are formed at once ([block, I, N]) and the
    recurrence runs token by token over them."""
    s, I = x.shape
    N = A.shape[1]
    pad = -s % SCAN_BLOCK

    def blocks(a):          # [s, ...] -> [s / block, block, ...]
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, SCAN_BLOCK) + a.shape[1:])

    def block(S, t):
        x_b, dt_b, B_b, C_b = t
        dA = jnp.exp(dt_b[:, :, None] * A[None])             # [blk, I, N]
        dBu = (dt_b * x_b)[:, :, None] * B_b[:, None, :]

        def one(S, u):
            dA_t, dBu_t, C_t = u
            S = (dA_t * S.astype(F32) + dBu_t).astype(state_dtype())
            return S, S.astype(F32) @ C_t

        return jax.lax.scan(one, S, (dA, dBu, C_b))

    S, y = jax.lax.scan(block, jnp.zeros((I, N), state_dtype()),
                        tuple(blocks(a) for a in (x, dt, B, C)))
    return y.reshape(-1, I)[:s], S


def mamba(p, a, *, eps):
    """a [s, d] normed -> (the Mamba branch [s, d], the state after the
    last token [I, N])."""
    xz = a @ _f32(p['in_proj'])
    x, z = jnp.split(xz, 2, -1)
    bias = p.get('conv_bias')
    x = causal_conv(x, _f32(p['conv']), None if bias is None else _f32(bias))
    R, N = p['dt_proj'].shape[0], p['b_norm'].shape[0]
    d, B, C = jnp.split(x @ _f32(p['x_proj']), [R, R + N], -1)
    d, B, C = (rms_norm(d, p['dt_norm'], eps), rms_norm(B, p['b_norm'], eps),
               rms_norm(C, p['c_norm'], eps))
    dt = jax.nn.softplus(d @ _f32(p['dt_proj']) + _f32(p['dt_bias']))
    A = -jnp.exp(_f32(p['A_log']).T)        # departure: stored [N, I]
    y, S = selective_scan(x, dt, A, B, C)
    y = (y + x * _f32(p['D'])) * jax.nn.silu(z)
    return y @ _f32(p['out_proj']), S


def layer_kinds(cfg):
    pattern = tuple(_get(cfg, 'mixer_pattern'))
    return pattern * (_get(cfg, 'n_layers') // len(pattern))


def forward(params, tokens, cfg, *, q_block=None, rows=None,
            wrap=lambda fn: fn, states=None):
    """tokens [s] int -> (logits [s or len(rows), vocab] float32, None).
    ``rows`` keeps only those positions' logits. ``wrap`` may compile
    the per-layer functions (the benchmark passes ``jax.jit``; it
    changes no mathematics). A list passed as ``states`` receives each
    Mamba layer's state after the last token, in layer order, in the
    program's layout [1, N, I]."""
    import functools
    eps = _get(cfg, 'norm_eps')
    fns = {
        'gqa': wrap(functools.partial(gqa, q_block=q_block)),
        'mamba': wrap(functools.partial(mamba, eps=eps)),
        'swiglu': wrap(swiglu),
        'unembed': wrap(lambda x, w: x @ _f32(w).T),
    }
    seen = {'gqa': 0, 'mamba': 0}
    stacks = {'gqa': params['layers'], 'mamba': params['mamba_layers']}
    with jax.default_matmul_precision('highest'):
        x = _f32(jnp.asarray(params['embed'])[jnp.asarray(tokens)])
        for kind in layer_kinds(cfg):
            i = seen[kind]
            seen[kind] += 1
            layer = jax.tree.map(lambda a, i=i: a[i], stacks[kind])
            a = rms_norm(x, layer['attn_norm'], eps)
            if kind == 'mamba':
                y, S = fns['mamba'](layer['mamba'], a)
                x = x + y
                if states is not None:
                    states.append(S.T[None])
            else:
                x = x + fns['gqa'](
                    {k: layer[k] for k in ('wq', 'wk', 'wv', 'wo')}, a)
            x = x + fns['swiglu'](rms_norm(x, layer['ffn_norm'], eps),
                                  layer['w_gate'], layer['w_up'],
                                  layer['w_down'])
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, params['final_norm'], eps)
        logits = fns['unembed'](x, params['embed'])
    return logits, None
