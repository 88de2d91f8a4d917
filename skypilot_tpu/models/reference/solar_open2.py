"""Plain reference of Solar Open 2's forward pass (``solar_open2``:
upstage/Solar-Open2-250B ``config.json``; the linear-attention layers are
Kimi Delta Attention, arXiv:2510.26692, as ``fla``'s
``KimiDeltaAttention`` has them): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, a Python loop over layers,
the recurrence token by token (a ``lax.scan`` over time), no chunking,
no cache, no kernel, no batching. It takes the program's parameter tree
(``models/kda.py``) in any dtype and upcasts each leaf where it is used.

    x = E[token]
    for l in 0 .. layers - 1:           pattern [GQA, KDA, KDA, KDA]
      h = rms(x, g1)
      GQA:  q, k, v = h Wq, h Wk, h Wv          NO rotary, no q/k norm
            o = softmax(q K^T / sqrt(head_dim), causal) V
            x = x + (o * sigmoid(h W_gate)) Wo            a channel
      KDA:  q~, k~, v = SiLU(conv4(h Wq)), SiLU(conv4(h Wk)), SiLU(conv4(h Wv))
            q = q~ / |q~| / sqrt(head_dim);  k = k~ / |k~|       per head
            g = -exp(A_log) softplus(W_a_up W_a_down h + dt_bias)  a channel
            beta = 2 sigmoid(W_beta h)                             a head
            S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T,  S_0 = 0
            o_t = S_t^T q_t
            x = x + (rms_head(o) * sigmoid(W_g_up W_g_down h + b_g)) Wo
      m = rms(x, g2)
      s = sigmoid(m W_r);  chosen = top-k(s + bias);  w = s[chosen] / sum
      x = x + sum_{e in chosen, e HELD} w_e SwiGLU_e(m) * scale + SwiGLU_shared(m)
    logits = rms(x, g_final) W_head                over the vocabulary slice

``cfg`` is a ``ModelConfig`` or the ``model`` object of a benchmark
configuration file (the same field names).

Departures from the published description, each noted at its line: the
program holds ``n_held_experts`` of the ``n_routed_experts`` routed over
and a slice of the vocabulary, and so does this (what the absent experts
would add is left out in both); queries of the GQA layers are processed
``q_block`` rows at a time; grouped heads are expanded by repetition;
q/k are normalised with an epsilon under the root. ``assumed`` (not in
``config.json``): the gate projections' rank, the bias on the output
gate's second projection (``fla`` has one; drawn at zero), the draws of
``A_log`` and ``dt_bias``, sigmoid scores with a selection-only bias.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
QK_NORM_EPS = 1e-6      # departure: |x| = sqrt(sum x^2 + 1e-6), as fla's


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


def position_encoding(x, positions):
    """What the GQA layer does to q and k with their positions:
    nothing (``use_rope`` false)."""
    del positions
    return x


def gqa(layer, a, *, q_block=None):
    """a [s, d] normed -> the gated attention branch [s, d]."""
    s = a.shape[0]
    pos = jnp.arange(s)
    q = position_encoding(jnp.einsum('sd,dhk->shk', a, _f32(layer['wq'])),
                          pos)
    k = position_encoding(jnp.einsum('sd,dhk->shk', a, _f32(layer['wk'])),
                          pos)
    v = jnp.einsum('sd,dhk->shk', a, _f32(layer['wv']))
    h, hd = q.shape[1:]
    # Departure: grouped KV heads repeated up to the heads.
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
    out = jnp.concatenate(outs, 0)
    out = output_gate(out, jnp.einsum('sd,dhk->shk', a,
                                      _f32(layer['w_attn_gate'])))
    return jnp.einsum('shk,hkd->sd', out, _f32(layer['wo']))


def output_gate(o, pre):
    """o * sigmoid(pre), elementwise over heads x channels."""
    return o * jax.nn.sigmoid(pre)


def short_conv(x, w):
    """x [s, c], w [taps, c] (the last row multiplies the current
    token): y_t = sum_j w[taps-1-j] x[t-j], zeros before the start; then
    SiLU."""
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), F32), x])
    return jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(taps)))


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + QK_NORM_EPS)


def decay(p, a):
    """The log decay g [s, H, dk]: a value per CHANNEL."""
    f = jnp.einsum('sr,rhk->shk', a @ _f32(p['a_down']), _f32(p['a_up']))
    return -jnp.exp(_f32(p['A_log']))[:, None] \
        * jax.nn.softplus(f + _f32(p['dt_bias']))


def write_strength(p, a):
    """beta [s, H] in (0, 2): negative eigenvalues allowed."""
    return 2.0 * jax.nn.sigmoid(a @ _f32(p['w_beta']))


def qk_normalise(q, k):
    return l2_normalise(q) * q.shape[-1] ** -0.5, l2_normalise(k)


def conv_qkv(p, a):
    """a [s, d] -> q~, k~, v [s, H, dk] behind the short convolution."""
    H, dk = p['wq'].shape[1:]
    x = jnp.concatenate(
        [jnp.einsum('sd,dhk->shk', a, _f32(p[w])).reshape(-1, H * dk)
         for w in ('wq', 'wk', 'wv')], -1)
    return [t.reshape(-1, H, dk)
            for t in jnp.split(short_conv(x, _f32(p['conv'])), 3, -1)]


def state_dtype():
    """The recurrent state's precision."""
    return F32


def delta_rule(q, k, v, g, beta):
    """The recurrence as written, a token at a time. q, k, g [s, H, dk];
    v [s, H, dv]; beta [s, H] -> (o [s, H, dv], the state after the last
    token [H, dk, dv])."""
    H, dk = q.shape[1:]

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S.astype(F32) * jnp.exp(g_t)[..., None]         # Diag(alpha) S
        u = b_t[:, None] * (v_t - jnp.einsum('hkv,hk->hv', S, k_t))
        S = S + k_t[..., None] * u[:, None, :]      # (I - b k k^T) . + b k v^T
        return S.astype(state_dtype()), jnp.einsum('hkv,hk->hv', S, q_t)

    S, o = jax.lax.scan(step, jnp.zeros((H, dk, v.shape[-1]), state_dtype()),
                        (q, k, v, g, beta))
    return o, S


def kda(p, a, *, eps):
    """a [s, d] normed -> (the KDA branch [s, d], the state after the
    last token [H, dk, dv])."""
    q, k, v = conv_qkv(p, a)
    q, k = qk_normalise(q, k)
    o, S = delta_rule(q, k, v, decay(p, a), write_strength(p, a))
    gate = jnp.einsum('sr,rhk->shk', a @ _f32(p['g_down']),
                      _f32(p['g_up'])) + _f32(p['g_bias'])
    o = output_gate(rms_norm(o, p['o_norm'], eps), gate)
    return jnp.einsum('shv,hvd->sd', o, _f32(p['wo'])), S


def routing(layer, x, cfg):
    """x [s, d] -> (chosen [s, k] int32 over ALL routed experts, weights
    [s, k]): sigmoid scores; top-k of score + correction bias; the chosen
    scores, without the bias, renormalised and scaled."""
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


def held_range(cfg):
    """(first, count) of the routed experts the tree holds."""
    held = _get(cfg, 'n_held_experts')
    if held is None:
        held = _get(cfg, 'n_routed_experts')
    return _get(cfg, 'first_held_expert', 0) or 0, held


def routed_ffn(layer, x, cfg, fns):
    """Departure: only the HELD experts' terms are added (the tree holds
    no other): one chip's share of the layer."""
    chosen, w = fns['routing'](layer, x)
    first, held = held_range(cfg)
    y = jnp.zeros_like(x)
    ex = layer['experts']
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        y = y + fns['expert_term'](x, ex['w_gate'][e], ex['w_up'][e],
                                   ex['w_down'][e], weight)
    sh = layer['shared']
    return y + fns['swiglu'](x, sh['w_gate'], sh['w_up'],
                             sh['w_down']), chosen


def layer_kinds(cfg):
    pattern = tuple(_get(cfg, 'mixer_pattern'))
    return pattern * (_get(cfg, 'n_layers') // len(pattern))


def forward(params, tokens, cfg, *, q_block=None, rows=None,
            wrap=lambda fn: fn, states=None):
    """tokens [s] int -> (logits [s or len(rows), vocab slice] float32,
    chosen experts [layers, s, k]). ``rows`` keeps only those positions'
    logits. ``wrap`` may compile the per-block functions (the benchmark
    passes ``jax.jit``; it changes no mathematics). A list passed as
    ``states`` receives each KDA layer's state after the last token,
    [H, dk, dv], in layer order."""
    import functools
    eps = _get(cfg, 'norm_eps')
    fns = {
        'gqa': wrap(functools.partial(gqa, q_block=q_block)),
        'kda': wrap(functools.partial(kda, eps=eps)),
        'routing': wrap(lambda layer, x: routing(
            {k: layer[k] for k in ('router', 'router_bias')}, x, cfg)),
        'expert_term': wrap(expert_term),
        'swiglu': wrap(swiglu),
        'unembed': wrap(lambda x, w: x @ _f32(w)),
    }
    seen = {'gqa': 0, 'kda': 0}
    stacks = {'gqa': params['layers'], 'kda': params['kda_layers']}
    with jax.default_matmul_precision('highest'):
        x = _f32(jnp.asarray(params['embed'])[jnp.asarray(tokens)])
        chosen = []
        for kind in layer_kinds(cfg):
            i = seen[kind]
            seen[kind] += 1
            layer = jax.tree.map(lambda a, i=i: a[i], stacks[kind])
            a = rms_norm(x, layer['attn_norm'], eps)
            if kind == 'kda':
                y, S = fns['kda'](layer['kda'], a)
                x = x + y
                if states is not None:
                    states.append(S)
            else:
                x = x + fns['gqa'](
                    {k: layer[k] for k in ('wq', 'wk', 'wv', 'wo',
                                           'w_attn_gate')}, a)
            y, picked = routed_ffn(layer, rms_norm(x, layer['ffn_norm'],
                                                   eps), cfg, fns)
            x = x + y
            chosen.append(picked)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, params['final_norm'], eps)
        logits = fns['unembed'](x, params['unembed'])
    return logits, jnp.stack(chosen)
