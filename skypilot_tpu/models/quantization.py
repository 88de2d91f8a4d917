"""Weight-only int8 / int4 quantization for serving.

Decode on TPU is HBM-bound on the weight stream (``PERF.md``, the
chat cell's roofline share); storing matmul weights as int8 +
per-output-channel scales halves that traffic, and int4 (two 4-bit codes packed per byte) halves
it AGAIN. Dequantization is expressed as convert+multiply immediately
before each einsum, which XLA fuses into the matmul's operand read —
the weight crosses HBM as int8 (or packed int4 nibbles). (The same
weight-only scheme JetStream/MaxText serve with; the reference
delegates serving to those engines, ``examples/tpu/v6e/README.md:119``.)

Quantized leaves are ``QuantizedWeight(int8, scale)`` /
``QuantizedWeight4(packed, scale)`` NamedTuples (jax pytrees);
``deq(w)`` is identity on plain arrays, so the model code calls it
unconditionally (int4 leaves dequantize only inside ``qeinsum`` — the
packed layout is contraction-specific).

int4 layout contract (the one place it is defined — graftcheck GC119
bans nibble bit-twiddling anywhere else in the compute dirs):

- codes are symmetric 4-bit, ``clip(round(w/scale), -7, 7)``, with
  ``scale = absmax/7`` per OUTPUT channel (or per ``SKYTPU_INT4_GROUP``
  -sized group along the last contracted axis);
- two codes pack into one uint8 byte along the LAST CONTRACTED axis
  (stride-1 in the flattened contraction order, so ``qeinsum`` unpacks
  a ``[k/2, n]`` byte matrix into ``[k, n]`` codes with one interleave
  reshape): byte ``j`` holds code ``2j`` in its low nibble and code
  ``2j+1`` in its high nibble;
- the MoE expert leaves stay int8 in int4 mode: ``models.moe``
  contracts them through generic ``deq()`` einsums whose packed axis
  ``deq`` cannot infer (and expert streams are gated, not hot).
"""
from __future__ import annotations

import os
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.utils.host import host_block

Params = Dict[str, Any]

# Per-layer matmul weights worth quantizing: everything except norms
# (tiny, fp32) and the embedding table (gather path, int8 gather is a
# different trick).
_QUANT_LEAVES = ('wq', 'wk', 'wv', 'wo', 'w_gate', 'w_up', 'w_down',
                 'moe_gate', 'moe_up', 'moe_down', 'unembed')


class QuantizedWeight(NamedTuple):
    int8: jax.Array           # same shape as the original weight
    scale: jax.Array          # original shape with contracted dims = 1

    @property
    def shape(self):
        return self.int8.shape

    @property
    def dtype(self):          # the COMPUTE dtype consumers see after deq
        return self.scale.dtype


class QuantizedWeight4(NamedTuple):
    """int4 weight leaf: ``packed`` is uint8 in the ORIGINAL weight's
    shape with the last contracted axis HALVED (two codes per byte, see
    the module docstring's layout contract); ``scale`` is the original
    shape with contracted dims = 1 — except the last contracted axis,
    which is ``n_groups`` under group-wise scales
    (``SKYTPU_INT4_GROUP``; 1 = per-output-channel)."""
    packed: jax.Array
    scale: jax.Array

    @property
    def dtype(self):          # the COMPUTE dtype consumers see after deq
        return self.scale.dtype


# Leaves quantized to int4 in int4 mode. MoE expert leaves are
# excluded (they dequantize through generic deq() einsums — see module
# docstring) and stay int8.
INT4_LEAVES = ('wq', 'wk', 'wv', 'wo', 'w_gate', 'w_up', 'w_down',
               'unembed')


def int4_group_size() -> int:
    """Group size (tokens of the last contracted axis) for int4 scales;
    0 (default) = one scale per output channel. Read at QUANTIZE time
    only — compiled programs bake in whatever the leaf carries."""
    return max(0, int(os.environ.get('SKYTPU_INT4_GROUP', '0') or 0))


def _xp(arr):
    """numpy for numpy inputs, jnp otherwise — the pack/unpack helpers
    serve both the host-side checkpoint loader and jitted programs."""
    return np if isinstance(arr, np.ndarray) else jnp


def pack_int4(codes, axis: int = -1):
    """Pack int8 codes in [-8, 7] two-per-byte along ``axis`` (must be
    even-sized): byte j = code 2j (low nibble) | code 2j+1 (high).
    Returns uint8 with ``axis`` halved; numpy in, numpy out."""
    xp = _xp(codes)
    if codes.shape[axis] % 2:
        raise ValueError(
            f'int4 pack axis must be even-sized, got shape '
            f'{codes.shape} axis {axis}')
    lo_sl = [slice(None)] * codes.ndim
    hi_sl = [slice(None)] * codes.ndim
    lo_sl[axis] = slice(0, None, 2)
    hi_sl[axis] = slice(1, None, 2)
    lo = codes[tuple(lo_sl)].astype(xp.uint8) & 0xF
    hi = codes[tuple(hi_sl)].astype(xp.uint8) & 0xF
    return lo | (hi << 4)


def unpack_int4(packed, axis: int = -1):
    """Inverse of :func:`pack_int4`: uint8 bytes -> sign-extended int8
    codes with ``axis`` doubled (low nibble first)."""
    xp = _xp(packed)
    lo = (packed & 0xF).astype(xp.int8)
    lo = xp.where(lo >= 8, lo - 16, lo)
    hi = (packed >> 4).astype(xp.int8)
    hi = xp.where(hi >= 8, hi - 16, hi)
    ax = axis if axis >= 0 else packed.ndim + axis
    st = xp.stack([lo, hi], axis=ax + 1)
    shape = packed.shape[:ax] + (packed.shape[ax] * 2,) \
        + packed.shape[ax + 1:]
    return st.reshape(shape)


import contextlib
import threading as _threading

_a8_region = _threading.local()


@contextlib.contextmanager
def w8a8_region():
    """TRACE-TIME flag: while active, ``qeinsum`` additionally
    quantizes the ACTIVATION operand per row (symmetric int8, scale =
    row absmax/127) and contracts int8 x int8 -> int32 — the MXU's
    native int8 path runs at 2x its bf16 rate (394 vs 197 TOPS on a
    v5e), which matters exactly where the matmuls are compute-bound:
    serving PREFILL. Decode stays W8A16 (bandwidth-bound; activation
    quantization would cost VPU work for nothing).

    Trace-time like ``llama._manual_region``: programs traced inside
    the region bake the int8 path in; the flag never affects already-
    compiled programs."""
    prev = getattr(_a8_region, 'active', False)
    _a8_region.active = True
    try:
        yield
    finally:
        _a8_region.active = prev


def deq(w) -> jax.Array:
    """Dequantize if quantized; identity otherwise. The convert+mul
    fuses into the consuming matmul's operand read."""
    if isinstance(w, QuantizedWeight):
        return w.int8.astype(w.scale.dtype) * w.scale
    if isinstance(w, QuantizedWeight4):
        # The packed axis is contraction-specific (last contracted
        # axis) — only qeinsum, which sees the einsum equation, can
        # unpack it. int4 mode deliberately leaves deq()-consumed
        # leaves (MoE experts) at int8.
        raise TypeError(
            'QuantizedWeight4 leaves dequantize only inside qeinsum '
            '(the packed axis is contraction-specific); deq() cannot '
            'recover the layout')
    return w


def qeinsum(eq: str, x: jax.Array, w, *, out_dtype=None) -> jax.Array:
    """``jnp.einsum(eq, x, w)`` that keeps int8 weights int8 across HBM.

    The pre-dequantize form (``einsum(x, deq(w))``) streams the weight at
    ~290 GB/s on a v5e — the scale-multiply keeps XLA from using its fast
    int8 operand path. Contracting the int8 CODES directly in the dot and
    applying the per-output-channel scale to the (tiny) output runs the
    same stream at ~430 GB/s measured, and is *more* accurate (the scale
    multiply happens once per output in fp32 instead of once per weight
    element in bf16). Supported ``eq`` shapes are the model's weight
    patterns: w's contracted axes lead and match x's trailing axes
    ('bsd,dhk->bshk', 'bshk,hkd->bsd', 'bsd,df->bsf', ...).

    Falls back to plain einsum for unquantized weights."""
    if not isinstance(w, (QuantizedWeight, QuantizedWeight4)):
        if out_dtype is not None:
            return jnp.einsum(eq, x, w, preferred_element_type=out_dtype)
        return jnp.einsum(eq, x, w)
    ins, _ = eq.split('->')
    xs, ws = ins.split(',')
    nc = sum(c in xs for c in ws)
    assert all(c in xs for c in ws[:nc]) and \
        xs[-nc:] == ws[:nc], f'unsupported qeinsum pattern {eq!r}'
    if isinstance(w, QuantizedWeight4):
        return _qeinsum4(x, w, nc, out_dtype)
    k = 1
    for d in w.shape[:nc]:
        k *= d
    n = 1
    for d in w.shape[nc:]:
        n *= d
    batch_shape = x.shape[:x.ndim - nc]
    x2 = x.reshape(batch_shape + (k,))
    w2 = w.int8.reshape(k, n)
    if getattr(_a8_region, 'active', False):
        # W8A8 (see w8a8_region): per-row symmetric int8 activations,
        # int8 x int8 -> int32 on the MXU's double-rate path; both
        # scales fold into the fp32 output.
        xf = x2.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        xscale = jnp.maximum(amax, 1e-8) / 127.0
        x8 = jnp.clip(jnp.round(xf / xscale), -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            x8, w2, (((x8.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = y.astype(jnp.float32) * xscale
    else:
        y = jax.lax.dot_general(
            x2, w2, (((x2.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    y = y * w.scale.reshape(n).astype(jnp.float32)
    out_dtype = out_dtype if out_dtype is not None else x.dtype
    return y.astype(out_dtype).reshape(batch_shape + w.shape[nc:])


def _qeinsum4(x: jax.Array, w: QuantizedWeight4, nc: int,
              out_dtype) -> jax.Array:
    """The int4 fused-dequant contraction behind qeinsum: packed codes
    cross HBM as bytes; the nibble unpack + sign-extend fuses into the
    dot's operand read (no bf16 — and no unpacked-int8 — weight copy is
    ever materialized in HBM as a program output). Per-channel scales
    (G=1) fold into the fp32 output exactly like the int8 path; group-
    wise scales (G>1) contract per group and weight the group partials,
    so the scale still never touches a per-element multiply."""
    kp = 1
    for d in w.packed.shape[:nc]:
        kp *= d
    k = kp * 2                       # last contracted axis was halved
    n = 1
    for d in w.packed.shape[nc:]:
        n *= d
    batch_shape = x.shape[:x.ndim - nc]
    x2 = x.reshape(batch_shape + (k,))
    # [k/2, n] bytes -> [k, n] sign-extended codes; pairs along the
    # last contracted axis are stride-1 in the flattened k order, so
    # one interleave reshape restores element order exactly.
    codes = unpack_int4(w.packed.reshape(kp, n), axis=0)
    G = 1
    for d in w.scale.shape[:nc]:
        G *= d
    if G == 1:
        if getattr(_a8_region, 'active', False):
            # W4A8: per-row symmetric int8 activations against the
            # unpacked int4 codes on the MXU's int8 path (prefill).
            xf = x2.astype(jnp.float32)
            amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
            xscale = jnp.maximum(amax, 1e-8) / 127.0
            x8 = jnp.clip(jnp.round(xf / xscale), -127,
                          127).astype(jnp.int8)
            y = jax.lax.dot_general(
                x8, codes, (((x8.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            y = y.astype(jnp.float32) * xscale
        else:
            y = jax.lax.dot_general(
                x2, codes, (((x2.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        y = y * w.scale.reshape(n).astype(jnp.float32)
        out_dtype = out_dtype if out_dtype is not None else x.dtype
        return y.astype(out_dtype).reshape(batch_shape
                                           + w.packed.shape[nc:])
    # Group-wise scales: the scale varies ALONG the contraction, so it
    # cannot fold into the output alone. Contract each g-sized group
    # separately (group as a dot batch dim — codes stay int-typed in
    # the dot) and sum the scale-weighted partials in fp32. W4A8 is
    # per-channel-only; grouped mode takes the fp32 contraction.
    last = w.packed.shape[nc - 1] * 2
    g = last // G
    other = k // last
    kg = other * G
    xb = x2.reshape((-1, other, G, g)).reshape((-1, kg, g))
    wg = codes.reshape((other, G, g, n)).reshape((kg, g, n))
    y = jax.lax.dot_general(
        xb, wg, (((2,), (1,)), ((1,), (0,))),
        preferred_element_type=jnp.float32)          # [kg, B, n]
    sflat = jnp.broadcast_to(
        w.scale.reshape(1, G, n), (other, G, n)).reshape(kg, 1, n)
    y = jnp.sum(y * sflat.astype(jnp.float32), axis=0)   # [B, n]
    out_dtype = out_dtype if out_dtype is not None else x.dtype
    return y.astype(out_dtype).reshape(batch_shape
                                       + w.packed.shape[nc:])


def _quantize_array(w: jax.Array, reduce_axes) -> QuantizedWeight:
    """Symmetric per-channel int8: scale = absmax/127 over the
    CONTRACTING axes, so each output channel keeps its dynamic range."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
    # Round the scale to the storage dtype FIRST so the codes are
    # computed against the exact scale dequantization will multiply by
    # (a bf16 scale differs from its fp32 parent by up to ~0.4%/channel).
    scale = (jnp.maximum(absmax, 1e-8) / 127.0).astype(w.dtype)
    q = jnp.clip(jnp.round(wf / scale.astype(jnp.float32)), -127,
                 127).astype(jnp.int8)
    return QuantizedWeight(int8=q, scale=scale)


def _quantize_array4(w: jax.Array, reduce_axes,
                     group: int = 0) -> QuantizedWeight4:
    """Symmetric 4-bit: scale = absmax/7 over the contracting axes
    (per output channel), or per ``group``-sized slice of the LAST
    contracting axis (group-wise). Codes pack two-per-byte along that
    same axis (see the module layout contract). Scale is rounded to
    the storage dtype FIRST, like the int8 path."""
    ax = reduce_axes[-1]
    m = w.shape[ax]
    wf = w.astype(jnp.float32)
    if group:
        if m % group or group % 2:
            raise ValueError(
                f'SKYTPU_INT4_GROUP={group} must be even and divide '
                f'the packed axis (size {m})')
        G = m // group
        split = w.shape[:ax] + (G, group) + w.shape[ax + 1:]
        wf_g = wf.reshape(split)
        red = tuple(a if a < ax else a + 1
                    for a in reduce_axes[:-1]) + (ax + 1,)
        absmax = jnp.max(jnp.abs(wf_g), axis=red, keepdims=True)
        scale = (jnp.maximum(absmax, 1e-8) / 7.0).astype(w.dtype)
        q = jnp.clip(jnp.round(wf_g / scale.astype(jnp.float32)),
                     -7, 7).astype(jnp.int8).reshape(w.shape)
        sshape = tuple(1 if a in reduce_axes else d
                       for a, d in enumerate(w.shape))
        sshape = sshape[:ax] + (G,) + sshape[ax + 1:]
        scale = scale.reshape(sshape)
    else:
        absmax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
        scale = (jnp.maximum(absmax, 1e-8) / 7.0).astype(w.dtype)
        q = jnp.clip(jnp.round(wf / scale.astype(jnp.float32)),
                     -7, 7).astype(jnp.int8)
    return QuantizedWeight4(packed=pack_int4(q, axis=ax), scale=scale)


# Contracting axes per leaf (leading axis 0 is the scanned layer stack
# for layer weights; it is never contracted). Shapes from
# ``llama.init_params`` / ``moe.init_moe_params``.
_REDUCE_AXES = {
    'wq': (1,),          # [L, d, h, hd]   contract d
    'wk': (1,),
    'wv': (1,),
    'wo': (1, 2),        # [L, h, hd, d]   contract h, hd
    'w_gate': (1,),      # [L, d, f]       contract d
    'w_up': (1,),
    'w_down': (1,),      # [L, f, d]       contract f
    'moe_gate': (2,),    # [L, E, d, f]    contract d
    'moe_up': (2,),
    'moe_down': (2,),    # [L, E, f, d]    contract f
    'unembed': (0,),     # [d, V]          contract d
}
# Public alias: the host-side loader (weights._host_quantize) quantizes
# against the same per-leaf contracting axes.
REDUCE_AXES = _REDUCE_AXES


_QUANT_LEAF_TYPES = (QuantizedWeight, QuantizedWeight4)


def is_quantized(params: Params) -> bool:
    """True if the pytree already carries quantized leaves (int8 OR
    int4 — e.g. loaded via ``weights.load_checkpoint(quantize=...)``)."""
    return quantized_mode(params) is not None


def quantized_mode(params: Params):
    """'int4' | 'int8' | None for a param tree: int4 wins when any
    packed leaf exists (int4 trees carry int8 MoE leaves alongside)."""
    leaves = jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, _QUANT_LEAF_TYPES))
    if any(isinstance(l, QuantizedWeight4) for l in leaves):
        return 'int4'
    if any(isinstance(l, QuantizedWeight) for l in leaves):
        return 'int8'
    return None


def _map_quant_leaves(tree: Params, leaf_fn) -> Params:
    """Single traversal shared by quantize_params and
    quantize_logical_axes — the two output trees MUST stay structurally
    in lockstep (tree_shardings tree-maps one over the other)."""
    out: Params = {}
    for key, val in tree.items():
        if key == 'layers':
            out[key] = {
                k: (leaf_fn(k, v) if k in _REDUCE_AXES else v)
                for k, v in val.items()
            }
        elif key in _REDUCE_AXES:
            out[key] = leaf_fn(key, val)
        else:
            out[key] = val
    return out


def quantize_params(params: Params, *, donate: bool = False,
                    mode: str = 'int8') -> Params:
    """Quantize the big matmul weights of a llama-family param pytree;
    embeddings/norms/router stay as-is. ``mode='int4'`` packs the dense
    leaves (:data:`INT4_LEAVES`) two codes per byte with per-channel
    (or ``SKYTPU_INT4_GROUP`` group-wise) scales; MoE expert leaves
    stay int8 (see module docstring).

    Leaves are quantized one at a time so the fp32 transient is
    per-leaf, not per-tree. With ``donate=True`` each source buffer is
    freed as soon as its quantized replacement exists — peak device
    memory stays ~(bf16 tree + one leaf) instead of (bf16 + quantized)
    trees, which is what lets a 7B bf16 checkpoint (~14 GB) quantize in
    place on a 16 GB v5e chip. Only donate buffers the caller will not
    reuse."""
    if mode not in ('int8', 'int4'):
        raise ValueError(f'unknown quantize mode {mode!r}')
    group = int4_group_size() if mode == 'int4' else 0

    def leaf(k, v):
        if mode == 'int4' and k in INT4_LEAVES:
            q = _quantize_array4(v, _REDUCE_AXES[k], group=group)
        else:
            q = _quantize_array(v, _REDUCE_AXES[k])
        if donate and isinstance(v, jax.Array):
            host_block(q)       # barrier only — q must exist before
            v.delete()          # its source buffer is freed
        return q

    return _map_quant_leaves(params, leaf)


def quantize_logical_axes(axes: Params, mode: str = 'int8') -> Params:
    """Map the bf16 param logical-axes tree (``llama.param_logical_axes``)
    to the quantized-param structure: each quantized leaf becomes a
    ``QuantizedWeight`` (or ``QuantizedWeight4`` under ``mode='int4'``,
    matching ``quantize_params``'s leaf choice) of axes tuples. Codes
    and scales reuse the parent's axes — the scale's contracted dims
    are size 1 (or the group count) and the packed axis is halved, and
    the divisibility-aware ``mesh.spec_for`` replicates non-dividing
    dims automatically, so scales land replicated over contracted mesh
    axes and sharded along the output-channel axes, exactly matching
    their parent."""

    def leaf(k, v):
        if mode == 'int4' and k in INT4_LEAVES:
            return QuantizedWeight4(packed=v, scale=v)
        return QuantizedWeight(int8=v, scale=v)

    return _map_quant_leaves(axes, leaf)


def quantized_bytes(params: Params) -> int:
    """Total parameter bytes as stored (int8 leaves count 1B/elem)."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total


def per_device_bytes(params: Params) -> int:
    """Bytes ONE device holds of the tree: each sharded leaf counts its
    local shard shape (exact — divisibility fallbacks and replicated
    axes included via ``sharding.shard_shape``), unsharded leaves their
    full size. The HBM-budget divisor pool sizing must use: dividing
    global bytes by ``mesh.size`` is wrong whenever an axis REPLICATES
    (dp, or a dimension tp does not divide) — under dp=2 it halves the
    accounted weights that are in fact fully resident per chip."""
    import math
    total = 0
    for leaf in jax.tree.leaves(params):
        sharding = getattr(leaf, 'sharding', None)
        if sharding is not None and hasattr(sharding, 'shard_shape'):
            try:
                local = math.prod(sharding.shard_shape(leaf.shape))
            except Exception:  # pylint: disable=broad-except
                local = leaf.size       # exotic sharding: conservative
        else:
            local = leaf.size
        total += local * leaf.dtype.itemsize
    return total
