"""graftcheck part C: static per-dispatch cost model.

Walks the closed jaxprs the runtime audit (``jaxpr_audit``) already
captures and prices every dispatch from the IR itself: HBM bytes
read/written **attributed by operand class** (packed int4/int8/bf16
weight bytes, KV-pool bytes, scales, page tables, activations), FLOPs,
and explicit collective bytes per mesh axis. Three consumers:

1. **Byte-budget gate** — every default jaxpr-audit preset declares a
   per-dispatch byte budget (:data:`BYTE_BUDGETS`); a regression fails
   the audit with a per-eqn attribution diff. This is what catches an
   accidental dequant materialization (int4 codes converted to bf16
   *before* the matmul quadruple the ``weight_int4``-class read
   stream), an extra pool sweep, or a stray gather the collective
   census can only count, not weigh.
2. **Static step bytes** — ``abstract_decode_cost`` /
   ``roofline_step_bytes`` price one decode substep of any config from
   abstract traces of the real decode program (no weights
   materialized); ``kv_static_check`` holds the stored bytes per token
   to the runtime's ``kv_token_bytes`` (what the
   ``skytpu_kv_read_bytes_per_step`` gauge multiplies) within
   :data:`KV_TOLERANCE`. (The benchmark's own roofline arithmetic is
   ``perfbench/roofline.py``.)
3. **``graftcheck costmodel``** — prints the attribution table for any
   preset.

Modeling contract (deterministic, fusion-aware, and self-consistent —
budgets are generated by this model and checked by this model):

- values are either **resident** (program inputs, loop/kernel
  boundary values, and outputs of materializing eqns — things that
  live in HBM) or **fused** (products of elementwise/layout chains,
  which live in registers and carry the set of resident values they
  derive from — their *footprint*);
- only **materializing** eqns charge bytes: dots, gathers/slices,
  scatters, concatenates, sorts, reductions, RNG, collectives,
  pallas_call, and every unknown primitive. A materializing eqn reads
  its operands' resident footprints (deduplicated) and writes its own
  outputs. Elementwise/layout/convert eqns are free and merge
  footprints — so a dequant chain feeding a matmul bills the matmul
  for the PACKED codes + scales, exactly what the fused program
  streams;
- a dequant forced across a materialization boundary (entering a
  scan/while body, a kernel, or a program output) bills at the
  boundary value's own (fat) aval — the accidental-materialization
  regression this gate exists to catch;
- gather/slice-family eqns read only what they produce (a page gather
  reads the touched rows, not the resident pool); scatter-family eqns
  read+write only their updates (the donated pool updates in place);
- ``scan`` multiplies its body by the trip count; ``cond`` prices the
  most expensive branch; ``while`` prices one iteration (noted);
- ``pallas_call`` is opaque: operand avals in, outputs out — except
  layer-stacked KV-pool operands under the scalar-prefetch contract
  annotated in :mod:`skypilot_tpu.ops.paged_attention`
  (``COST_KERNEL_KV_TRAFFIC``), which read one layer per call;
- operand classes propagate through elementwise chains (so a
  dequantized weight is *still* weight bytes), and matmul outputs are
  activations;
- only *explicit* collective eqns (psum/all_gather/...) are priced per
  mesh axis; collectives XLA inserts for resharding are the runtime
  census's job (``AuditReport.collectives``), not this model's.

KV bytes/step additionally get a capacity-normalized form: the pool
leaf avals divided by the pool's token capacity give stored
bytes/token, and ``live_tokens x bytes/token`` is by construction the
same quantity the ``skytpu_kv_read_bytes_per_step`` gauge publishes —
so the static model and the telemetry can be held to ±10% of each
other, and a pool dtype/layout drift breaks the contract loudly.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# ------------------------------------------------------------- classes
# Operand classes. Priority (below) decides the class of a
# mixed-operand elementwise result: KV beats weights beats tables
# beats activations, so a dequant chain (codes x scale) keeps its
# weight class until a matmul turns it into an activation.
WEIGHT_BF16 = 'weight_bf16'      # unquantized weight leaves (+ norms)
WEIGHT_INT8 = 'weight_int8'      # QuantizedWeight codes
WEIGHT_INT4 = 'weight_int4'      # QuantizedWeight4 packed nibbles
WEIGHT_SCALE = 'weight_scale'    # per-channel/group dequant scales
KV_POOL = 'kv_pool'              # cache k/v rows (any kv dtype)
KV_SCALE = 'kv_scale'            # per-row kv dequant scales
TABLE = 'table'                  # page tables, lengths, tokens, masks
ACTIVATION = 'activation'        # everything flowing between layers
CONST = 'const'                  # literals / on-the-fly iota etc.
ADAPTER = 'adapter_bank'         # multi-LoRA bank factors + scales

ALL_CLASSES = (WEIGHT_BF16, WEIGHT_INT8, WEIGHT_INT4, WEIGHT_SCALE,
               KV_POOL, KV_SCALE, ADAPTER, TABLE, ACTIVATION, CONST)

_PRIORITY = {KV_POOL: 8, KV_SCALE: 7, WEIGHT_INT4: 6, WEIGHT_INT8: 5,
             WEIGHT_BF16: 4, WEIGHT_SCALE: 3, ADAPTER: 2.5, TABLE: 2,
             ACTIVATION: 1, CONST: 0}

# Measured-vs-static tolerance for KV read bytes/step: the declared
# contract the tests hold the model to.
KV_TOLERANCE = 0.10

# Headroom the budget generator adds over the traced value. Wide
# enough to absorb trace-detail drift across jax point releases,
# narrow enough that the cheapest real regression this gate exists
# for (int4 codes materialized to bf16 = 4x the weight_int4 stream)
# cannot hide under it.
BUDGET_HEADROOM = 1.25

# Bytes a class ABSENT from a budget entry may reach before it
# violates: a budget lists every stream the dispatch is supposed to
# have, so a brand-new class showing up (say weight_bf16 reads in an
# int4 preset beyond the embedding gather) is a finding, not noise.
UNBUDGETED_FLOOR = 256 * 1024


# --------------------------------------------------------------- costs
def _aval_bytes(aval) -> int:
    import numpy as np
    shape = getattr(aval, 'shape', None)
    dtype = getattr(aval, 'dtype', None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:       # symbolic dim: price as 1
            pass
    return n * np.dtype(dtype).itemsize


@dataclasses.dataclass
class EqnCost:
    """One jaxpr eqn's priced traffic (already multiplied by the
    enclosing scan trip counts)."""
    prim: str
    read: Dict[str, int]
    written: Dict[str, int]
    flops: int = 0
    collective: Dict[str, int] = dataclasses.field(default_factory=dict)
    note: str = ''

    @property
    def read_total(self) -> int:
        return sum(self.read.values())

    def describe(self) -> str:
        parts = [f'{c}={v:,}B' for c, v in sorted(
            self.read.items(), key=lambda kv: -kv[1]) if v]
        tail = f' ({self.note})' if self.note else ''
        return f'{self.prim}: read ' + (', '.join(parts) or '0B') + tail


@dataclasses.dataclass
class DispatchCost:
    """Totals for ONE jitted dispatch (one device program call)."""
    label: str
    eqns: List[EqnCost] = dataclasses.field(default_factory=list)
    read: Dict[str, int] = dataclasses.field(default_factory=dict)
    written: Dict[str, int] = dataclasses.field(default_factory=dict)
    flops: int = 0
    collective: Dict[str, int] = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)
    # Capacity-normalized KV cost (set at engine boundaries): stored
    # bytes per cached token derived from the pool leaf avals — the
    # telemetry-comparable form.
    kv_bytes_per_token: Optional[float] = None
    kv_token_capacity: Optional[int] = None

    def add(self, eqn: EqnCost) -> None:
        self.eqns.append(eqn)
        for c, v in eqn.read.items():
            self.read[c] = self.read.get(c, 0) + v
        for c, v in eqn.written.items():
            self.written[c] = self.written.get(c, 0) + v
        self.flops += eqn.flops
        for ax, v in eqn.collective.items():
            self.collective[ax] = self.collective.get(ax, 0) + v

    # ------------------------------------------------------ derived
    @property
    def read_total(self) -> int:
        return sum(self.read.values())

    @property
    def written_total(self) -> int:
        return sum(self.written.values())

    def weight_bytes(self) -> int:
        """The weight stream of this dispatch (codes + scales +
        unquantized leaves)."""
        return sum(self.read.get(c, 0) for c in (
            WEIGHT_BF16, WEIGHT_INT8, WEIGHT_INT4, WEIGHT_SCALE))

    def kv_read_bytes_per_step(self, live_tokens: int) -> float:
        """Static KV bytes one decode substep streams for
        ``live_tokens`` of resident context — the quantity the
        ``skytpu_kv_read_bytes_per_step`` gauge measures."""
        if self.kv_bytes_per_token is None:
            raise ValueError(f'{self.label}: no KV capacity annotation')
        return live_tokens * self.kv_bytes_per_token

    def top_eqns(self, cls: str, n: int = 3) -> List[EqnCost]:
        ranked = sorted((e for e in self.eqns if e.read.get(cls, 0)),
                        key=lambda e: -e.read[cls])
        return ranked[:n]

    # ------------------------------------------------------- display
    def format_table(self) -> str:
        lines = [f'dispatch [{self.label}]: {self.read_total:,} B '
                 f'read, {self.written_total:,} B written, '
                 f'{self.flops:,} FLOPs, {len(self.eqns)} priced eqns']
        for c in ALL_CLASSES:
            r, w = self.read.get(c, 0), self.written.get(c, 0)
            if r or w:
                lines.append(f'  {c:<13} read {r:>14,} B   '
                             f'written {w:>14,} B')
        for ax, v in sorted(self.collective.items()):
            lines.append(f'  collective[{ax}] {v:,} B')
        if self.kv_bytes_per_token is not None:
            lines.append(f'  kv stored bytes/token: '
                         f'{self.kv_bytes_per_token:,.1f} '
                         f'(pool capacity {self.kv_token_capacity:,} '
                         'tokens)')
        for n_ in self.notes:
            lines.append(f'  note: {n_}')
        return '\n'.join(lines)

    def to_json(self) -> Dict[str, Any]:
        return {
            'label': self.label,
            'read_bytes': dict(self.read),
            'written_bytes': dict(self.written),
            'flops': self.flops,
            'collective_bytes': dict(self.collective),
            'kv_bytes_per_token': self.kv_bytes_per_token,
            'kv_token_capacity': self.kv_token_capacity,
            'notes': list(self.notes),
            'top_eqns': {c: [e.describe() for e in self.top_eqns(c)]
                         for c in ALL_CLASSES if self.read.get(c)},
        }


# --------------------------------------------------- the interpreter
# Fusible ops: elementwise / layout / convert — XLA never streams HBM
# for these alone; they merge footprints instead of charging bytes.
# Everything NOT listed here (and not handled specially below)
# defaults to MATERIALIZING — safe for unknown primitives.
_FUSED_OPS = {
    # layout / metadata
    'reshape', 'transpose', 'broadcast_in_dim', 'broadcast', 'squeeze',
    'expand_dims', 'rev', 'copy', 'stop_gradient', 'pad',
    'convert_element_type', 'bitcast_convert_type', 'device_put',
    'sharding_constraint', 'iota', 'split', 'concatenate',
    # elementwise arithmetic
    'add', 'sub', 'mul', 'div', 'rem', 'neg', 'abs', 'sign', 'max',
    'min', 'pow', 'integer_pow', 'exp', 'exp2', 'log', 'log1p',
    'expm1', 'sqrt', 'rsqrt', 'cbrt', 'tanh', 'logistic', 'erf',
    'erfc', 'erf_inv', 'sin', 'cos', 'tan', 'floor', 'ceil', 'round',
    'clamp', 'nextafter', 'real', 'imag', 'square',
    # comparisons / select / bitwise
    'eq', 'ne', 'lt', 'le', 'gt', 'ge', 'select_n', 'and', 'or',
    'xor', 'not', 'shift_left', 'shift_right_logical',
    'shift_right_arithmetic', 'population_count', 'clz', 'is_finite',
}
# NOTE convert_element_type is fused (it rides its consumer), but its
# OUTPUT keeps the input's class AND footprint — a materialized
# dequant is billed where the fat value actually crosses a boundary.

_SLICE_READ_OPS = {'gather', 'dynamic_slice', 'slice', 'take'}
_SCATTER_OPS = {'scatter', 'scatter-add', 'scatter_add', 'scatter_mul',
                'scatter_min', 'scatter_max', 'dynamic_update_slice'}
_COLLECTIVE_OPS = {'psum', 'all_gather', 'all_to_all', 'ppermute',
                   'pmax', 'pmin', 'psum_scatter', 'reduce_scatter',
                   'pbroadcast'}
_REDUCE_OPS = {'reduce_sum', 'reduce_max', 'reduce_min', 'reduce_prod',
               'reduce_and', 'reduce_or', 'argmax', 'argmin',
               'reduce_precision'}


def _out_class(prim: str, in_classes: Sequence[str]) -> str:
    cs = [c for c in in_classes if c != CONST]
    if prim in ('dot_general', 'conv_general_dilated'):
        return ACTIVATION
    if not cs:
        return CONST
    if len(set(cs)) > 1 and ACTIVATION in cs:
        return ACTIVATION
    return max(cs, key=lambda c: _PRIORITY[c])


def _dot_flops(eqn) -> int:
    (lhs_c, _rhs_c), _batch = eqn.params['dimension_numbers']
    out = eqn.outvars[0].aval
    n_out = 1
    for d in out.shape:
        n_out *= int(d)
    k = 1
    for d in lhs_c:
        k *= int(eqn.invars[0].aval.shape[d])
    return 2 * n_out * k


def _sub_jaxprs(eqn) -> List[Any]:
    subs = []
    for v in eqn.params.values():
        for s in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(s, 'eqns') or hasattr(s, 'jaxpr'):
                subs.append(s)
    return subs


def _kernel_kv_layer_sliced(eqn) -> bool:
    """True when this pallas_call is one of the annotated layer-stacked
    pool kernels (``COST_KERNEL_KV_TRAFFIC`` of ``ops.paged_attention``
    and ``ops.latent_paged_attention``): the kernel takes the FULL
    stacked pool with the layer as a scalar-prefetch index and DMAs one
    layer's pages per call."""
    try:
        from skypilot_tpu.ops import latent_paged_attention as lpa
        from skypilot_tpu.ops import paged_attention as pa
        names = set(pa.COST_KERNEL_KV_TRAFFIC) | set(
            lpa.COST_KERNEL_KV_TRAFFIC)
    except ImportError:     # ops unavailable in a lint-only install
        return False
    tag = str(eqn.params.get('name_and_src_info',
                             eqn.params.get('name', '')))
    return tag.split(' at ', 1)[0].strip() in names


class _Val:
    """Abstract value: operand class + resident footprint. A RESIDENT
    value lives in HBM (footprint = itself); a FUSED value lives in
    registers and its footprint is the set of resident values the
    fusion producing it must stream. Residency is an explicit flag —
    it cannot be inferred by matching the consumer's var id against
    the footprint, because transparent call boundaries (pjit) re-bind
    values to fresh outer vars."""
    __slots__ = ('cls', 'fp', 'resident')

    def __init__(self, cls: str, fp: Dict[int, Tuple[int, str]],
                 resident: bool = False):
        self.cls = cls
        self.fp = fp
        self.resident = resident


def _resident(var, cls: str) -> '_Val':
    b = _aval_bytes(var.aval)
    return _Val(cls, {id(var): (b, cls)} if b else {}, resident=True)


def _merge_fps(vals: Sequence['_Val']) -> Dict[int, Tuple[int, str]]:
    out: Dict[int, Tuple[int, str]] = {}
    for v in vals:
        out.update(v.fp)
    return out


class _Interpreter:
    def __init__(self, cost: DispatchCost):
        self.cost = cost

    def run(self, closed_jaxpr, in_classes: Sequence[str],
            mult: int = 1) -> List[str]:
        """Boundary entry: jaxpr inputs are resident. Returns outvar
        classes; fused outputs are materialized (a program output IS
        an HBM write)."""
        jaxpr = getattr(closed_jaxpr, 'jaxpr', closed_jaxpr)
        invars = list(jaxpr.invars)
        if len(invars) != len(in_classes):
            raise ValueError(
                f'{self.cost.label}: {len(invars)} jaxpr invars vs '
                f'{len(in_classes)} operand classes')
        in_vals = [_resident(v, c) for v, c in zip(invars, in_classes)]
        out_vals = self._body(jaxpr, in_vals, mult)
        for v, val in zip(jaxpr.outvars, out_vals):
            if hasattr(v, 'val') or val.resident:
                continue          # literal / already resident
            read: Dict[str, int] = {}
            for b, c in val.fp.values():
                _acc(read, c, b * mult)
            written: Dict[str, int] = {}
            _acc(written, val.cls, _aval_bytes(v.aval) * mult)
            self.cost.add(EqnCost(prim='<output materialize>',
                                  read=read, written=written))
        return [v.cls for v in out_vals]

    def _body(self, jaxpr, in_vals: List['_Val'],
              mult: int) -> List['_Val']:
        """Transparent pass: values (and footprints) flow through."""
        jaxpr = getattr(jaxpr, 'jaxpr', jaxpr)
        env: Dict[Any, '_Val'] = {}
        for v, val in zip(jaxpr.invars, in_vals):
            env[v] = val
        for v in jaxpr.constvars:
            env[v] = _resident(v, CONST)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env, mult)
        return [self._val(env, v) for v in jaxpr.outvars]

    # ------------------------------------------------------------ one eqn
    def _val(self, env, var) -> '_Val':
        if hasattr(var, 'val'):                    # Literal
            return _Val(CONST, {})
        got = env.get(var)
        return got if got is not None else _Val(CONST, {})

    def _eqn(self, eqn, env: Dict[Any, '_Val'], mult: int) -> None:
        prim = eqn.primitive.name
        in_vals = [self._val(env, v) for v in eqn.invars]

        handler = getattr(self, f'_h_{prim}', None)
        if handler is not None:
            out_vals = handler(eqn, in_vals, mult)
        elif prim in _FUSED_OPS:
            out_vals = self._h_fused(eqn, in_vals, mult)
        elif prim in _SLICE_READ_OPS:
            out_vals = self._h_slice(eqn, in_vals, mult)
        elif prim in _SCATTER_OPS:
            out_vals = self._h_scatter(eqn, in_vals, mult)
        elif prim in _COLLECTIVE_OPS:
            out_vals = self._h_collective(eqn, in_vals, mult)
        elif _sub_jaxprs(eqn):
            out_vals = self._h_call(eqn, in_vals, mult)
        else:
            out_vals = self._h_materialize(eqn, in_vals, mult)
        for v, val in zip(eqn.outvars, out_vals):
            if type(v).__name__ != 'DropVar':
                env[v] = val

    def _charge(self, eqn, in_vals, mult, note: str = '',
                out_cls: Optional[str] = None,
                read_override: Optional[Dict[str, int]] = None
                ) -> List['_Val']:
        """Materializing eqn: read the deduplicated resident footprint
        of the operands, write the outputs; outputs become resident."""
        prim = eqn.primitive.name
        if out_cls is None:
            out_cls = _out_class(prim, [v.cls for v in in_vals])
        read: Dict[str, int] = {}
        if read_override is not None:
            read = read_override
        else:
            for b, c in _merge_fps(in_vals).values():
                _acc(read, c, b * mult)
        written: Dict[str, int] = {}
        out_vals = []
        for v in eqn.outvars:
            _acc(written, out_cls, _aval_bytes(v.aval) * mult)
            out_vals.append(_resident(v, out_cls))
        flops = 0
        if prim == 'dot_general':
            flops = _dot_flops(eqn) * mult
        elif prim in _REDUCE_OPS:
            flops = sum(_n_elems(v.aval) for v in eqn.invars
                        if not hasattr(v, 'val')) * mult
        else:
            flops = sum(_n_elems(v.aval) for v in eqn.outvars) * mult
        self.cost.add(EqnCost(prim=prim, read=read, written=written,
                              flops=flops, note=note))
        return out_vals

    # --------------------------------------------------------- handlers
    def _h_fused(self, eqn, in_vals, mult):
        out_cls = _out_class(eqn.primitive.name,
                             [v.cls for v in in_vals])
        fp = _merge_fps(in_vals)
        return [_Val(out_cls, fp) for _ in eqn.outvars]

    def _h_materialize(self, eqn, in_vals, mult):
        return self._charge(eqn, in_vals, mult)

    def _h_dot_general(self, eqn, in_vals, mult):
        return self._charge(eqn, in_vals, mult, out_cls=ACTIVATION)

    def _h_slice(self, eqn, in_vals, mult):
        # The sliced operand's read is what comes OUT (a page gather
        # touches the referenced rows, not the resident pool); index
        # operands charge their footprints.
        opr = in_vals[0]
        out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        read: Dict[str, int] = {}
        _acc(read, opr.cls, out_b * mult)
        for b, c in _merge_fps(in_vals[1:]).values():
            _acc(read, c, b * mult)
        return self._charge(eqn, in_vals, mult, out_cls=opr.cls,
                            read_override=read)

    def _h_scatter(self, eqn, in_vals, mult):
        # Donated in-place update: only the updates (+indices) move;
        # the untouched operand rows never stream. The output KEEPS
        # the operand's resident footprint (it IS the pool).
        prim = eqn.primitive.name
        upd_idx = 2 if prim.startswith('scatter') else 1
        read: Dict[str, int] = {}
        upd_b = _aval_bytes(eqn.invars[upd_idx].aval)
        _acc(read, in_vals[upd_idx].cls, upd_b * mult)
        idx_vals = [v for i, v in enumerate(in_vals)
                    if i not in (0, upd_idx)]
        for b, c in _merge_fps(idx_vals).values():
            _acc(read, c, b * mult)
        written: Dict[str, int] = {}
        _acc(written, in_vals[0].cls, upd_b * mult)
        self.cost.add(EqnCost(prim=prim, read=read, written=written,
                              note='in-place update: updates bytes '
                                   'only'))
        return [_Val(in_vals[0].cls, dict(in_vals[0].fp),
                     in_vals[0].resident) for _ in eqn.outvars]

    def _h_collective(self, eqn, in_vals, mult):
        bytes_moved = max(
            sum(_aval_bytes(v.aval) for v in eqn.invars
                if not hasattr(v, 'val')),
            sum(_aval_bytes(v.aval) for v in eqn.outvars)) * mult
        axes = eqn.params.get('axes', eqn.params.get('axis_name', ()))
        if not isinstance(axes, (list, tuple)):
            axes = (axes,)
        coll = {str(ax): bytes_moved for ax in axes
                if isinstance(ax, str)}
        self.cost.add(EqnCost(prim=eqn.primitive.name, read={},
                              written={}, collective=coll))
        out_cls = _out_class(eqn.primitive.name,
                             [v.cls for v in in_vals])
        return [_resident(v, out_cls) for v in eqn.outvars]

    # Transparent inlined calls: footprints flow through unchanged.
    def _h_call(self, eqn, in_vals, mult):
        return self._body(_sub_jaxprs(eqn)[0], in_vals, mult)

    def _h_pjit(self, eqn, in_vals, mult):
        return self._body(eqn.params['jaxpr'], in_vals, mult)

    def _h_custom_jvp_call(self, eqn, in_vals, mult):
        sub = (eqn.params.get('call_jaxpr')
               or eqn.params.get('fun_jaxpr') or _sub_jaxprs(eqn)[0])
        return self._body(sub, in_vals, mult)

    def _h_custom_vjp_call(self, eqn, in_vals, mult):
        return self._h_custom_jvp_call(eqn, in_vals, mult)

    def _h_custom_vjp_call_jaxpr(self, eqn, in_vals, mult):
        return self._h_custom_jvp_call(eqn, in_vals, mult)

    # Loop/branch boundaries: body inputs are resident at their own
    # avals — a fat value entering a loop is materialized HERE, which
    # is exactly how XLA treats loop operands (and exactly where an
    # accidental bf16 dequant becomes a per-iteration fat stream).
    def _boundary(self, eqn_invars, in_vals, mult,
                  note: str) -> List[str]:
        read: Dict[str, int] = {}
        written: Dict[str, int] = {}
        for var, val in zip(eqn_invars, in_vals):
            if hasattr(var, 'val') or val.resident:
                continue          # literal / already resident
            for b, c in val.fp.values():
                _acc(read, c, b * mult)
            _acc(written, val.cls, _aval_bytes(var.aval) * mult)
        if read or written:
            self.cost.add(EqnCost(
                prim=f'<{note} boundary materialize>',
                read=read, written=written,
                note='fused value forced resident at loop boundary'))
        return [val.cls for val in in_vals]

    def _h_scan(self, eqn, in_vals, mult):
        length = int(eqn.params['length'])
        body = eqn.params['jaxpr']
        in_classes = self._boundary(eqn.invars, in_vals, mult, 'scan')
        jaxpr = getattr(body, 'jaxpr', body)
        body_vals = [_resident(v, c)
                     for v, c in zip(jaxpr.invars, in_classes)]
        out_vals = self._body(body, body_vals, mult * length)
        return [_resident(v, val.cls)
                for v, val in zip(eqn.outvars, out_vals)]

    def _h_while(self, eqn, in_vals, mult):
        body = eqn.params['body_jaxpr']
        n_c = int(eqn.params.get('cond_nconsts', 0))
        body_ins = in_vals[n_c:]
        classes = self._boundary(eqn.invars[n_c:], body_ins, mult,
                                 'while')
        jaxpr = getattr(body, 'jaxpr', body)
        body_vals = [_resident(v, c)
                     for v, c in zip(jaxpr.invars, classes)]
        out_vals = self._body(body, body_vals, mult)
        self.cost.notes.append(
            'while loop priced at ONE iteration (trip count unknown '
            'statically)')
        return [_resident(v, val.cls)
                for v, val in zip(eqn.outvars, out_vals)]

    def _h_cond(self, eqn, in_vals, mult):
        branches = eqn.params['branches']
        classes = [v.cls for v in in_vals[1:]]
        best = None
        for br in branches:
            probe = DispatchCost(label=f'{self.cost.label}/cond')
            jaxpr = getattr(br, 'jaxpr', br)
            br_vals = [_resident(v, c)
                       for v, c in zip(jaxpr.invars, classes)]
            out = _Interpreter(probe)._body(br, br_vals, mult)
            if best is None or probe.read_total > best[2].read_total:
                best = (br, out, probe)
        _br, out_vals, probe = best
        for e in probe.eqns:
            self.cost.add(e)
        return [_resident(v, val.cls)
                for v, val in zip(eqn.outvars, out_vals)]

    def _h_shard_map(self, eqn, in_vals, mult):
        mesh = eqn.params.get('mesh')
        try:
            n_dev = int(getattr(mesh, 'size', 1))
        except TypeError:
            n_dev = 1
        sub = eqn.params.get('jaxpr') or _sub_jaxprs(eqn)[0]
        # A real boundary; body avals are per-shard blocks, so xN
        # devices restores the global traffic the mesh moves.
        jaxpr = getattr(sub, 'jaxpr', sub)
        body_vals = [_resident(v, val.cls)
                     for v, val in zip(jaxpr.invars, in_vals)]
        out_vals = self._body(sub, body_vals,
                              mult * max(1, n_dev))
        return [_resident(v, val.cls)
                for v, val in zip(eqn.outvars, out_vals)]

    def _h_pallas_call(self, eqn, in_vals, mult):
        read: Dict[str, int] = {}
        written: Dict[str, int] = {}
        layer_sliced = _kernel_kv_layer_sliced(eqn)
        note = ''
        for v, val in zip(eqn.invars, in_vals):
            if hasattr(v, 'val'):
                continue
            b = _aval_bytes(v.aval)
            if (layer_sliced and val.cls in (KV_POOL, KV_SCALE)
                    and len(v.aval.shape) >= 2):
                # Layer-stacked pool with scalar-prefetch layer index:
                # one layer's pages DMA per call.
                b //= max(1, int(v.aval.shape[0]))
                note = ('kv pool priced per layer (scalar-prefetch '
                        'kernel contract)')
            _acc(read, val.cls, b * mult)
        out_cls = _out_class('pallas_call', [v.cls for v in in_vals])
        out_vals = []
        for v in eqn.outvars:
            _acc(written, out_cls, _aval_bytes(v.aval) * mult)
            out_vals.append(_resident(v, out_cls))
        self.cost.add(EqnCost(prim='pallas_call', read=read,
                              written=written, note=note))
        return out_vals


def _acc(d: Dict[str, int], cls: str, v: int) -> None:
    if v:
        d[cls] = d.get(cls, 0) + int(v)


def _n_elems(aval) -> int:
    n = 1
    for d in getattr(aval, 'shape', ()):
        try:
            n *= int(d)
        except TypeError:
            pass
    return n


def analyze_closed_jaxpr(closed_jaxpr, arg_classes: Sequence[str],
                         label: str = 'dispatch') -> DispatchCost:
    """Price one closed jaxpr. ``arg_classes`` is one operand class
    per flat jaxpr input (see :func:`classify_decode_args`)."""
    cost = DispatchCost(label=label)
    _Interpreter(cost).run(closed_jaxpr, list(arg_classes))
    return cost


# ----------------------------------------------- boundary annotations
def _fields(x) -> Optional[Tuple[str, ...]]:
    f = getattr(x, '_fields', None)
    return tuple(f) if f is not None else None


def _classify_class_tree(tree, cls: str) -> Any:
    """Mirror a pytree with one uniform operand class on every leaf."""
    if isinstance(tree, dict):
        return {k: _classify_class_tree(v, cls) for k, v in tree.items()}
    f = _fields(tree)
    if f is not None:
        return type(tree)(*(_classify_class_tree(v, cls) for v in tree))
    if isinstance(tree, (list, tuple)):
        return type(tree)(_classify_class_tree(v, cls) for v in tree)
    if tree is None:
        return None
    return cls


def classify_params(tree) -> Any:
    """Mirror a param pytree with operand-class labels: quantized
    leaves split into code + scale classes, everything else is the
    bf16 weight stream. Duck-typed on the NamedTuple field contract so
    the analysis layer needs no model imports."""
    f = _fields(tree)
    if f == ('int8', 'scale'):
        return type(tree)(int8=WEIGHT_INT8, scale=WEIGHT_SCALE)
    if f == ('packed', 'scale'):
        return type(tree)(packed=WEIGHT_INT4, scale=WEIGHT_SCALE)
    if isinstance(tree, dict):
        # The multi-LoRA bank is its OWN operand class: a decode step
        # gathers only the bank rows its batch names, so billing the
        # bank as weight stream would charge slots x rank bytes the
        # HBM never moves (the gather interpreter prices rows
        # touched).
        return {k: (_classify_class_tree(v, ADAPTER) if k == 'mlora'
                    else classify_params(v))
                for k, v in tree.items()}
    if f is not None:
        return type(tree)(*(classify_params(v) for v in tree))
    if isinstance(tree, (list, tuple)):
        return type(tree)(classify_params(v) for v in tree)
    if tree is None:
        return None
    return WEIGHT_BF16


def classify_cache(cache) -> Any:
    """KV-cache pytree -> class labels (PagedKVCache, or the contiguous
    ``llama.KVCache``; scales optional)."""
    f = _fields(cache)
    if f is not None and 'pool_k' in f:
        return type(cache)(
            pool_k=KV_POOL, pool_v=KV_POOL,
            k_scale=None if cache.k_scale is None else KV_SCALE,
            v_scale=None if cache.v_scale is None else KV_SCALE)
    if f is not None and 'length' in f:
        return type(cache)(
            k=KV_POOL, v=KV_POOL, length=TABLE,
            k_scale=None if cache.k_scale is None else KV_SCALE,
            v_scale=None if cache.v_scale is None else KV_SCALE)
    raise TypeError(f'not a KV cache pytree: {type(cache)!r}')


def classify_aux(x) -> Any:
    """Non-params, non-cache decode operands: integer/bool device
    values are control tables (tokens, lengths, page tables, active
    masks, rng keys), floats are activations (temps/top-p), and
    non-array statics get a placeholder (they never reach the
    jaxpr)."""
    import jax.numpy as jnp
    dt = getattr(x, 'dtype', None)
    if dt is None:
        return ACTIVATION
    if (jnp.issubdtype(dt, jnp.integer)
            or jnp.issubdtype(dt, jnp.bool_)):
        return TABLE
    return ACTIVATION


def classify_decode_args(args: Sequence[Any]) -> Tuple[Any, ...]:
    """Class labels for a decode dispatch's positional args: by the
    engine calling convention args[0] is the param tree, args[1] the
    KV cache, and the rest are per-call control/sampling operands."""
    return (classify_params(args[0]), classify_cache(args[1]),
            *(classify_aux(a) for a in args[2:]))


def kv_bytes_per_token(args: Sequence[Any], classes: Sequence[Any],
                       capacity: int, shard_degree: int = 1) -> float:
    """Stored KV bytes/token straight from the pool leaf avals: total
    kv_pool + kv_scale bytes over the pool's token capacity (per
    shard). Structurally the same quantity ``kv_token_bytes``
    computes arithmetically — which is exactly why the telemetry
    gauge and this model can be held to KV_TOLERANCE of each other."""
    import jax
    flat_a = jax.tree.leaves(tuple(args))
    flat_c = jax.tree.leaves(tuple(classes))
    kv = sum(_aval_bytes(a) for a, c in zip(flat_a, flat_c)
             if c in (KV_POOL, KV_SCALE))
    return kv / capacity / max(1, shard_degree)


# --------------------------------------------------- engine dispatches
def _static_argnums(args: Sequence[Any]) -> Tuple[int, ...]:
    """An arg is dynamic iff some leaf of its pytree carries a dtype
    (array or ShapeDtypeStruct); scalars/strings/None are static."""
    import jax

    def _dynamic(a) -> bool:
        return any(hasattr(leaf, 'dtype') for leaf in jax.tree.leaves(a))

    return tuple(i for i, a in enumerate(args) if not _dynamic(a))


def trace_dispatch(fn: Callable, args: Sequence[Any],
                   classes: Sequence[Any],
                   label: str = 'dispatch') -> DispatchCost:
    """make_jaxpr ``fn`` on (abstract) args and price it. ``args`` may
    be arrays or ShapeDtypeStructs; non-array entries are treated as
    static. ``classes`` mirrors ``args`` structurally."""
    import jax
    statics = _static_argnums(args)
    jx = jax.make_jaxpr(fn, static_argnums=statics)(*args)
    dyn_args = tuple(a for i, a in enumerate(args) if i not in statics)
    dyn_cls = tuple(c for i, c in enumerate(classes)
                    if i not in statics)
    flat_args = jax.tree.leaves(dyn_args)
    flat_cls = jax.tree.leaves(dyn_cls)
    if len(flat_args) != len(flat_cls):
        raise ValueError(f'{label}: class tree does not mirror args '
                         f'({len(flat_cls)} vs {len(flat_args)})')
    return analyze_closed_jaxpr(jx, flat_cls, label=label)


def engine_dispatch_costs(engine, fns: Sequence[Callable],
                          args: Optional[Sequence[Any]]
                          ) -> Dict[str, DispatchCost]:
    """Price an engine's steady-state decode chain from one captured
    dispatch's arg structs (the same capture the collective census
    lowers from). Slot engines have ONE fused program ('decode');
    paged engines add the donated pool 'merge'."""
    import jax
    if args is None:
        return {}
    classes = engine.decode_operand_classes(args)
    out: Dict[str, DispatchCost] = {}
    for fn in fns:
        try:
            out['decode'] = trace_dispatch(fn, args, classes,
                                           label='decode')
            continue
        except (TypeError, ValueError):
            pass              # the paged merge: different signature
            # (a 6-arg jit rejects the 12-arg decode call with
            # ValueError: static_argnums out of range)
        cfg = engine.cfg
        cache, table, lengths, active = (args[1], args[2], args[4],
                                         args[9])
        # args[10]/args[11] are the adapter indices / vocab mask; the
        # merge consumes neither.
        horizon = args[12]
        spec = cfg.kv_spec

        def ring(width):
            return jax.ShapeDtypeStruct(
                (cfg.n_cache_layers, engine.max_batch, horizon, spec.heads,
                 width), cfg.dtype,
                sharding=getattr(engine, '_ring_sh', None))
        margs = (cache, ring(spec.k_dim), ring(spec.v_dim), table, lengths,
                 active)
        mcls = (classify_cache(cache), ACTIVATION, ACTIVATION,
                TABLE, TABLE, TABLE)
        out['merge'] = trace_dispatch(fn, margs, mcls, label='merge')
    if 'decode' in out:
        from skypilot_tpu.inference.engine import kv_shard_degree
        cap = engine.kv_token_capacity()
        deg = kv_shard_degree(engine.cfg,
                              getattr(engine, 'mesh', None))
        out['decode'].kv_token_capacity = cap
        out['decode'].kv_bytes_per_token = kv_bytes_per_token(
            (args[1],), (classes[1],), cap, deg)
    return out


# ------------------------------------------------------------- budgets
def budget_from_costs(costs: Dict[str, DispatchCost],
                      headroom: float = BUDGET_HEADROOM
                      ) -> Dict[str, Dict[str, int]]:
    """Emit a budget dict from traced costs (the generator behind
    :data:`BYTE_BUDGETS`): per dispatch label, a read-bytes ceiling
    per operand class plus per-axis collective ceilings
    ('collective.<axis>' keys)."""
    out: Dict[str, Dict[str, int]] = {}
    for label, cost in sorted(costs.items()):
        entry: Dict[str, int] = {}
        for c, v in sorted(cost.read.items()):
            entry[c] = int(v * headroom)
        for ax, v in sorted(cost.collective.items()):
            entry[f'collective.{ax}'] = int(v * headroom)
        out[label] = entry
    return out


def check_budget(costs: Dict[str, DispatchCost],
                 budget: Dict[str, Dict[str, int]]) -> List[str]:
    """Budget gate: every budgeted dispatch must exist, stay under its
    per-class read ceilings AND its collective ceilings, and grow no
    unbudgeted stream past :data:`UNBUDGETED_FLOOR`. Violations name
    the fattest offending eqns — the attribution diff that points at
    the materialized dequant / extra sweep directly."""
    out: List[str] = []
    for label, caps in sorted(budget.items()):
        cost = costs.get(label)
        if cost is None:
            out.append(f'{label}: budgeted dispatch was never '
                       'captured (decode path changed?)')
            continue
        class_caps = {k: v for k, v in caps.items()
                      if not k.startswith('collective.')}
        for cls in sorted(set(cost.read) | set(class_caps)):
            cap = class_caps.get(cls, UNBUDGETED_FLOOR)
            got = cost.read.get(cls, 0)
            if got <= cap:
                continue
            tag = ('' if cls in class_caps
                   else ' [unbudgeted stream]')
            msg = (f'{label}: {cls} reads {got:,} B > budget '
                   f'{cap:,} B{tag}')
            for e in cost.top_eqns(cls):
                msg += f'\n      {e.describe()}'
            out.append(msg)
        for key, cap in sorted(caps.items()):
            if not key.startswith('collective.'):
                continue
            ax = key[len('collective.'):]
            got = cost.collective.get(ax, 0)
            if got > cap:
                out.append(f'{label}: collective[{ax}] moves '
                           f'{got:,} B > budget {cap:,} B')
    return out


# Per-preset byte budgets (read-bytes ceilings per dispatch label and
# operand class, BUDGET_HEADROOM over the traced values on the tiny
# preset configs). Regenerate with
# ``python -m skypilot_tpu.analysis.costmodel --emit-budgets`` after a
# deliberate cost change and review the diff like a lockfile — an
# unexplained jump IS the regression this gate exists to catch.
# Stale keys (a preset that no longer exists) fail
# tests/test_analysis.py loudly.
BYTE_BUDGETS: Dict[str, Dict[str, Dict[str, int]]] = {
    'adapters': {
        'decode': {
            'activation': 868800,
            'adapter_bank': 1316160,
            'const': 165800,
            'kv_pool': 2662400,
            'table': 13760,
            'weight_bf16': 1985920,
        },
        'merge': {
            'activation': 10240,
            'kv_pool': 168960,
            'table': 550,
        },
    },
    'digest': {
        'decode': {
            'activation': 653760,
            'const': 165800,
            'kv_pool': 2662400,
            'table': 4480,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 10240,
            'kv_pool': 168960,
            'table': 550,
        },
    },
    'disagg': {
        'decode': {
            'activation': 664000,
            'const': 165960,
            'kv_pool': 1331200,
            'kv_scale': 496640,
            'table': 7040,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 16640,
            'kv_pool': 84480,
            'kv_scale': 21120,
            'table': 920,
        },
    },
    'fleet-obs': {
        'decode': {
            'activation': 653760,
            'const': 165800,
            'kv_pool': 2662400,
            'table': 4480,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 10240,
            'kv_pool': 168960,
            'table': 550,
        },
    },
    'fused-attn': {
        'decode': {
            'activation': 344000,
            'const': 165560,
            'kv_pool': 1351680,
            'table': 2560,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 10240,
            'kv_pool': 168960,
            'table': 550,
        },
    },
    'int4': {
        'decode': {
            'activation': 740800,
            'const': 165960,
            'kv_pool': 665600,
            'kv_scale': 496640,
            'table': 7040,
            'weight_bf16': 105920,
            'weight_int4': 450560,
            'weight_scale': 19200,
        },
        'merge': {
            'activation': 19200,
            'kv_pool': 42240,
            'kv_scale': 21120,
            'table': 920,
        },
    },
    'int4-multistep': {
        'decode': {
            'activation': 301280,
            'const': 42020,
            'kv_pool': 250880,
            'kv_scale': 166400,
            'table': 2240,
            'weight_bf16': 52960,
            'weight_int4': 225280,
            'weight_scale': 9600,
        },
        'merge': {
            'activation': 9600,
            'kv_pool': 42240,
            'kv_scale': 21120,
            'table': 520,
        },
    },
    'kv-int4': {
        'decode': {
            'activation': 664000,
            'const': 165960,
            'kv_pool': 665600,
            'kv_scale': 496640,
            'table': 7040,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 19200,
            'kv_pool': 42240,
            'kv_scale': 21120,
            'table': 920,
        },
    },
    'kv-int8': {
        'decode': {
            'activation': 664000,
            'const': 165960,
            'kv_pool': 1331200,
            'kv_scale': 496640,
            'table': 7040,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 16640,
            'kv_pool': 84480,
            'kv_scale': 21120,
            'table': 920,
        },
    },
    'llama': {
        'forward': {
            'activation': 255520,
            'const': 800,
            'table': 160,
            'weight_bf16': 306560,
        },
    },
    'multistep': {
        'decode': {
            'activation': 257760,
            'const': 41940,
            'kv_pool': 1003520,
            'table': 1600,
            'weight_bf16': 954080,
        },
        'merge': {
            'activation': 5120,
            'kv_pool': 168960,
            'table': 310,
        },
    },
    'paged': {
        'decode': {
            'activation': 653760,
            'const': 165800,
            'kv_pool': 2662400,
            'table': 4480,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 10240,
            'kv_pool': 168960,
            'table': 550,
        },
    },
    'paged-gang': {
        'decode': {
            'activation': 653760,
            'const': 165800,
            'kv_pool': 2662400,
            'table': 4480,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 20480,
            'collective.dp': 20960,
            'table': 2320,
        },
    },
    'paged-spec': {
        'spec_verify': {
            'activation': 262580,
            'const': 180,
            'kv_pool': 419840,
            'table': 1385,
            'weight_bf16': 277400,
        },
    },
    'paged-tp': {
        'decode': {
            'activation': 653760,
            'const': 165800,
            'kv_pool': 2662400,
            'table': 4480,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 10240,
            'table': 1160,
        },
    },
    'paged-tp-int8': {
        'decode': {
            'activation': 664000,
            'const': 165960,
            'kv_pool': 1331200,
            'kv_scale': 496640,
            'table': 7040,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 16640,
            'table': 1960,
        },
    },
    'spec-multistep': {
        'spec_fused': {
            'activation': 1021800,
            'const': 540,
            'kv_pool': 998400,
            'table': 72855,
            'weight_bf16': 832200,
        },
    },
    'telemetry': {
        'decode': {
            'activation': 653760,
            'const': 165800,
            'kv_pool': 2662400,
            'table': 4480,
            'weight_bf16': 1908160,
        },
        'merge': {
            'activation': 10240,
            'kv_pool': 168960,
            'table': 550,
        },
    },
}


def budget_for(preset: str) -> Optional[Dict[str, Dict[str, int]]]:
    return BYTE_BUDGETS.get(preset)


# --------------------------------------------------- abstract tracing
def abstract_params(cfg, quantize: Optional[str] = None):
    """Param-tree ShapeDtypeStructs for ``cfg`` WITHOUT materializing
    weights (a 7B stays a 7B of structs): eval_shape over the real
    init + quantize paths, so layout changes propagate here for
    free."""
    import jax

    from skypilot_tpu.models import llama
    params = jax.eval_shape(
        lambda key: llama.init_params(key, cfg), jax.random.PRNGKey(0))
    if quantize:
        from skypilot_tpu.models import quantization
        params = jax.eval_shape(
            lambda p: quantization.quantize_params(p, mode=quantize),
            params)
    return params


def abstract_paged_cache(cfg, n_pages: int, page_size: int,
                         kv_cache_dtype: Optional[str] = None):
    """PagedKVCache ShapeDtypeStructs mirroring the engine's pool
    layout ([L, n_pages, hkv, page, d]; int4 packs two codes per byte
    with fp32 row scales)."""
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.inference.paged import PagedKVCache
    L, hkv, d = cfg.n_cache_layers, cfg.n_kv_heads, cfg.head_dim
    if kv_cache_dtype == 'int4':
        pool = jax.ShapeDtypeStruct((L, n_pages, hkv, page_size, d // 2),
                                    jnp.uint8)
    elif kv_cache_dtype and kv_cache_dtype != 'bf16':
        pool = jax.ShapeDtypeStruct((L, n_pages, hkv, page_size, d),
                                    jnp.int8)
    else:
        pool = jax.ShapeDtypeStruct((L, n_pages, hkv, page_size, d),
                                    cfg.dtype)
    scale = (jax.ShapeDtypeStruct((L, n_pages, hkv, page_size),
                                  jnp.float32)
             if kv_cache_dtype and kv_cache_dtype != 'bf16' else None)
    return PagedKVCache(pool_k=pool, pool_v=pool, k_scale=scale,
                        v_scale=scale)


def abstract_decode_cost(cfg, *, batch: int, avg_ctx: int,
                         quantize: Optional[str] = None,
                         kv_cache_dtype: Optional[str] = None,
                         page_size: int = 16,
                         horizon: int = 1) -> DispatchCost:
    """Price ONE paged decode substep for an arbitrary config —
    abstractly (eval_shape + make_jaxpr on structs; nothing compiles,
    no weights materialize)."""
    import functools

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.inference import paged as paged_lib
    params = abstract_params(cfg, quantize)
    pages_per_req = -(-(avg_ctx + horizon) // page_size)
    n_pages = batch * pages_per_req + 1
    cache = abstract_paged_cache(cfg, n_pages, page_size,
                                 kv_cache_dtype)
    table_p = jax.ShapeDtypeStruct((batch, pages_per_req), jnp.int32)
    tokens = jax.ShapeDtypeStruct((batch,), jnp.int32)
    lengths = jax.ShapeDtypeStruct((batch,), jnp.int32)
    active = jax.ShapeDtypeStruct((batch,), jnp.bool_)

    fn = functools.partial(
        lambda p, c, t, tok, ln, act: paged_lib.paged_decode_horizon(
            p, c, t, tok, ln, cfg, horizon=horizon, active=act))
    args = (params, cache, table_p, tokens, lengths, active)
    classes = (classify_params(params), classify_cache(cache),
               TABLE, TABLE, TABLE, TABLE)
    label = (f'{cfg.name} decode (batch={batch}, ctx={avg_ctx}'
             + (f', {quantize} weights' if quantize else '')
             + (f', {kv_cache_dtype} kv' if kv_cache_dtype else '')
             + ')')
    cost = trace_dispatch(fn, args, classes, label=label)
    cost.kv_token_capacity = n_pages * page_size
    cost.kv_bytes_per_token = kv_bytes_per_token(
        (cache,), (classify_cache(cache),), n_pages * page_size)
    return cost


def roofline_step_bytes(cfg, *, batch: int, avg_ctx: int,
                        quantize: Optional[str] = None,
                        kv_cache_dtype: Optional[str] = None
                        ) -> Dict[str, float]:
    """A decode step's roofline denominator, statically derived: the
    weight stream + live-context KV read of one decode step, per token
    position, with the full attribution of where the bytes went."""
    cost = abstract_decode_cost(cfg, batch=batch, avg_ctx=avg_ctx,
                                quantize=quantize,
                                kv_cache_dtype=kv_cache_dtype)
    live = batch * avg_ctx
    kv = cost.kv_read_bytes_per_step(live)
    weights = float(cost.weight_bytes())
    return {
        'weight_bytes': weights,
        'kv_bytes_per_token': float(cost.kv_bytes_per_token),
        'live_tokens': float(live),
        'kv_bytes': float(kv),
        'step_bytes': weights + kv,
        'flops': float(cost.flops),
        'read_by_class': {k: float(v) for k, v in cost.read.items()},
    }


def kv_static_check(cfg, kv_cache_dtype, measured_per_token: float,
                    mesh=None) -> Dict[str, Any]:
    """Cross-check the static stored-bytes/token against a measured
    (telemetry-derived) value within KV_TOLERANCE. Returns the
    comparison record."""
    cost = abstract_decode_cost(cfg, batch=1, avg_ctx=64,
                                kv_cache_dtype=kv_cache_dtype)
    static = float(cost.kv_bytes_per_token)
    if mesh is not None:
        from skypilot_tpu.inference.engine import kv_shard_degree
        static /= kv_shard_degree(cfg, mesh)
    ratio = static / measured_per_token if measured_per_token else 0.0
    return {
        'static_bytes_per_token': static,
        'measured_bytes_per_token': float(measured_per_token),
        'ratio': ratio,
        'tolerance': KV_TOLERANCE,
        'ok': bool(measured_per_token
                   and abs(ratio - 1.0) <= KV_TOLERANCE),
    }


# ----------------------------------------------------------- CLI hook
def preset_costs(preset: str) -> Tuple[Dict[str, DispatchCost],
                                       List[str]]:
    """Costs for one jaxpr-audit preset: runs the preset's audit
    driver (which captures the steady-state dispatch) and returns its
    priced dispatches plus any budget violations."""
    from skypilot_tpu.analysis import jaxpr_audit
    report = jaxpr_audit.run_preset(preset)
    return report.dispatch_costs, report.byte_budget_violations()


def _emit_budgets(names: Optional[List[str]] = None) -> str:
    """Dev helper: trace the presets and print a BYTE_BUDGETS literal
    (see the data's comment for the regeneration workflow)."""
    from skypilot_tpu.analysis import jaxpr_audit
    names = names or list(jaxpr_audit.DEFAULT_PRESETS)
    out: Dict[str, Dict[str, Dict[str, int]]] = {}
    for name in names:
        report = jaxpr_audit.PRESETS[name]()
        if report.dispatch_costs:
            out[name] = budget_from_costs(report.dispatch_costs)
    return json.dumps(out, indent=1, sort_keys=True)


if __name__ == '__main__':      # pragma: no cover - dev tool
    import sys
    if '--emit-budgets' in sys.argv:
        names = [a for a in sys.argv[1:] if not a.startswith('-')]
        print(_emit_budgets(names or None))
