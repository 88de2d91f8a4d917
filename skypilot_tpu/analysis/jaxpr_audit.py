"""graftcheck part B: runtime jaxpr + host-transfer auditor.

Proves, at runtime, the invariants the paged engine's performance
depends on (SageServe/ThunderServe-class serving wins hinge on a
sync-free, recompile-stable steady-state loop — PAPERS.md):

1. **Host-transfer freedom** — while an engine steps in steady state,
   no device value is read back to host except through the sanctioned
   :func:`skypilot_tpu.utils.host.host_sync` helper (the async
   pipeline's lagged readback). jax's native ``transfer_guard`` is a
   no-op on the zero-copy CPU backend CI runs on, so the interceptor
   patches the actual Python sync entry points instead
   (``ArrayImpl.__float__/__int__/__bool__/.item()/.tolist()``,
   ``jax.device_get``, ``np.asarray``/``np.array``) — backend
   independent by construction.
2. **Recompile stability** — the decode (and chunked-prefill) jit
   caches do not grow across repeated same-shaped calls; the observed
   static keys (horizon, sample) that form the recompile key are
   reported.
3. **Jaxpr hygiene** — the traced decode/prefill/forward jaxprs
   contain no host-callback primitives and no unexpected wide-dtype
   promotions (anything promoting to float64 on a TPU program is a
   bug); donation misses surface as captured compile warnings.

Pre-existing violations live in the same baseline mechanism as the AST
lint (the pytest gate hard-fails on new ones).
"""
from __future__ import annotations

import contextlib
import dataclasses
import traceback
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

_CALLBACK_PRIMS = {'pure_callback', 'io_callback', 'debug_callback',
                   'callback', 'outside_call', 'host_callback_call'}


@dataclasses.dataclass
class TransferEvent:
    kind: str          # '__float__' | 'item' | 'np.asarray' | ...
    sanctioned: bool   # made inside host_sync()/host_block()
    where: str         # innermost skypilot_tpu frame 'file:line (fn)'

    def __str__(self):
        tag = 'sanctioned' if self.sanctioned else 'UNSANCTIONED'
        return f'[{tag}] {self.kind} at {self.where}'


@dataclasses.dataclass
class AuditReport:
    name: str
    transfers: List[TransferEvent] = dataclasses.field(
        default_factory=list)
    compile_counts: Dict[str, Tuple[int, int]] = dataclasses.field(
        default_factory=dict)           # label -> (before, after)
    static_keys: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)           # observed decode static args
    callback_prims: List[str] = dataclasses.field(default_factory=list)
    promotions: List[str] = dataclasses.field(default_factory=list)
    f64_promotions: List[str] = dataclasses.field(default_factory=list)
    donation_warnings: List[str] = dataclasses.field(
        default_factory=list)
    # Collective-instruction census of the steady-state decode chain's
    # compiled HLO (mesh presets only): program label -> {op: count}.
    # The zero-resharding contract: no all-to-all / collective-permute
    # anywhere, and all-gathers bounded by the KNOWN decode set (the
    # tp-sharded argmax's tiny top-candidate gathers) — a pool- or
    # activation-shaped gather appearing here means a step's output
    # sharding stopped matching the next step's input sharding.
    collectives: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    allowed_all_gathers: int = 2
    # Per-label overrides: the dp>1 merge all-gathers ring-rows INSIDE
    # its shard_map body by design (dp pool replicas must not diverge
    # — see merge_rows_into_pool), so gang-shaped presets budget that
    # label explicitly instead of loosening the decode gate.
    allowed_all_gathers_by_label: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # Static per-dispatch cost model (analysis/costmodel.py): label ->
    # DispatchCost priced from the captured steady-state arg structs.
    # ``byte_budget`` is the preset's declared per-class read ceiling
    # (costmodel.BYTE_BUDGETS via run_preset); exceeding it fails ok()
    # with per-eqn byte attribution, same as a recompile would.
    preset: str = ''
    dispatch_costs: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    byte_budget: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    cost_error: str = ''

    @property
    def unsanctioned_transfers(self) -> List[TransferEvent]:
        return [t for t in self.transfers if not t.sanctioned]

    @property
    def recompiles(self) -> Dict[str, int]:
        return {k: after - before
                for k, (before, after) in self.compile_counts.items()}

    def collective_violations(self) -> List[str]:
        out = []
        for label, counts in self.collectives.items():
            for op in ('all-to-all', 'collective-permute'):
                if counts.get(op, 0):
                    out.append(f'{label}: {counts[op]} {op}')
            gathers = counts.get('all-gather', 0)
            allowed = self.allowed_all_gathers_by_label.get(
                label, self.allowed_all_gathers)
            if gathers > allowed:
                out.append(f'{label}: {gathers} all-gather(s) > '
                           f'{allowed} known')
        return out

    def byte_budget_violations(self) -> List[str]:
        """The byte-budget gate: only armed when a budget is declared
        for this preset. A declared budget with NO captured costs is a
        loud failure (the capture path regressed), never a silent
        pass."""
        if not self.byte_budget:
            return []
        if self.cost_error:
            return ['byte budget declared but the cost model failed: '
                    f'{self.cost_error}']
        if not self.dispatch_costs:
            return ['byte budget declared but no dispatch costs were '
                    'captured (decode never fired through the shim?)']
        from skypilot_tpu.analysis import costmodel
        return costmodel.check_budget(self.dispatch_costs,
                                      self.byte_budget)

    def ok(self) -> bool:
        return (not self.unsanctioned_transfers
                and not any(self.recompiles.values())
                and not self.callback_prims
                and not self.f64_promotions
                and not self.collective_violations()
                and not self.byte_budget_violations())

    def format(self) -> str:
        lines = [f'jaxpr audit: {self.name} — '
                 f'{"OK" if self.ok() else "VIOLATIONS"}']
        lines.append(f'  host transfers: {len(self.transfers)} total, '
                     f'{len(self.unsanctioned_transfers)} unsanctioned')
        for t in self.unsanctioned_transfers:
            lines.append(f'    {t}')
        for label, (before, after) in self.compile_counts.items():
            lines.append(f'  compile cache [{label}]: {before} -> '
                         f'{after} ({after - before} recompiles in '
                         'steady state)')
        if self.static_keys:
            keys = sorted({tuple(sorted(k.items()))
                           for k in self.static_keys})
            lines.append(f'  recompile key (observed static args): '
                         f'{[dict(k) for k in keys]}')
        if self.callback_prims:
            lines.append(f'  host-callback primitives: '
                         f'{self.callback_prims}')
        if self.promotions:
            lines.append(f'  dtype promotions: {self.promotions[:8]}'
                         + (' ...' if len(self.promotions) > 8 else ''))
        if self.f64_promotions:
            lines.append(f'  float64 promotions (BUG on TPU): '
                         f'{self.f64_promotions}')
        if self.donation_warnings:
            lines.append(f'  donation misses: {self.donation_warnings}')
        for label, counts in self.collectives.items():
            lines.append(f'  collectives [{label}]: '
                         f'{dict(sorted(counts.items())) or "none"}')
        for v in self.collective_violations():
            lines.append(f'  RESHARDING COLLECTIVE: {v}')
        for label, cost in self.dispatch_costs.items():
            lines.append(f'  cost [{label}]: {cost.read_total:,} B '
                         f'read, {cost.written_total:,} B written, '
                         f'{cost.flops:,} FLOPs')
        if self.cost_error:
            lines.append(f'  cost model error: {self.cost_error}')
        for v in self.byte_budget_violations():
            lines.append(f'  BYTE BUDGET: {v}')
        return '\n'.join(lines)

    def to_json(self) -> Dict[str, Any]:
        """Machine-readable report (the ``graftcheck --json`` schema;
        see docs/analysis.md)."""
        return {
            'name': self.name,
            'preset': self.preset,
            'ok': self.ok(),
            'transfers': {
                'total': len(self.transfers),
                'unsanctioned': [str(t) for t in
                                 self.unsanctioned_transfers],
            },
            'recompiles': dict(self.recompiles),
            'static_keys': self.static_keys,
            'callback_prims': list(self.callback_prims),
            'f64_promotions': list(self.f64_promotions),
            'collectives': {k: dict(v)
                            for k, v in self.collectives.items()},
            'collective_violations': self.collective_violations(),
            'dispatch_costs': {k: c.to_json()
                               for k, c in self.dispatch_costs.items()},
            'byte_budget': self.byte_budget,
            'byte_budget_violations': self.byte_budget_violations(),
            'cost_error': self.cost_error,
        }


# ------------------------------------------------------------------ intercept
def _caller_frame() -> str:
    """Innermost stack frame inside skypilot_tpu but outside this
    module / the host helper — where the sync was requested."""
    for frame in reversed(traceback.extract_stack(limit=40)):
        fn = frame.filename.replace('\\', '/')
        if ('skypilot_tpu' in fn and 'analysis/jaxpr_audit' not in fn
                and 'utils/host' not in fn):
            short = fn.split('skypilot_tpu/', 1)[-1]
            return f'{short}:{frame.lineno} ({frame.name})'
    return '<outside skypilot_tpu>'


@contextlib.contextmanager
def intercept_host_transfers(events: List[TransferEvent]):
    """Record every device->host materialization made while active.

    Patches the Python-level sync entry points on jax's ArrayImpl plus
    the module-level ``jax.device_get`` / ``np.asarray`` / ``np.array``
    names. Re-entrant internal calls (device_get materializes via
    ``_value``) are collapsed to one event via a depth guard. Events
    made inside host_sync()/host_block() are marked sanctioned."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.utils import host as host_lib

    array_t = type(jnp.zeros((), jnp.int32))
    depth = [0]

    def record(kind: str) -> None:
        if depth[0] == 0:
            events.append(TransferEvent(
                kind=kind, sanctioned=host_lib.in_sanctioned_sync(),
                where=_caller_frame()))

    def wrap_method(name: str):
        orig = getattr(array_t, name)

        def patched(self, *args, **kwargs):
            record(name)
            depth[0] += 1
            try:
                return orig(self, *args, **kwargs)
            finally:
                depth[0] -= 1
        return orig, patched

    def wrap_module(mod, name: str, kind: str, check_first_arg: bool):
        orig = getattr(mod, name)

        def patched(*args, **kwargs):
            is_dev = bool(args) and isinstance(args[0], array_t)
            if not check_first_arg or is_dev:
                record(kind)
            depth[0] += 1
            try:
                return orig(*args, **kwargs)
            finally:
                depth[0] -= 1
        return orig, patched

    method_names = ['__array__', '__float__', '__int__', '__bool__',
                    '__index__', 'item', 'tolist']
    saved_methods = {}
    for name in method_names:
        try:
            orig, patched = wrap_method(name)
            setattr(array_t, name, patched)
            saved_methods[name] = orig
        except (AttributeError, TypeError):
            continue
    saved_mods = []
    for mod, name, kind, chk in [
            (jax, 'device_get', 'jax.device_get', False),
            (np, 'asarray', 'np.asarray', True),
            (np, 'array', 'np.array', True)]:
        try:
            orig, patched = wrap_module(mod, name, kind, chk)
            setattr(mod, name, patched)
            saved_mods.append((mod, name, orig))
        except (AttributeError, TypeError):
            continue
    try:
        yield events
    finally:
        for name, orig in saved_methods.items():
            setattr(array_t, name, orig)
        for mod, name, orig in saved_mods:
            setattr(mod, name, orig)


# ------------------------------------------------------------------- jaxpr
def walk_jaxpr(jaxpr) -> Tuple[List[str], List[str]]:
    """Recursively walk a (closed) jaxpr: returns (callback primitive
    names, dtype-promotion descriptions from convert_element_type eqns
    that WIDEN the element type)."""
    import numpy as np
    callbacks: List[str] = []
    promotions: List[str] = []

    def visit(jx) -> None:
        jx = getattr(jx, 'jaxpr', jx)
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in _CALLBACK_PRIMS:
                callbacks.append(name)
            if name == 'convert_element_type' and eqn.invars:
                src = getattr(eqn.invars[0].aval, 'dtype', None)
                dst = eqn.params.get('new_dtype')
                if (src is not None and dst is not None
                        and np.dtype(dst).itemsize
                        > np.dtype(src).itemsize):
                    promotions.append(f'{src} -> {np.dtype(dst).name}')
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (list, tuple))
                            else [param]):
                    if hasattr(sub, 'eqns') or hasattr(sub, 'jaxpr'):
                        visit(sub)
    visit(jaxpr)
    return callbacks, promotions


def check_donation(jit_fn, *args, **kwargs) -> List[str]:
    """Compile ``jit_fn`` for the given arguments, capturing
    donation-miss warnings ('Some donated buffers were not usable',
    'buffer donations ... ignored')."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        jit_fn.lower(*args, **kwargs).compile()
    return [str(w.message) for w in caught
            if 'donat' in str(w.message).lower()]


def _cache_size(fn) -> int:
    getter = getattr(fn, '_cache_size', None)
    if getter is None:
        return -1
    try:
        return int(getter())
    except (TypeError, ValueError):    # jax-internal API drift
        return -1


def _jit_fns(fn) -> List[Any]:
    """The jitted function(s) behind ``fn``: itself if jitted, else any
    jitted functions captured in its closure (the paged engine's decode
    is a plain wrapper enqueueing two jitted programs)."""
    if hasattr(fn, '_cache_size'):
        return [fn]
    out = []
    for cell in getattr(fn, '__closure__', None) or ():
        obj = cell.cell_contents
        if hasattr(obj, '_cache_size'):
            out.append(obj)
    return out


# ------------------------------------------------------------------ presets
def _tiny_engine(speculate_k: int = 0, telemetry: bool = True,
                 kv_cache_dtype: Optional[str] = None,
                 mesh_tp: int = 0, mesh_dp: int = 0,
                 quantize: Optional[str] = None,
                 decode_steps_per_call: Optional[int] = None,
                 decode_impl: Optional[str] = None,
                 adapter_slots: int = 0, adapter_rank: int = 8,
                 model: str = 'tiny'):
    from skypilot_tpu.models import configs
    cfg = configs.get_config(model)
    extra: Dict[str, Any] = {}
    if quantize is not None:
        extra['quantize'] = quantize
    if adapter_slots:
        extra['adapter_slots'] = adapter_slots
        extra['adapter_rank'] = adapter_rank
    if decode_steps_per_call is not None:
        extra['decode_steps_per_call'] = decode_steps_per_call
    if decode_impl is not None:
        extra['decode_impl'] = decode_impl
    if mesh_tp and mesh_tp > 1:
        import jax

        from skypilot_tpu.parallel import mesh as mesh_lib
        need = mesh_tp * max(1, mesh_dp)
        if jax.device_count() < need:
            # LOUD: a single-device environment must fail the preset
            # with the fix in the message, not silently audit tp=1.
            raise RuntimeError(
                f'mesh preset needs {need} devices but only '
                f'{jax.device_count()} visible; run under '
                f'XLA_FLAGS=--xla_force_host_platform_device_count='
                f'{need} JAX_PLATFORMS=cpu (the graftcheck CLI '
                'does this re-exec automatically)')
        extra['mesh'] = mesh_lib.serving_mesh(tp=mesh_tp,
                                              dp=max(1, mesh_dp))
        extra['attn_impl'] = 'xla'
    from skypilot_tpu.inference.paged import PagedInferenceEngine
    # Chunk 16: the presets' prompts are longer than one chunk, so the
    # cursor chunks and the completing chunk both run.
    return PagedInferenceEngine(cfg, max_batch=4, max_seq=128,
                                prefill_chunk_tokens=16,
                                speculate_k=speculate_k,
                                kv_cache_dtype=kv_cache_dtype,
                                telemetry=telemetry, **extra)


def _drive(engine, prompts: List[List[int]], max_new: int = 8) -> None:
    for p in prompts:
        engine.add_request(list(p), max_new_tokens=max_new)
    engine.run_to_completion(horizon=8)


def _record_static_keys(engine, report: AuditReport,
                        capture: Optional[Dict[str, Any]] = None):
    """Shim the engine's decode fn to log the static args of each call
    — the (horizon, sample) tuple IS the recompile key the scheduler
    must keep stable; the engine passes them as trailing positionals.
    ``capture`` (optional dict) additionally records each call's full
    argument avals+shardings — what the mesh presets re-lower the
    steady-state decode chain from for the collective census."""
    inner = engine._decode_fn
    names = ('horizon', 'sample')

    def shim(*args, **kwargs):
        key = {k: kwargs[k] for k in names if k in kwargs}
        missing = [k for k in names if k not in key]
        if missing:
            tail = args[len(args) - len(missing):]
            key.update(dict(zip(missing, tail)))
        report.static_keys.append(key)
        if capture is not None:
            capture['args'] = _arg_structs(args)
        return inner(*args, **kwargs)

    engine._decode_fn = shim
    return inner


def _capture_spec_args(engine, capture: Dict[str, Any]) -> None:
    """Shim the spec jit getters so the verify/fused dispatch's args
    are captured for pricing: spec steady state never touches
    ``_decode_fn``, so the decode shim alone would leave speculative
    presets without dispatch costs. The spec jits take all-array args
    (sample and the page bucket are baked into the closure), so the
    capture is (arg structs, jit fn) — directly traceable."""
    for getter_name, label in (('_get_spec_verify', 'spec_verify'),
                               ('_get_spec_fused', 'spec_fused')):
        getter = getattr(engine, getter_name, None)
        if getter is None:
            continue

        def shim(*gargs, _getter=getter, _label=label, **gkw):
            fn = _getter(*gargs, **gkw)

            def wrapped(*args, **kwargs):
                capture[_label] = (_arg_structs(args), fn)
                return fn(*args, **kwargs)
            return wrapped

        setattr(engine, getter_name, shim)


def _capture_decode_args(engine, capture: Dict[str, Any]):
    """Minimal capture shim (no static-key recording) for audits that
    track dispatch counts through other entry points."""
    inner = engine._decode_fn

    def shim(*args, **kwargs):
        capture['args'] = _arg_structs(args)
        return inner(*args, **kwargs)

    engine._decode_fn = shim
    return inner


def _attach_costs(report: AuditReport, engine, inner,
                  capture: Dict[str, Any]) -> None:
    """Price the captured steady-state dispatches with the static cost
    model. Failures land in ``cost_error`` — fatal only for presets
    that declare a byte budget (see byte_budget_violations)."""
    try:
        from skypilot_tpu.analysis import costmodel
        report.dispatch_costs = costmodel.engine_dispatch_costs(
            engine, _jit_fns(inner), capture.get('args'))
        for label in ('spec_verify', 'spec_fused'):
            got = capture.get(label)
            if got is None:
                continue
            sargs, sfn = got
            classes = engine.decode_operand_classes(sargs)
            report.dispatch_costs[label] = costmodel.trace_dispatch(
                sfn, sargs, classes, label=label)
    except Exception as e:  # pragma: no cover - trace-shape drift
        report.cost_error = f'{type(e).__name__}: {e}'


def _arg_structs(args):
    """args -> ShapeDtypeStructs carrying mesh shardings. Committed
    NamedSharding args (params, cache, the pinned ring) keep their
    sharding; per-call host uploads (single-device placed) become
    unspecified, exactly how the real call presents them to jit.
    Structs, not arrays: donated buffers in ``args`` are dead by the
    time the census lowers from them."""
    import jax
    from jax.sharding import NamedSharding

    def struct(a):
        if isinstance(a, jax.Array):
            sh = (a.sharding if isinstance(a.sharding, NamedSharding)
                  else None)
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        return a

    return jax.tree.map(struct, args)


_COLLECTIVE_RE = None


def _count_collectives(hlo_text: str) -> Dict[str, int]:
    """Instruction-level census of communication ops in compiled HLO
    (matches the op at its defining instruction only, so async
    start/done pairs and textual mentions don't double-count)."""
    global _COLLECTIVE_RE
    import collections
    import re
    if _COLLECTIVE_RE is None:
        _COLLECTIVE_RE = re.compile(
            r'= \S+ (all-reduce|all-gather|all-to-all'
            r'|collective-permute|reduce-scatter)(?:-start)?\(')
    return dict(collections.Counter(
        m.group(1) for m in _COLLECTIVE_RE.finditer(hlo_text)))


def _decode_chain_collectives(engine, inner, captured
                              ) -> Dict[str, Dict[str, int]]:
    """Compile-and-census the steady-state decode chain from the last
    captured call's arg structs: the chain is (decode_steps, merge)
    behind a plain wrapper — the merge's ring operands are
    reconstructed at the pinned ``_ring_sh`` sharding (decode's output
    sharding IS merge's input sharding — the contract under test)."""
    import jax
    args = captured.get('args')
    if args is None:
        return {}
    out: Dict[str, Dict[str, int]] = {}
    for fn in _jit_fns(inner):
        try:
            txt = fn.lower(*args).compile().as_text()
            out['decode'] = _count_collectives(txt)
            continue
        except TypeError:
            pass        # the paged merge: different signature
        cache, table, lengths, active = args[1], args[2], args[4], args[9]
        # args[10]/args[11] are the adapter indices / vocab mask; the
        # merge consumes neither.
        horizon = args[12]
        cfg = engine.cfg
        ring = jax.ShapeDtypeStruct(
            (cfg.n_cache_layers, engine.max_batch, horizon, cfg.n_kv_heads,
             cfg.head_dim), cfg.dtype,
            sharding=getattr(engine, '_ring_sh', None))
        txt = fn.lower(cache, ring, ring, table, lengths,
                       active).compile().as_text()
        out['merge'] = _count_collectives(txt)
    return out


def audit_engine(rounds: int = 2, speculate_k: int = 0,
                 kv_cache_dtype: Optional[str] = None,
                 mesh_tp: int = 0, mesh_dp: int = 0,
                 warmup_rounds: int = 1,
                 merge_all_gathers: int = 0,
                 quantize: Optional[str] = None,
                 decode_impl: Optional[str] = None,
                 model: str = 'tiny') -> AuditReport:
    """Build a tiny engine, run one warmup wave (compiles allowed),
    then audit ``rounds`` identical same-shaped waves: every compile
    and every unsanctioned host transfer in those waves is a violation.

    The prompts are longer than one chunk, so the chunked-prefill path
    (cursor chunks + completing chunk) is exercised.
    ``speculate_k > 0`` drives the speculative propose→verify→commit
    steady state on REPETITIVE prompts (so proposals actually fire and
    acceptance varies per slot): the verify jit cache must stay bounded
    by the observed (k, sample, P) key set, and the only host
    readback per round is the sanctioned commit sync.

    ``mesh_tp >= 2`` audits the SHARDED serving path on a (tp,) CPU
    mesh (forced host platform device count): the same transfer/
    recompile gates, plus a collective census of the compiled decode
    chain — no all-to-all / collective-permute, and no all-gathers
    beyond the known decode set (the tp-sharded argmax's tiny top-
    candidate gathers). This is the zero-resharding contract: every
    step's pinned output shardings ARE the next step's input
    shardings, so a fat gather here means the chain broke."""
    spec_tag = f' + speculate_k={speculate_k}' if speculate_k else ''
    kv_tag = (f' + kv_cache_dtype={kv_cache_dtype}'
              if kv_cache_dtype else '')
    q_tag = f' + quantize={quantize}' if quantize else ''
    tp_tag = f' + tp={mesh_tp}' if mesh_tp else ''
    tp_tag += f' x dp={mesh_dp}' if mesh_dp else ''
    impl_tag = f' + decode_impl={decode_impl}' if decode_impl else ''
    impl_tag += f' + model={model}' if model != 'tiny' else ''
    report = AuditReport(
        name=f'paged engine (chunked prefill + decode'
             f'{spec_tag}{kv_tag}{q_tag}{tp_tag}{impl_tag})')
    engine = _tiny_engine(speculate_k,
                          kv_cache_dtype=kv_cache_dtype,
                          mesh_tp=mesh_tp, mesh_dp=mesh_dp,
                          quantize=quantize, decode_impl=decode_impl,
                          model=model)
    if speculate_k:
        # Repetitive prompts: the n-gram proposer matches, acceptance
        # is nonzero AND per-slot variable — the masked-commit shapes
        # are what must stay recompile-free.
        prompts = [[1, 2, 3, 4] * 7, [5, 6] * 11, [7, 8, 9] * 7]
    else:
        prompts = [[1, 2, 3] * 9, [4, 5] * 10, [7] * 21]  # >1 chunk
    for _ in range(max(1, warmup_rounds)):              # warmup: compiles
        _drive(engine, prompts)
    capture: Dict[str, Any] = {}
    inner = _record_static_keys(engine, report, capture)
    if speculate_k:
        _capture_spec_args(engine, capture)
    decode_jits = _jit_fns(inner)
    labels = {'decode': lambda: (sum(_cache_size(f)
                                     for f in decode_jits)
                                 if decode_jits else -1)}
    labels['prefill'] = lambda: len(engine._prefill_fns)
    spec_fns = engine._spec_verify_fns
    if speculate_k:
        # The verify program cache is keyed (k, sample, P) —
        # steady state must never grow it (per-slot acceptance rides
        # masked commits, not fresh shapes).
        labels['spec_verify'] = lambda: len(spec_fns)
    before = {k: get() for k, get in labels.items()}
    with intercept_host_transfers(report.transfers):
        for _ in range(rounds):
            _drive(engine, prompts)        # identical shapes: no compiles
    engine._decode_fn = inner
    if speculate_k:
        names = ('k', 'sample', 'P')
        report.static_keys.extend(
            dict(zip(names, key)) for key in sorted(spec_fns))
    report.compile_counts = {
        k: (before[k], get()) for k, get in labels.items()}
    if mesh_tp:
        report.collectives = _decode_chain_collectives(
            engine, inner, capture)
        if merge_all_gathers:
            report.allowed_all_gathers_by_label['merge'] = \
                merge_all_gathers
    _attach_costs(report, engine, inner, capture)
    return report


def audit_multistep(k: int = 4,
                    quantize: Optional[str] = None) -> AuditReport:
    """Multi-step on-device decode (``decode_steps_per_call=k``): the
    dispatch-amortization contract, audited.

    A paged engine with the knob pinned serves EQUAL-shape budget-bound
    requests (no eos/stop — early-free keeps every slot in lockstep),
    with ``max_new_tokens = 2k + 1``: one first token from prefill plus
    exactly ``2k`` decode tokens. Steady state must show, per round:

    - exactly TWO decode dispatches — ONE jitted call per k tokens
      (the whole point of the knob; a partial-k call or an extra
      tail dispatch fails the count);
    - every dispatch's static horizon == k (the jit key stays
      (k, sample, P) — a drifting horizon would both recompile and
      break the amortization claim);
    - the usual gates: zero unsanctioned d2h, zero steady-state
      recompiles."""
    q_tag = f', quantize={quantize}' if quantize else ''
    report = AuditReport(
        name=f'multi-step decode (decode_steps_per_call={k}{q_tag})')
    engine = _tiny_engine(quantize=quantize, decode_steps_per_call=k)
    prompts = [[3 + i, 5, 7, 9, 2, 4, 6, 8, 1, 3, 5, 7]
               for i in range(4)]               # equal shapes: lockstep
    max_new = 2 * k + 1

    def one_round() -> None:
        for p in prompts:
            engine.add_request(list(p), max_new_tokens=max_new)
        # Caller horizon 1: the KNOB must fuse k, not the caller.
        engine.run_to_completion(horizon=1)

    one_round()                                   # warmup: compiles
    capture: Dict[str, Any] = {}
    inner = _record_static_keys(engine, report, capture)
    decode_jits = _jit_fns(inner)
    labels = {'decode': lambda: (sum(_cache_size(f)
                                     for f in decode_jits)
                                 if decode_jits else -1),
              'prefill': lambda: len(engine._prefill_fns)}
    before = {name: get() for name, get in labels.items()}
    rounds = 2
    with intercept_host_transfers(report.transfers):
        for _ in range(rounds):
            one_round()
    engine._decode_fn = inner
    report.compile_counts = {
        name: (before[name], get()) for name, get in labels.items()}
    _attach_costs(report, engine, inner, capture)
    # ONE dispatch per k tokens: 2k decode tokens/round at lockstep =
    # exactly 2 dispatches/round. Recorded as an (expected, actual)
    # compile_counts pair so a mismatch fails ok() like a recompile.
    report.compile_counts['decode dispatches (ONE per '
                          f'{k} tokens)'] = (
        rounds * 2, len(report.static_keys))
    bad_h = [key for key in report.static_keys
             if key.get('horizon') != k]
    report.compile_counts['dispatches at horizon != k'] = (
        0, len(bad_h))
    return report


def audit_spec_multistep(k: int = 4, steps: int = 3) -> AuditReport:
    """In-scan speculative verify (``speculate_k`` x
    ``decode_steps_per_call``): the COMPOSED amortization contract.

    When both knobs are set, ``steps`` propose→verify→commit rounds
    fuse into ONE jitted dispatch (a lax.scan with the device n-gram
    proposer); greedy decode is byte-identical to the single-round
    path, so per-round commit counts — and therefore the number of
    verify rounds a wave needs — match a reference single-round
    engine exactly. Steady state must show:

    - fused dispatches == ceil(single-round verify dispatches /
      ``steps``) per wave: ONE dispatch per ``steps`` verify rounds,
      with no partial-round or tail dispatches beyond the final
      ceil;
    - ZERO single-round fallback dispatches (the pool reservation in
      ``_spec_can_fuse`` must hold at this scale — a fallback means
      the fusion silently degraded);
    - every fused jit key pins rounds == ``steps`` (a drifting rounds
      count would recompile AND break the amortization claim);
    - the usual gates: zero unsanctioned d2h (the stacked-commit
      host_sync is the ONE sanctioned readback per dispatch), zero
      steady-state growth of the spec program cache."""
    report = AuditReport(
        name=f'in-scan speculative verify (speculate_k={k} x '
             f'decode_steps_per_call={steps})')
    # Repetitive prompts so the n-gram proposer fires and acceptance
    # varies per slot (same shapes as the spec presets).
    prompts = [[1, 2, 3, 4] * 7, [5, 6] * 11, [7, 8, 9] * 7]
    max_new = 12

    def one_wave(engine) -> None:
        for p in prompts:
            engine.add_request(list(p), max_new_tokens=max_new)
        # Caller horizon 1: the KNOB must fuse the rounds, not the
        # caller's horizon loop.
        engine.run_to_completion(horizon=1)

    def count_calls(engine, name: str, counter: List[int]):
        orig = getattr(engine, name)

        def counting(*args, **kwargs):
            counter[0] += 1
            return orig(*args, **kwargs)
        setattr(engine, name, counting)

    # Reference: identical wave on a single-round verify engine — its
    # dispatch count is the ground truth the fusion must divide.
    ref = _tiny_engine(speculate_k=k)
    single = [0]
    count_calls(ref, '_spec_verify_call', single)
    one_wave(ref)                                 # warmup: compiles
    single[0] = 0
    one_wave(ref)                                 # counted wave

    engine = _tiny_engine(speculate_k=k,
                          decode_steps_per_call=steps)
    fused, fallback = [0], [0]
    count_calls(engine, '_spec_fused_call', fused)
    count_calls(engine, '_spec_verify_call', fallback)
    one_wave(engine)                              # warmup: compiles
    capture: Dict[str, Any] = {}
    inner = _capture_decode_args(engine, capture)
    _capture_spec_args(engine, capture)
    spec_fns = engine._spec_verify_fns
    before = len(spec_fns)
    fused[0] = fallback[0] = 0
    rounds = 2
    with intercept_host_transfers(report.transfers):
        for _ in range(rounds):
            one_wave(engine)
    engine._decode_fn = inner
    _attach_costs(report, engine, inner, capture)
    per_wave = -(-single[0] // steps)             # ceil
    report.compile_counts = {
        'spec program cache': (before, len(spec_fns)),
        f'fused dispatches (ONE per {steps} verify rounds; '
        f'{single[0]} single-round rounds/wave)': (
            rounds * per_wave, fused[0]),
        'single-round fallback dispatches': (0, fallback[0]),
    }
    names = ('mode', 'k', 'sample', 'P', 'rounds')
    report.static_keys.extend(
        dict(zip(names, key)) for key in sorted(spec_fns)
        if isinstance(key, tuple) and key and key[0] == 'fused')
    bad_r = [key for key in report.static_keys
             if key.get('rounds') != steps]
    report.compile_counts['fused keys at rounds != steps'] = (
        0, len(bad_r))
    return report


def audit_adapters() -> AuditReport:
    """Batched multi-LoRA decode under adapter-bank churn.

    A tiny engine with a 2-slot adapter bank serves waves where two
    slots decode under DIFFERENT adapters and one decodes the base
    model (zero-adapter row) — the gathered bank matmul rides inside
    the same fused programs. Between audited waves the wave's adapter
    pair rotates through four registered adapters, so every audited
    wave LRU-evicts both bank rows and loads two fresh ones. Steady
    state must show:

    - zero unsanctioned d2h and zero jit-cache growth across the
      churn waves: load/evict re-uploads bank rows (donated
      ``set_bank_row`` updates), it NEVER recompiles — the bank lives
      in params, so the (horizon, sample) jit key does not
      grow an adapter dimension;
    - the expected load/evict counts actually happened (2 loads + 2
      evictions per audited wave) — a silent cache hit would mean the
      churn, and therefore the gate, never ran;
    - the armed byte budget (costmodel BYTE_BUDGETS['adapters']): the
      decode dispatch's ``adapter_bank``-class HBM reads stay at
      bank-rows-touched bytes — the gather interpreter bills rows
      actually gathered, so a regression that reads the whole bank
      (or dequants it into activations) trips the ceiling."""
    import numpy as np

    from skypilot_tpu.models import multilora
    report = AuditReport(
        name='paged engine (chunked prefill + decode + multi-LoRA '
             'bank churn, 2 slots x 4 adapters)')
    engine = _tiny_engine(adapter_slots=2, adapter_rank=4)
    cfg = engine.cfg
    rng = np.random.default_rng(0)
    names = [f'ad{i}' for i in range(4)]
    for i, name in enumerate(names):
        tree = {}
        for t in multilora.default_targets(cfg):
            a_shape, b_shape = multilora.target_shapes(cfg, t, 4)
            tree[t] = {
                'a': rng.normal(0, 0.02, (cfg.n_layers,) + a_shape
                                ).astype(np.float32),
                'b': rng.normal(0, 0.02, (cfg.n_layers,) + b_shape
                                ).astype(np.float32)}
        engine.adapters.register(name, tree, scale=1.0 + i)
    prompts = [[1, 2, 3] * 9, [4, 5] * 10, [7] * 21]    # >1 chunk

    def wave(pair) -> None:
        # Two adapter rows + one base row per wave: the zero-adapter
        # slot rides the SAME gathered dispatch (where-select row).
        for p, adapter in zip(prompts, (pair[0], pair[1], None)):
            engine.add_request(list(p), max_new_tokens=8,
                               adapter=adapter)
        engine.run_to_completion(horizon=8)

    wave(names[0:2])           # warmup: compiles (incl. set_bank_row)
    wave(names[2:4])           # warmup: the evict/re-upload path
    capture: Dict[str, Any] = {}
    inner = _record_static_keys(engine, report, capture)
    decode_jits = _jit_fns(inner)
    labels = {'decode': lambda: (sum(_cache_size(f)
                                     for f in decode_jits)
                                 if decode_jits else -1),
              'prefill': lambda: len(engine._prefill_fns)}
    chunk_fns = getattr(engine, '_chunk_prefill_fns', None)
    if chunk_fns is not None:
        labels['chunk_prefill'] = lambda: len(chunk_fns)
    before = {k: get() for k, get in labels.items()}
    reg = engine.adapters
    loads0, evicts0 = reg.loads_total, reg.evictions_total
    rounds = 2
    with intercept_host_transfers(report.transfers):
        for i in range(rounds):
            # Rotate the pair: every audited wave evicts both rows.
            wave(names[0:2] if i % 2 == 0 else names[2:4])
    engine._decode_fn = inner
    report.compile_counts = {
        k: (before[k], get()) for k, get in labels.items()}
    report.compile_counts['adapter loads per churn wave (x2)'] = (
        rounds * 2, reg.loads_total - loads0)
    report.compile_counts['adapter evictions per churn wave (x2)'] = (
        rounds * 2, reg.evictions_total - evicts0)
    _attach_costs(report, engine, inner, capture)
    return report


def audit_llama_forward() -> AuditReport:
    """Static jaxpr audit of the llama training/prefill forward."""
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import configs, llama
    report = AuditReport(name='llama forward (jaxpr)')
    cfg = configs.get_config('tiny')
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    jx = jax.make_jaxpr(
        lambda p, t: llama.forward(p, t, cfg))(params, tokens)
    report.callback_prims, report.promotions = walk_jaxpr(jx)
    report.f64_promotions = [p for p in report.promotions
                             if 'float64' in p]
    try:
        from skypilot_tpu.analysis import costmodel
        classes = jax.tree.leaves(costmodel.classify_params(params))
        classes.append(costmodel.TABLE)           # the token ids
        report.dispatch_costs['forward'] = \
            costmodel.analyze_closed_jaxpr(jx, classes,
                                           label='forward')
    except Exception as e:  # pragma: no cover - trace-shape drift
        report.cost_error = f'{type(e).__name__}: {e}'
    return report


def audit_disagg() -> AuditReport:
    """Disaggregated prefill→decode handoff steady state (int8 wire).

    Two tiny paged engines play prefill worker and decode worker:
    each round the prefill engine admits + chunk-prefills a fixed
    prompt set, exports every request's KV snapshot at its first
    token (the sanctioned ``host_sync`` readback — the rows LEAVE the
    process by design), the wire codec round-trips it, and the decode
    engine ingests + decodes to completion. After a warmup round the
    audited rounds must show:

    - the DECODE worker compiles **zero prefill programs** — phase
      isolation is real, not just routing (its only programs are the
      ingest merge and the decode chain);
    - ingest causes **zero extra recompiles** (the ingest fn cache and
      decode jit caches stay at their warmup size) and **zero
      unsanctioned d2h transfers**;
    - the prefill worker's export adds no unsanctioned transfers
      either (every readback rides ``host_sync``)."""
    from skypilot_tpu.inference import kv_transfer
    report = AuditReport(
        name='disagg prefill→decode handoff (paged, int8 wire)')
    prefill = _tiny_engine(kv_cache_dtype='int8')
    decode = _tiny_engine(kv_cache_dtype='int8')
    prompts = [[1, 2, 3] * 9, [4, 5] * 10, [7] * 21]

    def one_round() -> None:
        rids = [prefill.add_request(list(p), max_new_tokens=24,
                                    hold=True) for p in prompts]
        first: Dict[int, int] = {}
        waiting = set(rids)
        while waiting:
            for rid, token, _fin in prefill.step(horizon=4):
                if rid in waiting:
                    first[rid] = token
                    waiting.discard(rid)
        for rid in rids:
            snap, _events = prefill.export_kv_snapshot(rid)
            assert snap is not None, f'export failed for {rid}'
            prefill.cancel(rid)
            snap = kv_transfer.decode_handoff(
                kv_transfer.encode_handoff(snap))
            decode.ingest_kv_snapshot(snap)
        decode.run_to_completion(horizon=8)
        prefill.run_to_completion(horizon=8)

    one_round()                                   # warmup: compiles
    capture: Dict[str, Any] = {}
    inner = _capture_decode_args(decode, capture)
    decode_jits = _jit_fns(inner)
    labels = {
        'decode-worker decode': lambda: (sum(
            _cache_size(f) for f in decode_jits)
            if decode_jits else -1),
        'decode-worker ingest': lambda: len(decode._ingest_fns),
        # Phase isolation: the decode worker must never compile a
        # prefill program — not at warmup, not ever. Recorded with a
        # ZERO baseline so any prefill compile (warmup included)
        # fails ok() as cache growth.
        'decode-worker prefill programs (must stay 0)': lambda: len(
            decode._prefill_fns),
        'prefill-worker export': lambda: len(prefill._export_fns),
        'prefill-worker prefill': lambda: len(prefill._prefill_fns),
    }
    before = {k: get() for k, get in labels.items()}
    before['decode-worker prefill programs (must stay 0)'] = 0
    with intercept_host_transfers(report.transfers):
        for _ in range(2):
            one_round()
    decode._decode_fn = inner
    report.compile_counts = {
        k: (before[k], get()) for k, get in labels.items()}
    _attach_costs(report, decode, inner, capture)
    return report


def audit_telemetry_parity() -> AuditReport:
    """Prove telemetry is free at the device boundary: a
    telemetry-ENABLED engine run performs zero unsanctioned d2h
    transfers and compiles exactly the same set of programs as a
    telemetry-OFF run (all measurement is host-side around
    dispatches). Per-mode steady-state recompiles and the on-vs-off
    jit-cache-size comparison both land in ``compile_counts``, so a
    parity break fails ``ok()`` like any other recompile."""
    report = AuditReport(name='telemetry parity (paged engine)')
    prompts = [[1, 2, 3] * 9, [4, 5] * 10, [7] * 21]

    def cache_total(engine) -> int:
        total = 0
        total += len(engine._prefill_fns) + len(engine._spec_verify_fns)
        decode_jits = _jit_fns(engine._decode_fn)
        total += sum(max(0, _cache_size(f)) for f in decode_jits)
        return total

    totals: Dict[bool, int] = {}
    for mode in (False, True):
        engine = _tiny_engine(telemetry=mode)
        _drive(engine, prompts)                   # warmup: compiles
        before = cache_total(engine)
        label = 'telemetry-on' if mode else 'telemetry-off'
        if mode:
            # Transfers recorded only for the telemetry-ON run: the
            # claim under test is that telemetry adds none.
            capture: Dict[str, Any] = {}
            inner = _capture_decode_args(engine, capture)
            with intercept_host_transfers(report.transfers):
                for _ in range(2):
                    _drive(engine, prompts)
            engine._decode_fn = inner
            _attach_costs(report, engine, inner, capture)
        else:
            for _ in range(2):
                _drive(engine, prompts)
        report.compile_counts[f'steady-state [{label}]'] = (
            before, cache_total(engine))
        totals[mode] = cache_total(engine)
    report.compile_counts['jit cache size (off vs on)'] = (
        totals[False], totals[True])
    return report


def audit_digest_export() -> AuditReport:
    """Prefix-digest export on the probe path, audited.

    ``hot_prefix_digest()`` ships the hottest prefix chains to the LB
    on every ``/metrics`` scrape (prefix-affinity routing). The
    contract that makes that free: the digest is built from the
    host-side heat tracker ONLY — no allocator matching, no device
    gather. Steady state with a scrape after EVERY wave (far hotter
    than the real ~1 Hz probe cadence) must show zero unsanctioned
    d2h transfers and zero jit-cache growth, and every scrape must
    return entries (the chains the waves registered) — an empty
    export means the heat tracker regressed, recorded as a
    compile-count mismatch so it fails ``ok()`` loudly."""
    report = AuditReport(
        name='hot-prefix digest export (paged probe path)')
    engine = _tiny_engine()
    prompts = [[1, 2, 3] * 9, [4, 5] * 10, [7] * 21]  # >= 1 full page
    _drive(engine, prompts)                       # warmup: compiles
    capture: Dict[str, Any] = {}
    inner = _record_static_keys(engine, report, capture)
    decode_jits = _jit_fns(inner)
    labels = {'decode': lambda: (sum(_cache_size(f)
                                     for f in decode_jits)
                                 if decode_jits else -1),
              'prefill': lambda: len(engine._prefill_fns)}
    before = {k: get() for k, get in labels.items()}
    rounds = 2
    scrapes: List[List[Dict[str, Any]]] = []
    with intercept_host_transfers(report.transfers):
        for _ in range(rounds):
            _drive(engine, prompts)
            scrapes.append(engine.hot_prefix_digest())
    engine._decode_fn = inner
    report.compile_counts = {
        k: (before[k], get()) for k, get in labels.items()}
    report.compile_counts['scrapes returning entries'] = (
        rounds, sum(1 for d in scrapes if d))
    _attach_costs(report, engine, inner, capture)
    return report


def audit_fleet_obs() -> AuditReport:
    """Fleet observability scrape path, audited.

    The fleet plane (telemetry/fleet.py) merges per-replica registry
    exports and completed-trace summaries on the controller and
    evaluates SLO burn rates — all of it host-side bookkeeping. The
    contract: a FULL fleet scrape after EVERY wave (registry
    ``export_wire()`` + trace-buffer drain + ``FleetAggregator``
    ingest + burn evaluation + a prometheus render, far hotter than
    the real probe cadence) adds zero unsanctioned d2h transfers and
    zero jit-cache growth to the engine hot loop. Every scrape must
    also land series in the aggregator and drain at least one
    completed trace — an empty scrape means the registry or the
    trace-buffer wiring regressed, recorded as a compile-count
    mismatch so it fails ``ok()`` loudly."""
    from skypilot_tpu.telemetry import clock as clock_lib
    from skypilot_tpu.telemetry import fleet as fleet_lib
    from skypilot_tpu.telemetry import registry as registry_lib
    from skypilot_tpu.telemetry import tracing
    report = AuditReport(
        name='fleet observability scrape (registry+trace -> aggregator)')
    engine = _tiny_engine(telemetry=True)
    prompts = [[1, 2, 3] * 9, [4, 5] * 10, [7] * 21]
    _drive(engine, prompts)                       # warmup: compiles
    capture: Dict[str, Any] = {}
    inner = _record_static_keys(engine, report, capture)
    decode_jits = _jit_fns(inner)
    labels = {'decode': lambda: (sum(_cache_size(f)
                                     for f in decode_jits)
                                 if decode_jits else -1),
              'prefill': lambda: len(engine._prefill_fns)}
    before = {k: get() for k, get in labels.items()}
    agg = fleet_lib.FleetAggregator(
        clock=clock_lib.now,
        slos=[fleet_lib.TierSLO(tier='latency', ttft_ms=2000.0,
                                target=0.99)])
    reg = registry_lib.get_registry()
    buf = tracing.get_trace_buffer()
    cursor = len(buf.snapshot())    # other presets' traces: skip them
    rounds = 2
    good_scrapes = 0
    with intercept_host_transfers(report.transfers):
        for _ in range(rounds):
            _drive(engine, prompts)
            cursor, traces = buf.summaries_since(cursor)
            wire = reg.export_wire()
            agg.ingest('audit-replica', {
                'clock': {'wall': clock_lib.now()},
                'registry': wire, 'traces': traces})
            rendered = agg.render_prometheus()
            if wire and traces and rendered:
                good_scrapes += 1
    engine._decode_fn = inner
    report.compile_counts = {
        k: (before[k], get()) for k, get in labels.items()}
    report.compile_counts['scrapes ingesting series+traces'] = (
        rounds, good_scrapes)
    report.compile_counts['aggregator sources'] = (
        1, agg.source_count())
    _attach_costs(report, engine, inner, capture)
    return report


PRESETS: Dict[str, Callable[[], AuditReport]] = {
    'paged': audit_engine,
    'paged-spec': lambda: audit_engine(speculate_k=4),
    'telemetry': audit_telemetry_parity,
    # int8 KV over bf16 weights — the DECOUPLED kv_cache_dtype path no
    # other preset drives (the coupled int8+int8 case is the chat cell):
    # quantize-on-write in every scan + fused-dequant reads must add
    # zero d2h transfers and zero steady-state jit-cache growth.
    'kv-int8': lambda: audit_engine(kv_cache_dtype='int8'),
    # int4 KV codes (packed nibble rows + absmax/7 scales): quantize-
    # on-write and fused in-kernel dequant reads must add zero d2h and
    # zero steady-state jit-cache growth — halving KV bytes must not
    # buy a single host round-trip.
    'kv-int4': lambda: audit_engine(kv_cache_dtype='int4'),
    # Cross-layer fused decode attention: the per-layer ring+current-
    # token merge folded into the kernel's final grid step. Same hot-
    # loop gates as 'paged' — the fusion must be free at the dispatch
    # boundary.
    'fused-attn': lambda: audit_engine(decode_impl='cross_layer'),
    # Sharded serving path (tp=2 CPU mesh): chunked prefill + decode +
    # ring merge over the head-sharded pool — zero steady-state
    # recompiles, zero unsanctioned d2h, and no resharding collectives
    # (no all-to-all; all-gathers bounded by the known sharded-argmax
    # pair). Needs >= 2 devices — the graftcheck CLI re-execs under a
    # forced host platform device count when short.
    'paged-tp': lambda: audit_engine(mesh_tp=2),
    # Gang-shaped mesh: (tp=2, dp=2) over 4 devices stands in for a
    # 2-process gang x 2 chips/process — on a pod the dp axis crosses
    # process boundaries, and the compiled HLO (and therefore this
    # collective census) is identical whether the devices are local or
    # remote: no all-to-all/collective-permute, no fat all-gathers in
    # the decode chain, merge collective-free ACROSS the process axis.
    # warmup_rounds=2: the dp-sharded pool crosses one page-table
    # bucket after its first full wave (cold-start shape, not a
    # steady-state leak — the cache is flat from the second wave on);
    # merge_all_gathers budgets the IN-BODY ring-row gathers the dp>1
    # shard_map merge performs by design (dp pool replicas must not
    # diverge).
    'paged-gang': lambda: audit_engine(mesh_tp=2, mesh_dp=2,
                                       warmup_rounds=2,
                                       merge_all_gathers=6),
    'paged-tp-int8': lambda: audit_engine(mesh_tp=2,
                                          kv_cache_dtype='int8'),
    # Disaggregated prefill→decode handoff: the decode worker's steady
    # state compiles ZERO prefill programs, and ingest adds zero
    # recompiles / unsanctioned d2h (int8 KV rides the wire codec).
    'disagg': audit_disagg,
    # int4 fused-dequant weights (packed codes + int8 KV via auto):
    # the unpack-inside-qeinsum path must add zero d2h transfers and
    # zero steady-state jit-cache growth on the hot loop.
    'int4': lambda: audit_engine(quantize='int4'),
    # Multi-step on-device decode: exactly ONE dispatch per k tokens,
    # every dispatch at static horizon k, zero recompiles/d2h.
    'multistep': audit_multistep,
    'int4-multistep': lambda: audit_multistep(quantize='int4'),
    # In-scan speculative verify: speculate_k x decode_steps_per_call
    # compose into ONE dispatch per `steps` verify rounds, pinned
    # against a single-round reference engine's dispatch count.
    'spec-multistep': audit_spec_multistep,
    # Batched multi-LoRA bank churn: loads/evicts between waves
    # re-upload bank rows with zero recompiles and zero unsanctioned
    # d2h; the gather matmul bills bank-rows-touched bytes (armed
    # byte budget on the adapter_bank class).
    'adapters': audit_adapters,
    # Prefix-digest export on the LB probe path: a hot_prefix_digest()
    # scrape after every wave adds zero unsanctioned d2h and zero
    # jit-cache growth (host-side heat tracker only), and every scrape
    # returns entries.
    'digest': audit_digest_export,
    # Fleet observability plane: a full controller-style scrape
    # (registry export + trace drain + aggregator ingest + SLO burn
    # eval + prometheus render) after every wave adds zero
    # unsanctioned d2h and zero jit-cache growth, and every scrape
    # lands series AND completed traces in the aggregator.
    'fleet-obs': audit_fleet_obs,
    'llama': audit_llama_forward,
    # Latent attention + dropless routed experts (tiny-glm) through the
    # paged engine as the chip runs it, decode through the latent paged
    # kernel: the latent pool, the grouped-matmul kernel's dynamic grid
    # and the experts-read count riding the token readback add zero
    # unsanctioned d2h and zero steady-state recompiles, and the decode
    # dispatch gathers no page of the pool.
    'paged-latent-moe': lambda: audit_engine(model='tiny-glm',
                                             decode_impl='pallas'),
    # A looped decoder (tiny-ouro: 3 passes over 2 layers, 6 cache
    # layers) through the paged engine as the chip runs it: the pass
    # scan around the layer scan adds no transfer and no recompile, and
    # the kernel reads the pool at pass * n_layers + layer.
    'paged-looped': lambda: audit_engine(model='tiny-ouro',
                                         decode_impl='pallas'),
}

# Presets that need a multi-device backend: preset -> device count.
# The CLI (and any other single-device driver) re-execs these under
# XLA_FLAGS=--xla_force_host_platform_device_count=<n>.
MULTI_DEVICE_PRESETS: Dict[str, int] = {
    'paged-tp': 2,
    'paged-tp-int8': 2,
    'paged-gang': 4,
}

DEFAULT_PRESETS: List[str] = [
    'paged', 'paged-spec', 'telemetry', 'kv-int8', 'kv-int4',
    'fused-attn', 'paged-tp', 'paged-tp-int8',
    'paged-gang', 'disagg', 'int4', 'multistep', 'int4-multistep',
    'spec-multistep', 'adapters', 'digest', 'fleet-obs', 'llama']


def run_preset(name: str) -> AuditReport:
    """Run one preset and arm its declared byte budget (the gate):
    presets listed in costmodel.BYTE_BUDGETS fail ok() when a captured
    dispatch's per-class HBM reads exceed the declared ceiling."""
    report = PRESETS[name]()
    report.preset = name
    try:
        from skypilot_tpu.analysis import costmodel
        report.byte_budget = costmodel.budget_for(name) or {}
    except Exception as e:  # pragma: no cover - import drift
        report.cost_error = report.cost_error or \
            f'{type(e).__name__}: {e}'
    return report


def run_presets(names: Optional[List[str]] = None) -> List[AuditReport]:
    names = names or list(DEFAULT_PRESETS)
    return [run_preset(n) for n in names]
