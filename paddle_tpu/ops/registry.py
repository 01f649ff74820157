"""Op registry: op type -> (lower-to-jax, infer_shape, grad maker, grad lower).

Replaces the reference's kernel registry + dispatch
(reference: paddle/fluid/framework/op_registry.h:68,
operator.cc:908 OperatorWithKernel::RunImpl, grad_op_desc_maker.h) with a
TPU-first design:

* **lower**: emits jax/lax ops into the executor's trace instead of
  launching a device kernel.  One lowering serves every place (CPU/TPU) —
  XLA does the per-backend codegen, so there is no OpKernelType
  {place,dtype,layout,library} dimension at all.
* **infer_shape**: defaults to ``jax.eval_shape`` over the lowering itself,
  so compile-time shape inference is exactly XLA's — no hand-written
  per-op InferShape except for ops whose output shape depends on attrs in
  non-traceable ways (fill_constant, reshape2, ...).
* **grad**: program-level grad-op descs like the reference's GradOpMaker
  (so distribution transpilers can rewrite the backward program), but the
  grad *kernels* default to ``jax.vjp`` replay of the forward lowering.
  The replayed primal computation is deduplicated by XLA CSE inside the
  single jitted program, so this costs nothing at run time.  Ops with
  stateful forward (dropout) register custom grads.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import GRAD_SUFFIX, EMPTY_VAR_NAME, Operator, Block
from ..framework.dtype import VarType, to_numpy_dtype, convert_dtype
from ..utils import chaos as _chaos

_SENTINEL_DIM = 97  # stands in for -1 (dynamic batch) during eval_shape

OPS: Dict[str, "OpDef"] = {}
#: the parts of a model that ops have been traced under (``run_op``): the
#: names a scope path is searched for to say whose time an instruction is
PARTS: set = set()


class OpDef:
    __slots__ = (
        "type",
        "lower",
        "infer_shape",
        "grad_maker",
        "no_grad",
        "stateful",
        "host",
        "spec_hint",
        "_generic_grad",
    )

    def __init__(self, type):
        self.type = type
        self.lower: Optional[Callable] = None
        self.infer_shape: Optional[Callable] = None
        self.grad_maker: Optional[Callable] = None
        self.no_grad = False
        self.stateful = False  # uses rng; grad must not replay
        self.host = False      # runs on host (RPC/IO) — cannot be jitted
        # static-verifier declaration supplement
        # (framework/verifier.py op_spec): the verifier derives each
        # op's input/output slots and attr defaults from the lowering
        # source by AST scan; lowerings with dynamic slot/attr access
        # declare the remainder here — {"inputs": [...], "outputs":
        # [...], "optional_inputs": [...], "attrs": {name: default},
        # "open": True} (open skips slot/attr conformance entirely).
        self.spec_hint: Optional[dict] = None


def op(type: str, *, infer=None, no_grad: bool = False, stateful: bool = False,
       host: bool = False, spec_hint: Optional[dict] = None):
    """Decorator registering a forward lowering for ``type``."""

    def deco(fn):
        d = OPS.setdefault(type, OpDef(type))
        d.lower = fn
        d.infer_shape = infer
        d.no_grad = no_grad
        d.stateful = stateful
        d.host = host
        if spec_hint is not None:
            d.spec_hint = spec_hint
        return fn

    return deco


def is_host_op(type: str) -> bool:
    d = OPS.get(type)
    return bool(d is not None and d.host)


def op_contains_host(op_, _visiting=None) -> bool:
    """True when the op is host-only OR any sub-block it holds (cond /
    while bodies) contains a host op, transitively.  Control flow over
    host state (LoDTensorArray writes, RPC) must execute as a host loop
    driving device kernels — the reference While op's architecture
    (controlflow/while_op.cc: inner Executor per iteration) — because
    lax.while_loop/lax.cond need fixed-shape, device-resident carries.

    The sub-block walk is memoized per (op, program-version): the
    executor's segmentation and every analyze_state pass call this for
    each top-level op, and re-walking nested while/cond bodies each time
    is quadratic compile-time work on control-flow-heavy programs.  A
    visiting-set guards against self-referential block attrs (a block
    already on the recursion stack is skipped, not re-entered)."""
    if is_host_op(op_.type):
        return True
    top_level = _visiting is None
    version = None
    if top_level:
        blk = getattr(op_, "block", None)
        if blk is not None:
            try:
                version = blk.program._version
            except Exception:
                version = None
        cached = getattr(op_, "_host_scan_cache", None)
        if cached is not None and version is not None \
                and cached[0] == version:
            return cached[1]
        _visiting = set()

    from ..framework.core import Block

    result = False
    for k, v in op_.attrs.items():
        blk = None
        if isinstance(v, Block):
            blk = v
        elif isinstance(v, int) and k.endswith("block"):
            try:
                blk = op_.block.program.blocks[v]
            except Exception:
                blk = None
        if blk is None or id(blk) in _visiting:
            continue
        _visiting.add(id(blk))
        try:
            if any(op_contains_host(sub, _visiting) for sub in blk.ops):
                result = True
                break
        finally:
            _visiting.discard(id(blk))
    if top_level and version is not None:
        # only the top-level result is cached: a sub-result computed
        # under cycle pruning could be unsound to reuse standalone
        op_._host_scan_cache = (version, result)
    return result


def grad_maker(type: str):
    """Decorator registering a custom grad-desc maker for ``type``."""

    def deco(fn):
        OPS.setdefault(type, OpDef(type)).grad_maker = fn
        return fn

    return deco


def infer_for(type: str):
    def deco(fn):
        OPS.setdefault(type, OpDef(type)).infer_shape = fn
        return fn

    return deco


def get_op_def(type: str) -> OpDef:
    try:
        return OPS[type]
    except KeyError:
        raise NotImplementedError(f"op {type!r} is not registered") from None


def is_registered(type: str) -> bool:
    return type in OPS


# --------------------------------------------------------------------------
# Lowering context
# --------------------------------------------------------------------------
class LowerCtx:
    """What a lowering sees: slot values, attrs, rng, output binding."""

    def __init__(self, op: Operator, env: Dict[str, Any], block=None):
        self.op = op
        self.env = env
        self.block = block

    # ops that understand SelectedRows inputs natively (reference: the
    # optimizers' SelectedRows kernels, operators/optimizers/*); every
    # other op sees a densified array so correctness never depends on
    # per-op sparse support
    SPARSE_AWARE = frozenset({
        "sgd", "momentum", "adam", "adagrad", "sum", "scale",
        "clip_by_norm", "split_selected_rows", "merge_selected_rows",
        "get_tensor_from_selected_rows",
    })

    # inputs ---------------------------------------------------------------
    def ins(self, slot: str, missing_ok: bool = False) -> List[Any]:
        from ..framework.selected_rows import SelectedRows

        sparse_ok = self.op.type in self.SPARSE_AWARE
        out = []
        for n in self.op.inputs.get(slot, []):
            if n == EMPTY_VAR_NAME:
                out.append(None)
            else:
                v = self.env.get(n)
                if v is None and n not in self.env:
                    if missing_ok:
                        out.append(None)
                        continue
                    raise KeyError(
                        f"op {self.op.type}: input var {n!r} (slot {slot}) "
                        f"has no value — not initialized or not fed"
                    )
                if isinstance(v, SelectedRows) and not sparse_ok:
                    v = v.to_dense()
                out.append(v)
        return out

    def in_(self, slot: str):
        vals = self.ins(slot)
        return vals[0] if vals else None

    def has_input(self, slot: str) -> bool:
        ns = self.op.inputs.get(slot, [])
        return bool(ns) and ns[0] != EMPTY_VAR_NAME

    # outputs --------------------------------------------------------------
    def out_names(self, slot: str) -> List[str]:
        return self.op.outputs.get(slot, [])

    def set_out(self, slot: str, *vals):
        names = self.op.outputs.get(slot, [])
        # exact-type check: list/tuple SUBCLASSES (TensorArrayValue,
        # RankTableValue markers) are single host values, not a splat
        # across the slot's var names — an empty marker would otherwise
        # bind nothing
        if len(vals) == 1 and type(vals[0]) in (list, tuple):
            vals = tuple(vals[0])
        for n, v in zip(names, vals):
            if n != EMPTY_VAR_NAME:
                self.env[n] = v

    def has_output(self, slot: str) -> bool:
        ns = self.op.outputs.get(slot, [])
        return bool(ns) and ns[0] != EMPTY_VAR_NAME

    # attrs ----------------------------------------------------------------
    def attr(self, name: str, default=None):
        return self.op.attrs.get(name, default)

    # rng ------------------------------------------------------------------
    RNG_VAR = "@RNG_KEY@"

    def rng(self):
        """Split a fresh key off the threaded program rng state."""
        key = self.env.get(self.RNG_VAR)
        if key is None:
            key = jax.random.key(0)
        key, sub = jax.random.split(key)
        self.env[self.RNG_VAR] = key
        return sub


class _ReplayCtx:
    """LowerCtx stand-in used for vjp replay / eval_shape: takes explicit
    slot->values and captures outputs."""

    def __init__(self, ins_vals: Dict[str, List[Any]], attrs: Dict[str, Any],
                 out_arity: Dict[str, int], rng_key=None):
        self._ins = ins_vals
        self.attrs = attrs
        self._out_arity = out_arity
        self.outs: Dict[str, List[Any]] = {}
        self._rng_key = rng_key
        self.op = None
        self.env = {}

    def ins(self, slot):
        return list(self._ins.get(slot, []))

    def in_(self, slot):
        vals = self._ins.get(slot, [])
        return vals[0] if vals else None

    def has_input(self, slot):
        vals = self._ins.get(slot, [])
        return bool(vals) and vals[0] is not None

    def out_names(self, slot):
        return ["_"] * self._out_arity.get(slot, 1)

    def set_out(self, slot, *vals):
        if len(vals) == 1 and type(vals[0]) in (list, tuple):
            vals = tuple(vals[0])
        self.outs[slot] = list(vals)

    def has_output(self, slot):
        return self._out_arity.get(slot, 0) > 0

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def rng(self):
        if self._rng_key is None:
            self._rng_key = jax.random.key(0)
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub


# --------------------------------------------------------------------------
# Shape inference
# --------------------------------------------------------------------------
def infer_shape(op: Operator, block: Block):
    """Compile-time shape/dtype inference for ``op``'s outputs, run at
    append_op time (the analog of OpDesc-level InferShape in the
    reference, operator.h:442)."""
    d = OPS.get(op.type)
    if d is None:
        return  # unknown ops (feed/fetch/custom) carry no inference
    if op.type.endswith("_grad"):
        _infer_grad_shapes(op, block)
        return
    if d.infer_shape is not None:
        d.infer_shape(op, block)
        return
    if d.lower is None:
        return
    if d.host:
        # host lowerings touch real side state (queues, tables, env
        # arrays) — eval_shape-tracing them would leak tracers into it;
        # their shapes are data-dependent and resolved at run time
        return
    _generic_infer(op, block, d)


def _var_struct(var):
    shape = tuple(_SENTINEL_DIM if s == -1 else s for s in var.shape)
    return jax.ShapeDtypeStruct(shape, to_numpy_dtype(var.dtype))


def _generic_infer(op: Operator, block: Block, d: OpDef):
    ins_structs = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n == EMPTY_VAR_NAME:
                vals.append(None)
            else:
                v = block._find_var_recursive(n)
                if v is None:
                    return  # can't infer
                vals.append(_var_struct(v))
        ins_structs[slot] = vals
    out_arity = {s: len(ns) for s, ns in op.outputs.items()}

    def f(ins):
        ctx = _ReplayCtx(ins, op.attrs, out_arity, rng_key=jax.random.key(0))
        d.lower(ctx)
        return ctx.outs

    try:
        outs = jax.eval_shape(f, ins_structs)
    except Exception:
        return  # leave output shapes as declared; executor re-traces anyway
    for slot, vals in outs.items():
        for n, v in zip(op.outputs.get(slot, []), vals):
            if n == EMPTY_VAR_NAME or v is None:
                continue
            var = block._find_var_recursive(n)
            if var is None:
                continue
            if not hasattr(v, "shape") or not hasattr(v, "dtype"):
                continue  # structured value (e.g. SelectedRows pytree)
            shape = tuple(-1 if s == _SENTINEL_DIM else s for s in v.shape)
            var.shape = shape
            var.dtype = convert_dtype(v.dtype)


def _infer_grad_shapes(op: Operator, block: Block):
    """Grad var shape == forward var shape; cheap, no tracing."""
    for slot, names in op.outputs.items():
        for n in names:
            if n == EMPTY_VAR_NAME:
                continue
            # strip higher-order/accumulation rename segments
            # (X@GRAD@GRADX_0, X@GRAD@RENAME_1) down to X@GRAD
            base = n
            if "@RENAME" in base:
                base = base.split("@RENAME")[0]
            if "@GRADX" in base:
                base = base.split("@GRADX")[0]
            if not base.endswith(GRAD_SUFFIX):
                continue
            gvar = block._find_var_recursive(n)
            fvar = block._find_var_recursive(base[: -len(GRAD_SUFFIX)])
            if gvar is not None and fvar is not None:
                gvar.shape = fvar.shape
                gvar.dtype = fvar.dtype


# --------------------------------------------------------------------------
# Execution of one op against an env (used by executor trace & dygraph)
# --------------------------------------------------------------------------
def run_op(op: Operator, env: Dict[str, Any], block=None):
    d = get_op_def(op.type)
    if d.lower is None:
        raise NotImplementedError(f"op {op.type!r} has no lowering")
    ctx = LowerCtx(op, env, block)
    # named_scope stamps the op type into the HLO metadata, so device
    # profiles (jax.profiler / TensorBoard) attribute kernels back to
    # framework ops — the annotation-correlation analog of the
    # reference's CUPTI DeviceTracer (platform/device_tracer.cc).
    # An op that says which part of the model it serves (attr ``part``: the
    # serving decoders' builders) lies under that scope first: the compiled
    # program then says of every instruction, fusions included, whose time
    # it is (profiler.device_symbols).
    part = op.attrs.get("part")
    try:
        if part:
            PARTS.add(part)
            with jax.named_scope(part), jax.named_scope(op.type):
                d.lower(ctx)
        else:
            with jax.named_scope(op.type):
                d.lower(ctx)
    except Exception as e:
        _raise_with_callstack(op, e)
    if _chaos.nan_poison_target() is not None:
        # chaos nan_inject=NAME@K: this step's trace poisons the named
        # op's float outputs (utils/chaos.py; one module-global None
        # check per op when chaos is off)
        _nan_poison_outputs(op, env)
    return ctx


def _nan_poison_outputs(op: Operator, env: Dict[str, Any]):
    """Overwrite the op's float outputs with NaN when the armed chaos
    target names this op (by type — every instance — or by one of its
    output var names).  Probe ops are never poisoned: the measurement
    must observe the fault, not be it."""
    tgt = _chaos.nan_poison_target()
    if tgt is None:
        return
    if op.type != tgt and tgt not in op.output_arg_names:
        return
    if op.attrs.get("op_namescope") == "/numerics_probe/":
        return
    for name in op.output_arg_names:
        if name == EMPTY_VAR_NAME:
            continue
        v = env.get(name)
        if v is None:
            continue
        try:
            if jnp.issubdtype(jnp.result_type(v), jnp.inexact):
                env[name] = v * float("nan")
        except Exception:
            continue


def _raise_with_callstack(op: Operator, e: Exception):
    """Attach the op's Python build-site callstack to the error
    (reference: framework/op_call_stack.cc InsertCallStackInfo) —
    with whole-block jit the C++-style 'which op failed and where was
    it built' context is otherwise lost."""
    stack = op.attrs.get("op_callstack")
    where = ""
    if stack:
        where = "\n  op built at:\n    " + "\n    ".join(stack)
    note = f"[operator {op.type!r} error]{where}"
    if hasattr(e, "add_note"):  # py3.11+
        e.add_note(note)
        raise e
    raise type(e)(f"{e}\n{note}") from e


# --------------------------------------------------------------------------
# Grad machinery
# --------------------------------------------------------------------------
def has_grad(type: str) -> bool:
    d = OPS.get(type)
    if d is None:
        # lazily-materialized generic grads (vjp replay) are themselves
        # differentiable -> higher-order autodiff (double/triple grad)
        if type.endswith("_grad"):
            fwd = type[: -len("_grad")]
            return fwd in OPS and OPS[fwd].lower is not None
        return False
    if d.no_grad:
        # generic grads were registered with no_grad as a bookkeeping
        # default; they replay a differentiable lowering, so they grad
        if getattr(d, "_generic_grad", False):
            return True
        return False
    return True


def make_grad_ops(op: Operator, no_grad_names=frozenset()) -> List[dict]:
    """Return grad op descs (list of dicts with type/inputs/outputs/attrs).

    Mirrors the reference's per-op GradOpMaker contract
    (grad_op_desc_maker.h) so ``append_backward`` stays a program rewrite.
    """
    d = OPS.get(op.type)
    if d is None and op.type.endswith("_grad"):
        try:
            d = resolve(op.type)  # materialize the generic grad def
        except NotImplementedError:
            return []
    if d is None:
        return []
    if d.no_grad and not getattr(d, "_generic_grad", False):
        return []
    if d.grad_maker is not None:
        return d.grad_maker(op, no_grad_names)
    return default_grad_maker(op, no_grad_names)


def default_grad_maker(op: Operator, no_grad_names=frozenset()) -> List[dict]:
    inputs: Dict[str, List[str]] = {s: list(ns) for s, ns in op.inputs.items()}
    for slot, names in op.outputs.items():
        inputs[slot] = list(names)  # forward outputs available to custom grads
        inputs[slot + GRAD_SUFFIX] = [
            n + GRAD_SUFFIX if n != EMPTY_VAR_NAME else EMPTY_VAR_NAME
            for n in names
        ]
    outputs = {}
    for slot, names in op.inputs.items():
        outputs[slot + GRAD_SUFFIX] = [
            (n + GRAD_SUFFIX) if n not in no_grad_names and n != EMPTY_VAR_NAME
            else EMPTY_VAR_NAME
            for n in names
        ]
    attrs = dict(op.attrs)
    # full attr snapshot of the fwd op, including its own "__" keys —
    # needed when the fwd op is itself a grad op (double backward), whose
    # replay depends on its __fwd_type__/__fwd_out_slots__
    attrs["__fwd_attrs__"] = dict(op.attrs)
    attrs["__fwd_out_slots__"] = {s: len(ns) for s, ns in op.outputs.items()}
    attrs["__fwd_type__"] = op.type
    return [
        dict(type=op.type + "_grad", inputs=inputs, outputs=outputs, attrs=attrs)
    ]


def _is_diff_value(v) -> bool:
    if v is None:
        return False
    try:
        return jnp.issubdtype(jnp.result_type(v), jnp.inexact)
    except Exception:
        return False


def generic_grad_lower(ctx):
    """vjp-replay grad kernel shared by every ``*_grad`` op that has no
    custom lowering (see module docstring).  Works from a real LowerCtx
    or from a _ReplayCtx (grad-of-grad replays a grad op as the
    "forward" — double/triple backward)."""
    gop = ctx.op
    if gop is not None:
        attrs_all = gop.attrs
        in_slot_names = list(gop.inputs)
        op_type = gop.type
    else:  # replay context
        attrs_all = ctx.attrs
        in_slot_names = list(ctx._ins)
        op_type = attrs_all.get("__replay_type__", "")
    fwd_type = attrs_all.get("__fwd_type__") or op_type[: -len("_grad")]
    fdef = get_op_def(fwd_type)
    out_arity: Dict[str, int] = dict(attrs_all.get("__fwd_out_slots__") or {})

    # Forward input slots: everything except the fwd-output slots and
    # the cotangent slots the grad maker added.  (An endswith-@GRAD test
    # would be wrong for grad-of-grad, where the replayed fwd op itself
    # has legitimate @GRAD-named data inputs.)
    cot_slots = {s + GRAD_SUFFIX for s in out_arity}
    fwd_in_slots = [
        s for s in in_slot_names if s not in out_arity and s not in cot_slots
    ]
    ins_vals = {s: ctx.ins(s) for s in fwd_in_slots}

    # Partition into differentiable leaves and closed-over values.
    spec = []
    flat = []
    for s in fwd_in_slots:
        for i, v in enumerate(ins_vals[s]):
            if _is_diff_value(v):
                spec.append((s, i))
                flat.append(v)

    fwd_attrs = attrs_all.get("__fwd_attrs__")
    if fwd_attrs is None:
        fwd_attrs = {k: v for k, v in attrs_all.items()
                     if not k.startswith("__")}
    else:
        fwd_attrs = dict(fwd_attrs)
    # the replayed op needs to know its own type if IT is a grad op
    fwd_attrs["__replay_type__"] = fwd_type
    out_slot_order = sorted(out_arity)

    def f(flat_vals):
        merged = {s: list(vs) for s, vs in ins_vals.items()}
        for (s, i), v in zip(spec, flat_vals):
            merged[s][i] = v
        rctx = _ReplayCtx(merged, fwd_attrs, out_arity)
        fdef.lower(rctx)
        outs = []
        for slot in out_slot_order:
            vals = rctx.outs.get(slot, [])
            vals = list(vals) + [None] * (out_arity[slot] - len(vals))
            outs.extend(vals)
        return tuple(outs)

    primal_outs, vjp_fn = jax.vjp(f, flat)

    # Cotangents: grad-op inputs named "<slot>@GRAD"; missing -> zeros.
    # A cotangent VAR may be declared but never produced when the
    # downstream grad kernel doesn't emit it (e.g. Label@GRAD of a loss:
    # the label path ends in stop_gradient data) — treat that as zeros
    # too (missing_ok).
    cots = []
    k = 0
    for slot in out_slot_order:
        if (slot + GRAD_SUFFIX) in in_slot_names:
            if gop is not None:
                gvals = ctx.ins(slot + GRAD_SUFFIX, missing_ok=True)
            else:
                gvals = ctx.ins(slot + GRAD_SUFFIX)
        else:
            gvals = []
        for i in range(out_arity[slot]):
            primal = primal_outs[k]
            g = gvals[i] if i < len(gvals) else None
            if g is None:
                if primal is None:
                    cots.append(None)
                else:
                    cots.append(jnp.zeros(jnp.shape(primal), jnp.result_type(primal)))
            else:
                g = jnp.asarray(g)
                if primal is not None and g.dtype != jnp.result_type(primal):
                    g = g.astype(jnp.result_type(primal))
                cots.append(g)
            k += 1
    (grads,) = (vjp_fn(tuple(cots)),)
    grads = grads[0]

    # Bind grads to "<slot>@GRAD" outputs, aligned by spec.
    by_slot: Dict[str, Dict[int, Any]] = {}
    for (s, i), g in zip(spec, grads):
        by_slot.setdefault(s, {})[i] = g
    for s in fwd_in_slots:
        gslot = s + GRAD_SUFFIX
        if gop is not None:
            names = gop.outputs.get(gslot, [])
            if not names:
                continue
            for i, n in enumerate(names):
                v = by_slot.get(s, {}).get(i)
                if n != EMPTY_VAR_NAME and v is not None:
                    ctx.env[n] = v
        else:
            # replay (grad-of-grad): capture through the replay ctx
            vals = [by_slot.get(s, {}).get(i)
                    for i in range(len(ins_vals[s]))]
            ctx.set_out(gslot, vals)


class _GenericGradDispatch:
    """Every unregistered ``*_grad`` type resolves to the generic vjp grad."""


def resolve(type: str) -> OpDef:
    d = OPS.get(type)
    if d is not None and d.lower is not None:
        return d
    if type.endswith("_grad"):
        fwd = type[: -len("_grad")]
        if fwd in OPS and OPS[fwd].lower is not None:
            gd = OPS.setdefault(type, OpDef(type))
            if gd.lower is None:
                gd.lower = generic_grad_lower
                gd.no_grad = True
                gd._generic_grad = True
            return gd
    raise NotImplementedError(f"op {type!r} is not registered")


# make run_op/get_op_def use resolve so *_grad lazily materializes
def get_op_def(type: str) -> OpDef:  # noqa: F811
    return resolve(type)


def eager_call(type: str, ins_vals: Dict[str, List[Any]], attrs: Dict[str, Any],
               out_arity: Dict[str, int], rng_key=None) -> Dict[str, List[Any]]:
    """Run one op's lowering directly on values (dygraph optimizer path)."""
    d = get_op_def(type)
    rctx = _ReplayCtx(ins_vals, attrs, out_arity, rng_key=rng_key)
    d.lower(rctx)
    return rctx.outs
