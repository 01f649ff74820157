"""Control-flow op lowerings: sub-block ops -> lax.cond / lax.while_loop.

Capability parity with reference: paddle/fluid/operators/controlflow/
(conditional_block_op.cc, while_op.cc — ops holding BLOCK attrs executed
by an inner Executor over sub-scopes).  TPU-native (SURVEY.md §7 hard-part
4): the sub-block is traced as a pure function of its carried values and
handed to XLA's structured control flow.  Every outer var a sub-block
reads is an explicit "Input" of the op (computed at build time by
layers/control_flow.py:_free_vars), so the executor's read-set analysis
and the vjp grad replay both see them — no hidden closure state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import grad_maker, infer_for, op
from ..framework.core import Block


def _resolve_block(ctx, attr_name) -> Block:
    blk = ctx.attr(attr_name)
    if isinstance(blk, Block):
        return blk
    return ctx.block.program.blocks[int(blk)]


def _run_block(blk: Block, env: dict):
    from . import registry

    for op_ in blk.ops:
        registry.run_op(op_, env, blk)
    return env


def _outer_env(ctx):
    names = ctx.attr("input_names", [])
    vals = ctx.ins("Input")
    return dict(zip(names, vals))


def _blocks_contain_host(blks) -> bool:
    from .registry import op_contains_host

    return any(op_contains_host(o) for b in blks for o in b.ops)


def _concrete_bool(v) -> bool:
    import numpy as _np

    return bool(_np.asarray(v).ravel()[0])


#: trace-time counters: how many while_loop forwards / grads lowered to
#: the static-trip lax.scan path this process (observable by tests — a
#: jaxpr-level check would couple tests to jax internals)
SCAN_STATS = {"forward": 0, "grad": 0}


def _const_from(blk, name, upto=None):
    """Static python value of `name` when its live producer is a literal
    fill_constant (no ValueTensor input), else None."""
    ops_ = blk.ops if upto is None else blk.ops[:upto]
    writers = [o for o in ops_ if name in o.output_arg_names]
    if not writers:
        return None
    o = writers[-1]
    if o.type != "fill_constant" or o.inputs.get("ValueTensor"):
        return None
    v = o.attrs.get("value")
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def _written_nonconst(blk, name):
    """True when any op in `blk` writes `name` other than a literal
    fill_constant — the value is not derivable statically."""
    return any(name in o.output_arg_names
               and (o.type != "fill_constant"
                    or o.inputs.get("ValueTensor"))
               for o in blk.ops)


def _static_trip_count(ctx, cb, bb):
    """Trip count of a while_loop as a python int when derivable from
    the graph (VERDICT weak #3 / ISSUE 4 satellite): cond is
    ``less_than(counter_carry, constant)``, the body advances the
    counter by a positive constant step (scale/bias, increment, or
    elementwise_add of a constant), and init/limit/step are integral
    literals (so the float counter accumulates exactly).  Returns None
    — keep lax.while_loop + host-replay grad — for anything dynamic.
    Gated by FLAGS_while_static_scan (0 restores the old lowering
    everywhere)."""
    from ..utils.flags import flag

    if not flag("while_static_scan", True):
        return None
    carry_names = ctx.attr("carry_names", [])
    cond_out = ctx.attr("cond_out_name")
    body_out_names = ctx.attr("body_out_names", [])
    if not carry_names or len(carry_names) != len(body_out_names):
        return None
    lt = None
    for o in cb.ops:
        if cond_out in o.output_arg_names:
            lt = o
    if lt is None or lt.type != "less_than":
        return None
    xn = lt.inputs.get("X", [None])[0]
    yn = lt.inputs.get("Y", [None])[0]
    if xn not in carry_names or not yn:
        return None
    # the limit must be loop-invariant: a carry (or anything the body
    # rewrites) changes across iterations, so its initial literal is
    # NOT the trip count — e.g. body doing n = n - 1 halves it
    if yn in carry_names or _written_nonconst(cb, yn) \
            or _written_nonconst(bb, yn):
        return None
    k = carry_names.index(xn)
    outer = ctx.block
    try:
        my_idx = outer.ops.index(ctx.op)
    except ValueError:
        return None
    limit = _const_from(cb, yn)
    if limit is None:
        limit = _const_from(outer, yn, upto=my_idx)
    init_names = ctx.op.inputs.get("X", [])
    if k >= len(init_names):
        return None
    init = _const_from(outer, init_names[k], upto=my_idx)
    # the body's counter update: last producer of the counter's slot
    prod = None
    for o in bb.ops:
        if body_out_names[k] in o.output_arg_names:
            prod = o
    step = None
    if prod is None:
        return None
    if prod.type == "scale" and prod.inputs.get("X", [None])[0] == xn \
            and float(prod.attrs.get("scale", 1.0)) == 1.0:
        step = float(prod.attrs.get("bias", 0.0))
    elif prod.type == "increment" and \
            prod.inputs.get("X", [None])[0] == xn:
        step = float(prod.attrs.get("step", 1.0))
    elif prod.type == "elementwise_add":
        a = prod.inputs.get("X", [None])[0]
        b = prod.inputs.get("Y", [None])[0]
        cn = b if a == xn else (a if b == xn else None)
        if cn is not None and cn not in carry_names \
                and not _written_nonconst(bb, cn):
            step = _const_from(bb, cn)
            if step is None:
                step = _const_from(outer, cn, upto=my_idx)
    if init is None or limit is None or step is None or step <= 0:
        return None
    if not (float(init).is_integer() and float(limit).is_integer()
            and float(step).is_integer()):
        return None  # non-integral float counters may drift vs the model
    i0, lim, st = int(init), int(limit), int(step)
    return max(0, -(-(lim - i0) // st))


def _host_while(cb, bb, base_env, carry_names, cond_out, body_out_names,
                init, on_step=None):
    """The ONE host while-loop protocol (forward host path and the grad
    op's replay both use it): evaluate cond on a copy of the live env,
    run the body, rebind carries positionally; ``on_step(carry)`` sees
    the carry BEFORE each executed step (trajectory recording)."""
    local = dict(base_env)
    local.update(zip(carry_names, init))
    while True:
        e = dict(local)
        _run_block(cb, e)
        if not _concrete_bool(e[cond_out]):
            break
        if on_step is not None:
            on_step([local[n] for n in carry_names])
        e = dict(local)
        _run_block(bb, e)
        local.update(
            {cn: e[bn] for cn, bn in zip(carry_names, body_out_names)})
    return [local[n] for n in carry_names]


@op("cond")
def _cond(ctx):
    """layers.cond: two sub-blocks, same output structure."""
    pred = jnp.reshape(ctx.in_("Cond"), ()).astype(bool)
    tb = _resolve_block(ctx, "true_block")
    fb = _resolve_block(ctx, "false_block")
    t_outs = ctx.attr("true_out_names", [])
    f_outs = ctx.attr("false_out_names", [])
    base_env = _outer_env(ctx)

    if _blocks_contain_host([tb, fb]):
        # host branch select (reference conditional_block_op.cc: inner
        # Executor runs only the taken block): required when a branch
        # holds host state ops (TensorArray writes) that lax.cond can't
        # trace.  The executor routes this op to the host segment, so
        # pred is concrete here.
        blk, outs_names = (tb, t_outs) if _concrete_bool(pred) else (fb, f_outs)
        local = dict(base_env)
        _run_block(blk, local)
        ctx.set_out("Out", [local[n] for n in outs_names])
        return

    def true_fn():
        local = dict(base_env)
        _run_block(tb, local)
        return tuple(local[n] for n in t_outs)

    def false_fn():
        local = dict(base_env)
        _run_block(fb, local)
        return tuple(local[n] for n in f_outs)

    outs = lax.cond(pred, true_fn, false_fn)
    ctx.set_out("Out", list(outs))


@infer_for("cond")
def _cond_infer(op_, block):
    t_outs = op_.attr("true_out_names", [])
    tb = op_.attr("true_block")
    tb = tb if isinstance(tb, Block) else block.program.blocks[int(tb)]
    for out_name, t_name in zip(op_.output("Out"), t_outs):
        src = tb._find_var_recursive(t_name)
        dst = block._find_var_recursive(out_name)
        if src is not None and dst is not None:
            dst.shape = src.shape
            dst.dtype = src.dtype


@op("while_loop")
def _while_loop(ctx):
    """layers.while_loop: functional carry over cond/body sub-blocks.
    Differentiable via the while_loop_grad host op below (forward
    replay + reverse vjp sweep); lax.while_loop itself is not
    reverse-differentiable, so fixed-length recurrence should still
    prefer the lax.scan-style rnn layers for speed."""
    cb = _resolve_block(ctx, "cond_block")
    bb = _resolve_block(ctx, "body_block")
    carry_names = ctx.attr("carry_names", [])
    cond_out = ctx.attr("cond_out_name")
    body_out_names = ctx.attr("body_out_names", [])
    base_env = _outer_env(ctx)

    carry_vals = ctx.ins("X")
    init = tuple(carry_vals)

    if _blocks_contain_host([cb, bb]):
        # Host loop driving device kernels — the reference While
        # architecture (while_op.cc: Executor per iteration).  Needed
        # for dynamic-length TensorArray carries (d2s list appends),
        # which mutate by object identity across iterations.
        ctx.set_out("Out", _host_while(
            cb, bb, base_env, carry_names, cond_out, body_out_names,
            list(carry_vals)))
        return

    tc = _static_trip_count(ctx, cb, bb)
    if tc is not None:
        # statically-known trip count: lax.scan instead of
        # lax.while_loop (reverse-differentiable by construction, no
        # conditional-root body to guard)
        SCAN_STATS["forward"] += 1

        def scan_body(carry, _):
            local = dict(base_env)
            local.update(zip(carry_names, carry))
            _run_block(bb, local)
            return tuple(local[n] for n in body_out_names), None

        outs, _ = lax.scan(scan_body, init, None, length=tc)
        ctx.set_out("Out", list(outs))
        return

    def cond_fun(carry):
        local = dict(base_env)
        local.update(zip(carry_names, carry))
        _run_block(cb, local)
        return jnp.reshape(local[cond_out], ()).astype(bool)

    def body_fun(carry):
        local = dict(base_env)
        local.update(zip(carry_names, carry))
        _run_block(bb, local)
        return tuple(local[n] for n in body_out_names)

    outs = lax.while_loop(cond_fun, body_fun, init)
    ctx.set_out("Out", list(outs))


def _scan_grad(ctx, bb, carry_names, body_out_names, free_names, free_vals,
               init, tc):
    """Static-trip while_loop backward: jax.vjp over a T-step lax.scan
    of the traced body.  Carry and free-var cotangents come from scan's
    transpose in one computation; integer carries (the loop counter)
    ride the scan as non-differentiable values and get zero grads."""

    def _is_diff(v):
        return hasattr(v, "dtype") and jnp.issubdtype(
            jnp.result_type(v), jnp.inexact)

    diff_c = [i for i, v in enumerate(init) if _is_diff(v)]
    diff_f = [i for i, v in enumerate(free_vals) if _is_diff(v)]
    gouts = ctx.ins("Out@GRAD", missing_ok=True)
    # final carries have the init's shapes/dtypes (scan invariance), so
    # missing cotangents zero-fill from init
    g_final = tuple(
        gouts[i] if (i < len(gouts) and gouts[i] is not None)
        else jnp.zeros_like(init[i]) for i in diff_c)

    def loop_fn(dc_vals, df_vals):
        free = list(free_vals)
        for j, i in enumerate(diff_f):
            free[i] = df_vals[j]
        carry0 = list(init)
        for j, i in enumerate(diff_c):
            carry0[i] = dc_vals[j]
        fenv = dict(zip(free_names, free))

        def sbody(carry, _):
            local = dict(fenv)
            local.update(zip(carry_names, carry))
            _run_block(bb, local)
            return tuple(local[n] for n in body_out_names), None

        final, _ = lax.scan(sbody, tuple(carry0), None, length=tc)
        return tuple(final[i] for i in diff_c)

    dvals = tuple(init[i] for i in diff_c)
    fvals = tuple(free_vals[i] for i in diff_f)
    _, vjp_fn = jax.vjp(loop_fn, dvals, fvals)
    d_carry, d_free = vjp_fn(g_final)

    gx = [None] * len(init)
    for j, i in enumerate(diff_c):
        gx[i] = d_carry[j]
    for i, v in enumerate(init):
        if gx[i] is None:
            gx[i] = jnp.zeros_like(v) if hasattr(v, "dtype") else None
    gf = [None] * len(free_vals)
    for j, i in enumerate(diff_f):
        gf[i] = d_free[j]
    for i, v in enumerate(free_vals):
        if gf[i] is None:
            gf[i] = jnp.zeros_like(v) if hasattr(v, "dtype") else None
    ctx.set_out("X@GRAD", gx)
    ctx.set_out("Input@GRAD", gf)


@op("while_loop_grad", host=True)
def _while_loop_grad(ctx):
    """Reverse pass for while_loop (reference: controlflow/while_op.cc
    WhileGradOp — inner executor over the grad block per step).
    TPU-native shape: REPLAY the forward host loop recording each
    step's carries (rematerialization instead of the reference's saved
    step scopes), then sweep backward applying jax.vjp of the traced
    body per iteration; free-var (parameter) cotangents accumulate
    across steps.  Integer carries (loop counters) ride the recorded
    trajectory and get no cotangent."""
    cb = _resolve_block(ctx, "cond_block")
    bb = _resolve_block(ctx, "body_block")
    if _blocks_contain_host([cb, bb]):
        raise NotImplementedError(
            "while_loop grad over host state (TensorArray writes) is "
            "not differentiable — use while_loop tensor carries or the "
            "rnn layers for trainable recurrence")
    carry_names = ctx.attr("carry_names", [])
    cond_out = ctx.attr("cond_out_name")
    body_out_names = ctx.attr("body_out_names", [])
    free_names = ctx.attr("input_names", [])
    free_vals = ctx.ins("Input")
    init = list(ctx.ins("X"))

    tc = _static_trip_count(ctx, cb, bb)
    if tc is not None:
        # static trip count: ONE scan-vjp computation — scan's native
        # transpose holds the trajectory as residuals — instead of the
        # per-iteration host replay + python reverse sweep
        SCAN_STATS["grad"] += 1
        _scan_grad(ctx, bb, carry_names, body_out_names, free_names,
                   free_vals, init, tc)
        return

    # ---- forward replay, recording the carry BEFORE each step ----------
    traj = []
    carry = _host_while(cb, bb, dict(zip(free_names, free_vals)),
                        carry_names, cond_out, body_out_names, init,
                        on_step=lambda c: traj.append(list(c)))

    def _is_diff(v):
        return hasattr(v, "dtype") and jnp.issubdtype(
            jnp.result_type(v), jnp.inexact)

    diff_c = [i for i, v in enumerate(init) if _is_diff(v)]
    diff_f = [i for i, v in enumerate(free_vals) if _is_diff(v)]

    # ---- incoming cotangents for the final carries ---------------------
    gouts = ctx.ins("Out@GRAD", missing_ok=True)
    g_full = [gouts[i] if (i < len(gouts) and gouts[i] is not None)
              else jnp.zeros_like(carry[i]) for i in range(len(carry))]
    g_carry = [g_full[i] for i in diff_c]
    g_free = [jnp.zeros_like(free_vals[i]) for i in diff_f]

    def step_diff(diff_carry_vals, diff_free_vals, nondiff_carry):
        local = dict(zip(free_names, free_vals))
        for j, i in enumerate(diff_f):
            local[free_names[i]] = diff_free_vals[j]
        cvals = list(nondiff_carry)
        for j, i in enumerate(diff_c):
            cvals[i] = diff_carry_vals[j]
        local.update(zip(carry_names, cvals))
        _run_block(bb, local)
        outs = [local[n] for n in body_out_names]
        return tuple(outs[i] for i in diff_c)

    # ---- reverse sweep -------------------------------------------------
    for t in range(len(traj) - 1, -1, -1):
        c_t = traj[t]
        dvals = tuple(c_t[i] for i in diff_c)
        fvals = tuple(free_vals[i] for i in diff_f)
        _, vjp_fn = jax.vjp(
            lambda dc, df: step_diff(dc, df, c_t), dvals, fvals)
        d_carry, d_free = vjp_fn(tuple(g_carry))
        g_carry = list(d_carry)
        g_free = [a + b for a, b in zip(g_free, d_free)]

    # ---- scatter back to full (diff + zero) grads ----------------------
    gx = [None] * len(init)
    for j, i in enumerate(diff_c):
        gx[i] = g_carry[j]
    for i, v in enumerate(init):
        if gx[i] is None:
            gx[i] = jnp.zeros_like(v) if hasattr(v, "dtype") else None
    gf = [None] * len(free_vals)
    for j, i in enumerate(diff_f):
        gf[i] = g_free[j]
    for i, v in enumerate(free_vals):
        if gf[i] is None:
            gf[i] = jnp.zeros_like(v) if hasattr(v, "dtype") else None
    ctx.set_out("X@GRAD", gx)
    ctx.set_out("Input@GRAD", gf)


@grad_maker("while_loop_grad")
def _while_loop_second_order(op_, no_grad_names=frozenset()):
    # only reached when a grad-of-grad pass actually NEEDS cotangents
    # through the loop (backward.py gates on known_grads): fail loudly
    # instead of silently dropping the loop's second-order contribution
    raise NotImplementedError(
        "second-order gradients through while_loop are not supported — "
        "rewrite the recurrence with the scan-based rnn layers")


@grad_maker("while_loop")
def _while_loop_grad_maker(op_, no_grad_names=frozenset()):
    from ..framework.core import EMPTY_VAR_NAME, GRAD_SUFFIX

    def g(names):
        return [n + GRAD_SUFFIX if n not in no_grad_names
                else EMPTY_VAR_NAME for n in names]

    return [dict(
        type="while_loop_grad",
        inputs={
            "X": op_.input("X"),
            "Input": op_.input("Input"),
            "Out" + GRAD_SUFFIX: [n + GRAD_SUFFIX
                                  for n in op_.output("Out")],
        },
        outputs={
            "X" + GRAD_SUFFIX: g(op_.input("X")),
            "Input" + GRAD_SUFFIX: g(op_.input("Input")),
        },
        attrs=dict(op_.attrs),
    )]


@infer_for("while_loop")
def _while_infer(op_, block):
    for out_name, in_name in zip(op_.output("Out"),
                                 op_.attr("carry_names", [])):
        src = block._find_var_recursive(in_name)
        dst = block._find_var_recursive(out_name)
        if src is not None and dst is not None:
            dst.shape = src.shape
            dst.dtype = src.dtype


@op("while")
def _while(ctx):
    """Old-style fluid While op: block updates the condition var itself.
    Carry = (cond, *carried vars); reference: controlflow/while_op.cc."""
    bb = _resolve_block(ctx, "sub_block")
    cond_name = ctx.attr("cond_name")
    carry_names = list(ctx.attr("carry_names", []))
    base_env = _outer_env(ctx)

    init = (ctx.in_("Cond"),) + tuple(ctx.ins("X"))

    if _blocks_contain_host([bb]):
        # host loop (see while_loop above); the block updates cond itself
        local = dict(base_env)
        local[cond_name] = ctx.in_("Cond")
        local.update(zip(carry_names, ctx.ins("X")))
        while _concrete_bool(local[cond_name]):
            e = dict(local)
            _run_block(bb, e)
            local[cond_name] = e[cond_name]
            local.update({n: e[n] for n in carry_names})
        ctx.set_out("CondOut", local[cond_name])
        ctx.set_out("XOut", [local[n] for n in carry_names])
        return

    def cond_fun(carry):
        return jnp.reshape(carry[0], ()).astype(bool)

    def body_fun(carry):
        local = dict(base_env)
        local[cond_name] = carry[0]
        local.update(zip(carry_names, carry[1:]))
        _run_block(bb, local)
        return (local[cond_name],) + tuple(local[n] for n in carry_names)

    outs = lax.while_loop(cond_fun, body_fun, init)
    # carried vars keep their own names (reference While mutates in place)
    ctx.set_out("CondOut", outs[0])
    ctx.set_out("XOut", list(outs[1:]))


@grad_maker("while")
def _while_grad_maker(op_, no_grad_names=frozenset()):
    # only reached when backward actually NEEDS cotangents through the
    # op (backward.py gates on known_grads): the in-place carry names
    # of the old-style While make grad plumbing ambiguous, so training
    # recurrence must use while_loop (differentiable above) or the
    # scan-based rnn layers — fail loudly instead of silently emitting
    # zero grads
    raise NotImplementedError(
        "gradients through the old-style While op are not supported — "
        "build the loop with layers.while_loop (differentiable) or the "
        "rnn layers")


@infer_for("while")
def _while_op_infer(op_, block):
    pass  # carried vars keep their declared specs


@op("select_input")
def _select_input(ctx):
    xs = ctx.ins("X")
    mask = jnp.reshape(ctx.in_("Mask"), ()).astype(jnp.int32)
    out = xs[0]
    for i in range(1, len(xs)):
        out = lax.cond(mask == i, lambda a=xs[i]: a, lambda b=out: b)
    ctx.set_out("Out", out)


# --------------------------------------------------------------------------
# LoDTensorArray ops (reference: controlflow/lod_array_length_op.cc,
# tensor_array_read_write_op.cc, tensor_array_to_tensor_op.cc).
# TPU-native scope: arrays are host-side python lists in the executor env
# (the executor's hybrid segmentation runs these between jit segments),
# which covers linear create->write->read/stack usage; inside a While /
# cond body the enclosing op falls back to a HOST loop (see
# _blocks_contain_host above) so dynamic-length arrays work there too —
# the reference While op's architecture (inner Executor per iteration).
# --------------------------------------------------------------------------
class TensorArrayValue(list):
    """Marker type for LOD_TENSOR_ARRAY values living in the env."""


@op("create_array", no_grad=True, host=True)
def _create_array(ctx):
    ctx.set_out("Out", TensorArrayValue())


@op("write_to_array", no_grad=True, host=True)
def _write_to_array(ctx):
    import numpy as _np

    arr = ctx.env.get(ctx.op.inputs["Array"][0])
    if not isinstance(arr, TensorArrayValue):
        arr = TensorArrayValue() if arr is None else TensorArrayValue(arr)
    x = ctx.in_("X")
    i = int(_np.asarray(ctx.in_("I")).ravel()[0])
    while len(arr) <= i:
        arr.append(None)
    arr[i] = x
    # output binds the SAME array name (reference mutates in place)
    ctx.env[ctx.op.outputs["Out"][0]] = arr


@op("read_from_array", no_grad=True, host=True)
def _read_from_array(ctx):
    import numpy as _np

    arr = ctx.env.get(ctx.op.inputs["X"][0])
    i = int(_np.asarray(ctx.in_("I")).ravel()[0])
    if not isinstance(arr, (list, TensorArrayValue)) or i >= len(arr) \
            or arr[i] is None:
        raise IndexError(
            f"read_from_array: index {i} not written "
            f"(len={len(arr) if isinstance(arr, list) else 'n/a'})")
    ctx.set_out("Out", arr[i])


@op("lod_array_length", no_grad=True, host=True)
def _lod_array_length(ctx):
    arr = ctx.env.get(ctx.op.inputs["X"][0])
    n = len(arr) if isinstance(arr, (list, TensorArrayValue)) else 0
    ctx.set_out("Out", jnp.asarray([n], jnp.int64))


@op("tensor_array_to_tensor", no_grad=True, host=True)
def _tensor_array_to_tensor(ctx):
    arr = ctx.env.get(ctx.op.inputs["X"][0])
    axis = ctx.attr("axis", 0)
    use_stack = ctx.attr("use_stack", False)
    vals = [v for v in (arr or []) if v is not None]
    if not vals:
        raise ValueError("tensor_array_to_tensor: empty array")
    if use_stack:
        out = jnp.stack(vals, axis=axis)
    else:
        out = jnp.concatenate(vals, axis=axis)
    ctx.set_out("Out", out)
    ctx.set_out("OutIndex", jnp.asarray(
        [jnp.shape(v)[axis] for v in vals], jnp.int32))


@op("tensor_array_pop", no_grad=True, host=True)
def _tensor_array_pop(ctx):
    """In-place pop returning the removed element.  The reference's
    dygraph_to_static composes this from slice + while
    (list_transformer.py tensor_array_pop); with host-resident arrays
    one op keeps it O(1) and the mutation visible by object identity."""
    arr = ctx.env.get(ctx.op.inputs["X"][0])
    if not isinstance(arr, (list, TensorArrayValue)) or not arr:
        raise IndexError("tensor_array_pop: empty or missing array")
    idx = int(ctx.attr("index", -1))
    ctx.set_out("Out", arr.pop(idx))
