"""Program rewrite toolkit: Pass registry + DAG pattern matcher.

Reference: paddle/fluid/framework/ir/pass.h:38 (Pass / PassRegistry),
ir/graph_pattern_detector.cc (PDNode / PDPattern / GraphPatternDetector),
ir/fuse_pass_base.h.  The reference rewrites an SSA graph of C++ nodes;
here the Program's op list IS the graph (vars link ops by name), so a
pass is a Python function over Blocks and a pattern is a list of op
templates with producer constraints — the same detector contract with
two orders of magnitude less machinery.

TPU-first note: XLA already fuses elementwise chains, so passes here are
about *semantic* rewrites XLA cannot do — mapping subgraphs onto Pallas
kernels (fused attention), deleting train-only ops for inference, dead
code elimination to cut trace/compile time.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .core import Block, Operator, Program
from .dtype import VarType

# --------------------------------------------------------------------------
# pass registry (reference: pass.h REGISTER_PASS)
# --------------------------------------------------------------------------
PASS_REGISTRY: Dict[str, type] = {}


class Pass:
    """Base pass: override apply_impl(program) -> program."""

    name: str = ""

    def apply(self, program: Program) -> Program:
        """Apply the pass; under ``FLAGS_verify_passes`` every
        application is bracketed by the static verifier
        (framework/verifier.py): snapshot the dataflow before, check
        for motion hazards / broken invariants after, and raise a
        VerifyError naming this pass, the op index and the hazard.
        Every current and future pass inherits the gate — the
        structural replacement for per-pass bit-identity arguments."""
        from . import verifier

        snap = verifier.snapshot(program) if verifier.enabled() else None
        out = self.apply_impl(program)
        out = out if out is not None else program
        if snap is not None:
            verifier.verify_pass(snap, out,
                                 self.name or type(self).__name__)
        return out

    def apply_impl(self, program: Program) -> Optional[Program]:
        raise NotImplementedError

    def set(self, **attrs):
        """Attribute injection like the reference's Pass::Set."""
        for k, v in attrs.items():
            setattr(self, k, v)
        return self


def register_pass(name: str):
    def deco(cls):
        cls.name = name
        PASS_REGISTRY[name] = cls
        return cls

    return deco


def get_pass(name: str, **attrs) -> Pass:
    try:
        cls = PASS_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"pass {name!r} is not registered; have {sorted(PASS_REGISTRY)}"
        ) from None
    return cls().set(**attrs)


class PassManager:
    """Ordered pass pipeline (reference: the analysis pass manager /
    build-strategy pass application loop)."""

    def __init__(self, passes: Sequence):
        self.passes = [p if isinstance(p, Pass) else get_pass(p)
                       for p in passes]

    def apply(self, program: Program) -> Program:
        for p in self.passes:
            program = p.apply(program)
        return program


# --------------------------------------------------------------------------
# graph utilities
# --------------------------------------------------------------------------
def producer_map(block: Block) -> Dict[str, Operator]:
    """var name -> last op writing it (SSA-enough for matched subgraphs)."""
    prod = {}
    for op_ in block.ops:
        for names in op_.outputs.values():
            for n in names:
                prod[n] = op_
    return prod


def consumer_count(block: Block) -> Dict[str, int]:
    cnt: Dict[str, int] = {}
    for op_ in block.ops:
        for names in op_.inputs.values():
            for n in names:
                cnt[n] = cnt.get(n, 0) + 1
    return cnt


def remove_ops(block: Block, ops: Sequence[Operator]):
    dead = set(id(o) for o in ops)
    block.ops[:] = [o for o in block.ops if id(o) not in dead]
    block.program._bump_version()


# --------------------------------------------------------------------------
# pattern matcher (reference: graph_pattern_detector.cc)
# --------------------------------------------------------------------------
class OpTemplate:
    """One PDNode: an op of `type` whose given input slots are fed by the
    named output of an earlier template ("producer.Slot")."""

    def __init__(self, name: str, type: str,
                 inputs: Optional[Dict[str, str]] = None,
                 predicate: Optional[Callable[[Operator], bool]] = None):
        self.name = name
        self.type = type
        self.inputs = inputs or {}
        self.predicate = predicate


def match_pattern(block: Block, templates: Sequence[OpTemplate],
                  allow_shared_intermediates: bool = False
                  ) -> List[Dict[str, Operator]]:
    """Find non-overlapping matches of the template DAG.

    Like GraphPatternDetector: templates are matched in order; each
    non-root template's constrained input slots must be fed by the var a
    previously-matched template produced.  Unless
    allow_shared_intermediates, every intermediate var (produced and
    consumed inside the match) must have no consumers outside the match —
    the detector's IsIntermediate() safety rule, which keeps a rewrite
    from deleting a value someone else reads.
    """
    prod = producer_map(block)
    cons = consumer_count(block)
    by_type: Dict[str, List[Operator]] = {}
    for op_ in block.ops:
        by_type.setdefault(op_.type, []).append(op_)

    matches: List[Dict[str, Operator]] = []
    used: set = set()

    def backtrack(i: int, bound: Dict[str, Operator]):
        if i == len(templates):
            matches.append(dict(bound))
            return True  # first match per root wins (greedy)
        t = templates[i]
        for cand in by_type.get(t.type, []):
            if id(cand) in used or any(id(cand) == id(o) for o in bound.values()):
                continue
            if t.predicate is not None and not t.predicate(cand):
                continue
            ok = True
            for slot, src in t.inputs.items():
                src_name, src_slot = src.split(".")
                src_op = bound.get(src_name)
                if src_op is None:
                    ok = False
                    break
                in_names = cand.inputs.get(slot, [])
                out_names = src_op.outputs.get(src_slot, [])
                if not in_names or not out_names or in_names[0] not in out_names:
                    ok = False
                    break
                if prod.get(in_names[0]) is not src_op:
                    ok = False  # someone overwrote the var in between
                    break
            if not ok:
                continue
            bound[t.name] = cand
            if backtrack(i + 1, bound):
                return True
            del bound[t.name]
        return False

    # try every candidate root, greedily claiming matched ops
    for root in list(by_type.get(templates[0].type, [])):
        if id(root) in used:
            continue
        if templates[0].predicate is not None and not templates[0].predicate(root):
            continue
        bound = {templates[0].name: root}
        if backtrack(1, bound):
            m = matches[-1]
            # intermediate-safety check
            if not allow_shared_intermediates and not _intermediates_private(
                    m, cons):
                matches.pop()
                continue
            used.update(id(o) for o in m.values())

    return matches


def _intermediates_private(match: Dict[str, Operator],
                           cons: Dict[str, int]) -> bool:
    ops = list(match.values())
    internal_inputs: Dict[str, int] = {}
    produced: Dict[str, Operator] = {}
    for o in ops:
        for names in o.outputs.values():
            for n in names:
                produced[n] = o
    for o in ops:
        for names in o.inputs.values():
            for n in names:
                if n in produced:
                    internal_inputs[n] = internal_inputs.get(n, 0) + 1
    for n, k in internal_inputs.items():
        if cons.get(n, 0) != k:
            return False  # consumed outside the match too
    return True


# --------------------------------------------------------------------------
# built-in passes
# --------------------------------------------------------------------------
@register_pass("remove_training_ops_pass")
class RemoveTrainingOpsPass(Pass):
    """Drop backward/optimize/lr-sched ops by op role (reference: the
    op-role filter inside Program._prune_with_input, io.py:1093) —
    always run before inference DCE, else in-place optimizer updates
    alias param names and reverse DCE drags training back in."""

    def apply_impl(self, program):
        from ..backward import OP_ROLE_KEY, OpRole

        mask = OpRole.Backward | OpRole.Optimize | OpRole.LRSched
        block = program.global_block()
        block.ops[:] = [
            op_ for op_ in block.ops
            if not (int(op_.attrs.get(OP_ROLE_KEY, 0)) & mask)
        ]
        program._bump_version()
        return program


@register_pass("dead_code_elimination_pass")
class DeadCodeEliminationPass(Pass):
    """Remove ops whose outputs are transitively unused (reference:
    ir/graph_helper + the inference prune pass).  `targets` (names) are
    kept alive; host/side-effect ops are always kept.  strict=True also
    removes persistable-writing ops not needed by the targets (the
    inference-prune behavior); the default keeps them (state updates are
    external effects in a training program)."""

    targets: Sequence[str] = ()
    strict: bool = False

    SIDE_EFFECT_OPS = frozenset({
        "print", "assert_op", "send", "recv", "send_barrier",
        "fetch_barrier", "checkpoint_notify", "listen_and_serv",
        "c_sync_calc_stream", "c_sync_comm_stream", "barrier",
    })

    def apply_impl(self, program):
        block = program.global_block()
        live = set(self.targets)
        keep: List[Operator] = []
        EMPTY = "@EMPTY@"
        for op_ in reversed(block.ops):
            out_names = [n for ns in op_.outputs.values() for n in ns
                         if n != EMPTY]
            is_live = any(n in live for n in out_names)
            if not is_live and not self.strict:
                # training graphs keep host side-effects; the strict
                # (inference) mode prunes them like the reference's
                # fetch-rooted prune does
                is_live = op_.type in self.SIDE_EFFECT_OPS
            # state-carrying ops (optimizers etc.) write their inputs in
            # place: output name == input name means external effect when
            # that var is persistable
            if not is_live and not self.strict:
                for n in out_names:
                    v = block._find_var_recursive(n)
                    if v is not None and getattr(v, "persistable", False):
                        is_live = True
                        break
            if is_live:
                keep.append(op_)
                for ns in op_.inputs.values():
                    live.update(n for n in ns if n != EMPTY)
        block.ops[:] = list(reversed(keep))
        program._bump_version()
        return program


@register_pass("delete_dropout_pass")
class DeleteDropoutPass(Pass):
    """Inference cleanup (reference: ir/delete_dropout_op_pass.cc):
    upscale_in_train dropout becomes identity (assign); downgrade_in_infer
    becomes scale(1-p)."""

    def apply_impl(self, program):
        block = program.global_block()
        for i, op_ in enumerate(list(block.ops)):
            if op_.type != "dropout":
                continue
            impl = op_.attrs.get("dropout_implementation", "downgrade_in_infer")
            p = op_.attrs.get("dropout_prob", 0.5)
            x = op_.inputs["X"]
            out = {"Out": op_.outputs["Out"]}
            idx = block.ops.index(op_)
            remove_ops(block, [op_])
            if impl == "upscale_in_train":
                block._insert_op(idx, "assign", inputs={"X": x}, outputs=out)
            else:
                block._insert_op(idx, "scale", inputs={"X": x}, outputs=out,
                                 attrs={"scale": 1.0 - p, "bias": 0.0})
        return program


def _is_scale_like(op_):
    return op_.type == "scale" and op_.attrs.get("bias", 0.0) in (0, 0.0)


def _is_qk_matmul(op_):
    """Q @ K^T with plain Q and no trailing alpha surprises beyond the
    scalar the rewrite folds into `scale`."""
    return (op_.attrs.get("transpose_Y", False)
            and not op_.attrs.get("transpose_X", False))


def _is_av_matmul(op_):
    """softmax(probs) @ V, untransposed, unscaled — the fused kernel has
    no epilogue scaling."""
    return (not op_.attrs.get("transpose_Y", False)
            and not op_.attrs.get("transpose_X", False)
            and op_.attrs.get("alpha", 1.0) in (1, 1.0))


def _is_last_axis_softmax(op_):
    return op_.attrs.get("axis", -1) in (-1, 3)


def _is_default_axis_add(op_):
    """The fused attention kernel applies BiasQK under plain numpy
    broadcasting; an elementwise_add with an explicit non-default axis
    broadcast would be silently reinterpreted, so only fuse the default
    (trailing-aligned) form."""
    return op_.attrs.get("axis", -1) == -1


@register_pass("fuse_multihead_attention_pass")
class FuseMultiheadAttentionPass(Pass):
    """Map the naive attention subgraph onto the Pallas flash-attention
    kernel (reference intent: ir/multihead_matmul_fuse_pass.cc — there it
    targets the cuda fused kernel; here `fused_multihead_attention`
    lowers to ops/pallas_kernels.py flash_attention).

    Matches, for Q/K/V of layout (batch, heads, seq, head_dim):
        qk = matmul(Q, K, transpose_Y=True)        [alpha = any]
        s  = scale(qk)                             [optional]
        m  = elementwise_add(s, mask)              [optional]
        sm = softmax(m)
        out = matmul(sm, V)
    and replaces the chain with one fused_multihead_attention op.
    """

    def apply_impl(self, program):
        block = program.global_block()
        # longest variant first so optional nodes are claimed when present
        variants = [
            [OpTemplate("qk", "matmul", predicate=_is_qk_matmul),
             OpTemplate("scale", "scale", {"X": "qk.Out"},
                        predicate=_is_scale_like),
             OpTemplate("mask", "elementwise_add", {"X": "scale.Out"},
                        predicate=_is_default_axis_add),
             OpTemplate("softmax", "softmax", {"X": "mask.Out"},
                        predicate=_is_last_axis_softmax),
             OpTemplate("av", "matmul", {"X": "softmax.Out"},
                        predicate=_is_av_matmul)],
            [OpTemplate("qk", "matmul", predicate=_is_qk_matmul),
             OpTemplate("scale", "scale", {"X": "qk.Out"},
                        predicate=_is_scale_like),
             OpTemplate("softmax", "softmax", {"X": "scale.Out"},
                        predicate=_is_last_axis_softmax),
             OpTemplate("av", "matmul", {"X": "softmax.Out"},
                        predicate=_is_av_matmul)],
            [OpTemplate("qk", "matmul", predicate=_is_qk_matmul),
             OpTemplate("mask", "elementwise_add", {"X": "qk.Out"},
                        predicate=_is_default_axis_add),
             OpTemplate("softmax", "softmax", {"X": "mask.Out"},
                        predicate=_is_last_axis_softmax),
             OpTemplate("av", "matmul", {"X": "softmax.Out"},
                        predicate=_is_av_matmul)],
            [OpTemplate("qk", "matmul", predicate=_is_qk_matmul),
             OpTemplate("softmax", "softmax", {"X": "qk.Out"},
                        predicate=_is_last_axis_softmax),
             OpTemplate("av", "matmul", {"X": "softmax.Out"},
                        predicate=_is_av_matmul)],
        ]
        fused = 0
        for templates in variants:
            for m in match_pattern(block, templates):
                self._rewrite(block, m)
                fused += 1
        self.fused_count = fused
        return program

    def _rewrite(self, block, m):
        qk, av = m["qk"], m["av"]
        q_name = qk.inputs["X"][0]
        k_name = qk.inputs["Y"][0]
        v_name = av.inputs["Y"][0]
        out = {"Out": av.outputs["Out"]}
        scale = qk.attrs.get("alpha", 1.0)
        if "scale" in m:
            scale = scale * m["scale"].attrs.get("scale", 1.0)
        inputs = {"Q": [q_name], "K": [k_name], "V": [v_name]}
        if "mask" in m:
            inputs["BiasQK"] = [m["mask"].inputs["Y"][0]]
        # insert where the AV matmul was: every value the fused op
        # reads (Q/K/V and the mask) is produced before av, which is not
        # guaranteed for qk (the mask may be computed after it)
        idx = block.ops.index(av)
        idx -= sum(1 for o in m.values() if block.ops.index(o) < idx)
        remove_ops(block, list(m.values()))
        block._insert_op(idx, "fused_multihead_attention",
                         inputs=inputs, outputs=out,
                         attrs={"scale": float(scale), "causal": False})


# --------------------------------------------------------------------------
# fused BN(+add)+activation passes (reference: ir/fuse_bn_act_pass.cc,
# ir/fuse_bn_add_act_pass.cc — the cudnn fused-BN rewrite; here the
# targets are ops/fused_ops.py fused_batch_norm_act /
# fused_bn_add_activation, whose closed-form backward avoids the
# vjp-replay residuals).  Unlike the attention pass these rewrite the
# forward AND its backward chain together, because by the time the
# executor sees a training program append_backward has already emitted
# relu_grad/elementwise_add_grad/batch_norm_grad ops that reference the
# unfused intermediates.
# --------------------------------------------------------------------------
def _consumers(block):
    cons: Dict[str, List[Operator]] = {}
    for op_ in block.ops:
        for names in op_.inputs.values():
            for n in names:
                cons.setdefault(n, []).append(op_)
    return cons


class _FuseBNActBase(Pass):
    #: vars the rewrite must not make unavailable (fetch targets)
    protected: Sequence[str] = ()

    def apply_impl(self, program):
        fused = 0
        for block in program.blocks:
            # vars referenced from ANY other block (while/cond carries,
            # sub-block free vars) are invisible to this block's consumer
            # map — never fuse away their producers
            external = set()
            for other in program.blocks:
                if other is block:
                    continue
                for op_ in other.ops:
                    for names in op_.inputs.values():
                        external.update(names)
                    for names in op_.outputs.values():
                        external.update(names)
            fused += self._apply_block(block, external)
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


@register_pass("fuse_bn_act_pass")
class FuseBNActPass(_FuseBNActBase):
    """batch_norm -> relu  (and its grad chain)  ==> fused_batch_norm_act."""

    def _apply_block(self, block, external=()):
        protected = set(self.protected) | set(external)
        fused = 0
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            for bn in list(block.ops):
                if bn.type != "batch_norm":
                    continue
                y0 = bn.outputs.get("Y", [None])[0]
                if not y0 or y0 in protected:
                    continue
                users = cons.get(y0, [])
                relu = next((o for o in users if o.type == "relu"
                             and o.inputs.get("X", [None])[0] == y0), None)
                if relu is None:
                    continue
                bn_grad = next((o for o in users if o.type == "batch_norm_grad"
                                and o.inputs.get("Y", [None])[0] == y0), None)
                relu_grad = next(
                    (o for o in users if o.type == "relu_grad"
                     and o.inputs.get("X", [None])[0] == y0), None)
                allowed = {id(relu), id(bn_grad), id(relu_grad)}
                if any(id(o) not in allowed for o in users):
                    continue
                y1 = relu.outputs["Out"][0]
                if (bn_grad is None) != (relu_grad is None):
                    continue  # half a backward: leave it alone
                if bn_grad is not None:
                    # relu_grad must feed exactly bn_grad's dY, and the
                    # rewrite stops producing dy0 — so it must not be a
                    # fetch target either
                    dy0 = relu_grad.outputs.get("X@GRAD", [None])[0]
                    if (dy0 in protected
                            or bn_grad.inputs.get("Y@GRAD", [None])[0] != dy0
                            or any(id(o) != id(bn_grad)
                                   for o in cons.get(dy0, []))):
                        continue
                    if relu_grad.inputs.get("Out", [None])[0] != y1:
                        continue
                # ---- rewrite forward
                idx = block.ops.index(bn)
                attrs = dict(bn.attrs)
                attrs["act_type"] = "relu"
                inputs = {k: list(v) for k, v in bn.inputs.items()}
                outputs = {k: list(v) for k, v in bn.outputs.items()}
                outputs["Y"] = [y1]
                remove_ops(block, [bn, relu])
                block._insert_op(idx, "fused_batch_norm_act",
                                 inputs=inputs, outputs=outputs, attrs=attrs)
                # ---- rewrite backward
                if bn_grad is not None:
                    gidx = block.ops.index(relu_grad)
                    ginputs = {
                        "X": list(bn.inputs["X"]),
                        "Y": [y1],
                        "Scale": list(bn.inputs["Scale"]),
                        "SavedMean": list(bn.outputs["SavedMean"]),
                        "SavedVariance": list(bn.outputs["SavedVariance"]),
                        "Y@GRAD": list(relu_grad.inputs["Out@GRAD"]),
                    }
                    goutputs = {
                        "X@GRAD": list(bn_grad.outputs.get("X@GRAD", [])),
                        "Scale@GRAD": list(bn_grad.outputs.get("Scale@GRAD", [])),
                        "Bias@GRAD": list(bn_grad.outputs.get("Bias@GRAD", [])),
                    }
                    remove_ops(block, [relu_grad, bn_grad])
                    block._insert_op(gidx, "fused_batch_norm_act_grad",
                                     inputs=ginputs, outputs=goutputs,
                                     attrs=dict(attrs))
                fused += 1
                changed = True
                break
        return fused


@register_pass("fuse_bn_add_act_pass")
class FuseBNAddActPass(_FuseBNActBase):
    """batch_norm -> elementwise_add -> relu (and grads) ==>
    fused_bn_add_activation.  Only same-shape adds with the default axis
    are fused (a broadcasting add is not the cudnn pattern and the fused
    kernel would reinterpret it)."""

    def _apply_block(self, block, external=()):
        protected = set(self.protected) | set(external)
        fused = 0
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            for bn in list(block.ops):
                if bn.type != "batch_norm":
                    continue
                y0 = bn.outputs.get("Y", [None])[0]
                if not y0 or y0 in protected:
                    continue
                users = cons.get(y0, [])
                add = next((o for o in users if o.type == "elementwise_add"
                            and o.attrs.get("axis", -1) == -1
                            and y0 in (o.inputs.get("X", [None])[0],
                                       o.inputs.get("Y", [None])[0])), None)
                if add is None:
                    continue
                bn_grad = next((o for o in users if o.type == "batch_norm_grad"
                                and o.inputs.get("Y", [None])[0] == y0), None)
                # the replayed elementwise_add_grad desc re-reads the
                # forward's X/Y, so it legitimately appears among y0's
                # (and ya's) consumers
                add_grad = next(
                    (o for o in users if o.type == "elementwise_add_grad"
                     and o.inputs.get("X", [None]) == add.inputs.get("X")
                     and o.inputs.get("Y", [None]) == add.inputs.get("Y")),
                    None)
                if any(id(o) not in {id(add), id(bn_grad), id(add_grad)}
                       for o in users):
                    continue
                # z = the other operand; shapes must match exactly
                xn, yn = add.inputs["X"][0], add.inputs["Y"][0]
                z = xn if yn == y0 else yn
                bn_slot_is_y = yn == y0
                vy, vz = block._find_var_recursive(y0), \
                    block._find_var_recursive(z)
                if (vy is None or vz is None or vy.shape is None
                        or list(vy.shape) != list(vz.shape)):
                    continue
                ya = add.outputs["Out"][0]
                if ya in protected:
                    continue
                ya_users = cons.get(ya, [])
                relu = next((o for o in ya_users if o.type == "relu"
                             and o.inputs.get("X", [None])[0] == ya), None)
                if relu is None:
                    continue
                relu_grad = next(
                    (o for o in ya_users if o.type == "relu_grad"
                     and o.inputs.get("X", [None])[0] == ya), None)
                if any(id(o) not in {id(relu), id(relu_grad), id(add_grad)}
                       for o in ya_users):
                    continue
                if bn_grad is not None or relu_grad is not None \
                        or add_grad is not None:
                    if bn_grad is None or relu_grad is None \
                            or add_grad is None:
                        continue  # half a backward: leave it alone
                    dya = relu_grad.outputs.get("X@GRAD", [None])[0]
                    if (dya in protected
                            or add_grad.inputs.get("Out@GRAD", [None])[0] != dya
                            or any(id(o) != id(add_grad)
                                   for o in cons.get(dya, []))):
                        continue
                    # add_grad's bn-side output must feed exactly bn_grad
                    bn_side = "Y@GRAD" if bn_slot_is_y else "X@GRAD"
                    z_side = "X@GRAD" if bn_slot_is_y else "Y@GRAD"
                    dy0 = add_grad.outputs.get(bn_side, [None])[0]
                    if (dy0 is None or dy0 in protected
                            or bn_grad.inputs.get("Y@GRAD", [None])[0] != dy0
                            or any(id(o) != id(bn_grad)
                                   for o in cons.get(dy0, []))):
                        continue
                    dz = add_grad.outputs.get(z_side, [None])[0]
                    if relu_grad.inputs.get("Out", [None])[0] != \
                            relu.outputs["Out"][0]:
                        continue
                y1 = relu.outputs["Out"][0]
                # ---- rewrite forward
                idx = block.ops.index(relu)
                idx -= sum(1 for o in (bn, add)
                           if block.ops.index(o) < idx)
                attrs = dict(bn.attrs)
                attrs["act_type"] = "relu"
                inputs = {k: list(v) for k, v in bn.inputs.items()}
                inputs["Z"] = [z]
                outputs = {k: list(v) for k, v in bn.outputs.items()}
                outputs["Y"] = [y1]
                remove_ops(block, [bn, add, relu])
                block._insert_op(idx, "fused_bn_add_activation",
                                 inputs=inputs, outputs=outputs, attrs=attrs)
                # ---- rewrite backward
                if bn_grad is not None:
                    gidx = block.ops.index(relu_grad)
                    ginputs = {
                        "X": list(bn.inputs["X"]),
                        "Y": [y1],
                        "Scale": list(bn.inputs["Scale"]),
                        "SavedMean": list(bn.outputs["SavedMean"]),
                        "SavedVariance": list(bn.outputs["SavedVariance"]),
                        "Y@GRAD": list(relu_grad.inputs["Out@GRAD"]),
                    }
                    goutputs = {
                        "X@GRAD": list(bn_grad.outputs.get("X@GRAD", [])),
                        "Scale@GRAD": list(bn_grad.outputs.get("Scale@GRAD", [])),
                        "Bias@GRAD": list(bn_grad.outputs.get("Bias@GRAD", [])),
                        "Z@GRAD": [dz] if dz else [],
                    }
                    remove_ops(block, [relu_grad, add_grad, bn_grad])
                    block._insert_op(gidx, "fused_bn_add_activation_grad",
                                     inputs=ginputs, outputs=goutputs,
                                     attrs=dict(attrs))
                fused += 1
                changed = True
                break
        return fused


# --------------------------------------------------------------------------
# profile-ranked epilogue fusion (r14) — the Pallas fusion layer's IR
# half.  utils/cost_model.find_fusion_chains supplies the structural
# matches (so ranking and rewrite can never disagree), and
# rank_fusion_candidates orders them by modeled+measured memory-traffic
# savings; this pass rewrites them best-first onto the fused ops in
# ops/fused_ops.py (fused_conv_bn_act / fused_matmul_bias_act), forward
# and the matching grad chain together — the same fwd+bwd-paired shape
# as fuse_bn_act_pass, per the README "writing a safe IR pass"
# checklist.  Gated by FLAGS_tpu_fuse in the executor pipeline, applied
# AFTER the NHWC layout pass (the fused ops carry one layout attr and
# both pass orders are verifier-clean).
# --------------------------------------------------------------------------
@register_pass("fuse_epilogue_pass")
class FuseEpiloguePass(Pass):
    """conv2d -> batch_norm/fused_batch_norm_act/fused_bn_add_activation
    (+ grads) ==> fused_conv_bn_act;  mul/matmul -> elementwise_add(1-D
    bias) -> act (+ grads) ==> fused_matmul_bias_act."""

    #: vars the rewrite must not make unavailable (fetch targets)
    protected: Sequence[str] = ()

    #: attrs the fused_conv_bn_act lowering reads, by source op
    _CONV_ATTRS = ("strides", "paddings", "dilations", "groups",
                   "padding_algorithm", "data_format")
    _BN_ATTRS = ("momentum", "epsilon", "is_test", "use_global_stats")

    def apply_impl(self, program):
        from ..utils import cost_model as cmod

        block = program.global_block()
        protected = set(self.protected)
        for other in program.blocks:
            if other is block:
                continue
            for op_ in other.ops:
                for names in op_.inputs.values():
                    protected.update(names)
                for names in op_.outputs.values():
                    protected.update(names)
        # calibrate the cost model ONCE per application (the profile is
        # fixed for the whole rewrite; only the chain set changes as
        # rewrites land, so the per-iteration re-rank reuses this cm)
        profile = cmod.measured_profile()
        cm = cmod.CostModel()
        if profile:
            _, modeled = cmod.backward_timeline(block.ops, block, cm)
            cm = cm.calibrated(profile["step_s"], modeled)
        fused = 0
        self.report: List[dict] = []
        changed = True
        while changed:
            changed = False
            # re-rank after every rewrite: a fusion changes the consumer
            # structure the next match must see
            for cand in cmod.rank_fusion_candidates(program,
                                                    profile=profile, cm=cm):
                if cand["saved_bytes"] <= 0:
                    continue
                if self._rewrite(block, cand["chain"], protected):
                    fused += 1
                    self.report.append(
                        {k: cand[k] for k in
                         ("kind", "ops", "out", "saved_bytes", "est_saved_s",
                          "measured_epilogue_s", "score_s", "calibrated")})
                    changed = True
                    break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _merged_role_attrs(*grad_ops):
        out = {}
        roles = [o.attrs.get("op_role") for o in grad_ops
                 if o is not None and "op_role" in o.attrs]
        if roles:
            out["op_role"] = roles[0]
        rv: List[str] = []
        for o in grad_ops:
            if o is not None:
                rv.extend(o.attrs.get("op_role_var", []) or [])
        if rv:
            out["op_role_var"] = rv
        return out

    def _rewrite(self, block, ch, protected):
        if ch["kind"] == "conv_bn_act":
            return self._rewrite_conv(block, ch, protected)
        return self._rewrite_matmul(block, ch, protected)

    def _rewrite_conv(self, block, ch, protected):
        conv, bn = ch["conv"], ch["bn"]
        conv_grad, bn_grad = ch["conv_grad"], ch["bn_grad"]
        act_op, act_grad = ch["act_op"], ch["act_grad"]
        # vars the rewrite stops producing must not be fetch targets
        gone = set()
        if bn_grad is not None:
            gone.add(ch["dconv"])
        if ch.get("bn_y"):
            gone.add(ch["bn_y"])
            if act_grad is not None:
                gone.add(ch["bn_y"] + "@GRAD")
        if gone & protected:
            return False
        attrs = {k: conv.attrs[k] for k in self._CONV_ATTRS
                 if k in conv.attrs}
        attrs.update({k: bn.attrs[k] for k in self._BN_ATTRS
                      if k in bn.attrs})
        attrs["act_type"] = ch["act"]
        if conv.type == "depthwise_conv2d":
            attrs["depthwise"] = True
        if "op_role" in bn.attrs:
            attrs["op_role"] = bn.attrs["op_role"]
        inputs = {
            "Input": list(conv.inputs["Input"]),
            "Filter": list(conv.inputs["Filter"]),
            "Scale": list(bn.inputs["Scale"]),
            "Bias": list(bn.inputs["Bias"]),
            "Mean": list(bn.inputs["Mean"]),
            "Variance": list(bn.inputs["Variance"]),
        }
        if ch["z"]:
            inputs["Z"] = [ch["z"]]
        outputs = {
            "Output": [ch["out"]],
            "ConvOut": [ch["conv_out"]],
            "MeanOut": list(bn.outputs.get("MeanOut", [])),
            "VarianceOut": list(bn.outputs.get("VarianceOut", [])),
            "SavedMean": list(bn.outputs.get("SavedMean", [])),
            "SavedVariance": list(bn.outputs.get("SavedVariance", [])),
        }
        dead_fwd = [conv, bn] + ([act_op] if act_op is not None else [])
        last = act_op if act_op is not None else bn
        idx = block.ops.index(last)
        idx -= sum(1 for o in dead_fwd[:-1] if block.ops.index(o) < idx)
        remove_ops(block, dead_fwd)
        block._insert_op(idx, "fused_conv_bn_act",
                         inputs=inputs, outputs=outputs, attrs=attrs)
        if bn_grad is not None:
            gattrs = {k: v for k, v in attrs.items() if k != "op_role"}
            gattrs.update(self._merged_role_attrs(act_grad, bn_grad,
                                                  conv_grad))
            dy_in = (act_grad.inputs["Out@GRAD"] if act_grad is not None
                     else bn_grad.inputs["Y@GRAD"])
            ginputs = {
                "Input": list(conv.inputs["Input"]),
                "Filter": list(conv.inputs["Filter"]),
                "ConvOut": [ch["conv_out"]],
                "Output": [ch["out"]],
                "Scale": list(bn.inputs["Scale"]),
                "SavedMean": list(bn.outputs["SavedMean"]),
                "SavedVariance": list(bn.outputs["SavedVariance"]),
                "Output@GRAD": list(dy_in),
            }
            goutputs = {
                "Input@GRAD": list(conv_grad.outputs.get("Input@GRAD", [])),
                "Filter@GRAD": list(conv_grad.outputs.get("Filter@GRAD", [])),
                "Scale@GRAD": list(bn_grad.outputs.get("Scale@GRAD", [])),
                "Bias@GRAD": list(bn_grad.outputs.get("Bias@GRAD", [])),
            }
            if ch["z"] and bn_grad.outputs.get("Z@GRAD"):
                goutputs["Z@GRAD"] = list(bn_grad.outputs["Z@GRAD"])
            dead_bwd = ([act_grad] if act_grad is not None else []) + \
                [bn_grad, conv_grad]
            gidx = block.ops.index(dead_bwd[0])
            remove_ops(block, dead_bwd)
            block._insert_op(gidx, "fused_conv_bn_act_grad",
                             inputs=ginputs, outputs=goutputs, attrs=gattrs)
        return True

    def _rewrite_matmul(self, block, ch, protected):
        mm, add, act_op = ch["mm"], ch["add"], ch["act_op"]
        mm_grad, add_grad, act_grad = \
            ch["mm_grad"], ch["add_grad"], ch["act_grad"]
        gone = {ch["mm_out"], ch["add_out"]}
        if act_grad is not None:
            gone |= {ch["add_out"] + "@GRAD", ch["mm_out"] + "@GRAD"}
        if gone & protected:
            return False
        attrs = {
            "act_type": ch["act"],
            "x_num_col_dims": ch["xnc"],
            "axis": add.attrs.get("axis", -1),
        }
        if "op_role" in act_op.attrs:
            attrs["op_role"] = act_op.attrs["op_role"]
        inputs = {"X": list(mm.inputs["X"]), "Y": list(mm.inputs["Y"]),
                  "Bias": list(add.inputs["Y"])}
        idx = block.ops.index(act_op)
        idx -= sum(1 for o in (mm, add) if block.ops.index(o) < idx)
        remove_ops(block, [mm, add, act_op])
        block._insert_op(idx, "fused_matmul_bias_act", inputs=inputs,
                         outputs={"Out": [ch["out"]]}, attrs=attrs)
        if act_grad is not None:
            gattrs = {k: v for k, v in attrs.items() if k != "op_role"}
            gattrs.update(self._merged_role_attrs(act_grad, add_grad,
                                                  mm_grad))
            ginputs = {
                "X": list(mm.inputs["X"]), "Y": list(mm.inputs["Y"]),
                "Bias": list(add.inputs["Y"]),
                "Out@GRAD": list(act_grad.inputs["Out@GRAD"]),
            }
            goutputs = {
                "X@GRAD": list(mm_grad.outputs.get("X@GRAD", [])),
                "Y@GRAD": list(mm_grad.outputs.get("Y@GRAD", [])),
                "Bias@GRAD": list(add_grad.outputs.get("Y@GRAD", [])),
            }
            gidx = block.ops.index(act_grad)
            remove_ops(block, [act_grad, add_grad, mm_grad])
            block._insert_op(gidx, "fused_matmul_bias_act_grad",
                             inputs=ginputs, outputs=goutputs, attrs=gattrs)
        return True


# --------------------------------------------------------------------------
# conv+BN inference fold (reference: ir/conv_bn_fuse_pass.cc) — needs the
# scope: the fold rewrites the conv FILTER VALUES (W' = W * scale*inv_std
# per output channel) and replaces the batch_norm with a per-channel bias
# add.  Inference-only: the bn must be running in is_test /
# use_global_stats mode.
# --------------------------------------------------------------------------
@register_pass("conv_bn_fuse_pass")
class ConvBNFusePass(Pass):
    scope = None
    protected: Sequence[str] = ()

    def apply_impl(self, program):
        import numpy as np

        fused = 0
        scope = self.scope
        if scope is None:
            self.fused_count = 0
            return program
        protected = set(self.protected)
        block = program.global_block()
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            prod = producer_map(block)
            for bn in list(block.ops):
                if bn.type != "batch_norm":
                    continue
                if not (bn.attrs.get("is_test")
                        or bn.attrs.get("use_global_stats")):
                    continue
                if bn.attrs.get("data_layout", "NCHW") not in ("NCHW",
                                                               "AnyLayout"):
                    continue  # the folded bias add below is axis=1 (NCHW)
                x0 = bn.inputs.get("X", [None])[0]
                conv = prod.get(x0)
                if conv is not None and conv.attrs.get(
                        "data_format", "NCHW") != "NCHW":
                    continue
                if (conv is None or conv.type != "conv2d"
                        or x0 in protected
                        or any(id(o) != id(bn) for o in cons.get(x0, []))):
                    continue
                w_name = conv.inputs["Filter"][0]
                vals = {}
                ok = True
                for slot in ("Scale", "Bias", "Mean", "Variance"):
                    v = scope.get(bn.inputs[slot][0])
                    if v is None:
                        ok = False
                        break
                    vals[slot] = np.asarray(v, np.float64)
                w = scope.get(w_name)
                if not ok or w is None:
                    continue
                # the filter must not be shared with another conv: scaling
                # it would silently change the other consumer
                if sum(1 for o in block.ops
                       if w_name in o.inputs.get("Filter", [])) > 1:
                    continue
                eps = bn.attrs.get("epsilon", 1e-5)
                a = vals["Scale"] / np.sqrt(vals["Variance"] + eps)
                b = vals["Bias"] - vals["Mean"] * a
                w_np = np.asarray(w)
                scope.set(w_name, (np.asarray(w_np, np.float64)
                                   * a[:, None, None, None]
                                   ).astype(w_np.dtype))
                y_name = bn.outputs["Y"][0]
                bias_name = y_name + "__bn_folded_bias"
                block.create_var(name=bias_name, shape=[int(a.shape[0])],
                                 dtype=VarType.FP32, persistable=True)
                scope.set(bias_name, b.astype(np.float32))
                idx = block.ops.index(bn)
                remove_ops(block, [bn])
                block._insert_op(idx, "elementwise_add",
                                 inputs={"X": [x0], "Y": [bias_name]},
                                 outputs={"Out": [y_name]},
                                 attrs={"axis": 1})
                fused += 1
                changed = True
                break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


# --------------------------------------------------------------------------
# embedding + eltwise-add + layer_norm fuse (reference:
# ir/embedding_eltwise_layernorm_fuse_pass.cc -> the
# fused_embedding_eltwise_layernorm op).  Matches k>=2 lookup_tables
# whose outputs sum through private default-axis adds into a last-axis
# layer_norm; inference-path only (the rewrite does not touch grads).
# --------------------------------------------------------------------------
@register_pass("embedding_eltwise_layernorm_fuse_pass")
class EmbeddingEltwiseLayernormFusePass(Pass):
    protected: Sequence[str] = ()

    def apply_impl(self, program):
        fused = 0
        block = program.global_block()
        protected = set(self.protected)
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            prod = producer_map(block)
            for ln in list(block.ops):
                if ln.type != "layer_norm":
                    continue
                if ln.attrs.get("begin_norm_axis", 1) != 2:
                    continue  # the fused op normalizes (b, s, h) over h
                # Mean/Variance side outputs must be dead
                if any(cons.get(n, []) for slot in ("Mean", "Variance")
                       for n in ln.outputs.get(slot, [])):
                    continue
                x0 = ln.inputs["X"][0]
                if x0 in protected:
                    continue
                lookups, adds = [], []
                ok = [True]

                def collect(name):
                    p = prod.get(name)
                    if p is None:
                        ok[0] = False
                        return
                    private = (len(cons.get(name, [])) == 1
                               and name not in protected)
                    if p.type == "elementwise_add" and \
                            p.attrs.get("axis", -1) == -1 and private:
                        adds.append(p)
                        collect(p.inputs["X"][0])
                        collect(p.inputs["Y"][0])
                    elif p.type in ("lookup_table", "lookup_table_v2") \
                            and private \
                            and p.attrs.get("padding_idx", -1) in (-1,):
                        lookups.append(p)
                    else:
                        ok[0] = False

                collect(x0)
                if not ok[0] or len(lookups) < 2 or not adds:
                    continue
                # the fused op applies the LN affine unconditionally, so
                # only layer_norms that HAVE Scale and Bias are fused
                if not ln.inputs.get("Scale") or not ln.inputs.get("Bias"):
                    continue
                ids = [lk.inputs["Ids"][0] for lk in lookups]
                embs = [lk.inputs["W"][0] for lk in lookups]
                inputs = {"Ids": ids, "Embs": embs,
                          "Scale": list(ln.inputs["Scale"]),
                          "Bias": list(ln.inputs["Bias"])}
                dead = adds + lookups + [ln]
                idx = block.ops.index(ln)
                idx -= sum(1 for o in dead if block.ops.index(o) < idx)
                remove_ops(block, dead)
                block._insert_op(
                    idx, "fused_embedding_eltwise_layernorm",
                    inputs=inputs, outputs={"Out": list(ln.outputs["Y"])},
                    attrs={"epsilon": ln.attrs.get("epsilon", 1e-5)})
                fused += 1
                changed = True
                break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


@register_pass("fc_fuse_pass")
class FcFusePass(Pass):
    """mul + elementwise_add [+ relu]  ==>  fc  (reference:
    ir/fc_fuse_pass.cc).  Inference-shape rewrite: only fires on forward
    chains with no grad consumers (run it from the inference
    PassStrategy, after remove_training_ops)."""

    protected: Sequence[str] = ()

    def apply_impl(self, program):
        fused = 0
        block = program.global_block()
        protected = set(self.protected)
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            for mul in list(block.ops):
                if mul.type != "mul" or \
                        mul.attrs.get("y_num_col_dims", 1) != 1:
                    continue
                y0 = mul.outputs["Out"][0]
                if y0 in protected:
                    continue
                # the fc kernel multiplies W as-is: only 2-D weights
                # match (mul itself flattens higher-rank Y; fc must not)
                wv = block._find_var_recursive(mul.inputs["Y"][0])
                if wv is None or wv.shape is None or len(wv.shape) != 2:
                    continue
                users = cons.get(y0, [])
                if len(users) != 1 or users[0].type != "elementwise_add":
                    continue
                add = users[0]
                # bias must be the non-mul operand, added along axis 1 of
                # a 2-D result (the fc bias shape), or the default axis
                xn, yn = add.inputs["X"][0], add.inputs["Y"][0]
                if xn != y0:
                    continue  # fc bias rides the Y slot in the fc pattern
                if add.attrs.get("axis", -1) not in (-1, 1):
                    continue
                # the Y operand must actually be a bias: a 1-D (or 1xN)
                # var, not a batch-shaped activation (the fc op reshapes
                # Bias to (1, n) — fusing an activation add would be a
                # silent wrong-result rewrite)
                bv = block._find_var_recursive(yn)
                if bv is None or bv.shape is None:
                    continue
                bshape = [d for d in bv.shape]
                if not (len(bshape) == 1
                        or (len(bshape) == 2 and bshape[0] == 1)):
                    continue
                bias = yn
                a1 = add.outputs["Out"][0]
                out_name = a1
                act = ""
                dead = [mul, add]
                a_users = cons.get(a1, [])
                if a1 not in protected and len(a_users) == 1 \
                        and a_users[0].type == "relu":
                    act = "relu"
                    out_name = a_users[0].outputs["Out"][0]
                    dead.append(a_users[0])
                idx = block.ops.index(mul)
                inputs = {"Input": list(mul.inputs["X"]),
                          "W": list(mul.inputs["Y"]),
                          "Bias": [bias]}
                attrs = {"in_num_col_dims":
                         mul.attrs.get("x_num_col_dims", 1),
                         "activation_type": act}
                remove_ops(block, dead)
                block._insert_op(idx, "fc", inputs=inputs,
                                 outputs={"Out": [out_name]}, attrs=attrs)
                fused += 1
                changed = True
                break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


@register_pass("seqpool_concat_fuse_pass")
class SeqpoolConcatFusePass(Pass):
    """N x sequence_pool feeding ONE concat(axis=1)  ==>
    fusion_seqpool_concat (reference: ir/seqpool_concat_fuse_pass.cc).
    All pools must share the pooltype; per-slot Length inputs ride
    along in order."""

    protected: Sequence[str] = ()

    def apply_impl(self, program):
        fused = 0
        block = program.global_block()
        protected = set(self.protected)
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            prod = producer_map(block)
            for cat in list(block.ops):
                if cat.type != "concat" or cat.attrs.get("axis", 0) != 1:
                    continue
                srcs = cat.inputs.get("X", [])
                pools = [prod.get(n) for n in srcs]
                if len(pools) < 2 or any(
                        p is None or p.type != "sequence_pool"
                        for p in pools):
                    continue
                ptypes = {(p.attrs.get("pooltype") or "SUM").upper()
                          for p in pools}
                if len(ptypes) != 1 or \
                        next(iter(ptypes)) not in ("SUM", "AVERAGE", "SQRT"):
                    continue
                # the fused kernel zero-fills empty sequences; a nonzero
                # pad_value pool must stay unfused to keep its semantics
                if any((p.attrs.get("pad_value") or 0.0) != 0.0
                       for p in pools):
                    continue
                # every pooled intermediate is private to this concat,
                # MaxIndex side outputs dead, names not protected
                ok = True
                for n, p in zip(srcs, pools):
                    if n in protected or len(cons.get(n, [])) != 1:
                        ok = False
                        break
                    for mi in p.outputs.get("MaxIndex", []):
                        if cons.get(mi, []):
                            ok = False
                            break
                if not ok:
                    continue
                xs, lens = [], []
                for p in pools:
                    xs.append(p.inputs["X"][0])
                    lens.extend(p.inputs.get("Length", []))
                if lens and len(lens) != len(pools):
                    continue  # mixed explicit/implicit lengths: leave it
                idx = block.ops.index(cat)
                idx -= sum(1 for p in pools if block.ops.index(p) < idx)
                inputs = {"X": xs}
                if lens:
                    inputs["Length"] = lens
                remove_ops(block, pools + [cat])
                block._insert_op(
                    idx, "fusion_seqpool_concat", inputs=inputs,
                    outputs={"Out": list(cat.outputs["Out"])},
                    attrs={"pooltype": next(iter(ptypes))})
                fused += 1
                changed = True
                break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


@register_pass("transpose_flatten_concat_fuse_pass")
class TransposeFlattenConcatFusePass(Pass):
    """N x (transpose2 -> flatten2) branches feeding ONE concat ==>
    fusion_transpose_flatten_concat (reference:
    ir/transpose_flatten_concat_fuse_pass.cc — the SSD/detection
    multi-head collection pattern).  All branches must share the
    transpose perm and flatten axis."""

    protected: Sequence[str] = ()

    def apply_impl(self, program):
        fused = 0
        block = program.global_block()
        protected = set(self.protected)
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            prod = producer_map(block)
            for cat in list(block.ops):
                if cat.type != "concat":
                    continue
                srcs = cat.inputs.get("X", [])
                flats = [prod.get(n) for n in srcs]
                if len(flats) < 2 or any(
                        f is None or f.type not in ("flatten2", "flatten")
                        for f in flats):
                    continue
                transposes = [prod.get(f.inputs["X"][0]) for f in flats]
                if any(t is None or t.type not in ("transpose2", "transpose")
                       for t in transposes):
                    continue
                perms = {tuple(t.attrs.get("axis", ())) for t in transposes}
                faxes = {int(f.attrs.get("axis", 1)) for f in flats}
                if len(perms) != 1 or len(faxes) != 1:
                    continue
                ok = True
                for f, t in zip(flats, transposes):
                    mids = [f.inputs["X"][0], f.outputs["Out"][0]]
                    if any(n in protected for n in mids):
                        ok = False
                    if len(cons.get(f.outputs["Out"][0], [])) != 1 or \
                            len(cons.get(f.inputs["X"][0], [])) != 1:
                        ok = False
                    # XShape side outputs must be dead
                    for side in (f.outputs.get("XShape", [])
                                 + t.outputs.get("XShape", [])):
                        if cons.get(side, []):
                            ok = False
                if not ok:
                    continue
                xs = [t.inputs["X"][0] for t in transposes]
                idx = block.ops.index(cat)
                dead = flats + transposes
                idx -= sum(1 for d in dead if block.ops.index(d) < idx)
                remove_ops(block, dead + [cat])
                block._insert_op(
                    idx, "fusion_transpose_flatten_concat",
                    inputs={"X": xs},
                    outputs={"Out": list(cat.outputs["Out"])},
                    attrs={"trans_axis": list(next(iter(perms))),
                           "flatten_axis": next(iter(faxes)),
                           "concat_axis": int(cat.attrs.get("axis", 0))})
                fused += 1
                changed = True
                break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


# --------------------------------------------------------------------------
# fused optimizer shell (reference: ir/fuse_optimizer_ops_pass/ —
# fuse_sgd_op_pass.cc, fuse_momentum_op_pass.cc, fuse_adam_op_pass.cc):
# merge per-parameter update ops sharing one LR var and hyperparams into
# a single multi-slot fused op.
# --------------------------------------------------------------------------
_FUSABLE_OPT = {
    "sgd": (("Param", "Grad"), ("ParamOut",)),
    "momentum": (("Param", "Grad", "Velocity"), ("ParamOut", "VelocityOut")),
    "adam": (("Param", "Grad", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow"),
             ("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
              "Beta2PowOut")),
}


@register_pass("squared_mat_sub_fuse_pass")
class SquaredMatSubFusePass(Pass):
    """matmul(x,y)^2 - matmul(x^2,y^2) [* scalar]  ==>
    fusion_squared_mat_sub (reference: ir/squared_mat_sub_fuse_pass.cc
    building operators/fused/fusion_squared_mat_sub_op.cc — the sim-net
    second-order feature cross).  Inference-shape rewrite."""

    protected: Sequence[str] = ()

    def apply_impl(self, program):
        fused = 0
        block = program.global_block()
        protected = set(self.protected)
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            prod = producer_map(block)
            for sub in list(block.ops):
                if sub.type != "elementwise_sub":
                    continue
                sq_xy = prod.get(sub.inputs["X"][0])
                mm_sq = prod.get(sub.inputs["Y"][0])
                if (sq_xy is None or mm_sq is None
                        or sq_xy.type != "square"
                        or mm_sq.type != "matmul"):
                    continue
                mm_xy = prod.get(sq_xy.inputs["X"][0])
                if mm_xy is None or mm_xy.type != "matmul":
                    continue
                sq_x = prod.get(mm_sq.inputs["X"][0])
                sq_y = prod.get(mm_sq.inputs["Y"][0])
                if (sq_x is None or sq_y is None or sq_x.type != "square"
                        or sq_y.type != "square"):
                    continue
                if (sq_x.inputs["X"][0] != mm_xy.inputs["X"][0]
                        or sq_y.inputs["X"][0] != mm_xy.inputs["Y"][0]):
                    continue
                if any(mm.attrs.get(k, False) for mm in (mm_xy, mm_sq)
                       for k in ("transpose_X", "transpose_Y")):
                    continue
                if any(mm.attrs.get("alpha", 1.0) != 1.0
                       for mm in (mm_xy, mm_sq)):
                    continue  # alpha scaling is not part of the fused op
                inner = [mm_xy.outputs["Out"][0], sq_xy.outputs["Out"][0],
                         sq_x.outputs["Out"][0], sq_y.outputs["Out"][0],
                         mm_sq.outputs["Out"][0]]
                if any(len(cons.get(n, [])) != 1 or n in protected
                       for n in inner):
                    continue
                out_name = sub.outputs["Out"][0]
                dead = [mm_xy, sq_xy, sq_x, sq_y, mm_sq, sub]
                scalar = 1.0
                users = cons.get(out_name, [])
                if (out_name not in protected and len(users) == 1
                        and users[0].type == "scale"
                        and users[0].attrs.get("bias", 0.0) == 0.0
                        and not users[0].inputs.get("ScaleTensor")):
                    scalar = float(users[0].attrs.get("scale", 1.0))
                    out_name = users[0].outputs["Out"][0]
                    dead.append(users[0])
                # earliest dead op's slot keeps topological order (the
                # square(x)/square(y) ops may precede the matmul)
                idx = min(block.ops.index(o) for o in dead)
                x_in, y_in = list(mm_xy.inputs["X"]), list(mm_xy.inputs["Y"])
                remove_ops(block, dead)
                block._insert_op(
                    idx, "fusion_squared_mat_sub",
                    inputs={"X": x_in, "Y": y_in},
                    outputs={"Out": [out_name]},
                    attrs={"scalar": scalar})
                fused += 1
                changed = True
                break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


@register_pass("repeated_fc_relu_fuse_pass")
class RepeatedFcReluFusePass(Pass):
    """N>=2 chained fc(relu) ops ==> fusion_repeated_fc_relu
    (reference: ir/repeated_fc_relu_fuse_pass.cc). Run AFTER
    fc_fuse_pass so the chain is already in fc form."""

    protected: Sequence[str] = ()

    def apply_impl(self, program):
        fused = 0
        block = program.global_block()
        protected = set(self.protected)
        changed = True
        while changed:
            changed = False
            cons = _consumers(block)
            prod = producer_map(block)

            def is_relu_fc(op_):
                return (op_ is not None and op_.type == "fc"
                        and op_.attrs.get("activation_type") == "relu"
                        and op_.attrs.get("in_num_col_dims", 1) == 1
                        and bool(op_.inputs.get("Bias")))  # fc bias optional

            for head in list(block.ops):
                if not is_relu_fc(head):
                    continue
                # head must START a chain: its input not from a relu-fc
                if is_relu_fc(prod.get(head.inputs["Input"][0])):
                    continue
                chain = [head]
                while True:
                    o = chain[-1].outputs["Out"][0]
                    users = cons.get(o, [])
                    if (o in protected or len(users) != 1
                            or not is_relu_fc(users[0])
                            or users[0].inputs["Input"][0] != o):
                        break
                    chain.append(users[0])
                if len(chain) < 2:
                    continue
                idx = block.ops.index(head)
                inputs = {"X": list(head.inputs["Input"]),
                          "W": [fc.inputs["W"][0] for fc in chain],
                          "Bias": [fc.inputs["Bias"][0] for fc in chain]}
                out_name = chain[-1].outputs["Out"][0]
                remove_ops(block, chain)
                block._insert_op(
                    idx, "fusion_repeated_fc_relu", inputs=inputs,
                    outputs={"Out": [out_name]}, attrs={})
                fused += 1
                changed = True
                break
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


# --------------------------------------------------------------------------
# NHWC layout propagation (reference intent: the transfer_layout logic in
# ir/layout transform + MLPerf-on-TPU channels-last recipes, arxiv
# 1909.09756 §4).  Paddle programs are built NCHW; the TPU's native conv
# layout is channels-last.  This pass walks the (already-differentiated)
# global block once and rewrites conv/bn/pool chains — forward AND grad
# ops — to compute in NHWC:
#
# * layout-preferring ops (conv2d/pool2d/batch_norm/fused bn-act, and
#   their grad ops) get data_format/data_layout = "NHWC" and their 4-D
#   data inputs/outputs renamed to `name@NHWC` alias vars;
# * layout-agnostic elementwise ops (relu/cast/sum/elementwise_add and
#   grads) ride along in NHWC when all their data inputs already are;
# * a transpose2 is inserted ONLY at subgraph boundaries: NCHW->NHWC
#   lazily on first NHWC use of an NCHW value, NHWC->NCHW lazily on
#   first NCHW use of an NHWC value.  Alias reuse makes adjacent
#   transpose pairs cancel by construction — a value transposed once is
#   never re-transposed, so an unbroken conv->bn->relu->conv chain has
#   exactly one transpose in and one out.
#
# Filters stay OIHW: the conv lowering passes NHWC dimension numbers to
# lax.conv_general_dilated with an OIHW rhs spec, so weights (and their
# grads, and the optimizer state) keep their NCHW-era layout — flipping
# FLAGS_tpu_nhwc mid-training is safe.
# --------------------------------------------------------------------------
_NHWC_SUFFIX = "@NHWC"

#: op type -> (layout attr, data input slots, data output slots).  Slots
#: not listed (Filter, Scale, running stats, ...) are per-channel or
#: kernel-layout values the NHWC lowering consumes unchanged.
_LAYOUT_OPS: Dict[str, tuple] = {
    "conv2d": ("data_format", ("Input",), ("Output",)),
    "depthwise_conv2d": ("data_format", ("Input",), ("Output",)),
    "conv2d_grad": ("data_format", ("Input", "Output", "Output@GRAD"),
                    ("Input@GRAD",)),
    "depthwise_conv2d_grad": ("data_format",
                              ("Input", "Output", "Output@GRAD"),
                              ("Input@GRAD",)),
    "pool2d": ("data_format", ("X",), ("Out",)),
    "pool2d_grad": ("data_format", ("X", "Out", "Out@GRAD"), ("X@GRAD",)),
    "batch_norm": ("data_layout", ("X",), ("Y",)),
    "batch_norm_grad": ("data_layout", ("X", "Y", "Y@GRAD"), ("X@GRAD",)),
    "fused_batch_norm_act": ("data_layout", ("X",), ("Y",)),
    "fused_batch_norm_act_grad": ("data_layout", ("X", "Y", "Y@GRAD"),
                                  ("X@GRAD",)),
    "fused_bn_add_activation": ("data_layout", ("X", "Z"), ("Y",)),
    "fused_bn_add_activation_grad": ("data_layout", ("X", "Y", "Y@GRAD"),
                                     ("X@GRAD", "Z@GRAD")),
    # r14 fused conv epilogues: ONE layout attr (data_format) governs
    # conv and BN; Filter/Filter@GRAD stay OIHW in both layouts
    "fused_conv_bn_act": ("data_format", ("Input", "Z"),
                          ("Output", "ConvOut")),
    "fused_conv_bn_act_grad": ("data_format",
                               ("Input", "ConvOut", "Output",
                                "Output@GRAD"),
                               ("Input@GRAD", "Z@GRAD")),
}

#: elementwise ops that compute identically in any layout: converted to
#: consume/produce NHWC aliases when every 4-D data input already has
#: one, so they never force a transpose back to NCHW mid-chain.
_LAYOUT_AGNOSTIC: Dict[str, tuple] = {
    "relu": (("X",), ("Out",)),
    "relu_grad": (("X", "Out", "Out@GRAD"), ("X@GRAD",)),
    "cast": (("X",), ("Out",)),
    "cast_grad": (("X", "Out", "Out@GRAD"), ("X@GRAD",)),
    "elementwise_add": (("X", "Y"), ("Out",)),
    "elementwise_add_grad": (("X", "Y", "Out", "Out@GRAD"),
                             ("X@GRAD", "Y@GRAD")),
    "sum": (("X",), ("Out",)),
}


@register_pass("layout_transform_pass")
class LayoutTransformPass(Pass):
    """NCHW -> NHWC propagation over conv/bn/pool/elementwise chains."""

    #: var names whose NCHW value must stay addressable (fetch targets)
    protected: Sequence[str] = ()

    def apply_impl(self, program):
        block = program.global_block()
        keep_nchw = set(self.protected)
        # names referenced from other blocks (while/cond bodies) must
        # keep their NCHW binding — sub-blocks are not rewritten
        for other in program.blocks:
            if other is block:
                continue
            for op_ in other.ops:
                for names in op_.inputs.values():
                    keep_nchw.update(names)
                for names in op_.outputs.values():
                    keep_nchw.update(names)
        self.converted_count = self._apply_block(block, keep_nchw)
        if self.converted_count:
            program._bump_version()
        return program

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _is_4d(block, name):
        if not name or name == "@EMPTY@":
            return False
        v = block._find_var_recursive(name)
        return v is not None and v.shape is not None and len(v.shape) == 4

    def _eligible(self, op_, block, attr_name, din, dout):
        if op_.attrs.get(attr_name, "NCHW") not in ("NCHW", "AnyLayout"):
            return False
        if op_.type.startswith("pool2d"):
            if op_.attrs.get("adaptive", False) and \
                    not op_.attrs.get("global_pooling", False):
                return False  # NHWC adaptive: only the lowering's
                #                divisible path; stay conservative
        names = []
        for slot in din:
            names.extend(op_.inputs.get(slot, []))
        for slot in dout:
            names.extend(n for n in op_.outputs.get(slot, [])
                         if n != "@EMPTY@")
        if not names:
            return False
        return all(self._is_4d(block, n) for n in names
                   if n != "@EMPTY@")

    # -- main walk ---------------------------------------------------------
    def _apply_block(self, block, keep_nchw):
        converted = 0
        new_ops: List[Operator] = []
        alias: Dict[str, str] = {}   # NCHW name -> live NHWC alias
        pending: set = set()         # names whose NCHW value is not
        #                              materialized (only alias is live)

        def alias_var(name):
            aname = name + _NHWC_SUFFIX
            if not block.has_var(aname):
                v = block._find_var_recursive(name)
                s = list(v.shape)
                block.create_var(name=aname,
                                 shape=(s[0], s[2], s[3], s[1]),
                                 dtype=v.dtype)
            return aname

        def to_nhwc(name):
            a = alias.get(name)
            if a is not None:
                return a
            a = alias_var(name)
            new_ops.append(Operator(
                block, "transpose2", inputs={"X": [name]},
                outputs={"Out": [a]}, attrs={"axis": [0, 2, 3, 1]}))
            alias[name] = a
            return a

        def to_nchw(name):
            if name in pending:
                new_ops.append(Operator(
                    block, "transpose2", inputs={"X": [alias[name]]},
                    outputs={"Out": [name]}, attrs={"axis": [0, 3, 1, 2]}))
                pending.discard(name)
            return name

        def invalidate_outputs(op_, except_slots=()):
            """An op overwriting an aliased name makes the alias stale."""
            for slot, names in op_.outputs.items():
                if slot in except_slots:
                    continue
                for n in names:
                    if n in alias:
                        alias.pop(n, None)
                        pending.discard(n)

        def convert(op_, attr_name, din, dout):
            """Rewrite one op to compute in NHWC: data input slots take
            (or create) aliases, data output slots produce aliases, the
            layout attr flips — including the __fwd_attrs__ snapshot the
            vjp replay of grad ops reads."""
            data_out_names = {n for slot in dout
                              for n in op_.outputs.get(slot, [])}
            # non-data input slots are per-channel/kernel values that
            # should never be pending; stay safe if one is
            for slot, names in list(op_.inputs.items()):
                if slot in din:
                    op_.inputs[slot] = [
                        to_nhwc(n) if n != "@EMPTY@" else n for n in names]
                else:
                    for n in names:
                        if n in pending:
                            to_nchw(n)
            invalidate_outputs(op_, except_slots=dout)
            for slot in dout:
                names = op_.outputs.get(slot, [])
                rewritten = []
                for n in names:
                    if n == "@EMPTY@":
                        rewritten.append(n)
                        continue
                    a = alias_var(n)
                    alias[n] = a
                    pending.add(n)
                    rewritten.append(a)
                if names:
                    op_.outputs[slot] = rewritten
            if attr_name is not None:
                op_.attrs[attr_name] = "NHWC"
                fa = op_.attrs.get("__fwd_attrs__")
                if isinstance(fa, dict):
                    fa = dict(fa)
                    fa[attr_name] = "NHWC"
                    op_.attrs["__fwd_attrs__"] = fa
            new_ops.append(op_)
            # fetch targets / persistables need their NCHW value live NOW
            for n in data_out_names:
                if n != "@EMPTY@" and n in pending:
                    v = block._find_var_recursive(n)
                    if n in keep_nchw or (v is not None and
                                          getattr(v, "persistable", False)):
                        to_nchw(n)

        for op_ in list(block.ops):
            spec = _LAYOUT_OPS.get(op_.type)
            agn = _LAYOUT_AGNOSTIC.get(op_.type)
            if spec is not None:
                attr_name, din, dout = spec
                if self._eligible(op_, block, din=din, dout=dout,
                                  attr_name=attr_name):
                    convert(op_, attr_name, din, dout)
                    converted += 1
                    continue
            elif agn is not None and self._agnostic_ok(op_, block, alias,
                                                       *agn):
                din, dout = agn
                convert(op_, None, din, dout)
                converted += 1
                continue
            # generic op: consume NCHW — materialize any pending input
            for names in op_.inputs.values():
                for n in names:
                    if n in pending:
                        to_nchw(n)
            invalidate_outputs(op_)
            new_ops.append(op_)

        # live-out NHWC values someone outside the block may read
        for n in sorted(pending):
            v = block._find_var_recursive(n)
            if n in keep_nchw or (v is not None
                                  and getattr(v, "persistable", False)):
                to_nchw(n)
        if converted:
            block.ops[:] = new_ops
        return converted

    def _agnostic_ok(self, op_, block, alias, din, dout):
        """Every 4-D data input must already be NHWC; elementwise_add
        additionally needs the default axis and equal shapes (a
        broadcasting add is layout-sensitive)."""
        names_in = [n for slot in din for n in op_.inputs.get(slot, [])
                    if n != "@EMPTY@"]
        names_out = [n for slot in dout for n in op_.outputs.get(slot, [])
                     if n != "@EMPTY@"]
        if not names_in or not names_out:
            return False
        if not all(self._is_4d(block, n) for n in names_in + names_out):
            return False
        if not all(n in alias for n in names_in):
            return False
        if op_.type.startswith("elementwise_add"):
            if op_.attrs.get("axis", -1) != -1:
                return False
            shapes = {tuple(block._find_var_recursive(n).shape)
                      for n in names_in}
            if len(shapes) != 1:
                return False
        return True

# --------------------------------------------------------------------------
# coalesced gradient communication (reference: ir/fuse_all_reduce_op_pass.cc
# + coalesce_grad_tensor_pass.cc): the per-tensor c_allreduce_sum ops a
# GradAllReduce transpile inserts each pay a collective launch; bucketing
# ~FLAGS_fuse_grad_size_in_MB of payload into one flattened collective
# amortizes the launches and gives XLA one large transfer to overlap with
# the remaining backward compute.
# --------------------------------------------------------------------------
@register_pass("fuse_all_reduce_pass")
class FuseAllReducePass(Pass):
    """Bucket in-place `c_allreduce_sum` ops into `c_fused_allreduce`
    (`c_fused_reduce_scatter` under ZeRO-2 — see ``sharding_stage``).

    Merge rules (each violation closes the current bucket):
    * only in-place (X == Out) sum-allreduces with static shapes and no
      `use_mean` are eligible;
    * members share one (ring_id, dtype, scatter-eligibility) —
      mixed-dtype buckets refuse to merge;
    * an intervening op that reads or writes a bucketed var closes the
      bucket first (the fused collective runs at the LAST member's
      position, so nothing may consume an unreduced value in between);
    * a bucket closes once its payload reaches ``max_bytes`` (so every
      full bucket carries >= max_bytes and the bucket count on an
      N-tensor program is <= ceil(total_bytes / max_bytes));
    * single-member buckets keep their original op — nothing to fuse.

    ``overlap=True`` (FLAGS_dp_comm_overlap, reference:
    multi_devices_graph_pass backward-op-aware allreduce ordering)
    additionally schedules the comm for backward overlap: buckets form
    in *last-gradient-ready* order instead of program-tail order, and
    each bucket's collective (plus its private in-place prologue, e.g.
    the 1/nranks scale) moves to just after the last op producing any
    of its inputs — so bucket 0's collective is in flight while later
    layers are still in backward, and on the pjit path the collective
    ops land interleaved into the backward op list where XLA's async
    collectives can overlap them.  Placement safety: every op touching
    a member var before its reduce sits at or before the bucket's
    anchor (the anchor IS the last such toucher), so no op changes the
    value it observes.

    ``sharding_stage >= 2`` with ``ndev > 1`` (ZeRO-2,
    FLAGS_dp_sharding): buckets whose every grad feeds a shard-eligible
    optimizer update lower to ``c_fused_reduce_scatter`` — each device
    receives only its 1/ndev row-shard of every reduced grad, which the
    DP runner's shard-aware update consumes directly (no full-gradient
    materialization; wire bytes halve vs allreduce).

    ``autotune=True`` (FLAGS_fuse_grad_size_in_MB="auto", r9): instead
    of the fixed byte threshold, bucket boundaries come from the
    modeled backward timeline (utils/cost_model.py).  An O(N^2) DP over
    the ready-ordered entries picks the contiguous partition minimizing
    the finish time of the serialized collective stream — each bucket's
    collective (ring alpha-beta model) should complete about when the
    next bucket's last gradient is ready, so est. exposed comm is
    minimized rather than bucket count.  Same-key contiguity and the
    ``placeable`` anchor-safety rule still bound every bucket; a
    numeric flag value restores the fixed threshold bit-for-bit.
    """

    max_bytes: int = 32 << 20
    compress: str = "none"
    overlap: bool = False
    sharding_stage: int = 0
    ndev: int = 1
    autotune: bool = False
    cost_model = None  # utils.cost_model.CostModel override (tests/CLI)

    def _payload_bytes(self, block, name):
        import numpy as np

        from .dtype import to_numpy_dtype

        var = block._find_var_recursive(name)
        if var is None or var.shape is None or var.dtype is None:
            return None
        shape = list(var.shape)
        if not shape or any(d is None or d < 0 for d in shape):
            return None
        try:
            itemsize = np.dtype(to_numpy_dtype(var.dtype)).itemsize
        except Exception:
            return None
        return int(np.prod(shape)) * itemsize, var.dtype

    # -- ZeRO-2 eligibility ------------------------------------------------
    def _scatter_names(self, block):
        """Grad names safe to reduce-scatter: every post-reduce consumer
        is either the (shard-eligible) optimizer update the DP runner
        wraps, or a no-op sync — anything else would read a 1/ndev
        shard where it expects the full tensor."""
        if int(self.sharding_stage) < 2 or int(self.ndev) <= 1:
            return set()
        from ..parallel.data_parallel import _update_shard_rows

        sync_ops = {"c_sync_comm_stream", "c_sync_calc_stream",
                    "c_wait_comm_stream", "c_wait_calc_stream", "barrier"}
        ok = set()
        consumers: Dict[str, List[Operator]] = {}
        for op_ in block.ops:
            for n in set(op_.input_arg_names):
                consumers.setdefault(n, []).append(op_)
        for op_ in block.ops:
            if op_.type != "c_allreduce_sum":
                continue
            g = op_.inputs.get("X", [None])[0]
            if not g:
                continue
            update = None
            safe = True
            seen_reduce = False
            for c in consumers.get(g, []):
                if c is op_:
                    seen_reduce = True
                    continue
                if not seen_reduce:
                    continue  # pre-reduce readers see the full local grad
                if c.type in sync_ops:
                    continue
                if (update is None
                        and _update_shard_rows(c, block, int(self.ndev))
                        and g in c.inputs.get("Grad", [])):
                    update = c
                    continue
                safe = False
                break
            if safe and update is not None:
                ok.add(g)
        return ok

    def _bucket_attrs(self, block, members):
        xs = [e["x"] for e in members]
        # the compress attr records the format that actually ships:
        # the lowering only compresses f32 payloads, so stamping
        # bf16 on another dtype would mislead comm accounting
        dtype = members[0]["dtype"]
        compress = self.compress if dtype == VarType.FP32 else "none"
        attrs = {"ring_id": members[0]["ring"], "compress": compress}
        if "op_role" in members[0]["op"].attrs:
            attrs["op_role"] = members[0]["op"].attrs["op_role"]
        return xs, attrs

    def apply_impl(self, program):
        self.fused_count = 0
        if self.max_bytes <= 0:
            return program
        block = program.global_block()
        scatter_names = self._scatter_names(block)
        if self.overlap:
            changed = self._apply_overlap(block, scatter_names)
        else:
            changed = self._apply_append(block, scatter_names)
        if changed:
            program._bump_version()
        return program

    # -- r7 schedule: fuse in program order, issue at last member ----------
    def _apply_append(self, block, scatter_names):
        buckets: List[List[dict]] = []
        cur: List[dict] = []
        cur_bytes = 0
        cur_key = None
        touched: set = set()

        def close():
            nonlocal cur, cur_bytes, cur_key
            if len(cur) >= 2:
                buckets.append(list(cur))
            cur, cur_bytes, cur_key = [], 0, None
            touched.clear()

        for op_ in list(block.ops):
            if (op_.type == "c_allreduce_sum"
                    and not op_.attrs.get("use_mean", False)):
                x = op_.inputs.get("X", [None])[0]
                o = op_.outputs.get("Out", [None])[0]
                info = self._payload_bytes(block, x) if x else None
                if x is None or x != o or info is None:
                    close()
                    continue
                nbytes, dtype = info
                key = (op_.attrs.get("ring_id", 0), dtype,
                       x in scatter_names)
                if cur and (key != cur_key or x in touched):
                    close()
                cur.append({"op": op_, "x": x, "dtype": dtype,
                            "ring": op_.attrs.get("ring_id", 0)})
                cur_bytes += nbytes
                cur_key = key
                touched.add(x)
                if cur_bytes >= self.max_bytes:
                    close()
                continue
            names = set(op_.input_arg_names) | set(op_.output_arg_names)
            if names & touched:
                close()
        close()

        for b in buckets:
            xs, attrs = self._bucket_attrs(block, b)
            fused_type = ("c_fused_reduce_scatter"
                          if b[0]["x"] in scatter_names
                          else "c_fused_allreduce")
            ops_ = [e["op"] for e in b]
            last = max(block.ops.index(o) for o in ops_)
            last -= sum(1 for o in ops_ if block.ops.index(o) < last)
            remove_ops(block, ops_)
            block._insert_op(last, fused_type,
                             inputs={"X": xs}, outputs={"Out": list(xs)},
                             attrs=attrs)
        self.fused_count = len(buckets)
        return bool(buckets)

    # -- overlap schedule: ready-order buckets, issue at last producer -----
    def _collect_entries(self, block, scatter_names):
        ops = list(block.ops)
        seen_reduce: Dict[str, int] = {}
        entries = []
        for i, op_ in enumerate(ops):
            if (op_.type != "c_allreduce_sum"
                    or op_.attrs.get("use_mean", False)):
                continue
            x = op_.inputs.get("X", [None])[0]
            o = op_.outputs.get("Out", [None])[0]
            info = self._payload_bytes(block, x) if x else None
            if x is None or x != o or info is None:
                continue
            if x in seen_reduce:
                # two reduces of one var: scheduling either would reorder
                # them — leave both in place
                seen_reduce[x] = -1
                continue
            seen_reduce[x] = len(entries)
            nbytes, dtype = info
            # walk back over the private in-place prologue (the
            # transpiler's 1/nranks scale): ops touching ONLY x move
            # with the collective; the first other toucher is the
            # anchor this bucket may not be issued before.
            chain: List[int] = []
            anchor = -1
            j = i - 1
            while j >= 0:
                o2 = ops[j]
                names = set(o2.input_arg_names) | set(o2.output_arg_names)
                if x in names:
                    if names <= {x} and x in o2.output_arg_names:
                        chain.append(j)
                        j -= 1
                        continue
                    anchor = j
                    break
                j -= 1
            chain.reverse()
            entries.append({"op": op_, "idx": i, "x": x, "nbytes": nbytes,
                            "dtype": dtype,
                            "ring": op_.attrs.get("ring_id", 0),
                            "chain": chain, "anchor": anchor})
        return [e for e in entries
                if seen_reduce.get(e["x"]) != -1], ops

    def _apply_overlap(self, block, scatter_names):
        entries, ops = self._collect_entries(block, scatter_names)
        if not entries:
            self.fused_count = 0
            return False
        entries.sort(key=lambda e: (e["anchor"], e["idx"]))

        touch: Dict[str, List[int]] = {}
        for i, o in enumerate(ops):
            for n in set(o.input_arg_names) | set(o.output_arg_names):
                touch.setdefault(n, []).append(i)

        def placeable(members, anchor):
            """A bucket issues after `anchor` (original index).  Every
            pre-reduce toucher of a member sits at or before its own
            anchor <= `anchor`, so those stay correct by construction —
            but a POST-reduce consumer of a member whose own reduce sat
            before `anchor` (e.g. the hierarchical all-gather between
            two shard allreduces) would now run before the moved
            collective and read an unreduced value: refuse."""
            for e in members:
                own = set(e["chain"])
                own.add(e["idx"])
                for j in touch.get(e["x"], []):
                    if j not in own and e["idx"] < j <= anchor:
                        return False
            return True

        def placement_horizon(e):
            """Last original index a bucket containing `e` may anchor
            at: one before e's first post-reduce toucher (the same rule
            placeable scans for) — inf when no such toucher exists.
            Precomputed once so the autotune DP checks a split in O(1)
            (running max anchor vs running min horizon) instead of
            rescanning every member's touch list per (i, j) pair."""
            own = set(e["chain"])
            own.add(e["idx"])
            h = float("inf")
            for j in touch.get(e["x"], []):
                if j not in own and j > e["idx"]:
                    h = min(h, j - 1)
            return h

        buckets: List[List[dict]] = None
        if self.autotune:
            buckets = self._autotune_buckets(
                entries, ops, block,
                [placement_horizon(e) for e in entries], scatter_names)
        if buckets is None:
            buckets = []
            cur: List[dict] = []
            cur_bytes = 0
            cur_key = None
            for e in entries:
                key = (e["ring"], e["dtype"], e["x"] in scatter_names)
                if cur and (key != cur_key or not placeable(
                        cur + [e], max(m["anchor"] for m in cur + [e]))):
                    buckets.append(cur)
                    cur, cur_bytes = [], 0
                cur.append(e)
                cur_bytes += e["nbytes"]
                cur_key = key
                if cur_bytes >= self.max_bytes:
                    buckets.append(cur)
                    cur, cur_bytes, cur_key = [], 0, None
            if cur:
                buckets.append(cur)

        moved: set = set()
        schedule: Dict[int, List[List[Operator]]] = {}
        fused = 0
        for b in buckets:  # already in ready (issue) order
            anchor = max(e["anchor"] for e in b)
            emit: List[Operator] = []
            for e in b:
                emit.extend(ops[j] for j in e["chain"])
                moved.update(e["chain"])
                moved.add(e["idx"])
            if len(b) == 1:
                emit.append(b[0]["op"])  # nothing to fuse: op kept, moved
            else:
                xs, attrs = self._bucket_attrs(block, b)
                fused_type = ("c_fused_reduce_scatter"
                              if b[0]["x"] in scatter_names
                              else "c_fused_allreduce")
                emit.append(Operator(block, fused_type,
                                     inputs={"X": xs},
                                     outputs={"Out": list(xs)},
                                     attrs=attrs))
                fused += 1
            schedule.setdefault(anchor, []).append(emit)

        out: List[Operator] = []
        for emit in schedule.get(-1, []):
            out.extend(emit)
        for i, op_ in enumerate(ops):
            if i in moved:
                continue
            out.append(op_)
            for emit in schedule.get(i, []):
                out.extend(emit)
        block.ops[:] = out
        self.fused_count = fused
        return True

    # -- measurement-driven bucket boundaries (r9 autotune) ----------------
    def _autotune_buckets(self, entries, ops, block, horizons,
                          scatter_names):
        """Partition the ready-ordered entries into variable buckets by
        minimizing the modeled finish time of the serialized collective
        stream (utils/cost_model.py).  finish(partition) determines the
        exposed tail past the backward horizon, so minimizing finish
        minimizes est. exposed comm.  DP over contiguous splits:
        best[i] = min over j of max(best[j], ready[i-1]) + comm(j..i),
        restricted to same-key, placement-safe buckets.  Returns None
        (caller falls back to the fixed-threshold greedy) when no valid
        partition exists."""
        from ..utils.cost_model import (backward_timeline,
                                        collective_time_s,
                                        default_cost_model)

        if not entries:
            return None
        # no explicit override: start from the measured profile when the
        # profiler has recorded one (r13 calibration loop) — the same
        # rates tools/dp_comm_stats models with
        cm = self.cost_model or default_cost_model(ops, block)
        times, _ = backward_timeline(ops, block, cm)
        ready = [times[e["anchor"]] if e["anchor"] >= 0 else 0.0
                 for e in entries]
        keys = [(e["ring"], e["dtype"], e["x"] in scatter_names)
                for e in entries]
        nranks = max(int(self.ndev), 1)
        N = len(entries)
        INF = float("inf")
        best = [INF] * (N + 1)
        best[0] = 0.0
        cut = [0] * (N + 1)
        for i in range(1, N + 1):
            nbytes = 0
            anc = -1
            safe = INF
            for j in range(i - 1, -1, -1):
                if keys[j] != keys[i - 1]:
                    break  # buckets are same-key contiguous runs
                nbytes += entries[j]["nbytes"]
                # bucket [j:i) anchors at its max member anchor; safe
                # iff that never passes any member's placement horizon
                anc = max(anc, entries[j]["anchor"])
                safe = min(safe, horizons[j])
                if best[j] == INF or anc > safe:
                    continue
                factor = 1.0 if keys[j][2] else 2.0
                comm = collective_time_s(nbytes, factor, nranks, cm)
                fin = max(best[j], ready[i - 1]) + comm
                if fin < best[i]:
                    best[i] = fin
                    cut[i] = j
        if best[N] == INF:
            return None
        bounds = []
        i = N
        while i > 0:
            bounds.append((cut[i], i))
            i = cut[i]
        bounds.reverse()
        return [entries[a:b] for a, b in bounds]


@register_pass("prefetch_autotune_pass")
class PrefetchAutotunePass(Pass):
    """Per-parameter ZeRO-3 prefetch-depth autotune (r16, the ROADMAP
    carry-over): instead of one FLAGS_dp_prefetch_depth for every
    parameter, derive each sharded parameter's window depth from the
    cost model — just deep enough that the modeled all-gather time is
    hidden behind the compute ops preceding its first consumer
    (utils/cost_model.py ``collective_time_s`` vs accumulated
    ``op_time_s``, profile-calibrated when a measured step exists).

    This is an ANALYSIS pass: it mutates nothing (the op-motion itself
    stays in the DP interpreter, driven by
    ``data_parallel._plan_param_prefetch(depths=...)``), but it runs
    through ``Pass.apply`` so the r10 verifier bracket covers it like
    every pass, and the windows it produces are re-validated by the
    verifier's ``check_prefetch_plan`` gather-window-never-crosses-a-
    param-write rule on the DP compile path.  Results land in
    ``self.report``: ``depths`` (param -> depth) and the planned
    ``records``.  Consumed by parallel/plan_search.py's ``auto``
    prefetch candidates."""

    ndev: int = 1
    use_shard_map: bool = False
    max_depth: int = 8
    cost_model = None  # utils.cost_model.CostModel override (tests/CLI)

    def apply_impl(self, program):
        from ..parallel.data_parallel import (_pjit_zero23_sets,
                                              _plan_param_prefetch,
                                              _plan_wrapped_updates)
        from ..utils.cost_model import (COMM_OPS, collective_time_s,
                                        default_cost_model, op_time_s)

        block = program.global_block()
        ops = list(block.ops)
        ndev = max(int(self.ndev), 1)
        if self.use_shard_map:
            plans, _, sharded = _plan_wrapped_updates(ops, block, ndev, 3)
            skip = set(plans)
        else:
            sharded, _ = _pjit_zero23_sets(ops, block, ndev, 3)
            skip = set()
        self.report = {"depths": {}, "records": [], "ndev": ndev}
        if not sharded or ndev <= 1:
            return program
        cm = self.cost_model or default_cost_model(ops, block)
        op_s = [0.0 if op_.type in COMM_OPS else op_time_s(op_, block, cm)
                for op_ in ops]
        from ..framework import memory_plan as _mp

        first_use: Dict[str, int] = {}
        for i, op_ in enumerate(ops):
            if id(op_) in skip:
                continue
            for n in set(op_.input_arg_names):
                if n in sharded:
                    first_use.setdefault(n, i)
        depths: Dict[str, int] = {}
        for p in sorted(sharded):
            b = _mp.var_bytes(block, p) or 0
            gather_s = collective_time_s(float(b), 1.0, ndev, cm)
            f = first_use.get(p, 0)
            acc, d, i = 0.0, 0, f - 1
            while i >= 0 and d < int(self.max_depth) and acc < gather_s:
                acc += op_s[i]
                d += 1
                i -= 1
            depths[p] = max(d, 1)
        records, _, _ = _plan_param_prefetch(ops, block, sharded, skip,
                                             1, depths=depths)
        self.report = {"depths": depths, "records": records, "ndev": ndev}
        return program


# --------------------------------------------------------------------------
# numerics probe (r20) — the observability mirror of the fusion passes:
# instead of rewriting compute, append cheap stat reductions over
# selected op outputs so every step fetches ONE packed vector of per-var
# health partials (framework/numerics.py finalizes and consumes them).
# Existing registered ops only (cast/abs/square/reduce_max/reduce_sum/
# isfinite_v2/size/stack + c_allreduce_{max,sum} for cross-shard
# combines), so the pass adds no op-sweep surface.
# --------------------------------------------------------------------------
@register_pass("numerics_probe_pass")
class NumericsProbePass(Pass):
    """Append in-program tensor-stat probes (FLAGS_numerics_probe).

    For every selected var (grad/param/update-role always, plus outputs
    of ops matching ``ops_regex`` — see
    ``numerics.select_probe_targets``) the pass emits five partial
    reductions in f32 — absmax, sum, sum-of-squares, finite-count,
    numel — and packs all of them into one ``@numerics_stats@`` vector
    via a single ``stack`` op.  Probes read FINAL values (appended
    after every producer), so their order is the program order of each
    var's last writer — the first-divergence order
    tools/bisect_divergence.py reports in.

    On the shard_map DP path (the program carries ``c_*`` ops) each
    partial of a *shard-variant* var — batch-sharded activation,
    ZeRO-sharded optimizer state, reduce-scattered grad — is combined
    across shards with ``c_allreduce_max`` / ``c_allreduce_sum`` (the
    ``cross_shard_norms`` trick), so finalized stats are layout-,
    ZeRO-stage- and DP-path-invariant; replicated values are combined
    with nothing (a psum would multiply them by ndev).  Outside a mesh
    the combines are identity, so the probed program still runs
    anywhere.

    Probe ops carry ``op_role=Optimize``: they consume ZeRO-3 params as
    shard-or-gathered values like update ops do, keeping them out of
    the prefetch planner's consumer windows (a forward-role read at the
    block end would drag every gather window across the param's update
    write — exactly what the verifier's window rule forbids)."""

    ops_regex: str = ""

    _COMBINE = {"absmax": "c_allreduce_max", "sum": "c_allreduce_sum",
                "sumsq": "c_allreduce_sum", "nonfinite": "c_allreduce_sum",
                "numel": "c_allreduce_sum"}

    def apply_impl(self, program):
        from . import numerics
        from ..backward import OP_ROLE_KEY, OpRole

        block = program.global_block()
        if block.has_var(numerics.STATS_VAR):
            program._numerics_layout = getattr(program,
                                               "_numerics_layout", None)
            return program  # already probed (pass is idempotent)
        targets = numerics.select_probe_targets(program, block,
                                                self.ops_regex)
        self.report = {"targets": targets}
        program._numerics_layout = None
        if not targets:
            return program
        # shard-variance via the shared distribution-state engine
        # (framework/shard_analysis.py — r26 replaced the pass's private
        # taint walk); it runs exactly when the DP runner would pick the
        # shard_map path — same predicate, so the two can never drift
        from . import shard_analysis
        from ..parallel.data_parallel import _program_has_collectives

        tainted = (shard_analysis.variant_names(program, block)
                   if _program_has_collectives(program) else set())
        self._attrs = {OP_ROLE_KEY: int(OpRole.Optimize),
                       "op_namescope": "/numerics_probe/"}
        scalars: List[str] = []
        for i, t in enumerate(targets):
            scalars.extend(self._emit(block, t, i,
                                      combine=t["var"] in tainted))
        block.create_var(name=numerics.STATS_VAR,
                         shape=[len(scalars)], dtype=VarType.FP32)
        block.append_op("stack", inputs={"X": scalars},
                        outputs={"Y": [numerics.STATS_VAR]},
                        attrs=dict(self._attrs, axis=0))
        program._numerics_layout = targets
        program._bump_version()
        return program

    # -- emission ----------------------------------------------------------
    def _mk(self, block, name, shape, dtype):
        if not block.has_var(name):
            block.create_var(name=name, shape=list(shape), dtype=dtype)
        return name

    def _emit(self, block, t, idx, combine):
        """Probe ops for one target; returns the 5 scalar names in
        PARTIALS order (globally combined when ``combine``)."""
        var = t["var"]
        v = block._find_var_recursive(var)
        shape = list(v.shape) if v.shape else [-1]
        is_float = v.dtype in (VarType.FP16, VarType.BF16, VarType.FP32,
                               VarType.FP64)
        base = f"@nprobe@{idx}@"
        A = self._attrs
        f32 = self._mk(block, base + "f32", shape, VarType.FP32)
        block.append_op("cast", inputs={"X": [var]}, outputs={"Out": [f32]},
                        attrs=dict(A, out_dtype=int(VarType.FP32)))
        absv = self._mk(block, base + "abs", shape, VarType.FP32)
        block.append_op("abs", inputs={"X": [f32]},
                        outputs={"Out": [absv]}, attrs=dict(A))
        sq = self._mk(block, base + "sq", shape, VarType.FP32)
        block.append_op("square", inputs={"X": [f32]},
                        outputs={"Out": [sq]}, attrs=dict(A))
        # NON-finite mask, counted directly: summing a mask of zeros is
        # exact in f32 at ANY tensor size, where summing the finite
        # mask's ones loses integer precision past 2^24 elements and a
        # host-side `numel - finite` would report phantom nonfinites on
        # large healthy tensors.  isfinite runs on the raw value for
        # float vars (an f32 cast of f64 could overflow large-but-
        # finite values to inf), on the f32 copy for bool/int vars
        # (isfinite rejects bool inputs).
        finb = self._mk(block, base + "finb", shape, VarType.BOOL)
        block.append_op("isfinite_v2",
                        inputs={"X": [var if is_float else f32]},
                        outputs={"Out": [finb]}, attrs=dict(A))
        nfb = self._mk(block, base + "nfb", shape, VarType.BOOL)
        block.append_op("logical_not", inputs={"X": [finb]},
                        outputs={"Out": [nfb]}, attrs=dict(A))
        nff = self._mk(block, base + "nf", shape, VarType.FP32)
        block.append_op("cast", inputs={"X": [nfb]},
                        outputs={"Out": [nff]},
                        attrs=dict(A, out_dtype=int(VarType.FP32)))
        # numel via shape -> f32 -> reduce_prod (the `size` op would
        # request an int64 the x64-disabled runtime warns about)
        shp = self._mk(block, base + "shape", [len(shape)], VarType.INT32)
        block.append_op("shape", inputs={"Input": [var]},
                        outputs={"Out": [shp]}, attrs=dict(A))
        shpf = self._mk(block, base + "shapef", [len(shape)], VarType.FP32)
        block.append_op("cast", inputs={"X": [shp]},
                        outputs={"Out": [shpf]},
                        attrs=dict(A, out_dtype=int(VarType.FP32)))

        red = dict(A, dim=[0], keep_dim=False, reduce_all=True)
        out: List[str] = []
        for part, src, rop in (
                ("absmax", absv, "reduce_max"), ("sum", f32, "reduce_sum"),
                ("sumsq", sq, "reduce_sum"),
                ("nonfinite", nff, "reduce_sum")):
            local = self._mk(block, base + part, [], VarType.FP32)
            block.append_op(rop, inputs={"X": [src]},
                            outputs={"Out": [local]}, attrs=dict(red))
            out.append(local)
        numel = self._mk(block, base + "numel", [], VarType.FP32)
        block.append_op("reduce_prod", inputs={"X": [shpf]},
                        outputs={"Out": [numel]}, attrs=dict(red))
        out.append(numel)
        if combine:
            combined = []
            for part, local in zip(("absmax", "sum", "sumsq", "nonfinite",
                                    "numel"), out):
                g = self._mk(block, base + part + "_g", [], VarType.FP32)
                block.append_op(self._COMBINE[part], inputs={"X": [local]},
                                outputs={"Out": [g]},
                                attrs=dict(A, ring_id=0))
                combined.append(g)
            out = combined
        return out

@register_pass("shard_safety_pass")
class ShardSafetyPass(Pass):
    """Static SPMD shard-safety gate (framework/shard_analysis.py): runs
    the distribution-state abstract interpreter and its check catalog —
    replication soundness, collectives under divergent control flow,
    comm/compute hazards — over the compiled program.  Analysis-only:
    the program is returned untouched, findings land in ``self.report``
    and are warned (or raised under ``FLAGS_shard_safety_strict``) by
    the shared :func:`shard_analysis.gate`.  Appended LAST in the
    pipeline so it sees every pass's output, including the numerics
    probe's cross-shard stat contract."""

    feed_names: tuple = ()
    fetch_names: tuple = ()
    where: str = "shard_safety_pass"

    def apply(self, program):
        # Analysis-only: the program cannot be mutated, so the base
        # class's snapshot/verify bracket would only re-prove what the
        # pass never touches.  Skipping it keeps the gate's per-compile
        # cost at the cost of the analysis itself.
        out = self.apply_impl(program)
        return out if out is not None else program

    def apply_impl(self, program):
        from . import shard_analysis

        diags = shard_analysis.gate(
            program, feed_names=tuple(self.feed_names),
            fetch_names=tuple(self.fetch_names), where=self.where)
        self.report = {"diagnostics": [d.as_dict() for d in diags]}
        return program


@register_pass("fuse_optimizer_ops_pass")
class FuseOptimizerOpsPass(Pass):
    def apply_impl(self, program):
        fused = 0
        block = program.global_block()
        groups: Dict[tuple, List[Operator]] = {}
        for op_ in block.ops:
            if op_.type not in _FUSABLE_OPT:
                continue
            gname = op_.inputs.get("Grad", [None])[0]
            gvar = block._find_var_recursive(gname) if gname else None
            if gvar is not None and gvar.type == VarType.SELECTED_ROWS:
                continue  # sparse updates keep their per-param kernels
            attr_key = frozenset(
                (k, tuple(v) if isinstance(v, list) else v)
                for k, v in op_.attrs.items()
                if k not in ("op_role", "op_namescope", "op_callstack",
                             "op_role_var"))
            key = (op_.type, op_.inputs["LearningRate"][0], attr_key)
            groups.setdefault(key, []).append(op_)
        for (otype, lr, _), ops_ in groups.items():
            if len(ops_) < 2:
                continue
            in_slots, out_slots = _FUSABLE_OPT[otype]
            inputs = {"LearningRate": [lr]}
            outputs: Dict[str, List[str]] = {}
            for s in in_slots:
                inputs[s] = [o.inputs[s][0] for o in ops_]
            for s in out_slots:
                outputs[s] = [o.outputs[s][0] for o in ops_]
            attrs = dict(ops_[0].attrs)
            # insert where the LAST member was: every grad is produced by
            # then; nothing between reads the updated params (updates are
            # the program tail)
            last = max(block.ops.index(o) for o in ops_)
            last -= sum(1 for o in ops_ if block.ops.index(o) < last)
            remove_ops(block, ops_)
            block._insert_op(last, "fused_" + otype, inputs=inputs,
                             outputs=outputs, attrs=attrs)
            fused += 1
        self.fused_count = fused
        if fused:
            program._bump_version()
        return program


# --------------------------------------------------------------------------
# tensor-parallel serving decoder (inference/serving.py, FLAGS_serving_tp)
# --------------------------------------------------------------------------
@register_pass("serving_tp_pass")
class ServingTPPass(Pass):
    """Insert the Megatron combine collectives into a serving decoder
    SHARD program (one built with ``gpt2_decoder.build_decoder_program(...,
    tp>1)``,
    whose head/width reshapes already bake the local sizes):

    * after the token+position embedding sum (``_srv_h0_*`` — both
      tables are hidden-sharded, so each rank holds ``1/tp`` of the
      columns): a ``c_concat`` (last-dim all-gather) reassembles the
      full residual width;
    * after each block's attention out-projection (``_srv_l{i}_o_*``)
      and MLP down-projection (``_srv_l{i}_ff2_*``) — the row-parallel
      matmuls whose outputs are partial sums: a ``c_allreduce_sum``;
    * around the tied-embedding logits head (``_srv_logits_*``): a
      ``c_split`` slices the full-width final hidden back to this
      rank's columns (matching ``dec_embed``'s shard), the matmul's
      partial logits then ``c_allreduce_sum`` to the full row.

    Consumers are rewired onto the combined values (pass-inserted
    producers are deliberate redirects under the verifier bracket).
    Every collective carries the serving TP ``ring_id`` so the
    lowering resolves the ``mp`` mesh axis, never the data-parallel
    ring.  ``inserted_count`` reports how many collectives landed —
    2 per block + 3 model-level for every program form."""

    ring_id: int = 0

    _H0 = re.compile(r"_srv_h0_\d+")
    _COMBINE = re.compile(r"_srv_l\d+_(?:o|ff2)_\d+")
    _LOGITS = re.compile(r"_srv_logits_\d+")

    def _redirect(self, block, start, old, new):
        for op_ in block.ops[start:]:
            op_.rename_input(old, new)

    def apply_impl(self, program):
        block = program.global_block()
        attrs = {"ring_id": int(self.ring_id)}
        inserted = 0
        i = 0
        while i < len(block.ops):
            op_ = block.ops[i]
            outs = [n for ns in op_.outputs.values() for n in ns]
            out = outs[0] if outs else None
            if op_.type == "elementwise_add" and out is not None \
                    and self._H0.fullmatch(out):
                full = block.create_var(name=out + "@TP_AG").name
                block._insert_op(i + 1, "c_concat",
                                 inputs={"X": [out]},
                                 outputs={"Out": [full]},
                                 attrs=dict(attrs))
                self._redirect(block, i + 2, out, full)
                inserted += 1
                i += 2
                continue
            if op_.type == "matmul" and out is not None \
                    and self._COMBINE.fullmatch(out):
                red = block.create_var(name=out + "@TP_AR").name
                block._insert_op(i + 1, "c_allreduce_sum",
                                 inputs={"X": [out]},
                                 outputs={"Out": [red]},
                                 attrs=dict(attrs))
                self._redirect(block, i + 2, out, red)
                inserted += 1
                i += 2
                continue
            if op_.type == "matmul" and out is not None \
                    and self._LOGITS.fullmatch(out):
                hf = op_.inputs["X"][0]
                loc = block.create_var(name=hf + "@TP_SPLIT").name
                block._insert_op(i, "c_split",
                                 inputs={"X": [hf]},
                                 outputs={"Out": [loc]},
                                 attrs=dict(attrs))
                op_.rename_input(hf, loc)
                red = block.create_var(name=out + "@TP_AR").name
                block._insert_op(i + 2, "c_allreduce_sum",
                                 inputs={"X": [out]},
                                 outputs={"Out": [red]},
                                 attrs=dict(attrs))
                self._redirect(block, i + 3, out, red)
                if program._form_extras.logits == out:
                    program._form_extras = \
                        program._form_extras._replace(logits=red)
                inserted += 2
                i += 3
                continue
            i += 1
        self.inserted_count = inserted
        if inserted:
            program._bump_version()
        return program


# ==========================================================================
# Plan-driven memory relief (rematerialization / host offload / plan
# escalation), priced per-var by the calibrated cost model
# ==========================================================================
_RELIEF_SCOPE = "/memory_relief/"
_RELIEF_MARK = "@RELIEF@"
_REMAT_SUFFIX = "@RELIEF@REMAT"
_D2H_SUFFIX = "@RELIEF@D2H"   # endswith @D2H => zero device bytes (planner)
_H2D_SUFFIX = "@RELIEF@H2D"


def _role_of(op_) -> int:
    try:
        return int(op_.attrs.get("op_role", 0))
    except Exception:
        return 0


def _read_in_subblocks(program: Program, name: str) -> bool:
    for blk in program.blocks:
        if blk.idx == 0:
            continue
        for op_ in blk.ops:
            if name in op_.input_arg_names:
                return True
    return False


def price_relief_candidates(program: Program, plan, cm, mode: str = "auto",
                            done=()) -> List[dict]:
    """Price remat / offload fixes for every activation whose lifetime
    crosses the modeled peak op, cheapest modeled seconds-per-byte-saved
    first.  ``plan`` is a ``MemoryPlan``; ``cm`` a ``CostModel``.  Only
    fixes that can actually lower *the* peak qualify: the var must be
    produced before and next consumed after ``plan.peak_op_index``."""
    from ..backward import OpRole
    from ..ops.registry import OPS
    from ..utils.cost_model import COMM_OPS, op_time_s
    from .verifier import EMPTY

    block = program.global_block()
    ops = list(block.ops)
    peak_i = plan.peak_op_index
    if peak_i is None:
        return []
    done = set(done)
    producer_at: Dict[str, int] = {}
    consumers: Dict[str, List[int]] = {}
    writers: Dict[str, List[int]] = {}
    for i, op_ in enumerate(ops):
        for nm in op_.input_arg_names:
            consumers.setdefault(nm, []).append(i)
        for nm in op_.output_arg_names:
            producer_at.setdefault(nm, i)
            writers.setdefault(nm, []).append(i)
    # per-op compute time; collectives ride the comm stream and hide
    # nothing for the host link
    op_s = [0.0 if op_.type in COMM_OPS else op_time_s(op_, block, cm)
            for op_ in ops]
    cum = [0.0]
    for s in op_s:
        cum.append(cum[-1] + s)  # cum[i] = compute time before op i

    bwd_bit = int(OpRole.Backward)
    out: List[dict] = []
    for name, info in (plan.per_var or {}).items():
        if info.get("class") != "activation" or info.get("resident"):
            continue
        if name in done or _RELIEF_MARK in name or name == EMPTY:
            continue
        saved = int(info.get("dev_bytes") or 0)
        if saved <= 0:
            continue
        p = producer_at.get(name)
        cons = consumers.get(name, [])
        bwd = [i for i in cons if _role_of(ops[i]) & bwd_bit]
        fwd = [i for i in cons if not (_role_of(ops[i]) & bwd_bit)]
        if p is None or not bwd:
            continue
        f_last = max(fwd) if fwd else p
        b_first = min(bwd)
        if not (f_last < peak_i < b_first):
            continue
        v = block._find_var_recursive(name)
        if v is None or v.shape is None:
            continue
        if _read_in_subblocks(block.program, name):
            continue  # sub-block capture: renaming would miss readers
        # ---- (a) rematerialize: replay the producer before b_first ----
        if mode in ("remat", "auto") and fwd:
            P = ops[p]
            d = OPS.get(P.type)
            real_outs = [o for o in P.output_arg_names if o != EMPTY]
            ok = (d is not None and not d.stateful and not d.host
                  and P.type not in COMM_OPS
                  and real_outs == [name]
                  and name not in P.input_arg_names
                  and not any(isinstance(a, Block)
                              for a in P.attrs.values()))
            if ok:
                # every producer input must still hold the same value
                # at the replay point
                for nm in set(P.input_arg_names):
                    if any(p < w < b_first for w in writers.get(nm, ())):
                        ok = False
                        break
            # replaying the producer revives its inputs: any input
            # that currently dies before the peak would be dragged back
            # across it, un-saving its own bytes — charge that against
            # the fix (single-op replay granularity: a chain remat that
            # nets zero is skipped, offload covers those vars instead)
            net = saved
            if ok:
                for nm in set(P.input_arg_names):
                    inm = (plan.per_var or {}).get(nm)
                    if inm is None or inm.get("resident"):
                        continue
                    last_use = max(consumers.get(nm, [p]) + [p])
                    if last_use < peak_i:
                        net -= int(inm.get("dev_bytes") or 0)
            if ok and net > 0:
                cost = max(op_s[p], cm.launch_s)
                out.append({"var": name, "fix": "remat",
                            "saved_bytes": net, "cost_s": cost,
                            "seconds_per_byte": cost / net,
                            "producer_index": p, "f_last": f_last,
                            "b_first": b_first})
        # ---- (b) host offload: d2h after f_last, h2d hoisted so the
        # transfer hides behind backward compute (r14 double-buffering) --
        if mode in ("offload", "auto"):
            d2h_s = saved / cm.d2h_bytes_per_s
            h2d_s = saved / cm.h2d_bytes_per_s
            hide_d2h = max(cum[peak_i] - cum[min(f_last + 1, len(ops))],
                           0.0)
            # hoist the h2d back from the consumer until the transfer
            # hides behind backward compute — but never at-or-before
            # the peak op, else the value is back on device at the
            # peak and the fix saves nothing
            h = b_first
            acc = 0.0
            while h - 1 > max(f_last + 1, peak_i) and acc < h2d_s:
                h -= 1
                acc += op_s[h]
            cost = (2.0 * cm.launch_s + max(0.0, d2h_s - hide_d2h)
                    + max(0.0, h2d_s - acc))
            out.append({"var": name, "fix": "offload",
                        "saved_bytes": saved, "cost_s": cost,
                        "seconds_per_byte": cost / saved,
                        "f_last": f_last, "b_first": b_first,
                        "h_insert": h})
    out.sort(key=lambda c: (c["seconds_per_byte"], c["var"], c["fix"]))
    return out


def relief_candidate_summary(program: Program, plan, top: int = 3,
                             feed_names: Sequence[str] = (),
                             fetch_names: Sequence[str] = ()) -> List[dict]:
    """Cheapest candidate fix per var, for the over-budget warning
    (actionable even with FLAGS_memory_relief=off)."""
    from ..utils.cost_model import default_cost_model

    block = program.global_block()
    cm = default_cost_model(list(block.ops), block)
    best: Dict[str, dict] = {}
    for c in price_relief_candidates(program, plan, cm, mode="auto"):
        best.setdefault(c["var"], c)  # already sorted cheapest-first
    return [{k: c[k] for k in ("var", "fix", "saved_bytes", "cost_s",
                               "seconds_per_byte")}
            for c in list(best.values())[:int(top)]]


@register_pass("memory_relief_pass")
class MemoryReliefPass(Pass):
    """Spend modeled recompute time or host-transfer time to buy back
    HBM when ``plan_memory()``'s modeled peak exceeds
    ``FLAGS_hbm_budget_mb`` (``FLAGS_memory_relief={off,remat,offload,
    auto}``; ``off`` leaves the pipeline byte-identical).

    Greedy loop: price every candidate fix (remat / offload / plan
    escalation), apply the cheapest by modeled seconds-per-byte-saved,
    re-run ``plan_memory()`` so savings compound, repeat until the peak
    fits.  Decisions land in ``self.report`` (attached to
    ``compiled._memory_plan.relief`` by ``plan_and_surface``):

    * **remat** — the producing op is replayed immediately before the
      first backward consumer (same op, same inputs: bit-identical) and
      backward readers are redirected to the ``@RELIEF@REMAT`` copy, so
      the original activation dies at its last forward consumer.
    * **offload** — a ``memcpy_d2h`` right after the last forward
      consumer stages the value to host (``@D2H`` names charge zero
      device bytes in the planner) and a ``memcpy_h2d`` hoisted far
      enough ahead of the backward consumer that the transfer hides
      behind backward compute (the r14 double-buffering rule; the
      resulting windows are validated by the r10
      ``check_prefetch_plan`` rule).
    * **plan** — when modeled cheaper, escalate the r16 parallel plan
      instead (raise the ZeRO stage / shrink the prefetch window); the
      caller picks the new ``stage``/``prefetch_depth`` out of the
      report.

    Raises ``MemoryBudgetError`` naming the residual gap when the peak
    still does not fit and ``FLAGS_hbm_budget_strict`` is set.
    """

    feed_names: Sequence[str] = ()
    fetch_names: Sequence[str] = ()
    ndev: int = 1
    stage = None            # None: FLAGS_dp_sharding
    use_shard_map = None
    prefetch_depth = None   # None: FLAGS_dp_prefetch_depth
    scope = None
    mode: str = "auto"
    budget = None           # bytes; None: FLAGS_hbm_budget_mb
    allow_escalate: bool = False
    max_fixes: int = 64
    report: Optional[dict] = None

    def apply_impl(self, program: Program) -> Program:
        from ..utils.cost_model import default_cost_model
        from ..utils.flags import flag
        from . import memory_plan as _mp

        block = program.global_block()
        budget = int(self.budget) if self.budget else _mp.budget_bytes()
        mode = str(self.mode or "auto")
        stage = self.stage
        if stage is None:
            stage = int(flag("dp_sharding") or 0)
        pf_depth = self.prefetch_depth
        if pf_depth is None:
            pf_depth = int(flag("dp_prefetch_depth") or 0)
        report = self.report = {
            "mode": mode, "engaged": False, "budget_bytes": int(budget),
            "peak_before_bytes": 0, "peak_after_bytes": 0, "fixes": [],
            "bytes_saved": 0, "modeled_overhead_s": 0.0,
            "stage": int(stage), "prefetch_depth": int(pf_depth),
            "offload_windows": [],
        }
        if not budget or mode == "off":
            return program

        def replan(st=None, pf=None):
            return _mp.plan_memory(
                program, feed_names=tuple(self.feed_names),
                fetch_names=tuple(self.fetch_names), ndev=int(self.ndev),
                stage=(stage if st is None else st),
                use_shard_map=self.use_shard_map,
                prefetch_depth=(pf_depth if pf is None else pf),
                scope=self.scope)

        plan = replan()
        report["peak_before_bytes"] = int(plan.peak_bytes)
        report["peak_after_bytes"] = int(plan.peak_bytes)
        if plan.peak_bytes <= budget:
            return program
        report["engaged"] = True
        cm = default_cost_model(list(block.ops), block)
        done: set = set()
        changed = False
        while (plan.peak_bytes > budget
               and len(report["fixes"]) < int(self.max_fixes)):
            cands = price_relief_candidates(program, plan, cm, mode=mode,
                                            done=done)
            cands += self._price_h2d_sinks(block, plan, cm)
            cands.sort(key=lambda c: c["seconds_per_byte"])
            best = cands[0] if cands else None
            if self.allow_escalate and mode == "auto":
                esc = self._price_escalation(program, plan, cm, replan,
                                             stage, pf_depth)
                if esc is not None and (
                        best is None
                        or esc["seconds_per_byte"]
                        < best["seconds_per_byte"]):
                    best = esc
            if best is None:
                break
            before = plan.peak_bytes
            if best["fix"] == "remat":
                self._apply_remat(block, best)
                done.add(best["var"])
            elif best["fix"] == "offload":
                self._apply_offload(block, best)
                done.add(best["var"])
            elif best["fix"] == "sink":
                op_ = block.ops.pop(best["op_index"])
                block.ops.insert(best["new_index"], op_)
                changed = True
            else:  # plan escalation
                stage = int(best["stage"])
                pf_depth = int(best["prefetch_depth"])
                report["stage"] = stage
                report["prefetch_depth"] = pf_depth
            plan = replan()
            fx = {"var": best["var"], "fix": best["fix"],
                  "saved_bytes": int(max(before - plan.peak_bytes, 0)),
                  "modeled_cost_s": float(best["cost_s"]),
                  "seconds_per_byte": float(best["seconds_per_byte"])}
            if best["fix"] == "plan":
                fx["stage"] = stage
                fx["prefetch_depth"] = pf_depth
            report["fixes"].append(fx)
            report["modeled_overhead_s"] = float(
                report["modeled_overhead_s"] + best["cost_s"])
            if best["fix"] != "plan":
                changed = True
        report["peak_after_bytes"] = int(plan.peak_bytes)
        report["bytes_saved"] = int(
            max(report["peak_before_bytes"] - plan.peak_bytes, 0))
        if changed:
            program._bump_version()
            self._check_offload_windows(block)
        if plan.peak_bytes > budget:
            gap_mb = (plan.peak_bytes - budget) / float(1 << 20)
            report["residual_gap_mb"] = round(gap_mb, 3)
            from ..utils.flags import flag as _flag
            if bool(_flag("hbm_budget_strict")):
                raise _mp.MemoryBudgetError(
                    f"[memory_relief] modeled HBM peak "
                    f"{plan.peak_bytes / float(1 << 20):.1f} MB still "
                    f"exceeds FLAGS_hbm_budget_mb="
                    f"{budget / float(1 << 20):.1f} MB after "
                    f"{len(report['fixes'])} relief fix(es): residual "
                    f"gap {gap_mb:.3f} MB (mode={mode}; raise the "
                    f"budget, enable more fix kinds, or shrink the "
                    f"model)")
        return program

    # -- fix application ---------------------------------------------------
    def _apply_remat(self, block: Block, cand: dict) -> None:
        from ..backward import OP_ROLE_KEY, OpRole

        name = cand["var"]
        b_first = cand["b_first"]
        P = block.ops[cand["producer_index"]]
        new = name + _REMAT_SUFFIX
        src = block._find_var_recursive(name)
        if not block.has_var(new):
            block.create_var(name=new, shape=list(src.shape),
                             dtype=src.dtype)
        outputs = {slot: [new if n == name else n for n in names]
                   for slot, names in P.outputs.items()}
        attrs = dict(P.attrs)
        attrs[OP_ROLE_KEY] = int(OpRole.Backward)
        attrs["op_namescope"] = _RELIEF_SCOPE
        block._insert_op(b_first, P.type,
                         inputs={k: list(v) for k, v in P.inputs.items()},
                         outputs=outputs, attrs=attrs)
        for op_ in block.ops[b_first + 1:]:
            op_.rename_input(name, new)

    def _apply_offload(self, block: Block, cand: dict) -> None:
        from ..backward import OP_ROLE_KEY, OpRole

        name = cand["var"]
        f_last, h = cand["f_last"], cand["h_insert"]
        src = block._find_var_recursive(name)
        d2h, h2d = name + _D2H_SUFFIX, name + _H2D_SUFFIX
        for nm in (d2h, h2d):
            if not block.has_var(nm):
                block.create_var(name=nm, shape=list(src.shape),
                                 dtype=src.dtype)
        role_fwd = _role_of(block.ops[f_last])
        block._insert_op(f_last + 1, "memcpy_d2h",
                         inputs={"X": [name]}, outputs={"Out": [d2h]},
                         attrs={OP_ROLE_KEY: int(role_fwd),
                                "op_namescope": _RELIEF_SCOPE})
        hi = h + 1  # shifted by the d2h insert
        block._insert_op(hi, "memcpy_h2d",
                         inputs={"X": [d2h]}, outputs={"Out": [h2d]},
                         attrs={OP_ROLE_KEY: int(OpRole.Backward),
                                "op_namescope": _RELIEF_SCOPE})
        for op_ in block.ops[hi + 1:]:
            op_.rename_input(name, h2d)

    # -- window tightening: an h2d staged for overlap can end up BEFORE
    # the (moved) peak as the greedy loop reshapes the timeline — sinking
    # it just past the peak trades exposed transfer time for peak bytes
    def _price_h2d_sinks(self, block, plan, cm):
        from ..utils.cost_model import COMM_OPS, op_time_s

        peak_i = plan.peak_op_index
        if peak_i is None:
            return []
        ops = list(block.ops)
        op_s = [0.0 if o.type in COMM_OPS else op_time_s(o, block, cm)
                for o in ops]
        cum = [0.0]
        for t in op_s:
            cum.append(cum[-1] + t)
        out = []
        for i, op_ in enumerate(ops):
            if op_.type != "memcpy_h2d" \
                    or op_.attrs.get("op_namescope") != _RELIEF_SCOPE \
                    or i >= peak_i:
                continue
            nm = (op_.outputs.get("Out") or [None])[0]
            cons = [j for j in range(i + 1, len(ops))
                    if nm in ops[j].input_arg_names]
            if not cons or min(cons) <= peak_i:
                continue  # value needed at/before the peak: cannot sink
            saved = int((plan.per_var or {}).get(nm, {}).get("dev_bytes")
                        or 0)
            if saved <= 0:
                continue
            fc = min(cons)
            src = (op_.inputs.get("X") or [None])[0]
            h2d_s = saved / cm.h2d_bytes_per_s
            exposed_old = max(0.0, h2d_s - (cum[fc] - cum[i + 1]))
            exposed_new = max(0.0, h2d_s - (cum[fc] - cum[peak_i + 1]))
            cost = max(exposed_new - exposed_old, 0.0) + cm.launch_s
            out.append({"var": nm, "fix": "sink", "saved_bytes": saved,
                        "cost_s": cost, "seconds_per_byte": cost / saved,
                        "op_index": i, "new_index": peak_i,
                        "first_consumer": fc, "src": src})
        return out

    # -- fix (c): escalate the r16 parallel plan ---------------------------
    def _price_escalation(self, program, plan, cm, replan, stage,
                          pf_depth):
        if int(self.ndev) <= 1:
            return None
        import dataclasses

        from ..parallel import plan_search as _ps

        base = _ps.ParallelPlan.from_flags()
        base = dataclasses.replace(base, stage=int(stage),
                                   prefetch_depth=int(pf_depth))
        usm = bool(self.use_shard_map)
        try:
            t0 = _ps.modeled_step_time(
                program, int(self.ndev), base, usm)["modeled_step_s"]
        except Exception:
            return None
        moves = []
        if int(stage) < 3:
            moves.append((int(stage) + 1, int(pf_depth)))
        elif int(pf_depth) > 0:
            moves.append((int(stage), 0))
        best = None
        for st, pf in moves:
            try:
                p2 = replan(st=st, pf=pf)
                t2 = _ps.modeled_step_time(
                    program, int(self.ndev),
                    dataclasses.replace(base, stage=st,
                                        prefetch_depth=pf),
                    usm)["modeled_step_s"]
            except Exception:
                continue
            saved = int(plan.peak_bytes - p2.peak_bytes)
            if saved <= 0:
                continue
            cost = max(float(t2 - t0), 0.0) + cm.launch_s
            cand = {"var": "<plan>", "fix": "plan", "saved_bytes": saved,
                    "cost_s": cost, "seconds_per_byte": cost / saved,
                    "stage": st, "prefetch_depth": pf}
            if best is None or (cand["seconds_per_byte"]
                                < best["seconds_per_byte"]):
                best = cand
        return best

    # -- offload windows must satisfy the r10 prefetch-window rule ---------
    def _check_offload_windows(self, block: Block) -> None:
        from . import verifier

        ops = list(block.ops)
        records = []
        for i, op_ in enumerate(ops):
            if op_.type != "memcpy_h2d" \
                    or op_.attrs.get("op_namescope") != _RELIEF_SCOPE:
                continue
            out = (op_.outputs.get("Out") or [None])[0]
            cons = [j for j in range(i + 1, len(ops))
                    if out in ops[j].input_arg_names]
            if not cons:
                continue
            records.append({"param": out, "gather_at": i + 1,
                            "first_consumer": min(cons),
                            "last_consumer": max(cons)})
        self.report["offload_windows"] = records
        if records and verifier.enabled():
            verifier.check_prefetch_plan_or_raise(
                ops, block, records, "memory_relief_offload")
