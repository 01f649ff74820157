"""Static liveness-based HBM memory planner over Program/Block.

Every headline memory claim this repo makes (the ZeRO ladder's "1/ndev
bytes per device", the KV pool's residency, the r14 fusion's saved
traffic) was, until now, an assertion derived by hand.  This module is
the memory model those claims check against: a pure static analysis
that reuses the verifier's per-op read/write sets
(framework/verifier.py ``op_reads_writes`` — registry OpDef metadata,
in-place ops write their inputs via output==input name) to compute

* per-var **lifetime intervals** over the op list (state is resident
  from op 0; an activation lives from its defining write to its last
  read; fetches and persistable writes live to the end),
* the per-op **live-set byte timeline** (per device), and
* the **peak-HBM op** — the op index where modeled residency tops out,
  with the top live vars at that point.

It is aware of the structural facts that make a naive sum-of-var-bytes
wrong here:

* **donated input/output aliasing** (the executor step session): an
  in-place state update reuses its input buffer under buffer donation;
  with donation off (``FLAGS_tpu_donate_buffers=0`` /
  ``FLAGS_tpu_step_session=0``) the old and new copies coexist and the
  model charges the extra copy from the update to the end of the step;
* **ZeRO row-sharding** (``FLAGS_dp_sharding``): stage-3 parameters and
  stage>=1 optimizer state count 1/ndev per device (same partition-rule
  engine + planning helpers as parallel/data_parallel.py — shared, so
  the model and the runtime cannot drift); stage>=2 gradients count
  1/ndev from their
  reduce-scatter point (shard_map path: after the
  ``c_fused_reduce_scatter`` op; pjit path: throughout, GSPMD never
  materializes the full gradient);
* **fused gradient buckets**: ``c_fused_allreduce`` /
  ``c_fused_reduce_scatter`` concatenate their members into one flat
  transient buffer inside the lowering — modeled as an explicit per-op
  transient (see :data:`TRANSIENT_BYTES`);
* **ZeRO-3 prefetch windows**: a gathered parameter is transiently
  full-size for exactly its window — the records come from
  ``compiled._prefetch_plan`` (or are re-derived with
  ``data_parallel._plan_param_prefetch`` for standalone analysis);
  with depth 0 the just-in-time gather bumps each consumer op instead;
* **while→scan carry reuse**: a sub-block's vars are NOT summed into
  the parent — the loop body's own peak (carries reuse their buffers
  across iterations under scan) is charged as a transient at the loop
  op;
* **fixed resident blocks** (the serving KV page pool): scope-resident
  persistable state the program reads (the pools are block vars of the
  decode program, so they fall out of the state analysis naturally);
  ``extra_resident`` adds engine-level blocks the program cannot see.

Three surfaces consume the plan:

1. compile time — ``Executor._compile`` and the DP compile path attach
   ``_memory_plan``, publish the ``hbm_modeled_peak_bytes`` gauge, and
   enforce ``FLAGS_hbm_budget_mb`` (warn; ``FLAGS_hbm_budget_strict``
   raises :class:`MemoryBudgetError` naming the peak op and the top-10
   live vars);
2. runtime reconciliation — ``utils/memory.py`` measures the per-step
   peak (PJRT allocator counters on chip, a shard-aware live-arrays
   census on the CPU proxy) and ``tools/mem_report.py`` prints modeled
   vs measured side by side;
3. the failure path — :func:`record_oom_debris` dumps plan + telemetry
   + trace to ``FLAGS_oom_debris_dir`` when the executor catches a
   ``RESOURCE_EXHAUSTED``, so a chip OOM is diagnosable post-mortem.

The analysis is pure: it registers no ops, mutates no program, and
changes no numerics (pinned by test).
"""
from __future__ import annotations

import json
import os
import threading
import time
import traceback
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .core import Block, Program
from .dtype import to_numpy_dtype
from .verifier import EMPTY, op_reads_writes

__all__ = [
    "MemoryPlan", "MemoryBudgetError", "plan_memory", "var_bytes",
    "check_budget", "budget_bytes", "memory_audit", "transient_bytes",
    "TRANSIENT_BYTES", "AUDITED_DEFAULT", "is_resource_exhausted",
    "record_oom_debris", "emit_trace_counters",
]

_MB = float(1 << 20)


# ==========================================================================
# per-var byte model
# ==========================================================================
def var_bytes(block: Block, name: str, assumed_batch: int = 64
              ) -> Optional[int]:
    """Full (unsharded) bytes of one var: shape x dtype itemsize, with
    dynamic (-1) dims standing in as ``assumed_batch`` (the cost-model
    convention).  None when the var is undeclared or shapeless (host
    ids, LoD metadata) — such names cost the model nothing."""
    var = block._find_var_recursive(name)
    if var is None or var.shape is None or var.dtype is None:
        return None
    n = 1
    for d in var.shape:
        if d is None:
            return None
        d = int(d)
        n *= assumed_batch if d < 0 else max(d, 1)
    try:
        itemsize = np.dtype(to_numpy_dtype(var.dtype)).itemsize
    except Exception:
        return None
    return int(n) * int(itemsize)


#: var classes the plan reports (resident-vs-transient breakdown)
CLASSES = ("param", "opt_state", "grad", "feed", "kv_pool", "state",
           "activation")


def _classify(name: str, *, params: set, opt_state: set, feeds: set,
              resident: bool) -> str:
    if name in feeds:
        return "feed"
    if name in params:
        return "param"
    if name in opt_state:
        return "opt_state"
    if name.endswith("@GRAD") or "@GRAD@" in name:
        return "grad"
    if name.startswith("kv_k_") or name.startswith("kv_v_"):
        # the paged K/V pools: one fixed device block per layer per
        # side, sized by the ALLOCATOR's pool shape — page-level
        # bookkeeping (r19 CoW sharing included) happens INSIDE this
        # block, so a page mapped by N sequences is modeled once, and
        # the modeled kv_pool bytes agree with the runtime census
        # whether or not prefixes are shared (pinned by test)
        return "kv_pool"
    return "state" if resident else "activation"


# ==========================================================================
# per-op transient model + the coverage-gate audit surface
# ==========================================================================
def _fused_bucket_payload(op_, block, assumed_batch):
    total = 0
    for n in op_.inputs.get("X", []):
        b = var_bytes(block, n, assumed_batch)
        if b:
            total += b
    return total


def _t_fused_allreduce(op_, block, ndev, assumed_batch):
    """Flat concat of the bucket (one payload) + the reduced flat
    result (one payload) before it is sliced back per member."""
    return 2 * _fused_bucket_payload(op_, block, assumed_batch)


def _t_fused_reduce_scatter(op_, block, ndev, assumed_batch):
    """Flat (nranks, total/nranks) payload + the 1/ndev scattered
    shard."""
    p = _fused_bucket_payload(op_, block, assumed_batch)
    return p + (p // max(ndev, 1))


def _t_allgather(op_, block, ndev, assumed_batch):
    """The gathered result is ndev x the input — the declared output
    var usually carries the gathered shape already, but the transient
    concat buffer is charged explicitly so a shapeless output cannot
    hide it."""
    return ndev * _fused_bucket_payload(op_, block, assumed_batch)


def _t_coalesce(op_, block, ndev, assumed_batch):
    """coalesce_tensor materializes one flat FusedOutput over all
    inputs."""
    total = 0
    for names in op_.inputs.values():
        for n in names:
            b = var_bytes(block, n, assumed_batch)
            if b:
                total += b
    return total


def _t_paged_attention(op_, block, ndev, assumed_batch):
    """The CPU gather fallback materializes per-sequence K/V gathers of
    the block-table width: ~2 x (num_seqs, table_width*page_size,
    head_dim) — bounded above by 2 x the pool bytes it gathers from.
    (On TPU the Pallas kernel streams pages; this is the fallback's
    worst case, which is the honest CPU-proxy number.)"""
    total = 0
    for slot in ("KCache", "VCache"):
        for n in op_.inputs.get(slot, []):
            b = var_bytes(block, n, assumed_batch)
            if b:
                total += b
    return total


def _slot_bytes(op_, block, assumed_batch, slot):
    return sum(var_bytes(block, n, assumed_batch) or 0
               for n in op_.inputs.get(slot, []))


def _t_mla_paged_attention(op_, block, ndev, assumed_batch):
    """As ``paged_attention``: the gather fallback materializes each
    row's latent rows over the block-table width, bounded above by the
    pool it gathers from (the kernel streams pages)."""
    return _slot_bytes(op_, block, assumed_batch, "Cache")


def _t_mla_prefill_attention(op_, block, ndev, assumed_batch):
    """``W_kvb`` widens the latent rows to per-head keys and values, and
    queries, keys and values are laid heads-first for the kernel: about
    four arrays the size of QNope beyond the declared slots (the jnp
    fallback's score blocks are smaller at any prompt a test runs)."""
    return 4 * _slot_bytes(op_, block, assumed_batch, "QNope")


def _t_gqa_paged_attention(op_, block, ndev, assumed_batch):
    """As ``paged_attention``: the gather fallback materializes each row's
    K and V rows over the block-table width, bounded above by the pools it
    gathers from (the kernel streams pages)."""
    return _slot_bytes(op_, block, assumed_batch, "KCache") \
        + _slot_bytes(op_, block, assumed_batch, "VCache")


def _t_gqa_prefill_attention(op_, block, ndev, assumed_batch):
    """Queries, keys and values are laid heads-first for the kernel and its
    float32 output is laid back and gated: about four arrays the size of Q
    beyond the declared slots (the jnp fallback's score blocks are smaller
    at any prompt a test runs)."""
    return 4 * _slot_bytes(op_, block, assumed_batch, "Q")


def _t_kda_mixer(op_, block, ndev, assumed_batch):
    """The whole KDA mixer in one op: per row the convolution's inputs and
    outputs (``[q | k | v]`` twice), the decay, the recurrence's output and
    the gate, all float32: about ten arrays of a row's ``heads * head_dim``
    values, whatever X's own width.  ``gdn_mixer`` the same, by its
    ``value_dim`` (the wider of its two head sizes)."""
    x = op_.inputs.get("X", [])
    v = block._find_var_recursive(x[0]) if x else None
    shape = getattr(v, "shape", None) or ()
    rows = shape[0] if shape and isinstance(shape[0], int) and shape[0] > 0 \
        else assumed_batch
    width = int(op_.attrs.get("heads", 1)) * int(
        op_.attrs.get("head_dim", 0) or op_.attrs.get("value_dim", 1))
    return 10 * 4 * rows * width


def _t_moe_experts(op_, block, ndev, assumed_batch):
    """Every token is copied once per chosen expert into expert order,
    passed through the grouped matmuls and copied back: about three
    arrays of k times X's bytes (sorted rows, the down projection's
    rows, their unsorted copy; the gated middle is narrower)."""
    idx = op_.inputs.get("Idx", [])
    v = block._find_var_recursive(idx[0]) if idx else None
    shape = getattr(v, "shape", None) or ()
    k = shape[-1] if shape and isinstance(shape[-1], int) \
        and shape[-1] > 0 else 8
    return 3 * k * _slot_bytes(op_, block, assumed_batch, "X")


def _t_sample_token(op_, block, ndev, assumed_batch):
    """The top-k/top-p filters sort the logits rows and build filtered
    copies before the categorical draw: ~3 logits-sized f32 temporaries
    (sorted values, cumulative probs, masked logits) beyond the
    (num_rows,) output — charged explicitly because the output is tiny
    and would hide them under the default."""
    total = 0
    for n in op_.inputs.get("Logits", []):
        b = var_bytes(block, n, assumed_batch)
        if b:
            total += 3 * b
    return total


def _t_subblock(op_, block, ndev, assumed_batch):
    """Control-flow ops: the body's own peak (computed over vars the
    sub-block declares — loop carries alias the parent's values under
    the scan lowering, so they are charged once, in the parent)."""
    total = 0
    for v in op_.attrs.values():
        if isinstance(v, Block):
            total += _subblock_peak(v, assumed_batch)
    return total


def _subblock_peak(blk: Block, assumed_batch: int) -> int:
    """Live-set peak of one sub-block counting only its OWN vars
    (captures live in an ancestor are already charged there).  Carries
    reuse their buffers across iterations (while→scan), so one
    iteration's live set IS the loop's contribution."""
    events = [(i,) + op_reads_writes(op_) for i, op_ in enumerate(blk.ops)]
    own = set(blk.vars)
    first: Dict[str, int] = {}
    last: Dict[str, int] = {}
    for i, rs, ws in events:
        for n in ws:
            if n in own:
                first.setdefault(n, i)
                last[n] = i
        for n in rs:
            if n in own:
                last[n] = i
                first.setdefault(n, 0)  # read-before-write: carry-like
    n_ops = max(len(blk.ops), 1)
    diff = [0] * (n_ops + 1)
    for n, lo in first.items():
        b = var_bytes(blk, n, assumed_batch)
        if not b:
            continue
        hi = last.get(n, lo)
        diff[lo] += b
        diff[hi + 1] -= b
    peak = cur = 0
    for i in range(n_ops):
        cur += diff[i]
        peak = max(peak, cur)
    # nested blocks
    for op_ in blk.ops:
        for v in op_.attrs.values():
            if isinstance(v, Block):
                peak = max(peak, _subblock_peak(v, assumed_batch))
    return peak


#: op type -> fn(op, block, ndev, assumed_batch) -> extra transient
#: device bytes the op's lowering materializes BEYOND its declared
#: inputs/outputs.  This is the planner's explicit byte model — the
#: analog of cost_model._EPILOGUE_TRAFFIC, and like it, guarded by the
#: op-sweep coverage gate (tests/test_memory_plan.py): a registered op
#: must either appear here or in AUDITED_DEFAULT below, so a new op
#: with a hidden full-size temporary cannot ride the silent default.
TRANSIENT_BYTES = {
    "c_fused_allreduce": _t_fused_allreduce,
    "c_fused_reduce_scatter": _t_fused_reduce_scatter,
    "c_allgather": _t_allgather,
    "c_concat": _t_allgather,          # all-gather then concat: same peak
    "coalesce_tensor": _t_coalesce,
    "paged_attention": _t_paged_attention,
    "mla_paged_attention": _t_mla_paged_attention,
    "mla_prefill_attention": _t_mla_prefill_attention,
    "gqa_paged_attention": _t_gqa_paged_attention,
    "gqa_prefill_attention": _t_gqa_prefill_attention,
    "moe_experts": _t_moe_experts,
    "kda_mixer": _t_kda_mixer,
    "gdn_mixer": _t_kda_mixer,
    "sample_token": _t_sample_token,
    "while": _t_subblock,
    "while_loop": _t_subblock,
    "recurrent": _t_subblock,
    "conditional_block": _t_subblock,
    "conditional_block_infer": _t_subblock,
    "cond": _t_subblock,
    "run_program": _t_subblock,
}

#: ops audited (r15) to have NO device transient beyond their declared
#: inputs/outputs: the lowering is jnp/lax compositions whose
#: intermediates are op-output-sized or smaller, or the op is host-side
#: (RPC, IO, LoD bookkeeping) and owns no device buffer at all.  An op
#: in neither table fails the coverage sweep — classify it when you
#: register it.  Grad ops derive coverage from their forward op (the
#: generic-vjp backward replays the forward's lowering).
AUDITED_DEFAULT = frozenset("""
abs accuracy acos adadelta adagrad adam adamax adamw adaptive_pool3d
add_position_encoding addmm affine_channel affine_grid allclose
amp_check_finite_and_scale anchor_generator arg_max arg_min argsort
array_to_lod_tensor asin assert assert_op assign assign_value atan
attention_lstm auc average_accumulates batch_fc batch_norm batched_iou
bce_loss beam_gather_states beam_search beam_search_decode bicubic_interp
bilinear_interp bilinear_tensor_product bipartite_match bmm box_clip
box_coder box_decoder_and_assign bpr_loss brelu broadcast_tensors cast ceil
center_loss checkpoint_notify cholesky chunk_eval clip clip_by_norm
collect_fpn_proposals concat conv2d conv2d_transpose conv3d conv3d_transpose
conv_shift cos cos_sim cosh create_array create_custom_reader crf_decoding
crop crop_tensor cross cross_entropy cross_entropy2 cross_entropy_grad2
ctc_align cudnn_lstm cumsum cvm cvm_grad data_norm decayed_adagrad
deformable_conv deformable_conv_v1 deformable_psroi_pooling
deformable_roi_pooling delete_var density_prior_box depthwise_conv2d
depthwise_conv2d_transpose dequantize dequantize_abs_max dequantize_linear
dequantize_log dequeue detection_map dgc dgc_clip_by_norm dgc_momentum diag
diag_embed diag_v2 dist distribute_fpn_proposals distributed_lookup_table
distributed_lookup_table_grad dot dpsgd dropout dropout_grad dynamic_gru
dynamic_lstm dynamic_lstmp edit_distance einsum elementwise_add
elementwise_div elementwise_floordiv elementwise_max elementwise_min
elementwise_mod elementwise_mul elementwise_pow elementwise_sub elu
embedding enqueue equal erf exp expand expand_as expand_v2 expm1 eye
fake_channel_wise_dequantize_max_abs fake_channel_wise_quantize_abs_max
fake_channel_wise_quantize_dequantize_abs_max fake_dequantize_max_abs
fake_init fake_quantize_abs_max fake_quantize_dequantize_abs_max
fake_quantize_dequantize_moving_average_abs_max
fake_quantize_moving_average_abs_max fake_quantize_range_abs_max fc feed
fetch fetch_barrier fill fill_any_like fill_constant
fill_constant_batch_size_like fill_zeros_like fill_zeros_like2
filter_by_instag flatten flatten2 flatten_contiguous_range flip floor
frobenius_norm fsp ftrl gather gather_nd gather_tree gaussian_random
gaussian_random_batch_size_like gelu gen_nccl_id generate_mask_labels
generate_proposal_labels generate_proposals geo_sgd get_places
get_tensor_from_selected_rows global_step_counter greater_equal
greater_than grid_sampler group_norm gru gru_unit hard_shrink hard_sigmoid
hard_swish hash hierarchical_sigmoid hinge_loss histogram huber_loss
im2sequence increment index_sample index_select inplace_abn instance_norm
inverse iou_similarity is_empty isfinite isfinite_v2 isinf isinf_v2 isnan
isnan_v2 kldiv_loss kron l1_norm label_smooth lamb lars_momentum layer_norm
leaky_relu less_equal less_than linear_chain_crf linear_interp linspace
listen_and_serv load load_combine locality_aware_nms lod_array_length
lod_rank_table lod_reset lod_tensor_to_array log log10 log1p log2 log_loss
log_softmax logical_and logical_not logical_or logical_xor logsigmoid
logsumexp lookup_sparse_table lookup_table lookup_table_dequant
lookup_table_sparse_grad lookup_table_v2 lrn lstm lstm_unit lstmp
margin_rank_loss masked_select match_matrix_tensor matmul matmul_v2
matmul_with_flatten max_pool2d_with_index max_pool3d_with_index
max_sequence_len maximum maxout mean mean_iou memcpy merge_ids
merge_lod_tensor merge_lod_tensor_infer merge_selected_rows meshgrid
memcpy_d2h memcpy_h2d
mine_hard_examples minimum minus modified_huber_loss momentum
moving_average_abs_max_scale mse_loss mul multiclass_nms multiclass_nms2
multihead_matmul multiplex nce nearest_interp nll_loss norm not_equal
one_hot one_hot_v2 p_norm pad pad2d pad3d pad_constant_like partial_concat
partial_sum pixel_shuffle polygon_box_transform pool2d pool3d
positive_negative_pair pow precision_recall prefetch prelu print prior_box
proximal_adagrad proximal_gd prroi_pool psroi_pool pull_sparse
pull_sparse_v2 push_dense push_sparse push_sparse_v2 py_func py_func_grad
pyramid_hash quantize quantize_linear queue_generator randint random_crop
randperm range rank_attention rank_loss read read_from_array reciprocal
recv recv_save reduce_all reduce_any reduce_max reduce_mean reduce_min
reduce_prod reduce_sum ref_by_trainer_id relu relu6 reorder_lod_tensor_by_rank
requantize reshape reshape2 retinanet_detection_output
retinanet_target_assign reverse rmsprop rnn_memory_helper roi_align
roi_perspective_transform roi_pool roll round row_conv rpn_target_assign
rsqrt sample_logits sampled_softmax_with_cross_entropy sampling_id save
save_combine scale scatter scatter_nd_add seed selu send send_barrier
sequence_concat sequence_conv sequence_enumerate sequence_erase
sequence_expand sequence_expand_as sequence_mask sequence_pad sequence_pool
sequence_reshape sequence_reverse sequence_scatter sequence_slice
sequence_softmax sequence_topk_avg_pooling sequence_unpad sgd shape
shard_index share_data shrink_rnn_memory shuffle_batch shuffle_channel
sigmoid sigmoid_cross_entropy_with_logits sigmoid_focal_loss sign silu
similarity_focus sin sinh size slice smooth_l1_loss soft_relu softmax
softmax_with_cross_entropy softmax_with_cross_entropy_grad softplus
softsign space_to_depth spectral_norm split split_byref split_ids
split_lod_tensor split_selected_rows spp sqrt square squared_l2_distance
squared_l2_norm squeeze squeeze2 ssd_loss_core stack stanh strided_slice
sum swish sync_batch_norm tan tanh tanh_shrink target_assign tdm_child
tdm_sampler teacher_student_sigmoid_loss temporal_shift tensor_array_pop
tensor_array_to_tensor thresholded_relu tile top_k top_k_v2 trace transpose
transpose2 tree_conv tril_triu trilinear_interp truncated_gaussian_random
unbind unfold uniform_random uniform_random_batch_size_like unique
unique_with_counts unpool unsqueeze unsqueeze2 unstack
update_loss_scaling var_conv_2d warpctc
where where_index while_loop_grad write_to_array yolo_box yolov3_loss
select_input select_output kv_cache_append kv_dequant
latent_cache_append matmul_f32acc moe_router rms_norm rope_half
rope_interleaved slot_is_live swiglu token_score
allreduce alltoall barrier broadcast c_allreduce_max c_allreduce_min
c_allreduce_prod c_allreduce_sum c_broadcast c_comm_init c_comm_init_all
c_gen_nccl_id c_identity c_reducescatter c_split c_sync_calc_stream
c_sync_comm_stream c_wait_calc_stream c_wait_comm_stream
fused_adam fused_batch_norm_act fused_batch_norm_act_grad
fused_bn_add_activation fused_bn_add_activation_grad fused_conv_bn_act
fused_conv_bn_act_grad fused_elemwise_activation
fused_embedding_eltwise_layernorm fused_embedding_fc_lstm
fused_embedding_seq_pool fused_fc_elementwise_layernorm
fused_matmul_bias_act fused_matmul_bias_act_grad fused_momentum
fused_multihead_attention fused_multihead_attention_grad fused_sgd
fusion_gru fusion_lstm fusion_repeated_fc_relu fusion_seqconv_eltadd_relu
fusion_seqexpand_concat_fc fusion_seqpool_concat fusion_seqpool_cvm_concat
fusion_squared_mat_sub fusion_transpose_flatten_concat
""".split())
# Audit notes (what kept suspects OFF the default list): in-place
# psum-style allreduces write their input (no second buffer);
# `kv_cache_append` and `latent_cache_append` write in place into the
# donated pool; `moe_router`'s scores are (rows, experts), under its X;
# `kv_dequant` is an elementwise cast(+scale) into its declared slot;
# `c_identity`/`c_split` are views.  ON the explicit table instead:
# fused bucket collectives (flat concat payload), `c_allgather` /
# `c_concat` (ndev x payload), `coalesce_tensor` (flat FusedOutput),
# `paged_attention` (CPU fallback's per-sequence K/V gathers), and
# every sub-block op (the body's peak is invisible to the parent's
# declared slots).


def memory_audit(op_type: str) -> str:
    """Coverage verdict for one op type: ``"explicit"`` (entry in
    :data:`TRANSIENT_BYTES`), ``"default"`` (on the audited list, or a
    (higher-order) grad of a covered forward op — the generic-vjp
    backward replays the forward's lowering), ``"custom"`` (registered
    at runtime through utils/custom_op.py — the author's contract, not
    auditable statically), else ``"unclassified"`` — which the
    op-sweep-style gate turns into a test failure."""
    t = op_type
    while True:
        if t in TRANSIENT_BYTES:
            return "explicit" if t == op_type else "default"
        if t in AUDITED_DEFAULT:
            return "default"
        try:
            from ..utils.custom_op import CUSTOM_REGISTERED

            if t in CUSTOM_REGISTERED:
                return "custom"
        except Exception:
            pass
        if not t.endswith("_grad"):
            return "unclassified"
        t = t[: -len("_grad")]


def transient_bytes(op_, block: Block, ndev: int = 1,
                    assumed_batch: int = 64) -> int:
    """Extra transient device bytes op_'s lowering materializes beyond
    its declared inputs/outputs (0 for audited-default ops)."""
    fn = TRANSIENT_BYTES.get(op_.type)
    if fn is None:
        return 0
    try:
        return int(fn(op_, block, ndev, assumed_batch))
    except Exception:
        return 0


def _relief_mode() -> str:
    """The configured FLAGS_memory_relief mode ("off" default)."""
    from ..utils.flags import flag

    try:
        return str(flag("memory_relief", "off") or "off")
    except Exception:
        return "off"


#: host-staging suffix the memory_relief_pass gives its offloaded
#: copies: a ``...@D2H`` var lives in host RAM between the paired
#: memcpy_d2h / memcpy_h2d ops and holds ZERO device bytes — the whole
#: point of the offload fix
HOST_STAGE_SUFFIX = "@D2H"


# ==========================================================================
# the plan
# ==========================================================================
class MemoryBudgetError(RuntimeError):
    """Raised when FLAGS_hbm_budget_mb is exceeded under
    FLAGS_hbm_budget_strict."""


class MemoryPlan:
    """One program's modeled HBM footprint (per device)."""

    __slots__ = ("peak_bytes", "peak_op_index", "peak_op_type", "timeline",
                 "resident_bytes", "resident_by_class", "per_var",
                 "transients", "top_at_peak", "ndev", "stage", "donate",
                 "path", "assumed_batch", "n_ops", "extra_resident_bytes",
                 "prefetch_windows", "relief", "relief_candidates")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))

    # -- views -------------------------------------------------------------
    @property
    def peak_mb(self) -> float:
        return self.peak_bytes / _MB

    @property
    def resident_mb(self) -> float:
        return self.resident_bytes / _MB

    def top_live_at_peak(self, k: int = 10) -> List[Tuple[str, int]]:
        return list(self.top_at_peak[:k])

    def as_dict(self, top: int = 10) -> dict:
        return {
            "peak_bytes": int(self.peak_bytes),
            "peak_mb": round(self.peak_mb, 3),
            "peak_op": {"index": self.peak_op_index,
                        "type": self.peak_op_type},
            "resident_bytes": int(self.resident_bytes),
            "resident_mb": round(self.resident_mb, 3),
            "resident_by_class": {k: int(v) for k, v in
                                  sorted(self.resident_by_class.items())},
            "extra_resident_bytes": int(self.extra_resident_bytes),
            "top_live_at_peak": [
                {"var": n, "bytes": int(b), "class": c}
                for n, b, c in self.top_live_at_peak(top)],
            "transient_peak_bytes": max(
                (t["bytes"] for t in self.transients), default=0),
            "n_transients": len(self.transients),
            "prefetch_windows": self.prefetch_windows,
            "n_ops": self.n_ops,
            "ndev": self.ndev,
            "stage": self.stage,
            "path": self.path,
            "donate": bool(self.donate),
            "assumed_batch": self.assumed_batch,
            # relief decision table (memory_relief_pass) — the OOM
            # debris plan.json carries it for free; with the pass off
            # the entry says so explicitly
            "relief": (self.relief if self.relief is not None
                       else {"mode": _relief_mode(), "engaged": False}),
        }

    def format_table(self, top: int = 10) -> str:
        d = self.as_dict(top)
        lines = [
            f"modeled peak: {d['peak_mb']:.3f} MB at op "
            f"#{d['peak_op']['index']} ({d['peak_op']['type']}) over "
            f"{d['n_ops']} ops  [ndev={d['ndev']} stage={d['stage']} "
            f"path={d['path']} donate={d['donate']}]",
            f"resident: {d['resident_mb']:.3f} MB  "
            + "  ".join(f"{k}={v / _MB:.3f}MB"
                        for k, v in d["resident_by_class"].items() if v),
            f"{'Top live vars at peak':<44} {'MB':>10}  class",
        ]
        for row in d["top_live_at_peak"]:
            lines.append(f"{row['var'][:44]:<44} "
                         f"{row['bytes'] / _MB:>10.3f}  {row['class']}")
        relief = d.get("relief") or {}
        if relief.get("engaged"):
            lines.append(
                f"relief[{relief.get('mode')}]: peak "
                f"{relief.get('peak_before_bytes', 0) / _MB:.3f} -> "
                f"{relief.get('peak_after_bytes', 0) / _MB:.3f} MB, "
                f"saved {relief.get('bytes_saved', 0) / _MB:.3f} MB for "
                f"{relief.get('modeled_overhead_s', 0.0):.3e} s modeled")
            lines.append(f"{'Relief fixes':<44} {'MB saved':>10}  fix")
            for fx in relief.get("fixes", ()):
                lines.append(
                    f"{str(fx.get('var', ''))[:44]:<44} "
                    f"{fx.get('saved_bytes', 0) / _MB:>10.3f}  "
                    f"{fx.get('fix')}")
        return "\n".join(lines)


def _zero_shard_sets(program: Program, block: Block, ops, ndev: int,
                     stage: int, use_shard_map: bool):
    """(opt_sharded, sharded_params, grad_sharded, scatter_ops) from the
    SAME planning helpers the DP runtime uses — one source of truth for
    what shards at each ZeRO stage."""
    from ..parallel.data_parallel import (_pjit_zero23_sets,
                                          _plan_wrapped_updates,
                                          _sharded_opt_state)

    opt_sharded: set = set()
    sharded_params: set = set()
    grad_sharded: set = set()
    scatter_at: Dict[str, int] = {}  # grad name -> reduce-scatter op idx
    if stage < 1 or ndev <= 1:
        return opt_sharded, sharded_params, grad_sharded, scatter_at
    if use_shard_map:
        _, opt_sharded, sharded_params = _plan_wrapped_updates(
            ops, block, ndev, stage)
        if stage >= 2:
            for i, op_ in enumerate(ops):
                if op_.type == "c_fused_reduce_scatter":
                    for g in op_.inputs.get("X", []):
                        grad_sharded.add(g)
                        scatter_at[g] = i
    else:
        opt_sharded = _sharded_opt_state(ops, block, ndev)
        sharded_params, grad_constraints = _pjit_zero23_sets(
            ops, block, ndev, stage)
        for names in grad_constraints.values():
            grad_sharded.update(names)
    return opt_sharded, sharded_params, grad_sharded, scatter_at


def _tp_predicate(block: Block, tp: int, tp_rules: Optional[Dict]):
    """name -> True when the var holds 1/tp per device under tensor
    parallelism: it matches a ``tp_rules`` pattern (exact name or
    fullmatch regex — the same resolution ``apply_tensor_parallel``
    uses), or, with no rules given, it carries a ``shard_parameter``
    annotation (``var._sharding``)."""
    if tp <= 1:
        return lambda name: False
    if tp_rules:
        import re as _re

        pats = []
        for p in tp_rules:
            try:
                pats.append((p, _re.compile(p)))
            except _re.error:
                pats.append((p, None))

        def match(name: str) -> bool:
            for p, rx in pats:
                if name == p or (rx is not None and rx.fullmatch(name)):
                    return True
            return False

        return match

    def annotated(name: str) -> bool:
        v = block._find_var_recursive(name)
        return bool(getattr(v, "_sharding", None))

    return annotated


def plan_memory(program: Program, feed_names: Sequence[str] = (),
                fetch_names: Sequence[str] = (), *,
                ndev: int = 1, stage: Optional[int] = None,
                use_shard_map: Optional[bool] = None,
                donate: Optional[bool] = None,
                prefetch_records: Optional[Sequence[dict]] = None,
                prefetch_depth: Optional[int] = None,
                assumed_batch: int = 64,
                extra_resident: Optional[Dict[str, int]] = None,
                tp: int = 1,
                tp_rules: Optional[Dict] = None,
                scope=None) -> MemoryPlan:
    """Compute the modeled per-device HBM plan for ``program``.

    ``stage`` / ``prefetch_depth`` / ``donate`` default from the live
    flags (FLAGS_dp_sharding / FLAGS_dp_prefetch_depth /
    FLAGS_tpu_donate_buffers & FLAGS_tpu_step_session).
    ``prefetch_records`` takes precedence over re-deriving the ZeRO-3
    windows (pass ``compiled._prefetch_plan`` for the compiled truth).
    ``extra_resident`` adds named fixed blocks the program cannot see
    (e.g. an engine-held KV pool when planning the reference program).
    ``scope`` resolves the byte size of resident vars the program
    declares SHAPELESS (the serving K/V pools: persistable block vars
    whose real array lives only in the scope) — the compile paths pass
    their scope so those fixed blocks are charged at true size.

    ``tp`` (with ``tp_rules``, a name/regex -> spec dict like
    ``decoder_tp_rules``'s) prices tensor parallelism: a var matching a
    rule — or, with no rules given, carrying a ``_sharding``
    annotation — holds ``1/tp`` of its global bytes per device
    (weights, KV pools and scale pools shard; activations, block
    tables and the allocator stay replicated).  ``extra_resident``
    entries matching a rule divide too, so an engine-held pool priced
    from outside the program scales with the candidate degree.
    """
    from ..utils.flags import flag
    from ..parallel.data_parallel import _program_has_collectives

    if stage is None:
        stage = int(flag("dp_sharding") or 0)
    if donate is None:
        donate = bool(flag("tpu_donate_buffers", True)) and \
            bool(flag("tpu_step_session", True))
    if use_shard_map is None:
        use_shard_map = _program_has_collectives(program)
    ndev = max(int(ndev), 1)
    block = program.global_block()
    ops = list(block.ops)
    n_ops = max(len(ops), 1)
    feed_names = set(feed_names)
    fetch_names = set(fetch_names)

    opt_sharded, sharded_params, grad_sharded, scatter_at = \
        _zero_shard_sets(program, block, ops, ndev, stage, use_shard_map)

    tp = max(int(tp), 1)
    tp_sharded = _tp_predicate(block, tp, tp_rules)

    params = {p.name for p in program.all_parameters()}
    events = [op_reads_writes(op_) for op_ in ops]

    # ---- lifetime intervals ---------------------------------------------
    written: set = set()
    first_def: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    resident: set = set()       # live from op 0 (state / feeds)
    inplace_updated: set = set()  # resident names written in place
    for i, (rs, ws) in enumerate(events):
        for n in rs:
            if n == EMPTY:
                continue
            if n not in written and n not in feed_names:
                resident.add(n)
            last_use[n] = i
        # sub-block free reads keep the captured value live
        for sb in (v for v in ops[i].attrs.values() if isinstance(v, Block)):
            for sop in sb.ops:
                for n in sop.input_arg_names:
                    if n != EMPTY and n not in sb.vars:
                        if n not in written and n not in feed_names:
                            resident.add(n)
                        last_use[n] = i
        for n in ws:
            if n == EMPTY:
                continue
            if n in resident:
                inplace_updated.add(n)
            first_def.setdefault(n, i)
            written.add(n)
            last_use.setdefault(n, i)
    for n in feed_names:
        resident.add(n)
    # persistable writes and fetches live to the end of the step
    for n in list(written):
        v = block._find_var_recursive(n)
        if n in fetch_names or (v is not None
                                and getattr(v, "persistable", False)):
            last_use[n] = n_ops - 1

    def _scope_bytes(name: str) -> Optional[int]:
        if scope is None:
            return None
        try:
            v = scope.get(name)
        except Exception:
            return None
        nb = getattr(v, "nbytes", None)
        return int(nb) if nb else None

    def dev_bytes(name: str) -> Optional[int]:
        if name.endswith(HOST_STAGE_SUFFIX):
            # relief offload staging buffer: host RAM, not HBM
            return 0
        b = var_bytes(block, name, assumed_batch)
        v = block._find_var_recursive(name)
        if b is None or v is None or not v.shape:
            # undeclared or ()-shaped declaration: the scope value (the
            # compile-time ground truth — e.g. the serving K/V pools
            # declare shapeless and stage the real array) wins
            sb = _scope_bytes(name)
            if sb is not None:
                b = sb
        if b is None:
            return None
        if tp > 1 and tp_sharded(name):
            # tensor-parallel shard: weights / KV pools hold 1/tp of
            # the global bytes per device (scope arrays report the
            # GLOBAL logical nbytes under a NamedSharding, so the
            # division applies on that path too)
            b //= tp
        if ndev > 1:
            if name in sharded_params or name in opt_sharded \
                    or name in feed_names:
                # ZeRO-3 params / ZeRO-1 opt state resident 1/ndev;
                # feeds are batch-sharded over the dp axis
                return b // ndev
        return b

    classes: Dict[str, str] = {}
    per_var: Dict[str, dict] = {}
    diff = [0] * (n_ops + 1)

    def charge(name, lo, hi, nbytes):
        diff[lo] += nbytes
        diff[min(hi, n_ops - 1) + 1] -= nbytes

    resident_bytes = 0
    resident_by_class = {c: 0 for c in CLASSES}
    for n in sorted(resident | written | feed_names):
        if n.startswith("@"):
            continue
        b = dev_bytes(n)
        if not b:
            continue
        is_res = n in resident
        cls = _classify(n, params=params, opt_state=opt_sharded or set(),
                        feeds=feed_names, resident=is_res)
        # opt-state classification at stage 0: fall back to the slot
        # tables (opt_sharded is empty then)
        if cls == "state" and ("moment" in n.lower()
                               or "velocity" in n.lower()
                               or "_beta" in n.lower()
                               or "pow_acc" in n.lower()):
            cls = "opt_state"
        classes[n] = cls
        lo = 0 if is_res else first_def.get(n, 0)
        hi = last_use.get(n, lo)
        if is_res:
            hi = n_ops - 1  # state re-enters the scope after the step
        sharded_grad = (ndev > 1 and n in grad_sharded)
        b_full = var_bytes(block, n, assumed_batch) or b
        if sharded_grad and n in scatter_at:
            # shard_map ZeRO-2: full until the reduce-scatter, 1/ndev
            # after it (the steady-state per-dev grad-buffer bytes)
            charge(n, lo, scatter_at[n], b_full)
            if scatter_at[n] < hi:
                charge(n, scatter_at[n] + 1, hi, b_full // ndev)
            eff = b_full // ndev
        elif sharded_grad:
            # pjit ZeRO-2: GSPMD reduce-scatters at production — the
            # full gradient never materializes
            eff = b_full // ndev
            charge(n, lo, hi, eff)
        else:
            eff = b
            charge(n, lo, hi, eff)
        if is_res:
            resident_bytes += eff
            resident_by_class[cls] += eff
        # donation aliasing: with donation OFF, the in-place update's
        # result is a second buffer coexisting with the (scope-owned)
        # input copy until the post-step writeback
        if not donate and n in inplace_updated:
            charge(n, first_def.get(n, 0), n_ops - 1, eff)
        per_var[n] = {"bytes": int(b_full),
                      "dev_bytes": int(eff), "class": cls,
                      "first": lo, "last": hi, "resident": is_res,
                      "sharded": bool(sharded_grad
                                      or (tp > 1 and tp_sharded(n))
                                      or (ndev > 1
                                          and (n in sharded_params
                                               or n in opt_sharded)))}

    extra_resident = dict(extra_resident or {})
    if tp > 1:
        extra_resident = {k: (int(v) // tp if tp_sharded(k) else int(v))
                          for k, v in extra_resident.items()}
    extra_bytes = int(sum(extra_resident.values()))
    resident_bytes += extra_bytes
    if extra_bytes:
        resident_by_class["kv_pool"] += extra_bytes

    # ---- ZeRO-3 gather windows ------------------------------------------
    prefetch_windows = 0
    if ndev > 1 and stage >= 3 and sharded_params:
        if prefetch_records is None:
            if prefetch_depth is None:
                from ..utils.flags import flag as _flag

                prefetch_depth = int(_flag("dp_prefetch_depth") or 0)
            if prefetch_depth > 0:
                from ..parallel.data_parallel import _plan_param_prefetch

                prefetch_records, _, _ = _plan_param_prefetch(
                    ops, block, sharded_params, set(), prefetch_depth)
            else:
                prefetch_records = []
        if prefetch_records:
            for rec in prefetch_records:
                p = rec.get("param")
                b = var_bytes(block, p, assumed_batch)
                if not b:
                    continue
                bump = b - b // ndev  # full copy minus the resident shard
                charge(p, int(rec.get("gather_at", 0)),
                       int(rec.get("last_consumer", 0)), bump)
                prefetch_windows += 1
        else:
            # depth 0: just-in-time gather at every consumer op
            for p in sharded_params:
                b = var_bytes(block, p, assumed_batch)
                if not b:
                    continue
                bump = b - b // ndev
                for i, (rs, _) in enumerate(events):
                    if p in rs:
                        charge(p, i, i, bump)

    # ---- timeline + per-op transients -----------------------------------
    transients: List[dict] = []
    trans = [0] * n_ops
    for i, op_ in enumerate(ops):
        t = transient_bytes(op_, block, ndev, assumed_batch)
        if t:
            trans[i] = t
            transients.append({"op_index": i, "type": op_.type,
                               "bytes": int(t)})

    timeline: List[int] = []
    cur = extra_bytes
    peak = -1
    peak_i = 0
    for i in range(n_ops):
        cur += diff[i]
        total = cur + trans[i]
        timeline.append(int(total))
        if total > peak:
            peak, peak_i = total, i

    # ---- top live vars at the peak op -----------------------------------
    top: List[Tuple[str, int, str]] = []
    for n, info in per_var.items():
        if info["first"] <= peak_i <= info["last"]:
            top.append((n, info["dev_bytes"], info["class"]))
    for n, b in extra_resident.items():
        top.append((n, int(b), "kv_pool"))
    top.sort(key=lambda t: -t[1])

    return MemoryPlan(
        peak_bytes=int(max(peak, 0)), peak_op_index=peak_i,
        peak_op_type=(ops[peak_i].type if ops else "<empty>"),
        timeline=timeline, resident_bytes=int(resident_bytes),
        resident_by_class=resident_by_class, per_var=per_var,
        transients=transients, top_at_peak=top, ndev=ndev, stage=stage,
        donate=donate, path=("shard_map" if use_shard_map else "pjit"),
        assumed_batch=assumed_batch, n_ops=len(ops),
        extra_resident_bytes=extra_bytes,
        prefetch_windows=prefetch_windows)


def plan_and_surface(program: Program, where: str,
                     feed_names: Sequence[str] = (),
                     fetch_names: Sequence[str] = (), *,
                     block: Optional[Block] = None,
                     **plan_kw) -> Optional["MemoryPlan"]:
    """The compile-path entry both the executor and the DP runner call:
    build the plan, publish the ``hbm_modeled_peak_bytes{where=}``
    gauge, enforce FLAGS_hbm_budget_mb (:func:`check_budget` warns /
    raises per FLAGS_hbm_budget_strict), and emit the modeled timeline
    onto the profiler's memory lane when a session is live.
    Best-effort except for the budget gate: a planner bug must not take
    compilation down (logged at debug), but a configured budget
    violation MUST surface."""
    import logging

    try:
        plan = plan_memory(program, feed_names=feed_names,
                           fetch_names=fetch_names, **plan_kw)
    except Exception:
        logging.getLogger(__name__).debug(
            "memory planning failed for %s", where, exc_info=True)
        return None
    from ..utils import telemetry as tm

    tm.gauge("hbm_modeled_peak_bytes",
             "modeled per-device HBM peak of the last compilation "
             "(framework/memory_plan.py)",
             labels=("where",)).labels(where=where).set(plan.peak_bytes)
    # memory_relief_pass decisions (framework/ir.py): the compile
    # pipeline leaves its report on the program; the plan carries it to
    # compiled._memory_plan, the OOM debris dump, and the relief gauges
    relief = getattr(program, "_memory_relief", None)
    if relief is not None:
        plan.relief = relief
        if relief.get("engaged"):
            surface_relief(relief, where)
    b = budget_bytes()
    if b and plan.peak_bytes > b and plan.relief_candidates is None:
        # over budget with no relief applied: price the top candidate
        # fixes so the warning is actionable even with relief off
        try:
            from .ir import relief_candidate_summary

            plan.relief_candidates = relief_candidate_summary(
                program, plan, feed_names=feed_names,
                fetch_names=fetch_names)
        except Exception:
            plan.relief_candidates = []
    check_budget(plan, where)
    try:
        emit_trace_counters(plan, block if block is not None
                            else program.global_block())
    except Exception:
        pass
    return plan


# ==========================================================================
# budget gate (FLAGS_hbm_budget_mb)
# ==========================================================================
def budget_bytes() -> int:
    """The configured HBM budget in bytes (0 = unset/off)."""
    from ..utils.flags import flag

    try:
        mb = float(flag("hbm_budget_mb") or 0)
    except (TypeError, ValueError):
        return 0
    return int(mb * _MB) if mb > 0 else 0


def check_budget(plan: MemoryPlan, where: str = "compile",
                 strict: Optional[bool] = None) -> Optional[str]:
    """Enforce FLAGS_hbm_budget_mb against the modeled peak: returns
    None under budget; over budget, builds a message naming the peak op
    and the top-10 live vars, then warns (default) or raises
    :class:`MemoryBudgetError` (FLAGS_hbm_budget_strict).  Off (the
    default, budget 0) this is one flag read."""
    b = budget_bytes()
    if not b or plan is None or plan.peak_bytes <= b:
        return None
    from ..utils.flags import flag

    if strict is None:
        strict = bool(flag("hbm_budget_strict"))
    tops = ", ".join(f"{n}={v / _MB:.2f}MB[{c}]"
                     for n, v, c in plan.top_live_at_peak(10))
    msg = (f"[{where}] modeled HBM peak {plan.peak_mb:.2f} MB exceeds "
           f"FLAGS_hbm_budget_mb={b / _MB:g} at op "
           f"#{plan.peak_op_index} ({plan.peak_op_type}); top live vars: "
           f"{tops}")
    cands = getattr(plan, "relief_candidates", None)
    if cands:
        # priced by the memory_relief_pass machinery: what turning
        # FLAGS_memory_relief on would do, cheapest first
        fixes = ", ".join(
            f"{c['var']} {c['fix']} saves {c['saved_bytes'] / _MB:.2f}MB "
            f"@{c['seconds_per_byte']:.1e}s/B" for c in cands[:3])
        msg += (f"; candidate fixes (set FLAGS_memory_relief to apply): "
                f"{fixes}")
    if strict:
        raise MemoryBudgetError(msg)
    import warnings

    warnings.warn(msg, ResourceWarning, stacklevel=3)
    return msg


def surface_relief(report: dict, where: str) -> None:
    """Publish one relief report (memory_relief_pass.report) onto the
    hbm_relief_* gauges.  Best-effort: telemetry failure must not take
    compilation down."""
    try:
        from ..utils import telemetry as tm

        tm.gauge("hbm_relief_bytes_saved",
                 "modeled HBM bytes the memory_relief_pass bought back "
                 "at the last compilation",
                 labels=("where",)).labels(where=where).set(
            int(report.get("bytes_saved", 0)))
        tm.gauge("hbm_relief_modeled_overhead_s",
                 "modeled seconds/step the relief fixes spend "
                 "(recompute + exposed host transfer + plan delta)",
                 labels=("where",)).labels(where=where).set(
            float(report.get("modeled_overhead_s", 0.0)))
        counts: Dict[str, int] = {}
        for fx in report.get("fixes", ()):
            counts[fx.get("fix", "?")] = counts.get(fx.get("fix", "?"), 0) + 1
        g = tm.gauge("hbm_relief_vars",
                     "relieved vars by fix kind at the last compilation",
                     labels=("where", "fix"))
        for fix in ("remat", "offload", "plan"):
            g.labels(where=where, fix=fix).set(counts.get(fix, 0))
    except Exception:
        pass


# ==========================================================================
# chrome-trace memory lane (profiler counter events)
# ==========================================================================
def emit_trace_counters(plan: MemoryPlan, block: Optional[Block] = None,
                        name: str = "hbm_modeled_live_bytes") -> int:
    """Emit the modeled live-bytes timeline as chrome-trace counter
    ("C"-phase) events on the ``memory`` lane, spaced by the cost
    model's modeled per-op times so the lane's shape lines up with the
    modeled step.  No-op (returns 0) when the profiler is off."""
    from .. import profiler

    if not profiler.is_profiler_enabled() or not plan.timeline:
        return 0
    dt = None
    if block is not None:
        try:
            from ..utils.cost_model import CostModel, op_time_s

            cm = CostModel()
            dt = [op_time_s(op_, block, cm) for op_ in block.ops]
        except Exception:
            dt = None
    if not dt or len(dt) != len(plan.timeline):
        dt = [1e-6] * len(plan.timeline)
    budget = budget_bytes()
    t = time.perf_counter()
    n = 0
    for v, step in zip(plan.timeline, dt):
        args = {"bytes": int(v)}
        if budget:
            args["budget_bytes"] = int(budget)
        profiler.counter_event(name, args, cat="memory", ts=t)
        t += max(step, 1e-9)
        n += 1
    # close the lane at the resident floor so the counter doesn't dangle
    profiler.counter_event(name, {"bytes": int(plan.resident_bytes),
                                  **({"budget_bytes": int(budget)}
                                     if budget else {})},
                           cat="memory", ts=t)
    return n


# ==========================================================================
# OOM flight recorder (FLAGS_oom_debris_dir)
# ==========================================================================
#: allocator-OOM phrasings across the XLA/PJRT error surfaces.  No bare
#: "OOM" marker: it substring-matches unrelated messages ("ZOOM", a
#: user path) and a misfiled debris dump is a misleading post-mortem.
_RESOURCE_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory",
                     "Allocation failure")
_debris_lock = threading.Lock()
_debris_seq = 0


def is_resource_exhausted(exc: BaseException) -> bool:
    """True when ``exc`` looks like a device allocator OOM (XLA raises
    ``XlaRuntimeError: RESOURCE_EXHAUSTED: ...``; the markers also
    catch the PJRT C-API phrasings)."""
    s = f"{type(exc).__name__}: {exc}"
    return any(m in s for m in _RESOURCE_MARKERS)


def record_oom_debris(where: str, exc: BaseException,
                      plan: Optional[MemoryPlan] = None,
                      program: Optional[Program] = None,
                      extra: Optional[dict] = None) -> Optional[str]:
    """Dump a post-mortem debris directory for a device OOM: the
    modeled memory plan, a telemetry snapshot, the profiler's trace (if
    a session is live), measured device memory stats, and the error
    with traceback.  Returns the directory path, or None when
    ``FLAGS_oom_debris_dir`` is unset.  Never raises — the original
    exception must keep propagating unchanged."""
    from ..utils.flags import flag

    root = flag("oom_debris_dir") or ""
    if not root:
        return None
    global _debris_seq
    try:
        with _debris_lock:
            _debris_seq += 1
            seq = _debris_seq
        d = os.path.join(str(root),
                         f"oom_{where}_{os.getpid()}_{seq}")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "error.txt"), "w") as f:
            f.write(f"where: {where}\n")
            f.write(f"type: {type(exc).__name__}\n")
            f.write(f"error: {exc}\n\n")
            f.write("".join(traceback.format_exception(
                type(exc), exc, exc.__traceback__)))
        if plan is not None:
            with open(os.path.join(d, "plan.json"), "w") as f:
                json.dump({**plan.as_dict(20),
                           "timeline_bytes": plan.timeline}, f, indent=2)
        try:
            from ..utils import telemetry

            with open(os.path.join(d, "telemetry.json"), "w") as f:
                json.dump(telemetry.snapshot(), f, indent=2)
        except Exception:
            pass
        try:
            from .. import profiler

            events = profiler.get_events()
            if events:
                profiler._write_chrome_trace(
                    events, os.path.join(d, "trace.json"))
        except Exception:
            pass
        try:
            from ..utils.memory import memory_stats

            with open(os.path.join(d, "memory_stats.json"), "w") as f:
                json.dump(memory_stats(0), f, indent=2)
        except Exception:
            pass
        if program is not None:
            try:
                counts: Dict[str, int] = {}
                for blk in program.blocks:
                    for op_ in blk.ops:
                        counts[op_.type] = counts.get(op_.type, 0) + 1
                with open(os.path.join(d, "program.json"), "w") as f:
                    json.dump({"n_blocks": len(program.blocks),
                               "op_counts": dict(sorted(counts.items()))},
                              f, indent=2)
            except Exception:
                pass
        if extra:
            with open(os.path.join(d, "context.json"), "w") as f:
                json.dump(extra, f, indent=2, default=str)
        import logging

        logging.getLogger(__name__).error(
            "RESOURCE_EXHAUSTED in %s — debris dumped to %s", where, d)
        return d
    except Exception:
        return None
