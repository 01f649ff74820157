"""The GPT-2-shaped decoder as a model description ``ServingEngine`` serves
through the seam of ``decoder_program.py``: a standard pre-LN transformer LM
(learned positions, LayerNorm, GELU, tied head) built from ONE layer
description in five forms: a full-sequence REFERENCE program in the naive
attention composition (matmul/softmax/matmul: what an exported user model
looks like; also the one-at-a-time oracle the tests pin token-identity
against), a PREFILL program (reference body + ``kv_cache_append`` of the
prompt's K/V; the engine applies ``fuse_multihead_attention_pass`` over it),
the paged DECODE program, and the CHUNK and VERIFY forms of prefix caching,
chunked prefill and speculative decoding.  The one description with
tensor-parallel rules and an int8 K/V pool.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from ..executor import Executor
from ..framework.core import Program
from ..framework.dtype import VarType
from ..framework.place import CPUPlace
from ..framework.scope import Scope, scope_guard
from .decoder_program import (SERVING_TP_AXIS, FormExtras, _B, _emit_head,
                              _kv_append, _kv_gather_deq, _kv_pool_params,
                              _sampled)
from .kv_cache import KVCacheConfig
from .spec_decode import SamplingParams

__all__ = ["DecoderConfig", "decoder_param_specs", "init_decoder_weights",
           "validate_tp_degree", "decoder_tp_rules", "build_decoder_program",
           "export_decoder", "load_decoder_config"]


# ==========================================================================
# Model description
# ==========================================================================
@dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 128
    hidden: int = 64
    num_heads: int = 4
    num_layers: int = 2
    ffn_hidden: int = 0          # 0 -> 4 * hidden
    max_seq_len: int = 256
    eos_id: int = -1             # -1: no EOS, run to max_new_tokens

    @property
    def head_dim(self) -> int:
        return self.hidden // self.num_heads

    @property
    def ffn(self) -> int:
        return self.ffn_hidden or 4 * self.hidden

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in (
            "vocab_size", "hidden", "num_heads", "num_layers",
            "ffn_hidden", "max_seq_len", "eos_id")}

    @classmethod
    def from_dict(cls, d: dict) -> "DecoderConfig":
        return cls(**{k: d[k] for k in cls().to_dict() if k in d})

    # -- the seam: what the engine asks of a model description
    # (``decoder_program.ServedModel``) ------------------------------------
    param_dtype = "float32"

    def param_specs(self) -> Dict[str, tuple]:
        return decoder_param_specs(self)

    def init_weights(self, seed: int = 0) -> Dict[str, np.ndarray]:
        """GPT-2's convenience (``ServingEngine(cfg)`` with no weights seeds
        them here); no part of ``ServedModel``."""
        return init_decoder_weights(self, seed)

    def build_program(self, mode: str, sampling=None,
                      kv_dtype: str = "float32", tp: int = 1) -> tuple:
        return build_decoder_program(self, mode, sampling=sampling,
                                     kv_dtype=kv_dtype, tp=tp)

    def validate(self, tp: int = 1, **_served_with) -> None:
        validate_tp_degree(self, tp)

    def tp_rules(self, kv_dtype: str = "float32") -> Dict[str, tuple]:
        return decoder_tp_rules(self, kv_dtype=kv_dtype)

    def kv_cache_config(self, num_pages: int, page_size: int,
                        kv_dtype: str) -> KVCacheConfig:
        return KVCacheConfig(
            num_pages=num_pages, page_size=page_size,
            num_kv_heads=self.num_heads, head_dim=self.head_dim,
            num_layers=self.num_layers, dtype=kv_dtype)

    def cache_pool_names(self) -> List[str]:
        """The pool vars of the serving forms, a K and a V a layer."""
        return [f"kv_{side}_{i}" for i in range(self.num_layers)
                for side in ("k", "v")]

    def kv_token_bytes(self, kv_dtype: str, tp: int = 1) -> int:
        """Bytes one token holds in one device's pools, all layers."""
        return (2 * self.num_layers * (self.num_heads // tp)
                * self.head_dim * np.dtype(kv_dtype).itemsize)

    def state_pool_specs(self, state_slots: int) -> Dict[str, tuple]:
        return {}

    def window_pool_names(self) -> List[str]:
        return []


def decoder_param_specs(cfg: DecoderConfig) -> Dict[str, tuple]:
    """name -> shape for every weight var (shared by all three program
    forms; the decode/prefill builders re-declare the SAME names so one
    scope serves them all)."""
    h, f = cfg.hidden, cfg.ffn
    specs = {
        "dec_embed": (cfg.vocab_size, h),
        "dec_pos_embed": (cfg.max_seq_len, h),
        "dec_lnf_scale": (h,), "dec_lnf_bias": (h,),
    }
    for i in range(cfg.num_layers):
        p = f"dec_l{i}_"
        specs.update({
            p + "ln1_scale": (h,), p + "ln1_bias": (h,),
            p + "wq": (h, h), p + "wk": (h, h), p + "wv": (h, h),
            p + "wo": (h, h),
            p + "ln2_scale": (h,), p + "ln2_bias": (h,),
            p + "w1": (h, f), p + "w2": (f, h),
        })
    return specs


def init_decoder_weights(cfg: DecoderConfig, seed: int = 0
                         ) -> Dict[str, np.ndarray]:
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape in decoder_param_specs(cfg).items():
        if name.endswith("_scale"):
            out[name] = np.ones(shape, np.float32)
        elif name.endswith("_bias"):
            out[name] = np.zeros(shape, np.float32)
        else:
            out[name] = (rng.randn(*shape) / np.sqrt(shape[-1])) \
                .astype(np.float32)
    return out


# ==========================================================================
# Program builders
# ==========================================================================
def validate_tp_degree(cfg: DecoderConfig, tp: int) -> None:
    """Bugfix rider: reject infeasible TP degrees at engine/program
    construction with a clear error, instead of a shape crash
    mid-prefill.  Every sharded dimension — attention/KV heads (the
    pool's split axis AND the kernel's head grouping), the hidden
    width, and the MLP width — must divide evenly by ``tp``."""
    tp = int(tp or 1)
    if tp < 1:
        raise ValueError(f"serving_tp must be >= 1, got {tp}")
    if tp == 1:
        return
    bad = []
    if cfg.num_heads % tp:
        bad.append(f"num_heads={cfg.num_heads} (the KV pool and the "
                   f"paged_attention head grouping shard on kv_heads)")
    if cfg.hidden % tp:
        bad.append(f"hidden={cfg.hidden}")
    if cfg.ffn % tp:
        bad.append(f"ffn={cfg.ffn}")
    if bad:
        raise ValueError(
            f"serving_tp={tp} does not divide " + ", ".join(bad) +
            "; pick a degree that splits every sharded dim evenly")


def decoder_tp_rules(cfg: DecoderConfig, axis: str = SERVING_TP_AXIS,
                     kv_dtype: str = "float32"
                     ) -> Dict[str, tuple]:
    """Regex -> logical-axis spec for the serving decoder, composed
    from the generic partition-rule constructors
    (parallel/tensor_parallel.py): Megatron attention-head + MLP
    column/row sharding per block, hidden-sharded embeddings (the
    positional table follows the token table so the embed sum stays
    local), plus the paged KV pools split on their ``kv_heads`` dim
    (layout ``(kv_heads, pages, page_size, head_dim)``) and the int8
    scale pools alongside.  LayerNorm scales/biases stay replicated
    (no rule).  The derivation is pinned against hand-written specs by
    tests/test_serving_tp.py."""
    from ..parallel.tensor_parallel import attention_head_rules, \
        embedding_rules, megatron_mlp_rules

    rules: Dict[str, tuple] = {}
    rules.update(attention_head_rules(
        r"dec_l\d+_wq", r"dec_l\d+_wk", r"dec_l\d+_wv", r"dec_l\d+_wo",
        axis=axis))
    rules.update(megatron_mlp_rules(
        [r"dec_l\d+_w1", r"dec_l\d+_w2"], axis=axis))
    rules.update(embedding_rules("dec_embed", axis=axis, mode="hidden"))
    rules["dec_pos_embed"] = (None, axis)
    rules[r"kv_[kv]_\d+"] = (axis, None, None, None)
    if kv_dtype == "int8":
        rules[r"kv_[kv]_scale_\d+"] = (axis, None)
    return {k: tuple(v) for k, v in rules.items()}


def build_decoder_program(cfg: DecoderConfig, mode: str,
                          sampling: Optional[SamplingParams] = None,
                          kv_dtype: str = "float32", tp: int = 1) -> tuple:
    """Build one of the program forms; returns
    ``(program, feed_names, fetch_names)``.

    mode="reference": full-sequence next-token program (naive attention
      composition) — the export form and the one-at-a-time oracle.
    mode="prefill":   reference body + kv_cache_append of every prompt
      position's K/V at allocator-assigned slots.
    mode="decode":    single-token batched step over the paged cache.
    mode="chunk":     a SLICE of one prompt at an offset: the chunk's
      K/V enter the pool at allocator slots, and its attention runs
      over the POOL-RESIDENT prefix (cached/previous-chunk pages
      gathered through the sequence's block table) plus the chunk
      itself — the program form prefix-cache-hit suffixes and chunked
      prefill share.  The host-built mask carries both the causal
      structure and the valid-context bound.
    mode="verify":    the chunk form BATCHED over B sequences — the
      spec-decode accept-prefix verify kernel.  Each row is one
      request's ``[last_token, draft...]`` slice; ALL row positions'
      logits are scored (no last_index), so row j yields the target
      model's next token after chunk position j — exactly what
      accept-prefix compares the draft against.  One call scores
      K+1 positions for the whole batch.

    ``sampling`` (serving forms only): when armed (temperature > 0) the
    argmax head is replaced by the in-program ``sample_token`` op and
    the program grows a ``sample_seeds`` RNG-lane feed (one lane per
    emitted row).  ``None``/greedy builds the exact default programs.

    ``kv_dtype`` (serving forms only; FLAGS_kv_cache_dtype): the KV
    pool storage dtype.  "float32" (default) builds the exact legacy
    programs.  "bfloat16" adds a ``kv_dequant`` cast after every pool
    gather; "int8" also threads the per-(kv_head, page) scale pools
    through ``kv_cache_append`` (quantize-on-write) and the reads, so
    attention always accumulates in f32.  The reference form never
    touches the pool and ignores it.

    ``tp`` > 1 builds the tensor-parallel SHARD body: every head/width
    reshape bakes the LOCAL head count (``num_heads // tp``) and local
    context width (``hidden // tp``) — the per-device program each mesh
    rank runs under shard_map.  The combines (per-block allreduces, the
    embedding all-gather, the logits split/reduce) are NOT built here;
    the verifier-bracketed ``serving_tp_pass`` inserts them.  ``tp=1``
    is byte-identical to the unsharded builder (pinned).
    """
    if mode not in ("reference", "prefill", "decode", "chunk", "verify"):
        raise ValueError(f"bad mode {mode!r}")
    if kv_dtype not in ("float32", "bfloat16", "int8"):
        raise ValueError(f"bad kv_dtype {kv_dtype!r}")
    quant = kv_dtype == "int8"
    if _sampled(sampling) and mode == "reference":
        raise ValueError("the reference form is the greedy oracle; "
                         "sampling applies to serving forms only")
    tp = int(tp or 1)
    validate_tp_degree(cfg, tp)
    # H/h below are the PER-DEVICE head count and attention-context
    # width (== the global values at tp=1): the sharded body computes
    # on 1/tp of the heads; full-width sites (residual stream, final
    # layer norm, hflat) keep cfg.hidden because the inserted
    # collectives re-assemble the hidden dim before them
    H, D, h = cfg.num_heads // tp, cfg.head_dim, cfg.hidden
    hl = h // tp
    prog = Program()
    prog._label = mode  # names the compiled step pt_<mode> and its spans
    b = _B(prog)
    for n, s in decoder_param_specs(cfg).items():
        b.param(n, s)

    if mode in ("chunk", "verify"):
        # NOTE: this branch repeats the decoder body because its
        # attention reads K/V through a pool gather — a shape the
        # shared loop below can't express without growing a third
        # conditional axis.  Any model change must land in both; drift
        # is NOT silent: the chunked==monolithic token-identity tests
        # (tests/test_prefix_cache.py) and the verify==reference
        # logits-parity test (tests/test_spec_decode) pin the two bodies
        # together.  "verify" is "chunk" BATCHED: (B, S) rows, a block
        # table a row, every row position scored.
        batched = mode == "verify"
        lead = -1 if batched else 1
        tokens = b.feed("tokens", (lead, -1), VarType.INT32)
        positions = b.feed("positions", (lead, -1), VarType.INT32)
        mask = b.feed("attn_mask", (lead, 1, -1, -1), VarType.FP32)
        feeds = ["tokens", "positions", "attn_mask"]
        if not batched:
            last_index = b.feed("last_index", (1,), VarType.INT32)
            feeds.append("last_index")
        slot_map = b.feed("slot_mapping", (-1,), VarType.INT32)
        tables = b.feed("verify_tables", (-1, -1), VarType.INT32) \
            if batched else b.feed("chunk_tables", (-1,), VarType.INT32)
        feeds += ["slot_mapping", tables]
        seeds = None
        if _sampled(sampling):
            seeds = b.feed("sample_seeds", (lead,), VarType.INT32)
            feeds.append("sample_seeds")
        x = b.lookup("dec_embed", tokens)
        pos = b.lookup("dec_pos_embed", positions)
        hid = b.add(x, pos, "h0")

        def gathered(pool, scale, tag, i):
            """The context's K or V rows (B, H, C, D): the stored pool
            (H, P, rows, width) gathered through the block table(s) (and
            dequantized back to f32), batch-major where the tables are a
            row's."""
            g = _kv_gather_deq(b, pool, scale, tables, kv_dtype,
                               f"l{i}_{tag}g")
            if batched:                      # (H, B, W, rows, width)
                return b.reshape(b.transpose(g, [1, 0, 2, 3, 4]),
                                 [0, 0, -1, D], f"l{i}_{tag}4")
            return b.reshape(g, [1, H, -1, D], f"l{i}_{tag}4")

        for i in range(cfg.num_layers):
            p = f"dec_l{i}_"
            hn = b.layer_norm(hid, p + "ln1_scale", p + "ln1_bias", 2,
                              f"l{i}_ln1")
            q = b.matmul(hn, p + "wq", tag=f"l{i}_q")
            k = b.matmul(hn, p + "wk", tag=f"l{i}_k")
            v = b.matmul(hn, p + "wv", tag=f"l{i}_v")
            # every row's K/V enter the pool FIRST (flattened over the
            # batch), so the gather below sees prefix AND chunk through
            # one block table
            k3 = b.reshape(k, [-1, H, D], f"l{i}_k3")
            v3 = b.reshape(v, [-1, H, D], f"l{i}_v3")
            kc, vc, ksc, vsc = _kv_pool_params(b, i, quant, kv_dtype)
            _kv_append(b, k3, v3, slot_map, kc, vc, ksc, vsc)
            q4 = b.transpose(b.reshape(q, [0, 0, H, D]), [0, 2, 1, 3],
                             f"l{i}_q4")                 # (B, H, S, D)
            k4 = gathered(kc, ksc, "k", i)
            v4 = gathered(vc, vsc, "v", i)
            s = b.matmul(q4, k4, transpose_Y=True, alpha=D ** -0.5,
                         tag=f"l{i}_qk")                 # (B, H, S, C)
            s = b.add(s, mask, f"l{i}_masked")
            sm = b.tmp(f"l{i}_probs")
            b.op("softmax", {"X": [s]}, {"Out": [sm]}, {"axis": -1})
            av = b.matmul(sm, v4, tag=f"l{i}_av")        # (B, H, S, D)
            ctxv = b.reshape(b.transpose(av, [0, 2, 1, 3]), [0, 0, hl],
                             f"l{i}_ctx")
            hid = b.add(hid, b.matmul(ctxv, p + "wo", tag=f"l{i}_o"),
                        f"l{i}_res1")
            hn2 = b.layer_norm(hid, p + "ln2_scale", p + "ln2_bias", 2,
                               f"l{i}_ln2")
            ff = b.matmul(b.gelu(b.matmul(hn2, p + "w1", tag=f"l{i}_ff1")),
                          p + "w2", tag=f"l{i}_ff2")
            hid = b.add(hid, ff, f"l{i}_res2")
        hid = b.reshape(hid, [-1, h], "hflat")              # (B*S, h)
        if not batched:
            h2d, hid = hid, b.tmp("hlast")
            b.op("gather", {"X": [h2d], "Index": [last_index]},
                 {"Out": [hid]}, {"axis": 0})
        hf = b.layer_norm(hid, "dec_lnf_scale", "dec_lnf_bias", 1, "lnf")
        logits = b.matmul(hf, "dec_embed", transpose_Y=True, tag="logits")
        out = _emit_head(b, logits, "next_tokens" if batched
                         else "next_token", sampling, seeds)
        # the verify==reference parity hook
        prog._form_extras = FormExtras(logits=logits)
        return prog, feeds, [out]

    paged = mode == "decode"
    if paged:
        tokens = b.feed("tokens", (-1,), VarType.INT32)
        positions = b.feed("positions", (-1,), VarType.INT32)
        tables = b.feed("block_tables", (-1, -1), VarType.INT32)
        ctx_lens = b.feed("context_lens", (-1,), VarType.INT32)
        slot_map = b.feed("slot_mapping", (-1,), VarType.INT32)
        feeds = ["tokens", "positions", "block_tables", "context_lens",
                 "slot_mapping"]
    else:
        tokens = b.feed("tokens", (1, -1), VarType.INT32)
        positions = b.feed("positions", (1, -1), VarType.INT32)
        mask = b.feed("attn_mask", (1, 1, -1, -1), VarType.FP32)
        last_index = b.feed("last_index", (1,), VarType.INT32)
        feeds = ["tokens", "positions", "attn_mask", "last_index"]
        if mode == "prefill":
            slot_map = b.feed("slot_mapping", (-1,), VarType.INT32)
            feeds.append("slot_mapping")
    seeds = None
    if _sampled(sampling):
        # one RNG lane per emitted row: B lanes for the paged decode
        # batch, a single lane for the prefill's first token
        seeds = b.feed("sample_seeds", (-1,) if paged else (1,),
                       VarType.INT32)
        feeds.append("sample_seeds")

    x = b.lookup("dec_embed", tokens)
    pos = b.lookup("dec_pos_embed", positions)
    hid = b.add(x, pos, "h0")

    for i in range(cfg.num_layers):
        p = f"dec_l{i}_"
        hn = b.layer_norm(hid, p + "ln1_scale", p + "ln1_bias",
                          2 if not paged else 1, f"l{i}_ln1")
        q = b.matmul(hn, p + "wq", tag=f"l{i}_q")
        k = b.matmul(hn, p + "wk", tag=f"l{i}_k")
        v = b.matmul(hn, p + "wv", tag=f"l{i}_v")
        if paged:
            q3 = b.reshape(q, [0, H, D], f"l{i}_q3")     # (B, H, D)
            k3 = b.reshape(k, [0, H, D], f"l{i}_k3")
            v3 = b.reshape(v, [0, H, D], f"l{i}_v3")
            kc, vc, ksc, vsc = _kv_pool_params(b, i, quant, kv_dtype)
            _kv_append(b, k3, v3, slot_map, kc, vc, ksc, vsc)
            att = b.tmp(f"l{i}_att")
            pa_ins = {"Q": [q3], "KCache": [kc], "VCache": [vc],
                      "BlockTables": [tables], "ContextLens": [ctx_lens]}
            if quant:
                # the kernel dequantizes per page inside its online-
                # softmax loop — quantized pages never round-trip
                # through a dense f32 gather
                pa_ins["KScale"], pa_ins["VScale"] = [ksc], [vsc]
            b.op("paged_attention", pa_ins,
                 {"Out": [att]}, {"scale": float(D ** -0.5)})
            ctxv = b.reshape(att, [0, hl], f"l{i}_ctx")
        else:
            # the NAIVE composition on (1, S, h): 4-D q/k/v + the
            # matmul/softmax/matmul chain fuse_multihead_attention_pass
            # rewrites to the flash op
            q4 = b.transpose(b.reshape(q, [0, 0, H, D]), [0, 2, 1, 3],
                             f"l{i}_q4")
            k4 = b.transpose(b.reshape(k, [0, 0, H, D]), [0, 2, 1, 3],
                             f"l{i}_k4")
            v4 = b.transpose(b.reshape(v, [0, 0, H, D]), [0, 2, 1, 3],
                             f"l{i}_v4")
            if mode == "prefill":
                # the prompt's K/V enter the pool HERE, at allocator
                # slots; padded bucket positions carry the drop sentinel
                k3 = b.reshape(k, [-1, H, D], f"l{i}_k3")
                v3 = b.reshape(v, [-1, H, D], f"l{i}_v3")
                kc, vc, ksc, vsc = _kv_pool_params(b, i, quant, kv_dtype)
                _kv_append(b, k3, v3, slot_map, kc, vc, ksc, vsc)
            s = b.matmul(q4, k4, transpose_Y=True, alpha=D ** -0.5,
                         tag=f"l{i}_qk")
            s = b.add(s, mask, f"l{i}_masked")
            sm = b.tmp(f"l{i}_probs")
            b.op("softmax", {"X": [s]}, {"Out": [sm]}, {"axis": -1})
            av = b.matmul(sm, v4, tag=f"l{i}_av")
            ctxv = b.reshape(b.transpose(av, [0, 2, 1, 3]), [0, 0, hl],
                             f"l{i}_ctx")
        hid = b.add(hid, b.matmul(ctxv, p + "wo", tag=f"l{i}_o"),
                    f"l{i}_res1")
        hn2 = b.layer_norm(hid, p + "ln2_scale", p + "ln2_bias",
                           2 if not paged else 1, f"l{i}_ln2")
        ff = b.matmul(b.gelu(b.matmul(hn2, p + "w1", tag=f"l{i}_ff1")),
                      p + "w2", tag=f"l{i}_ff2")
        hid = b.add(hid, ff, f"l{i}_res2")

    if not paged:
        # last REAL position's hidden row (feed-indexed: bucket padding
        # never reaches the logits)
        h2d = b.reshape(hid, [-1, h], "hflat")
        hid = b.tmp("hlast")
        b.op("gather", {"X": [h2d], "Index": [last_index]},
             {"Out": [hid]}, {"axis": 0})
    hf = b.layer_norm(hid, "dec_lnf_scale", "dec_lnf_bias", 1, "lnf")
    logits = b.matmul(hf, "dec_embed", transpose_Y=True, tag="logits")
    out_name = "next_tokens" if paged else "next_token"
    _emit_head(b, logits, out_name, sampling, seeds)
    # the verify==reference parity hook
    prog._form_extras = FormExtras(logits=logits)
    return prog, feeds, [out_name]


# ==========================================================================
# Export / load ("the converted decoder")
# ==========================================================================
def export_decoder(model_dir: str, cfg: DecoderConfig, seed: int = 0,
                   weights: Optional[Dict[str, np.ndarray]] = None) -> None:
    """Export the decoder in its REFERENCE form (naive attention
    composition — what a converted/exported user model looks like) plus
    a ``decoder.json`` sidecar so the serving engine can rebuild the
    prefill/decode forms around the same weights."""
    prog, feeds, fetches = build_decoder_program(cfg, "reference")
    scope = Scope()
    for name, arr in (weights or init_decoder_weights(cfg, seed)).items():
        scope.set(name, arr)
    exe = Executor(CPUPlace())
    from .. import io as pt_io

    with scope_guard(scope):
        pt_io.save_inference_model(
            model_dir, feeds, [prog.global_block().var(fetches[0])], exe,
            main_program=prog)
    with open(os.path.join(model_dir, "decoder.json"), "w") as f:
        json.dump(cfg.to_dict(), f)


def load_decoder_config(model_dir: str) -> DecoderConfig:
    with open(os.path.join(model_dir, "decoder.json")) as f:
        return DecoderConfig.from_dict(json.load(f))
