"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip, three phases through the entry points a user
calls, at the full width of models the repo supports (weights random, made
from a seed):

  train/resnet50   static Program -> fluid.Executor(TPUPlace(0)), b128 224px AMP
  train/bert-base  dygraph jit_train_step, 12x768, b38 s512, AMP-O2, dropout on
  serve/decoder    export_decoder -> ServingEngine(place=TPUPlace(0)) at
                   GPT-2-small width, mixed-length requests, paged decode

Each phase checks its result by the repo's own means (losses finite and
falling; served tokens against ``greedy_reference`` on the same chip) and
proves from the lowered program's text that its Pallas kernel is in the
program — a kernel that silently gave way to the jnp path fails the phase.
The serve phase also reads its prefill and decode programs as the chip's
compiler left them: a KV pool stored lane-full must be held in the compiler's
own row-major layout, and no program may copy, reshape or transpose a whole
pool, so "both pool kernels work where the chip holds the pool" is something
a run checks.
Any exception in any phase ends the run non-zero.  The per-phase lines are
smoke observations (compile seconds, steady step ms, peak bytes), not
benchmark metrics.  The last line of stdout is the contract's JSON object.

    python chip_smoke.py              # one chip, all three phases
    python chip_smoke.py --chips 4    # four chips: DP-4 ResNet-50 against the
                                      # one-chip trajectory, tp=4 decode
                                      # against tp=1 — and nothing else

With no TPU the script exits non-zero before any phase.  Rehearsal off the
chip (tiny sizes, kernels interpreted) is asked for on its own command line:

    JAX_PLATFORMS=cpu PT_PALLAS_INTERPRET=1 PT_FLASH_ATTENTION=1 \\
    FLAGS_tpu_nhwc=1 FLAGS_tpu_fuse=1 \\
    python chip_smoke.py --size tiny --rehearse-on-cpu
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

# Sizes are the script's, never the library's.  "full" is what the chip is
# asked; "tiny" exists for the CPU rehearsal (interpret mode has no PRNG, so
# tiny BERT keeps attention dropout off and forces the flash kernel at s=128).
SIZES = {
    "full": {
        # lr 0.01 as __graft_entry__ trains it: on ONE repeated batch lr 0.1
        # (the ImageNet schedule's value) diverges within three steps
        "resnet": dict(depth=50, image=224, classes=1000, batch=128, steps=6,
                       lr=0.01),
        "bert": dict(cfg={}, seq=512, batch=38, steps=6),
        "serve": dict(
            cfg=dict(vocab_size=50257, hidden=768, num_heads=12,
                     num_layers=12, max_seq_len=1024),
            num_pages=2048, page_size=16, token_budget=1024, max_batch=8,
            # two prefill buckets (32, 512) and one block-table width (32
            # pages) keep the number of compiled shapes small
            prompts=[300, 20, 280, 31, 17, 400, 25, 270], new_tokens=16),
        # the latent-attention / sparse-expert decoder at JoyAI-LLM-Flash's
        # published widths and a small depth: the dense layer, one expert
        # layer with all 256 experts, the MTP block (6.2 GB of bfloat16)
        "mla": dict(
            cfg=dict(vocab_size=129280, hidden=2048, num_heads=32,
                     num_layers=2, first_k_dense=1, intermediate=7168,
                     moe_intermediate=768, n_routed_experts=256,
                     n_shared_experts=1, num_experts_per_tok=8,
                     q_lora_rank=1536, kv_lora_rank=512,
                     qk_nope_head_dim=128, qk_rope_head_dim=64,
                     v_head_dim=128, rope_theta=32e6, max_seq_len=2048,
                     weights_dtype="bfloat16", mtp_layers=1),
            # the long prompt is served by the engines without a drafter
            # (the drafter's program has a table row a prompt position,
            # and 2,048 of 128 pages do not fit the chip's scalar memory):
            # its table is two of mla_decode's chunks wide, the others'
            # contexts and three rows of padding fill one each
            num_pages=512, page_size=16, token_budget=2048, max_batch=8,
            prompts=[300, 20, 280, 31], long_prompt=1100, new_tokens=8),
        # Kimi-Linear's cut at published widths and one period of depth
        # (KDA, KDA, KDA, MLA): the dense layer and three expert layers of
        # 64 held experts of 256, a quarter of the vocabulary (3.5 GB)
        "hybrid": dict(
            cfg=dict(vocab_size=40960, hidden=2304, num_heads=32,
                     num_layers=4, first_k_dense=1, intermediate=9216,
                     moe_intermediate=1024, n_routed_experts=256,
                     experts_held=64, n_shared_experts=1,
                     num_experts_per_tok=8, q_lora_rank=0, kv_lora_rank=512,
                     qk_nope_head_dim=128, qk_rope_head_dim=64,
                     v_head_dim=128, rope=False, rms_norm_eps=1e-5,
                     routed_scaling_factor=2.446,
                     mixers=("kda", "kda", "kda", "mla"), kda_heads=32,
                     kda_head_dim=128, kda_gate_rank=128, max_seq_len=2048,
                     weights_dtype="bfloat16"),
            num_pages=512, page_size=16, token_budget=2048, max_batch=8,
            prompts=[300, 20, 280, 31, 1100], new_tokens=8),
        # Laguna-XS.2's cut at published widths and one period of depth
        # (full, window, window, window, full): the dense layer and four
        # expert layers of 64 held experts of 256, a quarter of the
        # vocabulary (2.3 GB); one prompt of 8,000 tokens, so that a window
        # layer's walk is held to 33 pages at 8 k of context
        "gqa": dict(
            cfg=dict(vocab_size=25088, hidden=2048, num_layers=5,
                     mixers=("full", "window", "window", "window", "full"),
                     heads_full=48, heads_window=64, num_kv_heads=8,
                     head_dim=128, window=512, first_k_dense=1,
                     intermediate=8192, moe_intermediate=512,
                     n_routed_experts=256, experts_held=64,
                     num_experts_per_tok=8, max_seq_len=8704,
                     weights_dtype="bfloat16"),
            rope_full=dict(lanes=64, base=500000.0, yarn_factor=64.0,
                           original_max_position=4096, beta_fast=64.0,
                           beta_slow=1.0,
                           attention_factor=1.4158883083359672),
            rope_window=dict(lanes=128, base=10000.0),
            num_pages=768, page_size=16, token_budget=8320, max_batch=8,
            prompts=[300, 20, 600, 31, 8000], new_tokens=8, pad_to=8192),
        # Olmo-Hybrid's cut at published widths and one period of depth
        # (linear, linear, linear, full): 30 heads of 96 x 192 and of 128,
        # every feed-forward dense, the whole vocabulary (3.2 GB); one prompt
        # past a prefill bucket of 2,048
        "olmo": dict(
            cfg=dict(vocab_size=100352, hidden=3840, num_layers=4,
                     mixers=("linear", "linear", "linear", "full"),
                     heads_full=30, heads_window=30, num_kv_heads=30,
                     head_dim=128, window=0, gate=False, first_k_dense=4,
                     intermediate=11008, n_routed_experts=0,
                     n_shared_experts=0, num_experts_per_tok=0,
                     linear_heads=30, linear_key_dim=96,
                     linear_value_dim=192, linear_neg_eigval=True,
                     norm_after=True, qk_norm=True, max_seq_len=2592,
                     weights_dtype="bfloat16"),
            num_pages=512, page_size=16, token_budget=4224, max_batch=8,
            prompts=[300, 20, 280, 31, 2100], new_tokens=8, pad_to=4096),
        # LongCat-Flash-Chat's cut at published widths and one layer of
        # depth: two MLA sub-layers of 64 heads with both low-rank scales,
        # two dense halves of 12,288, 16 held of 512 routed experts beside
        # 256 identity experts, top-12 by softmax, an eighth of the
        # vocabulary (2.9 GB of bfloat16)
        "longcat": dict(
            cfg=dict(vocab_size=16384, hidden=6144, num_heads=64,
                     num_layers=1, first_k_dense=0, intermediate=12288,
                     moe_intermediate=2048, n_routed_experts=512,
                     experts_held=16, n_shared_experts=0,
                     num_experts_per_tok=12, q_lora_rank=1536,
                     kv_lora_rank=512, qk_nope_head_dim=128,
                     qk_rope_head_dim=64, v_head_dim=128, rope_theta=1e7,
                     rms_norm_eps=1e-5, routed_scaling_factor=6.0,
                     norm_topk_prob=False, shortcut=True,
                     router_scoring="softmax", zero_experts=256,
                     scale_q_lora=True, scale_kv_lora=True, max_seq_len=2048,
                     weights_dtype="bfloat16"),
            num_pages=512, page_size=16, token_budget=2048, max_batch=8,
            prompts=[300, 20, 280, 31, 1100], new_tokens=8),
    },
    "tiny": {
        "resnet": dict(depth=18, image=32, classes=10, batch=8, steps=5,
                       lr=0.01),
        "bert": dict(
            cfg=dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=128,
                     attention_probs_dropout_prob=0.0),
            seq=128, batch=2, steps=5),
        # head_dim 64 and pages of 16, as "full": the pools are stored
        # lane-full, so the rehearsal drives the kernels' packed form
        "serve": dict(
            cfg=dict(vocab_size=128, hidden=256, num_heads=4, num_layers=2,
                     max_seq_len=128),
            num_pages=64, page_size=16, token_budget=128, max_batch=8,
            prompts=[40, 5, 36, 9, 7, 50, 6, 34], new_tokens=6),
        # widths the three kernels engage at (8 heads, lanes of 128)
        "mla": dict(
            cfg=dict(vocab_size=256, hidden=128, num_heads=8, num_layers=2,
                     first_k_dense=1, intermediate=256, moe_intermediate=128,
                     n_routed_experts=8, num_experts_per_tok=2,
                     q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, max_seq_len=128,
                     weights_dtype="bfloat16", mtp_layers=1),
            num_pages=64, page_size=16, token_budget=128, max_batch=4,
            prompts=[40, 5, 36, 9], long_prompt=70, new_tokens=6),
        "hybrid": dict(
            cfg=dict(vocab_size=256, hidden=128, num_heads=8, num_layers=4,
                     first_k_dense=1, intermediate=256, moe_intermediate=128,
                     n_routed_experts=8, experts_held=4,
                     num_experts_per_tok=2, q_lora_rank=0, kv_lora_rank=32,
                     qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                     rope=False, rms_norm_eps=1e-5,
                     mixers=("kda", "kda", "kda", "mla"), kda_heads=8,
                     kda_head_dim=16, kda_gate_rank=16, max_seq_len=128,
                     weights_dtype="bfloat16"),
            num_pages=64, page_size=16, token_budget=128, max_batch=4,
            prompts=[40, 5, 36, 9, 70], new_tokens=6),
        "gqa": dict(
            cfg=dict(vocab_size=256, hidden=128, num_layers=5,
                     mixers=("full", "window", "window", "window", "full"),
                     heads_full=6, heads_window=8, num_kv_heads=2,
                     head_dim=16, window=16, first_k_dense=1,
                     intermediate=256, moe_intermediate=128,
                     n_routed_experts=8, experts_held=4,
                     num_experts_per_tok=2, max_seq_len=256,
                     weights_dtype="bfloat16"),
            rope_full=dict(lanes=8, base=500000.0, yarn_factor=64.0,
                           original_max_position=16, beta_fast=64.0,
                           beta_slow=1.0,
                           attention_factor=1.4158883083359672),
            rope_window=dict(lanes=16, base=10000.0),
            num_pages=64, page_size=8, token_budget=256, max_batch=4,
            prompts=[40, 5, 36, 9, 100], new_tokens=6, pad_to=128),
        "olmo": dict(
            cfg=dict(vocab_size=256, hidden=128, num_layers=4,
                     mixers=("linear", "linear", "linear", "full"),
                     heads_full=8, heads_window=8, num_kv_heads=8,
                     head_dim=16, window=0, gate=False, first_k_dense=4,
                     intermediate=256, n_routed_experts=0,
                     n_shared_experts=0, num_experts_per_tok=0,
                     linear_heads=6, linear_key_dim=24, linear_value_dim=48,
                     linear_neg_eigval=True, norm_after=True, qk_norm=True,
                     max_seq_len=256, weights_dtype="bfloat16"),
            num_pages=64, page_size=8, token_budget=256, max_batch=4,
            prompts=[40, 5, 36, 9, 150], new_tokens=6, pad_to=256),
        "longcat": dict(
            cfg=dict(vocab_size=256, hidden=128, num_heads=8, num_layers=2,
                     first_k_dense=0, intermediate=256, moe_intermediate=128,
                     n_routed_experts=8, experts_held=2, n_shared_experts=0,
                     num_experts_per_tok=3, q_lora_rank=64, kv_lora_rank=32,
                     qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                     rope_theta=1e7, rms_norm_eps=1e-5,
                     routed_scaling_factor=6.0, norm_topk_prob=False,
                     shortcut=True, router_scoring="softmax", zero_experts=4,
                     scale_q_lora=True, scale_kv_lora=True, max_seq_len=128,
                     weights_dtype="bfloat16"),
            num_pages=64, page_size=16, token_budget=128, max_batch=4,
            prompts=[40, 5, 36, 9, 70], new_tokens=6),
    },
}

# The MLA decoder's served logits against its float32 reference, bfloat16
# weights and cache: the limits of benchmark/configs/joyai-llm-flash.json
MLA_LOGIT_ABS_TOL, MLA_ROUTE_SLACK_TOL = 0.06, 0.008
# the hybrid decoder's, the reference routed as the engine was on the prompt's
# rows too: the limits of benchmark/configs/kimi-linear-48b-a3b.json
HYBRID_LOGIT_ABS_TOL, HYBRID_ROUTE_SLACK_TOL = 0.06, 0.008
# the shortcut-connected decoder's: the limits of
# benchmark/configs/longcat-flash-chat.json, the slack in units of 1 / 768
LONGCAT_LOGIT_ABS_TOL, LONGCAT_ROUTE_SLACK_TOL = 0.06, 0.05
# the grouped-query decoder's: the limits of benchmark/configs/laguna-xs2.json
GQA_LOGIT_ABS_TOL, GQA_ROUTE_SLACK_TOL = 0.06, 0.008
# the Olmo-Hybrid-shaped decoder's (no router): the limit of
# benchmark/configs/olmo-hybrid-7b.json
OLMO_LOGIT_ABS_TOL = 0.12
# gqa_decode's walk at the "full" sizes: contexts of 300, 20, 600, 31 and
# 8,000 tokens are 561 pages of context a layer, of which a window layer
# walks 89 (at most 33 a row): (2 x 561 + 3 x 89) / (5 x 561) = 0.495 over
# the two full and three window layers
GQA_WALK_OVER_CONTEXT_MAX = 0.55
GQA_WINDOW_WALK_PAGES_MAX = 33

# mla_decode's grid at the "full" sizes: contexts of 300, 20, 280, 31 and
# 1,100 tokens and three rows of padding are 1 + 1 + 1 + 1 + 2 + 3 chunks
# that hold context of the 16 the eight tables span: the grid is 0.5625 of
# the tables, and never over this
MLA_GRID_OVER_TABLES_MOST = 0.6

# A served token may differ from the reference's argmax only on a near-tie:
# its reference logit must be within this much of the reference maximum
# (logits of the seeded model have unit scale).
LOGIT_TIE_TOL = 5e-2


def say(**fields):
    print(json.dumps(fields), flush=True)


class Watch:
    """Compilations and persistent-cache traffic, from JAX's own monitoring
    events, the lowered text of every program (jax_dump_ir_to), and the
    compiled text of the serving programs (``want_compiled_text``)."""

    @staticmethod
    def want_compiled_text(compiled_dir):
        """Ask XLA, before JAX starts, to write the serving step programs
        (``jit_pt_prefill`` / ``jit_pt_decode``, executor.named_step) as it
        compiled them: layout copies exist only after its optimizations."""
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_dump_to={compiled_dir} --xla_dump_hlo_as_text"
            " --xla_dump_hlo_module_re=.*pt_(prefill|decode).*").strip()

    def __init__(self, jax, dump_dir, compiled_dir):
        self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0
        self.dump_dir = dump_dir
        self.compiled_dir = compiled_dir
        jax.config.update("jax_dump_ir_to", dump_dir)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.compiles, self.compile_s, self.hits, self.misses,
                set(os.listdir(self.dump_dir)))

    def since(self, mark):
        """(counters, lowered programs dumped) since ``mark``."""
        c, s, h, m, files = mark
        return ({"compilations": self.compiles - c,
                 "backend_compile_s": round(self.compile_s - s, 2),
                 "cache_hits": self.hits - h,
                 "cache_misses": self.misses - m},
                sorted(set(os.listdir(self.dump_dir)) - files))

    def compiled_texts(self):
        """{file name: text} of every program XLA compiled and dumped."""
        texts = {}
        for path in sorted(glob.glob(os.path.join(
                self.compiled_dir, "*after_optimizations.txt"))):
            with open(path) as f:
                texts[os.path.basename(path)] = f.read()
        return texts


def gmm_calls_and_readers(text):
    """Of one compiled program: how many ``moe_gmm`` custom calls it holds,
    and the instructions that read a call's output and are neither the
    next call nor the combine (its kernel ``moe_combine``, or XLA's gather
    where a decode step's handful of rows keeps it): a select over the
    whole output, say; each as its line's first 160 characters."""
    lines = [line.strip() for line in text.splitlines()]
    calls = [m.group(1) for m in (
        re.match(r"(?:ROOT )?%(moe_gmm[\w.\-]*) = .* custom-call\(", line)
        for line in lines) if m]
    reads = re.compile("|".join(rf"%{re.escape(c)}[,)]" for c in calls))
    strangers = [
        line[:160] for line in lines if calls and reads.search(line)
        and not re.match(r"(?:ROOT )?%moe_(gmm|combine)[\w.\-]* = ", line)
        and not ("moe_combine" in line and "gather" in line)]
    return len(calls), strangers


def gmm_walk(phase, eng):
    """What the engine's prefills' grouped matmuls walked, from its own
    count: two calls an expert layer, and the (row tile, expert) visits
    over the row tiles that hold a routed row."""
    eng.core.moe_stats              # folds the calls' tokens per expert
    walk = {key: value for key, value in
            eng.stats["kernels"].get("prefill", {}).items()
            if key.startswith("moe_gmm_")}
    if not walk.get("moe_gmm_row_tiles"):
        raise RuntimeError(f"{phase}: the prefill form counted no moe_gmm "
                           f"call: {eng.stats['kernels']}")
    walk["visits_over_row_tiles"] = (walk["moe_gmm_visits"]
                                     / walk["moe_gmm_row_tiles"])
    return walk


def pool_forms(stored, head_dim):
    """The dims an array the size of a KV pool can show in a compiled
    program, first the shape the pool is stored in (``KVCacheConfig.
    pool_shape``), then what a reshape or transpose of a whole pool would
    make of it: the logical ``(kv_heads, pages, page_size, d)``, the flat
    ``(kv_heads, slots, d)`` and the page-minor view ``(kv_heads, page_size,
    d, pages)``."""
    n_kv, n_pages, rows, width = stored
    page_size = rows * width // head_dim
    forms = [stored, (n_kv, n_pages, page_size, head_dim),
             (n_kv, n_pages * page_size, head_dim),
             (n_kv, page_size, head_dim, n_pages)]
    return [",".join(map(str, f)) for f in dict.fromkeys(forms)]


def pool_makers(text, forms):
    """The instructions of one compiled program whose result holds an array
    the size of a KV pool, in any of ``forms`` (:func:`pool_forms`), as
    ``[(opcode, dims, line)]``: what a layout copy, a reshape, a transpose
    or a scatter of a whole pool would show up as."""
    dims = re.compile(r"\[(%s)\]" % "|".join(forms))
    made = []
    for line in text.splitlines():
        head, sep, rhs = line.partition(" = ")
        if not sep or not head.lstrip().startswith(("%", "ROOT ")):
            continue
        if rhs.startswith("("):         # a tuple type: skip to its close
            depth = 0
            for end, ch in enumerate(rhs):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
        else:
            end = rhs.find(" ")
        found = dims.search(rhs[:end + 1])
        if found:
            made.append((rhs[end + 1:].lstrip().split("(", 1)[0],
                         found.group(1), line))
    return made


def pool_traffic(text, forms, views_free=True, append="kv_append"):
    """``(moved, prefetched, held)`` for one compiled program: the
    pool-sized results that cost the device a pass over a pool (or, with
    ``views_free`` off, that show a pool in another form than the stored
    one, whatever it costs), the count of XLA's own asynchronous moves of a
    pool into its scoped memory (``S(1)``: ``slice-start``/``copy-start``
    pairs and the ``ConcatBitcast`` joining them — the compiler's choice of
    schedule, overlapped with compute, seen at the smoke's 2048 pages), and
    the parameter layouts the pools are held in.  Free are parameters, the
    append kernel (its output aliases its pool operand), taking tuples apart
    and together, and bitcasts: all of them where the kernel may work on a
    view (a pool the chip holds page-minor), only those that keep the stored
    dims where it may not."""
    free = ("get-tuple-element", "tuple")
    moved, prefetched, held = [], 0, set()
    for op, dims, line in pool_makers(text, forms):
        if op == "parameter":
            kind = line.partition(" = ")[2].split(" ", 1)[0]
            if not kind.startswith("("):        # a loop body's tuple
                held.add(kind)
        elif op in free or op == "custom-call" and append in line:
            continue
        elif op == "bitcast" and (views_free or dims == forms[0]):
            continue
        elif op.endswith(("-start", "-done")) or "ConcatBitcast" in line:
            prefetched += op.endswith("-start")
        else:
            moved.append((op, line))
    return moved, prefetched, sorted(held)


class Ctx:
    """What every phase needs: the place, the watch, the device report."""

    def __init__(self, args):
        self.tmp = tempfile.mkdtemp(prefix="chip_smoke_ir_")
        self.dump_dir = os.path.join(self.tmp, "lowered")
        compiled_dir = os.path.join(self.tmp, "compiled")
        os.mkdir(self.dump_dir)
        Watch.want_compiled_text(compiled_dir)
        import jax

        import paddle_tpu as pt

        self.jax, self.pt = jax, pt
        self.rehearsal = args.rehearse_on_cpu
        self.sizes = SIZES[args.size]
        dev = jax.devices()[0]
        if dev.platform != "tpu" and not self.rehearsal:
            sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                     f"{dev.platform!r} ({dev.device_kind}); nothing was run")
        self.place = pt.CPUPlace() if self.rehearsal else pt.TPUPlace(0)
        self.device = self.place.jax_device()
        self.interpreted = os.environ.get("PT_PALLAS_INTERPRET") == "1"
        self.watch = Watch(jax, self.dump_dir, compiled_dir)
        self.cache_dir = pt.COMPILE_CACHE_DIR

    def cache_entries(self):
        return len(os.listdir(self.cache_dir)) \
            if os.path.isdir(self.cache_dir) else 0

    def memory(self, device_id=0):
        """The allocator's own counters; a backend that reports none is an
        error on the chip (the rehearsal's CPU reports none)."""
        if self.rehearsal:
            return {"peak_bytes_in_use": "not measured (cpu rehearsal)"}
        s = self.pt.memory_stats(device_id)
        if s["source"] != "pjrt" or "peak_bytes_in_use" not in s:
            raise RuntimeError(f"device {device_id} reports no allocator "
                               f"statistics: {s}")
        return {"peak_bytes_in_use": s["peak_bytes_in_use"],
                "bytes_in_use": s["bytes_in_use"]}

    def require_kernels(self, phase, modules, kernels):
        """Most calls of each kernel in one lowered program among
        ``modules``: the Mosaic custom call carrying the kernel's name —
        or, when kernels are interpreted (CPU rehearsal), its name scope
        (a kernel inside a nested ``jit`` is counted once, at its body).
        A kernel found in none fails the phase."""
        needles = {k: f'{k}/pallas_call"' if self.interpreted
                   else f'kernel_name = "{k}"' for k in kernels}
        found = dict.fromkeys(kernels, 0)
        for m in modules:
            with open(os.path.join(self.dump_dir, m)) as f:
                text = f.read()
            if self.interpreted or "@tpu_custom_call" in text:
                for k, needle in needles.items():
                    found[k] = max(found[k], text.count(needle))
        missing = [k for k, n in found.items() if n == 0]
        if missing:
            raise RuntimeError(
                f"{phase}: kernel(s) {missing} are not in any program the "
                f"phase compiled — the jnp path took their place")
        return found

    def require_one_call_a_layer(self, phase, kernel, layers):
        """Every compiled program that holds ``kernel`` runs it once a
        layer and behind no branch, ``layers`` naming the depths the
        phase's programs have.  Returns the most calls found in a program
        (0 where kernels are interpreted: XLA loops, no call)."""
        most = 0
        for name, text in self.watch.compiled_texts().items():
            calls = sum(line.lstrip().startswith(f"%{kernel}")
                        and " custom-call(" in line
                        for line in text.splitlines())
            if calls and (calls not in layers or " conditional(" in text):
                raise RuntimeError(
                    f"{phase}: {name} holds {calls} call(s) of {kernel}, "
                    f"not one a layer ({layers}), or holds a conditional")
            most = max(most, calls)
        return most

    def require_gmm_calls(self, phase, expert_layers, before):
        """Every program compiled since ``before`` (the names
        ``compiled_texts`` had as the phase began) that holds ``moe_gmm``
        runs it twice an expert layer (gate-and-up, down: ``expert_layers``
        names the depths the phase's programs have) and what a call returns
        is read by the next call or by the combine's gather alone: no
        operation of the kernel's output size between them.  Returns the
        most calls found in a program (0 where kernels are interpreted)."""
        most = 0
        for name, text in self.watch.compiled_texts().items():
            if name in before:              # an earlier phase's program
                continue
            calls, strangers = gmm_calls_and_readers(text)
            if calls and (calls not in [2 * n for n in expert_layers]
                          or strangers):
                raise RuntimeError(
                    f"{phase}: {name} holds {calls} call(s) of moe_gmm, not "
                    f"two an expert layer ({expert_layers}), or reads a "
                    f"call's output by other than the next call or the "
                    f"combine's gather: {strangers[:3]}")
            most = max(most, calls)
        return most

    def require_pool_in_place(self, phase, kv_config, n_pools,
                              append="kv_append"):
        """Every prefill and decode program the phase compiled, read as the
        chip's compiler left it.  A pool stored lane-full
        (``kv_config.pool_shape()``: rows of 128 lanes) must be held in the
        compiler's own row-major layout, where both pool kernels work, and
        NO program may hold a pool-sized result but the append's: no copy,
        and no reshape or transpose of a whole pool either, free or not (a
        view of the pool in another form is how the re-layout comes back).
        A pool that could not be stored so (head_dim under the lanes, pages
        not whole tiles: the chip holds it page-minor) is given the old
        allowance: the append still moves none, a decode program may hold
        one ``copy`` a pool, ``paged_decode``'s operand, and bitcast views
        are free.  Interpreted kernels (the CPU rehearsal) are XLA loops
        over the pool, so there the programs are read and counted and
        nothing is required of them."""
        texts = self.watch.compiled_texts()
        if not texts:
            raise RuntimeError(f"{phase}: XLA dumped no compiled prefill or "
                               f"decode program to read")
        stored = kv_config.pool_shape()
        forms = pool_forms(stored, kv_config.head_dim)
        lane_full = stored[3] % 128 == 0
        copies, prefetches, held = 0, 0, set()
        for name, text in texts.items():
            moved, prefetched, layouts = pool_traffic(
                text, forms, views_free=not lane_full, append=append)
            held.update(layouts)
            prefetches = max(prefetches, prefetched)
            allowed = n_pools if "paged_decode" in text and not lane_full \
                else 0
            if not self.interpreted and (
                    len(moved) > allowed
                    or any(op != "copy" for op, _ in moved)):
                raise RuntimeError(
                    f"{phase}: {name} copies, reshapes or transposes a "
                    f"whole KV pool {len(moved)} time(s), {allowed} "
                    f"allowed, the first: "
                    f"{[line.strip()[:200] for _, line in moved[:3]]}")
            copies = max(copies, len(moved))
        not_default = [h for h in held if "{3,2,1,0" not in h]
        if lane_full and not_default and not self.rehearsal:
            raise RuntimeError(
                f"{phase}: a lane-full pool {stored} is not held in the "
                f"compiler's row-major layout: {not_default}")
        return {"pool_stored_shape": list(stored),
                "pool_tokens_per_row": kv_config.tokens_per_row,
                "programs_read": len(texts), "pools_held_as": sorted(held),
                "most_pool_copies_in_a_program": copies,
                "most_async_pool_prefetches_in_a_program": prefetches}

    def require_state_in_place(self, phase, specs):
        """The slot pools of a model with recurrent layers (``specs``: name
        -> (shape, dtype)), in every program the phase compiled: a result of
        a pool's size may be the decode kernel's (its output aliases its
        pool operand), an update in place (``dynamic-update-slice``,
        ``scatter``, or a fusion of one) or a view; a ``copy`` of one is the
        compiler re-laying a pool, a pass over gigabytes every step, and is
        refused.  Returns what it read."""
        texts = self.watch.compiled_texts()
        forms = sorted({",".join(map(str, shape))
                        for shape, _dtype in specs.values()})
        made, copies = {}, []
        for name, text in texts.items():
            for op, _dims, line in pool_makers(text, forms):
                made[op] = made.get(op, 0) + 1
                if op in ("copy", "transpose", "reshape"):
                    copies.append(f"{name}: {line.strip()[:200]}")
        if copies and not self.interpreted:
            raise RuntimeError(f"{phase}: a program copies or re-lays a "
                               f"whole state pool: {copies[:3]}")
        return {"state_pool_shapes": forms, "state_pool_results_by_op": made}

    def close(self):
        shutil.rmtree(self.tmp, ignore_errors=True)


def train_phase(ctx, phase, mark, run_step, steps, kernels, report):
    """Drive ``run_step`` (returns the loss as a float: a host read, so a
    step's wall time is the device's) ``steps`` times; the first two are
    warm-up (trace + compile; the dygraph step compiles twice, its
    optimizer state being born in the first call).  Losses must be finite
    and fall, the kernels must be in a lowered program, and nothing may
    compile after warm-up."""
    losses, ms, warm = [], [], None
    for i in range(steps):
        if i == 2:
            warm = ctx.watch.mark()
        t0 = time.perf_counter()
        losses.append(run_step())
        ms.append((time.perf_counter() - t0) * 1e3)
    if not np.isfinite(losses).all():
        raise RuntimeError(f"{phase}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"{phase}: loss did not fall {losses}")
    seen, modules = ctx.watch.since(mark)
    say(phase=phase, **report, losses=[round(v, 4) for v in losses],
        first_step_s=round(ms[0] / 1e3, 2),
        steady_step_ms=round(statistics.median(ms[2:]), 2), **seen,
        compilations_after_warmup=ctx.watch.since(warm)[0]["compilations"],
        kernel_calls=ctx.require_kernels(phase, modules, kernels),
        **ctx.memory())


# ---------------------------------------------------------------------------
# train/resnet50 — and its data-parallel twin for --chips 4
# ---------------------------------------------------------------------------
def build_resnet(size):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.resnet import build_resnet as net

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, size["image"], size["image"]])
        label = fluid.layers.data("label", [1], dtype="int64")
        loss, _, _, _ = net(img, label, depth=size["depth"],
                            class_num=size["classes"])
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.MomentumOptimizer(size["lr"], 0.9))
        opt.minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(size["batch"], 3, size["image"],
                            size["image"]).astype(np.float32),
            "label": rng.randint(0, size["classes"],
                                 (size["batch"], 1)).astype(np.int32)}
    return main, startup, loss, feed


def phase_resnet(ctx):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.scope import Scope, scope_guard

    size = ctx.sizes["resnet"]
    main, startup, loss, feed = build_resnet(size)
    exe = fluid.Executor(ctx.place)
    mark = ctx.watch.mark()
    with scope_guard(Scope()):
        exe.run(startup)
        # staged once: the step is measured, not the feed
        feed = {k: ctx.jax.device_put(v, ctx.device) for k, v in feed.items()}
        train_phase(
            ctx, "train/resnet50", mark,
            lambda: float(exe.run(main, feed=feed,
                                  fetch_list=[loss.name])[0]),
            size["steps"], ["bn_act_fwd", "bn_act_bwd"], size)


def phase_dp4(ctx):
    """DP-4 ResNet-50 (global batch as above) against the one-chip loss
    trajectory, both in this process, from one set of initial weights."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import profiler
    from paddle_tpu.framework.scope import Scope, scope_guard

    jax = ctx.jax
    phase, size = "dp4/resnet50", ctx.sizes["resnet"]
    steps = max(3, size["steps"] - 2)
    main, startup, loss, feed = build_resnet(size)
    exe = fluid.Executor(ctx.place)
    one = Scope()
    with scope_guard(one):
        exe.run(startup)
        init = {k: np.asarray(v) for k, v in one.items()
                if not k.startswith("@")}
        single = [float(exe.run(main, feed=feed, fetch_list=[loss.name])[0])
                  for _ in range(steps)]
    del one
    exe.close()
    gc.collect()

    exe = fluid.Executor(ctx.place)
    mark = ctx.watch.mark()
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    four = Scope()
    for k, v in init.items():
        four.set(k, v)
    ms = []
    with scope_guard(four):
        dp = []
        profiler.enable_profiler()      # the program's spans, host side only
        for _ in range(steps):
            t0 = time.perf_counter()
            out = exe.run(prog, feed=feed, fetch_list=[loss.name])[0]
            dp.append(float(np.mean(out)))
            ms.append((time.perf_counter() - t0) * 1e3)
        placed = [e["args"]["arrays"] for e in profiler.get_events()
                  if e["name"] == "executor/bind"]
        profiler.disable_profiler(print_summary=False)
        # the step session: the first step places the whole state, every
        # later one binds it from the step before and places nothing
        if placed[0] == 0 or any(placed[1:]):
            raise RuntimeError(f"{phase}: arrays placed a step {placed}, "
                               f"want all on the first and 0 after")
        # state really is on four distinct devices, and the step's
        # compiled text holds the gradient all-reduce
        spread = device_spread(ctx, [v for _, v in four.items()
                                     if isinstance(v, jax.Array)])
        jitted, *abstract_args = prog.__dict__["_last_exec"]
        hlo = jitted.lower(*abstract_args).compile().as_text()
    if "all-reduce" not in hlo:
        raise RuntimeError(f"{phase}: no all-reduce in the compiled step")
    # Step 1 checks the sharded forward (global-batch BN statistics under
    # GSPMD) in bf16; later steps get the envelope dryrun_multichip uses:
    # an untrained deep BN+ReLU net amplifies reduction-order noise.
    if not np.isfinite(dp).all():
        raise RuntimeError(f"{phase}: non-finite loss {dp}")
    if abs(single[0] - dp[0]) > 1e-2 * abs(single[0]):
        raise RuntimeError(f"{phase}: step-1 loss diverged, one chip "
                           f"{single} four {dp}")
    for a, b in zip(single[1:], dp[1:]):
        if abs(a - b) > max(1e-3, 0.2 * abs(a)):
            raise RuntimeError(f"{phase}: trajectory diverged, one chip "
                               f"{single} four {dp}")
    seen, _ = ctx.watch.since(mark)
    say(phase=phase, **{**size, "steps": steps},
        one_chip_losses=[round(v, 4) for v in single],
        dp4_losses=[round(v, 4) for v in dp],
        step1_absdiff=abs(single[0] - dp[0]),
        first_step_s=round(ms[0] / 1e3, 2), steady_step_ms=round(ms[-1], 2),
        arrays_placed=placed, all_reduces=hlo.count(" all-reduce("),
        custom_calls=hlo.count("tpu_custom_call"), **seen, **spread)


def device_spread(ctx, arrays):
    """Every array lives on all of the host's devices (sharded or
    replicated there, never parked on one), and the allocator's
    bytes_in_use is roughly level across them."""
    jax = ctx.jax
    devs = jax.devices()
    for a in arrays:
        on = {s.device for s in a.addressable_shards}
        if on != set(devs):
            raise RuntimeError(f"array {a.shape} lives on {len(on)} of "
                               f"{len(devs)} devices")
    if ctx.rehearsal:
        return {"arrays_on_all_devices": len(arrays)}
    used = [ctx.memory(i)["bytes_in_use"] for i in range(len(devs))]
    # chip 0 also hosted the one-chip leg and may keep a little more; the
    # others must agree with each other and none may sit nearly empty
    rest = used[1:]
    if max(rest) > 1.1 * min(rest) or min(used) < 0.5 * max(rest):
        raise RuntimeError(f"bytes_in_use is lopsided across devices: {used}")
    return {"arrays_on_all_devices": len(arrays), "bytes_in_use": used}


# ---------------------------------------------------------------------------
# train/bert-base
# ---------------------------------------------------------------------------
def phase_bert(ctx):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.dygraph import guard, jit_train_step
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    size = ctx.sizes["bert"]
    cfg = BertConfig(**size["cfg"])
    rng = np.random.RandomState(0)
    shape = (size["batch"], size["seq"])
    ids = ctx.jax.device_put(
        rng.randint(0, cfg.vocab_size, shape).astype(np.int32), ctx.device)
    labels = ctx.jax.device_put(
        rng.randint(0, cfg.vocab_size, shape).astype(np.int32), ctx.device)
    mark = ctx.watch.mark()
    with guard(ctx.place):
        model = BertForPretraining(cfg)
        opt = fluid.optimizer.AdamOptimizer(
            1e-4, parameter_list=model.parameters())
        train = jit_train_step(model, opt, lambda m, i, l: m(i, l),
                               amp=True, amp_level="O2")
        # ids/labels are committed to the place's device, so the jitted
        # step runs there.  s=512 is one 512-block per axis: the
        # single-block forward and the fused (saved-lse) backward
        train_phase(
            ctx, "train/bert-base", mark,
            lambda: float(train(ids, labels).numpy()), size["steps"],
            ["flash_fwd_single", "flash_bwd_fused"],
            dict(layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
                 heads=cfg.num_attention_heads, seq=size["seq"],
                 batch=size["batch"],
                 attn_dropout=cfg.attention_probs_dropout_prob))


# ---------------------------------------------------------------------------
# serve/decoder — and tp=4 against tp=1 for --chips 4
# ---------------------------------------------------------------------------
def serve(ctx, model_dir, size, tp):
    """Submit the requests and drive step() until the engine is idle, as
    examples/serve_decoder_lm.py does.  Returns (engine, requests, steady
    decode summary: the engine steps after the first that compiled
    nothing)."""
    from paddle_tpu.inference.serving import Request, ServingEngine

    eng = ServingEngine(model_dir=model_dir, place=ctx.place, tp=tp,
                        num_pages=size["num_pages"],
                        page_size=size["page_size"],
                        max_batch=size["max_batch"],
                        token_budget=size["token_budget"])
    rng = np.random.RandomState(0)
    reqs = [Request(i, rng.randint(0, eng.cfg.vocab_size, size=n).tolist(),
                    max_new_tokens=size["new_tokens"])
            for i, n in enumerate(size["prompts"])]
    for r in reqs:
        eng.submit(r)
    steps = []
    while eng.has_work():
        c0, t0 = ctx.watch.compiles, time.perf_counter()
        eng.step()          # ends in a host read of the step's tokens
        steps.append(((time.perf_counter() - t0) * 1e3,
                      ctx.watch.compiles - c0))
    for r in reqs:
        if len(r.out_tokens) != size["new_tokens"]:
            raise RuntimeError(f"request {r.req_id} finished with "
                               f"{len(r.out_tokens)} tokens, asked "
                               f"{size['new_tokens']}")
    quiet = [ms for ms, compiled in steps[1:] if compiled == 0]
    return eng, reqs, {
        "engine_steps": len(steps), "steps_without_compilation": len(quiet),
        "steady_decode_step_ms":
            round(statistics.median(quiet), 2) if quiet else None}


def reference_gap(core, prompt, out):
    """Teacher-forced agreement of ``out`` with the reference program on
    ``core``: per position, how far the reference logit of the served token
    sits below the reference maximum (0.0 = the reference's own argmax)."""
    gaps, seq = [], list(prompt)
    for tok in out:
        logits = core.reference_logits(seq)
        if not np.isfinite(logits).all():
            raise RuntimeError("non-finite reference logits")
        gaps.append(float(logits.max() - logits[tok]))
        seq.append(tok)
    return gaps


def compare_tokens(phase, what, core, prompt, got, want):
    """Token identity, or — where precision flips a near-tie — every served
    token within LOGIT_TIE_TOL of the reference maximum.  Never skipped."""
    if got == want:
        return {"compared": what, "agreement": "token-identical"}
    gaps = reference_gap(core, prompt, got)
    if max(gaps) > LOGIT_TIE_TOL:
        raise RuntimeError(
            f"{phase}: {what}: served {got} vs {want}; reference logit "
            f"gaps {gaps} exceed the near-tie tolerance {LOGIT_TIE_TOL}")
    return {"compared": what, "agreement": "logits-within-tolerance",
            "tolerance": LOGIT_TIE_TOL, "worst_gap": max(gaps),
            "positions_not_argmax": sum(g > 0 for g in gaps)}


def export(size):
    from paddle_tpu.inference.gpt2_decoder import (DecoderConfig,
                                                   export_decoder)

    model_dir = tempfile.mkdtemp(prefix="chip_smoke_decoder_")
    export_decoder(model_dir, DecoderConfig(**size["cfg"]), seed=0)
    return model_dir


def phase_serve(ctx):
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax = ctx.jax
    phase, size = "serve/decoder", ctx.sizes["serve"]
    model_dir = export(size)
    # a program read back from the persistent cache is not compiled, so
    # XLA would dump nothing to read: this phase compiles its own
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        mark = ctx.watch.mark()
        eng, reqs, steady = serve(ctx, model_dir, size, tp=1)
        seen, modules = ctx.watch.since(mark)
        kernels = ctx.require_kernels(phase, modules,
                                      ["paged_decode", "kv_append"])
        in_place = ctx.require_pool_in_place(
            phase, eng.core.kv_config, n_pools=2 * eng.cfg.num_layers)
        oracle = eng.core.greedy_reference(reqs[0].prompt,
                                           size["new_tokens"])
        verdict = compare_tokens(phase, "request 0 vs greedy_reference",
                                 eng.core, reqs[0].prompt,
                                 reqs[0].out_tokens, oracle)
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    say(phase=phase, **size["cfg"], num_pages=size["num_pages"],
        page_size=size["page_size"], prompts=size["prompts"],
        new_tokens=size["new_tokens"], scheduler=eng.stats,
        kv_peak_pages=eng.kv.stats()["peak_pages"], **steady, **seen,
        kernel_calls=kernels, **in_place, **verdict, **ctx.memory())


def phase_mla(ctx):
    """The latent-attention / sparse-expert decoder through ServingEngine:
    its three kernels in the lowered programs, no operation of pool size in
    the compiled ones, the served logits against the plain reference, and
    pipelined steps and the MTP drafter leaving greedy tokens unchanged."""
    import importlib.util

    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                                  MTPDrafter,
                                                  init_mla_weights)
    from paddle_tpu.inference.serving import Request, ServingEngine

    jax = ctx.jax
    phase, size = "serve/mla", ctx.sizes["mla"]
    cfg = MLADecoderConfig(**size["cfg"])
    spec = importlib.util.spec_from_file_location(
        "reference_joyai", os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "benchmark", "reference", "joyai-llm-flash.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    ref_cfg = cfg.source_config()
    weights = {n: jax.device_put(w, ctx.device)
               for n, w in init_mla_weights(cfg, 0).items()}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in size["prompts"]]
    long_prompt = rng.randint(0, cfg.vocab_size,
                              size=size["long_prompt"]).tolist()

    def engine(**kw):
        eng = ServingEngine(
            cfg=cfg, weights=weights, kv_dtype="bfloat16", place=ctx.place,
            num_pages=size["num_pages"], page_size=size["page_size"],
            max_batch=size["max_batch"], token_budget=size["token_budget"],
            **kw)
        eng.core.keep_scores = True
        return eng

    def drive(eng, prompts=prompts):
        reqs = [Request(i, p, size["new_tokens"])
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        return reqs

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        mark = ctx.watch.mark()
        compiled_before = set(ctx.watch.compiled_texts())
        plain = engine()
        reqs = drive(plain, prompts + [long_prompt])
        seen, modules = ctx.watch.since(mark)
        kernels = ctx.require_kernels(
            phase, modules, ["mla_decode", "latent_append", "moe_gmm"])
        in_place = ctx.require_pool_in_place(
            phase, plain.core.kv_config,
            n_pools=len(cfg.cache_pool_names()), append="latent_append")
        decode_calls = ctx.require_one_call_a_layer(
            phase, "mla_decode", (cfg.num_layers, cfg.mtp_layers))
        gmm_calls = ctx.require_gmm_calls(
            phase, (cfg.num_layers - cfg.first_k_dense, cfg.mtp_layers),
            compiled_before)
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = plain.core.served_scores(r.req_id)
        ref = reference.served_token_scores(
            weights, ref_cfg, r.prompt, r.out_tokens, routes, pad_to=512)
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max()))
    if worst > MLA_LOGIT_ABS_TOL or slack > MLA_ROUTE_SLACK_TOL:
        raise RuntimeError(
            f"{phase}: served logits lie {worst} from the reference (limit "
            f"{MLA_LOGIT_ABS_TOL}), routing slack {slack} (limit "
            f"{MLA_ROUTE_SLACK_TOL})")
    # what mla_decode walked: its grid is the chunks that hold context,
    # here fewer than the chunks the tables span
    walk = plain.stats["kernels"].get("decode")
    if walk is None:
        raise RuntimeError(f"{phase}: the decode form counted no mla_decode "
                           f"call: {plain.stats['kernels']}")
    grid_share = (walk["mla_decode_grid_steps"]
                  / walk["mla_decode_table_chunks"])
    if ctx.sizes is SIZES["full"] and grid_share > MLA_GRID_OVER_TABLES_MOST:
        raise RuntimeError(
            f"{phase}: mla_decode's grid ran {grid_share:.3f} of the chunks "
            f"its tables span, over {MLA_GRID_OVER_TABLES_MOST}: {walk}")
    gmm = gmm_walk(phase, plain)
    del plain
    gc.collect()
    # pipelined steps (tokens stay on the device between calls): the same
    # tokens by the same schedule
    piped = engine(pipeline=True)
    piped_reqs = drive(piped, [r.prompt for r in reqs])
    if [r.out_tokens for r in piped_reqs] != [r.out_tokens for r in reqs]:
        raise RuntimeError(f"{phase}: pipelined steps changed the tokens "
                           f"served: {[r.out_tokens for r in piped_reqs]} "
                           f"vs {[r.out_tokens for r in reqs]}")
    del piped
    gc.collect()
    # the drafter on: the same tokens, and its logits against the reference
    drafter = MTPDrafter()
    spec_eng = engine(spec_k=1, proposer=drafter)
    spec_reqs = drive(spec_eng)
    if [r.out_tokens for r in spec_reqs] \
            != [r.out_tokens for r in reqs[:len(prompts)]]:
        raise RuntimeError(f"{phase}: the MTP drafter changed the tokens "
                           f"served: {[r.out_tokens for r in spec_reqs]} vs "
                           f"{[r.out_tokens for r in reqs]}")
    r0 = Request("mtp", prompts[3], 2)
    spec_eng.core.kv.append_tokens("mtp", len(r0.prompt), tokens=r0.prompt)
    hidden = np.asarray(reference.hidden_states(
        weights, np.asarray(r0.prompt, np.int32), ref_cfg)[0])
    drafter.after_prefill(r0, hidden, reqs[3].out_tokens[0],
                          keep_logits=True)
    want = np.asarray(reference.mtp_logits_all_positions(
        weights, r0.prompt + [reqs[3].out_tokens[0]], ref_cfg))
    mtp_gap = float(np.abs(drafter.last_logits - want).max())
    if mtp_gap > MLA_LOGIT_ABS_TOL:
        raise RuntimeError(f"{phase}: the MTP module's logits lie {mtp_gap} "
                           f"from the reference's")
    say(phase=phase, **size["cfg"], num_pages=size["num_pages"],
        prompts=size["prompts"], long_prompt=len(long_prompt),
        new_tokens=size["new_tokens"], scheduler=spec_eng.stats, **seen, kernel_calls=kernels, **in_place,
        mla_decode_calls_a_program=decode_calls, mla_decode_walk=walk,
        mla_decode_grid_over_tables=grid_share,
        moe_gmm_calls_a_program=gmm_calls, moe_gmm_prefill_walk=gmm,
        served_logits_worst_gap=worst, route_slack=slack,
        mtp_logits_worst_gap=mtp_gap,
        drafter="tokens identical with the drafter on and off",
        pipeline="tokens identical with pipelined steps on and off",
        **ctx.memory())


def serve_prompts(ctx, cfg, weights, size, prompts, **kw):
    """An engine of ``size`` over bfloat16 pools, its scores kept, and
    ``prompts`` served to ``size["new_tokens"]`` each: ``(engine,
    requests)``."""
    from paddle_tpu.inference.serving import Request, ServingEngine

    eng = ServingEngine(
        cfg=cfg, weights=weights, kv_dtype="bfloat16", place=ctx.place,
        num_pages=size["num_pages"], page_size=size["page_size"],
        max_batch=size["max_batch"], token_budget=size["token_budget"], **kw)
    eng.core.keep_scores = True
    reqs = [Request(i, p, size["new_tokens"]) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    return eng, reqs


def phase_hybrid(ctx):
    """The hybrid decoder (KDA layers with a state slot a sequence beside an
    MLA layer's paged latent rows, expert layers holding a share of their
    experts) through ServingEngine: its kernels in the lowered programs, no
    operation of latent-pool or state-pool size in the compiled ones but the
    in-place writes, the served logits against the plain reference, and
    pipelined steps leaving greedy tokens unchanged."""
    import importlib.util

    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                                  init_mla_weights)

    jax = ctx.jax
    phase, size = "serve/hybrid", ctx.sizes["hybrid"]
    cfg = MLADecoderConfig(**size["cfg"])
    spec = importlib.util.spec_from_file_location(
        "reference_kimi", os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "benchmark", "reference", "kimi-linear-48b-a3b.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    weights = {n: jax.device_put(w, ctx.device)
               for n, w in init_mla_weights(cfg, 0).items()}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in size["prompts"]]

    def drive(**kw):
        return serve_prompts(ctx, cfg, weights, size, prompts, **kw)

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        mark = ctx.watch.mark()
        compiled_before = set(ctx.watch.compiled_texts())
        plain, reqs = drive()
        seen, modules = ctx.watch.since(mark)
        kernels = ctx.require_kernels(
            phase, modules, ["kda_prefill", "kda_decode", "mla_decode",
                             "latent_append", "moe_gmm"])
        in_place = ctx.require_pool_in_place(
            phase, plain.core.kv_config,
            n_pools=len(cfg.cache_pool_names()), append="latent_append")
        state = ctx.require_state_in_place(
            phase, cfg.state_pool_specs(size["max_batch"]))
        gmm_calls = ctx.require_gmm_calls(
            phase, (cfg.num_layers - cfg.first_k_dense,), compiled_before)
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = plain.core.served_scores(r.req_id)
        ref = reference.served_token_scores(
            weights, cfg.source_config(), r.prompt, r.out_tokens, routes,
            pad_to=2048 if ctx.sizes is SIZES["full"] else 128,
            prompt_routes=plain.core.prompt_routes(r.req_id))
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max()))
    if ctx.sizes is SIZES["full"] and (worst > HYBRID_LOGIT_ABS_TOL
                                       or slack > HYBRID_ROUTE_SLACK_TOL):
        raise RuntimeError(
            f"{phase}: served logits lie {worst} from the reference (limit "
            f"{HYBRID_LOGIT_ABS_TOL}), routing slack {slack} (limit "
            f"{HYBRID_ROUTE_SLACK_TOL})")
    gmm = gmm_walk(phase, plain)
    stats, slots = plain.stats, plain.kv.stats()["state_slots"]
    del plain
    gc.collect()
    piped, piped_reqs = drive(pipeline=2)
    if [r.out_tokens for r in piped_reqs] != [r.out_tokens for r in reqs]:
        raise RuntimeError(f"{phase}: pipelined steps changed the tokens "
                           f"served: {[r.out_tokens for r in piped_reqs]} "
                           f"vs {[r.out_tokens for r in reqs]}")
    del piped
    gc.collect()
    say(phase=phase, **{k: v for k, v in size["cfg"].items()},
        num_pages=size["num_pages"], prompts=size["prompts"],
        new_tokens=size["new_tokens"], scheduler=stats, state_slots=slots,
        **seen, kernel_calls=kernels, **in_place, **state,
        moe_gmm_calls_a_program=gmm_calls, moe_gmm_prefill_walk=gmm,
        served_logits_worst_gap=worst, route_slack=slack,
        pipeline="tokens identical with pipelined steps on and off",
        **ctx.memory())


def phase_longcat(ctx):
    """The shortcut-connected decoder with zero-computation experts (two MLA
    sub-layers and two dense halves a layer, the expert layer across them,
    a 1/32 share of the routed experts) through ServingEngine: its kernels
    in the lowered programs, no operation of latent-pool size in the
    compiled ones but the in-place writes, two ``moe_gmm`` calls a layer, the
    served logits against the plain reference, the choices it counts by
    kind, and pipelined steps leaving greedy tokens unchanged."""
    import importlib.util

    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                                  init_mla_weights)

    jax = ctx.jax
    phase, size = "serve/longcat", ctx.sizes["longcat"]
    cfg = MLADecoderConfig(**size["cfg"])
    spec = importlib.util.spec_from_file_location(
        "reference_longcat", os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "benchmark", "reference", "longcat-flash-chat.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    weights = {n: jax.device_put(w, ctx.device)
               for n, w in init_mla_weights(cfg, 0).items()}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in size["prompts"]]

    def drive(**kw):
        return serve_prompts(ctx, cfg, weights, size, prompts, **kw)

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        mark = ctx.watch.mark()
        compiled_before = set(ctx.watch.compiled_texts())
        plain, reqs = drive()
        seen, modules = ctx.watch.since(mark)
        kernels = ctx.require_kernels(
            phase, modules, ["mla_decode", "latent_append", "moe_gmm"])
        in_place = ctx.require_pool_in_place(
            phase, plain.core.kv_config,
            n_pools=len(cfg.cache_pool_names()), append="latent_append")
        gmm_calls = ctx.require_gmm_calls(phase, (cfg.num_layers,),
                                          compiled_before)
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    full = ctx.sizes is SIZES["full"]
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = plain.core.served_scores(r.req_id)
        ref = reference.served_token_scores(
            weights, cfg.source_config(), r.prompt, r.out_tokens, routes,
            pad_to=2048 if full else 128)
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max()))
    if full and (worst > LONGCAT_LOGIT_ABS_TOL
                 or slack > LONGCAT_ROUTE_SLACK_TOL):
        raise RuntimeError(
            f"{phase}: served logits lie {worst} from the reference (limit "
            f"{LONGCAT_LOGIT_ABS_TOL}), routing slack {slack} (limit "
            f"{LONGCAT_ROUTE_SLACK_TOL})")
    gmm = gmm_walk(phase, plain)
    stats, moe = plain.stats, plain.core.moe_stats
    choices = {p: {k: v for k, v in st.items() if k.startswith("choices_")}
               for p, st in moe.items()}
    if not 0 < choices["prefill"]["choices_identity"] \
            < choices["prefill"]["choices_all"]:
        raise RuntimeError(f"{phase}: the identity experts' choices are not "
                           f"counted: {choices}")
    del plain
    gc.collect()
    piped, piped_reqs = drive(pipeline=2)
    if [r.out_tokens for r in piped_reqs] != [r.out_tokens for r in reqs]:
        raise RuntimeError(f"{phase}: pipelined steps changed the tokens "
                           f"served: {[r.out_tokens for r in piped_reqs]} "
                           f"vs {[r.out_tokens for r in reqs]}")
    del piped
    gc.collect()
    say(phase=phase, **{k: v for k, v in size["cfg"].items()},
        num_pages=size["num_pages"], prompts=size["prompts"],
        new_tokens=size["new_tokens"], scheduler=stats, **seen,
        kernel_calls=kernels, **in_place, moe_gmm_calls_a_program=gmm_calls,
        moe_gmm_prefill_walk=gmm, choices=choices,
        served_logits_worst_gap=worst, route_slack=slack,
        pipeline="tokens identical with pipelined steps on and off",
        **ctx.memory())


def phase_gqa(ctx):
    """The grouped-query decoder with window layers (K and V pools in two
    groups of pages, the window layers' freed behind the window) through
    ServingEngine: its kernels in the lowered programs, no operation of
    either group's pool size in the compiled ones but the append's, the
    served logits against the plain reference, the walk of ``gqa_decode``
    against the context (a window layer's bounded whatever the context), and
    pipelined steps leaving greedy tokens unchanged."""
    import dataclasses
    import importlib.util

    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.inference.gqa_decoder import (GQADecoderConfig, Rope,
                                                  init_gqa_weights)
    from paddle_tpu.ops import gqa_kernels

    jax = ctx.jax
    phase, size = "serve/gqa", ctx.sizes["gqa"]
    cfg = GQADecoderConfig(rope_full=Rope(**size["rope_full"]),
                           rope_window=Rope(**size["rope_window"]),
                           **size["cfg"])
    spec = importlib.util.spec_from_file_location(
        "reference_laguna", os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "benchmark", "reference", "laguna-xs2.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    weights = {n: jax.device_put(w, ctx.device)
               for n, w in init_gqa_weights(cfg, 0).items()}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in size["prompts"]]

    def drive(**kw):
        return serve_prompts(ctx, cfg, weights, size, prompts, **kw)

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        mark = ctx.watch.mark()
        plain, reqs = drive()
        seen, modules = ctx.watch.since(mark)
        kernels = ctx.require_kernels(
            phase, modules, ["gqa_prefill", "gqa_decode", "moe_gmm"]
            + ([] if ctx.interpreted else ["kv_append"]))
        kvc = plain.core.kv_config
        in_place = {
            "full": ctx.require_pool_in_place(
                phase, kvc, n_pools=2 * len(cfg.full_layers)),
            "window": ctx.require_pool_in_place(
                phase, dataclasses.replace(kvc, num_pages=kvc.window_pages),
                n_pools=2 * len(cfg.window_layers))}
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = plain.core.served_scores(r.req_id)
        ref = reference.served_token_scores(
            weights, cfg.source_config(), r.prompt, r.out_tokens, routes,
            pad_to=size["pad_to"],
            prompt_routes=plain.core.prompt_routes(r.req_id))
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max()))
    if ctx.sizes is SIZES["full"] and (worst > GQA_LOGIT_ABS_TOL
                                       or slack > GQA_ROUTE_SLACK_TOL):
        raise RuntimeError(
            f"{phase}: served logits lie {worst} from the reference (limit "
            f"{GQA_LOGIT_ABS_TOL}), routing slack {slack} (limit "
            f"{GQA_ROUTE_SLACK_TOL})")
    # the walk, by the engine's own count and by the function the kernel's
    # wrapper sizes its grid with, at the requests' last contexts
    counts = plain.stats["kernels"].get("decode", {})
    over = counts.get("gqa_decode_pages_walked", 0) / max(
        counts.get("gqa_decode_pages_in_context", 0), 1)
    ends = np.array([len(r.prompt) + len(r.out_tokens) - 1 for r in reqs])
    page, window = kvc.page_size, cfg.window
    first = np.maximum(ends - window - page + 1, 0) // page * page
    _, _, walked = gqa_kernels.decode_span(ends, first, page, window)
    if ctx.sizes is SIZES["full"] and (
            not counts or over > GQA_WALK_OVER_CONTEXT_MAX
            or walked.max() > GQA_WINDOW_WALK_PAGES_MAX):
        raise RuntimeError(
            f"{phase}: gqa_decode walked {over} of the pages in context "
            f"(limit {GQA_WALK_OVER_CONTEXT_MAX}) and a window layer "
            f"{walked.tolist()} pages a row at contexts {ends.tolist()} "
            f"(limit {GQA_WINDOW_WALK_PAGES_MAX}): {counts}")
    gmm = gmm_walk(phase, plain)
    stats, groups = plain.stats, plain.kv.stats()["groups"]
    if groups["window"]["peak_pages"] > size["max_batch"] \
            * kvc.window_pages_per_seq:
        raise RuntimeError(f"{phase}: the window group held more than "
                           f"{kvc.window_pages_per_seq} pages a sequence: "
                           f"{groups}")
    del plain
    gc.collect()
    piped, piped_reqs = drive(pipeline=2)
    if [r.out_tokens for r in piped_reqs] != [r.out_tokens for r in reqs]:
        raise RuntimeError(f"{phase}: pipelined steps changed the tokens "
                           f"served: {[r.out_tokens for r in piped_reqs]} "
                           f"vs {[r.out_tokens for r in reqs]}")
    del piped
    gc.collect()
    say(phase=phase, **{k: v for k, v in size["cfg"].items()},
        num_pages=size["num_pages"], prompts=size["prompts"],
        new_tokens=size["new_tokens"], scheduler=stats, page_groups=groups,
        **seen, kernel_calls=kernels, pools_in_place=in_place,
        moe_gmm_prefill_walk=gmm, gqa_decode_walk_over_context=over,
        window_walk_pages_a_row=walked.tolist(),
        served_logits_worst_gap=worst, route_slack=slack,
        pipeline="tokens identical with pipelined steps on and off",
        **ctx.memory())


def phase_olmo(ctx):
    """The Olmo-Hybrid-shaped decoder (Gated DeltaNet layers with a state
    slot a sequence beside a full layer's paged K/V rows, dense throughout)
    through ServingEngine: its four kernels in the lowered programs, no
    operation of K/V-pool or state-pool size in the compiled ones but the
    in-place writes, the served logits against the plain reference, and
    pipelined steps leaving greedy tokens unchanged."""
    import importlib.util

    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.inference.gqa_decoder import (GQADecoderConfig, Rope,
                                                  init_gqa_weights)

    jax = ctx.jax
    phase, size = "serve/olmo", ctx.sizes["olmo"]
    cfg = GQADecoderConfig(rope_full=Rope(lanes=0), rope_window=Rope(lanes=0),
                           **size["cfg"])
    spec = importlib.util.spec_from_file_location(
        "reference_olmo", os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "benchmark", "reference", "olmo-hybrid-7b.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    weights = {n: jax.device_put(w, ctx.device)
               for n, w in init_gqa_weights(cfg, 0).items()}
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in size["prompts"]]

    def drive(**kw):
        return serve_prompts(ctx, cfg, weights, size, prompts, **kw)

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        mark = ctx.watch.mark()
        plain, reqs = drive()
        seen, modules = ctx.watch.since(mark)
        kernels = ctx.require_kernels(
            phase, modules, ["gdn_prefill", "gdn_decode", "gqa_prefill",
                             "gqa_decode"]
            + ([] if ctx.interpreted else ["kv_append"]))
        in_place = ctx.require_pool_in_place(
            phase, plain.core.kv_config, n_pools=len(cfg.cache_pool_names()))
        state = ctx.require_state_in_place(
            phase, cfg.state_pool_specs(size["max_batch"]))
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    worst = 0.0
    for r in reqs:
        got, _ = plain.core.served_scores(r.req_id)
        ref = reference.served_token_scores(
            weights, cfg.source_config(), r.prompt, r.out_tokens,
            pad_to=size["pad_to"])
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
    if ctx.sizes is SIZES["full"] and worst > OLMO_LOGIT_ABS_TOL:
        raise RuntimeError(
            f"{phase}: served logits lie {worst} from the reference (limit "
            f"{OLMO_LOGIT_ABS_TOL})")
    stats, slots = plain.stats, plain.kv.stats()["state_slots"]
    counted = {k: v for part in ("prefill", "decode")
               for k, v in stats["kernels"].get(part, {}).items()}
    if not counted.get("gdn_prefill_tokens") \
            or not counted.get("gdn_decode_sequences"):
        raise RuntimeError(f"{phase}: the forms counted no gdn kernel's "
                           f"work: {stats['kernels']}")
    del plain
    gc.collect()
    piped, piped_reqs = drive(pipeline=2)
    if [r.out_tokens for r in piped_reqs] != [r.out_tokens for r in reqs]:
        raise RuntimeError(f"{phase}: pipelined steps changed the tokens "
                           f"served: {[r.out_tokens for r in piped_reqs]} "
                           f"vs {[r.out_tokens for r in reqs]}")
    del piped
    gc.collect()
    say(phase=phase, **{k: v for k, v in size["cfg"].items()},
        num_pages=size["num_pages"], prompts=size["prompts"],
        new_tokens=size["new_tokens"], scheduler=stats, state_slots=slots,
        state_slot_bytes=cfg.state_slot_bytes(),
        **seen, kernel_calls=kernels, **in_place, **state,
        served_logits_worst_gap=worst,
        pipeline="tokens identical with pipelined steps on and off",
        **ctx.memory())


def phase_tp4(ctx):
    """tp=4 decode against tp=1 tokens for the same requests."""
    phase, size = "tp4/decoder", ctx.sizes["serve"]
    model_dir = export(size)
    try:
        one, reqs1, _ = serve(ctx, model_dir, size, tp=1)
        mark = ctx.watch.mark()
        four, reqs4, steady = serve(ctx, model_dir, size, tp=4)
        seen, modules = ctx.watch.since(mark)
        kernels = ctx.require_kernels(phase, modules,
                                      ["paged_decode", "kv_append"])
        verdicts = [compare_tokens(phase, f"request {a.req_id} tp=4 vs tp=1",
                                   one.core, a.prompt, b.out_tokens,
                                   a.out_tokens)
                    for a, b in zip(reqs1, reqs4)]
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    # weights and KV pages really are spread: every sharded var holds a
    # quarter per device, on four distinct devices
    core = four.core
    sharded = [n for n in core.scope.local_var_names()
               if core._tp_spec(n) is not None]
    if not any(n.startswith("kv_") for n in sharded):
        raise RuntimeError(f"{phase}: no KV pool is sharded")
    for n in sharded:
        a = core.scope.get(n)
        if {s.data.nbytes * 4 for s in a.addressable_shards} != {a.nbytes}:
            raise RuntimeError(f"{phase}: {n} is not split four ways")
    del one
    gc.collect()
    spread = device_spread(ctx, [core.scope.get(n) for n in sharded])
    worst = max((v.get("worst_gap", 0.0) for v in verdicts), default=0.0)
    say(phase=phase, **size["cfg"], requests=len(reqs4),
        token_identical=sum(v["agreement"] == "token-identical"
                            for v in verdicts),
        within_tolerance=sum(v["agreement"] != "token-identical"
                             for v in verdicts),
        tolerance=LOGIT_TIE_TOL, worst_gap=worst,
        sharded_vars=len(sharded), **steady, **seen, kernel_calls=kernels,
        **spread)


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the DP-4 and tp=4 paths and what they "
                         "are compared with")
    ap.add_argument("--size", choices=sorted(SIZES), default="full")
    ap.add_argument("--only", default=None,
                    help="comma-separated phases to run instead of all of "
                         "the chip count's (resnet, bert, serve, mla, hybrid, "
                         "gqa, olmo, longcat, dp4, tp4)")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="skip the TPU assertion (and the device's memory "
                         "counters): a rehearsal, never a result")
    args = ap.parse_args(argv)

    ctx = Ctx(args)
    jax = ctx.jax
    try:
        if jax.device_count() < args.chips:
            sys.exit(f"chip_smoke: --chips {args.chips} but JAX has "
                     f"{jax.device_count()} device(s)")
        import jaxlib

        t0 = time.perf_counter()
        entries0 = ctx.cache_entries()
        say(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
            libtpu=_libtpu_version(), platform=ctx.device.platform,
            device_kind=ctx.device.device_kind, devices=jax.device_count(),
            size=args.size, rehearsal=ctx.rehearsal,
            compile_cache_dir=ctx.cache_dir, cache_entries_before=entries0,
            note="smoke observations, not benchmark metrics")
        phases = (phase_dp4, phase_tp4) if args.chips == 4 else \
            (phase_resnet, phase_bert, phase_serve, phase_mla, phase_hybrid,
             phase_gqa, phase_olmo, phase_longcat)
        if args.only:
            phases = [globals()["phase_" + name]
                      for name in args.only.split(",")]
        for phase in phases:
            phase(ctx)
            gc.collect()
        say(phase="end", wall_s=round(time.perf_counter() - t0, 1),
            compilations=ctx.watch.compiles,
            backend_compile_s=round(ctx.watch.compile_s, 1),
            cache_hits=ctx.watch.hits, cache_misses=ctx.watch.misses,
            compile_cache_dir=ctx.cache_dir,
            cache_entries_before=entries0,
            cache_entries_after=ctx.cache_entries())
    finally:
        ctx.close()
    if ctx.rehearsal:
        # not the contract's line: a rehearsal is never a result
        say(rehearsal="passed", platform=ctx.device.platform)
        return
    say(ok=True, device={"platform": ctx.device.platform,
                         "kind": ctx.device.device_kind,
                         "count": len(jax.devices())})


def _libtpu_version():
    try:
        import libtpu
    except ImportError:
        return None
    return getattr(libtpu, "__version__", None)


if __name__ == "__main__":
    main()
