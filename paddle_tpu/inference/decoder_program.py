"""The model side of the serving seam: what a model description owes the
engine, and what every description builds its program forms with.

    serving.py  --reads-->  decoder_program.py  <--build with--  gpt2_decoder.py
    kv_cache.py             (this module)                        mla_decoder.py
    admission.py                                                 gqa_decoder.py
    (engine, scheduler)                                          (--> ops/)

The arrows point one way: no model module imports the engine's, and this
module imports neither.  A new model's author starts here:

* :class:`ServedModel` is the whole of what ``ServingEngine`` asks a
  description (three attributes, nine methods), and :class:`FormExtras`
  what a program form may offer a call beyond its tokens, set once on the
  program as ``_form_extras``.
* :func:`build_form` builds one form of a decoder whose layers are
  :meth:`_MB.block`: ``h = x + Mix(RMSNorm(x))``, ``y = h +
  FFN(RMSNorm(h))``, the feed-forward half a SwiGLU or the routed experts
  (or, where the description says ``shortcut``, :meth:`_MB.shortcut_pair`:
  two such sub-blocks with the expert layer on a shortcut across them).
  The model gives its own feeds, its live-row mask and ONE hook,
  ``mix(i, x)``, the mixer of layer ``i`` before its ``wo`` (latent
  attention, KDA, grouped-query attention full or windowed, Gated
  DeltaNet: :data:`MIXER_PARTS`).  The shared code never asks which model
  it serves.
* a model file holds its configuration class (the protocol's methods in
  the class's own body), its weights' specs and seeds, its mixers, and its
  forms' kernel walk.  GPT-2 (``gpt2_decoder.py``) builds its forms from the
  primitives alone (:class:`_B` and the K/V pool helpers).
"""
from __future__ import annotations

import contextlib
import functools
from typing import (Callable, Dict, List, NamedTuple, Optional, Protocol,
                    runtime_checkable)

import numpy as np

from ..framework.core import Program
from ..framework.dtype import VarType, convert_dtype
from ..ops import mla_kernels
from .kv_cache import KVCacheConfig


# tensor-parallel decode (FLAGS_serving_tp): the mesh axis a description's
# ``tp_rules`` shard over, and the dedicated collective ring its allreduces
# run on (ring 0 belongs to the data-parallel paths — the serving mesh must
# never capture it)
SERVING_TP_AXIS = "mp"
SERVING_TP_RING_ID = 7


# ==========================================================================
# The seam
# ==========================================================================
@runtime_checkable
class ServedModel(Protocol):
    """What ``ServingEngine`` asks of a model description; it never asks
    which it serves.  Every method is defined in the description's own class
    body (the benchmark's runners delete ``state_pool_specs`` from a class to
    take the state away, and look with ``hasattr``).  ``DecoderConfig.
    init_weights`` is GPT-2's convenience and no part of this."""

    max_seq_len: int
    eos_id: int
    num_layers: int

    def param_specs(self) -> Dict[str, tuple]:
        """name -> shape of every weight."""

    def build_program(self, mode: str, sampling=None,
                      kv_dtype: str = "float32", tp: int = 1) -> tuple:
        """``(program, feeds, fetches)`` of the form ``mode``
        (``reference``, ``prefill``, ``decode``; ``chunk`` and ``verify``
        where the model is served with them), the program carrying its
        :class:`FormExtras`."""

    def validate(self, tp: int = 1, kv_dtype: str = "float32",
                 prefix_cache: bool = False, prefill_chunk: int = 0,
                 spec_k: int = 0) -> None:
        """Raise ValueError for what this model is not served with."""

    def tp_rules(self, kv_dtype: str = "float32") -> Dict[str, tuple]:
        """Regex -> partition spec of the weights and pools under tensor
        parallelism; empty where the model has none."""

    def kv_cache_config(self, num_pages: int, page_size: int,
                        kv_dtype: str) -> KVCacheConfig:
        """The paged cache's geometry."""

    def cache_pool_names(self) -> List[str]:
        """The pool vars of the serving forms."""

    def kv_token_bytes(self, kv_dtype: str, tp: int = 1) -> int:
        """Bytes one token holds in one device's pools, all layers."""

    def state_pool_specs(self, state_slots: int) -> Dict[str, tuple]:
        """name -> (shape, dtype) of the pools that hold one slot a
        sequence (a recurrent layer's state); empty where none does."""

    def window_pool_names(self) -> List[str]:
        """The pools of the window group of pages; empty where none is."""


class FormExtras(NamedTuple):
    """What a program form offers a call beyond its tokens, by var name
    (None: not offered), set once on the program as ``_form_extras``."""
    logits: Optional[str] = None       # the parity hook
    hidden: Optional[str] = None       # the rows before the final norm
    score: Optional[str] = None        # each token's logit, the row's LSE
    routes: Optional[str] = None       # (expert layers, emitting rows, k)
    routes_all: Optional[str] = None   # (expert layers, rows, k)
    counts: Optional[str] = None       # (expert layers, experts)
    absent: Optional[str] = None       # (expert layers,): rows held elsewhere
    #: (expert layers, 3): choices on held experts, on identity experts, all
    choices: Optional[str] = None
    #: ``(feed, kv_config) -> counts`` of what the call's kernels walk
    kernel_stats: Optional[Callable] = None
    #: a decode form whose kernels walk the chunks that hold context and no
    #: column beyond them: ``kv_config -> pages``, the widest block table
    #: that costs such a call its int32s alone (None from it: the kernel
    #: does not engage for these pages).  The engine feeds such a form one
    #: table width; a form without it is fed the contexts' own bucket
    live_walk_pages: Optional[Callable] = None


# ==========================================================================
# The primitives every builder uses
# ==========================================================================
class _B:
    """Tiny block-building helper: explicit var names, direct append_op."""

    #: the part of the model the ops built from here on serve (attr ``part``,
    #: which ``registry.run_op`` turns into their outermost scope); None,
    #: as GPT-2's forms have it, adds nothing
    part: Optional[str] = None

    def __init__(self, program: Program):
        self.blk = program.global_block()
        self._n = 0

    def tmp(self, tag: str):
        self._n += 1
        return self.blk.create_var(name=f"_srv_{tag}_{self._n}").name

    def feed(self, name, shape, dtype=VarType.FP32):
        return self.blk.create_var(name=name, shape=shape, dtype=dtype,
                                   is_data=True).name

    def param(self, name, shape, dtype=VarType.FP32):
        return self.blk.create_var(name=name, shape=shape, dtype=dtype,
                                   persistable=True).name

    def op(self, type, inputs, outputs, attrs=None):
        attrs = attrs or {}
        if self.part is not None:
            attrs = {"part": self.part, **attrs}
        self.blk.append_op(type, inputs=inputs, outputs=outputs, attrs=attrs)

    # common composites --------------------------------------------------
    def matmul(self, x, y, transpose_Y=False, alpha=1.0, tag="mm"):
        o = self.tmp(tag)
        self.op("matmul", {"X": [x], "Y": [y]}, {"Out": [o]},
                {"transpose_X": False, "transpose_Y": transpose_Y,
                 "alpha": float(alpha)})
        return o

    def add(self, x, y, tag="add"):
        o = self.tmp(tag)
        self.op("elementwise_add", {"X": [x], "Y": [y]}, {"Out": [o]},
                {"axis": -1})
        return o

    def reshape(self, x, shape, tag="rs"):
        o = self.tmp(tag)
        self.op("reshape2", {"X": [x]}, {"Out": [o]},
                {"shape": list(shape)})
        return o

    def transpose(self, x, perm, tag="tr"):
        o = self.tmp(tag)
        self.op("transpose2", {"X": [x]}, {"Out": [o]},
                {"axis": list(perm)})
        return o

    def layer_norm(self, x, scale, bias, begin, tag="ln"):
        o = self.tmp(tag)
        self.op("layer_norm",
                {"X": [x], "Scale": [scale], "Bias": [bias]},
                {"Y": [o], "Mean": [self.tmp(tag + "_m")],
                 "Variance": [self.tmp(tag + "_v")]},
                {"begin_norm_axis": begin, "epsilon": 1e-5})
        return o

    def lookup(self, table, ids, tag="emb"):
        o = self.tmp(tag)
        self.op("lookup_table_v2", {"W": [table], "Ids": [ids]},
                {"Out": [o]})
        return o

    def gelu(self, x):
        o = self.tmp("gelu")
        self.op("gelu", {"X": [x]}, {"Out": [o]})
        return o



def _sampled(sampling) -> bool:
    return sampling is not None and not sampling.greedy


def _emit_head(b: _B, logits: str, out_name: str, sampling,
               seeds: Optional[str]) -> str:
    """The token head every program form shares: argmax by default (the
    bit-identity baseline), the in-program ``sample_token`` op when
    sampling is armed — sampling params are baked as attrs, the per-row
    RNG lanes arrive through the ``seeds`` feed."""
    out = b.blk.create_var(name=out_name, dtype=VarType.INT64).name
    if _sampled(sampling):
        b.op("sample_token", {"Logits": [logits], "Seeds": [seeds]},
             {"Out": [out]},
             {"temperature": float(sampling.temperature),
              "top_k": int(sampling.top_k),
              "top_p": float(sampling.top_p)})
    else:
        b.op("arg_max", {"X": [logits]}, {"Out": [out]},
             {"axis": -1, "keepdims": False, "flatten": False})
    return out


def _kv_pool_params(b: _B, i: int, quant: bool, kv_dtype: str = "float32"):
    """Declare layer ``i``'s K/V pool vars (plus the int8 scale pools
    when ``quant``); returns ``(kc, vc, ksc, vsc)`` — scale names are
    None for unquantized storage, so the default program grows NO new
    vars (the byte-identity pin).  The pool var descs carry the STORAGE
    dtype (shape stays (): the runtime pools are scope-priced), so an
    offline ``progcheck --mem`` of a serialized program can still
    report what the pool stores."""
    dt = convert_dtype(kv_dtype)
    kc = b.param(f"kv_k_{i}", (), dtype=dt)
    vc = b.param(f"kv_v_{i}", (), dtype=dt)
    if not quant:
        return kc, vc, None, None
    return kc, vc, b.param(f"kv_k_scale_{i}", ()), \
        b.param(f"kv_v_scale_{i}", ())


def _kv_append(b: _B, k3, v3, slot_map, kc, vc, ksc, vsc):
    """One ``kv_cache_append`` — quantize-on-write when the scale pools
    ride along (int8 storage)."""
    ins = {"K": [k3], "V": [v3], "SlotMapping": [slot_map],
           "KCache": [kc], "VCache": [vc]}
    outs = {"KCacheOut": [kc], "VCacheOut": [vc]}
    if ksc is not None:
        ins["KScale"], ins["VScale"] = [ksc], [vsc]
        outs["KScaleOut"], outs["VScaleOut"] = [ksc], [vsc]
    b.op("kv_cache_append", ins, outs)


def _kv_gather_deq(b: _B, pool, scale, tables, kv_dtype, tag):
    """Pool gather for the dense (chunk/verify) attention forms, with
    the storage-dtype read path: gather pages through the block table,
    then ``kv_dequant`` back to f32 (int8: the SAME gather applied to
    the scale pool rides along, so each page meets its own scale).  The
    f32 path emits the plain gather — byte-identical to the unquantized
    program.  The gather runs on the pool AS STORED (``KVCacheConfig.
    pool_shape``: pages on axis 1, a page ``(rows, width)``); the callers'
    reshape of the GATHERED pages to ``(…, tokens, D)`` reads them back
    as token rows, both forms being row-major — never a reshape of a
    pool."""
    g = b.tmp(tag)
    b.op("gather", {"X": [pool], "Index": [tables]}, {"Out": [g]},
         {"axis": 1})
    if kv_dtype == "float32":
        return g
    ins = {"X": [g]}
    if scale is not None:
        sg = b.tmp(tag + "_sc")
        b.op("gather", {"X": [scale], "Index": [tables]}, {"Out": [sg]},
             {"axis": 1})
        ins["Scale"] = [sg]
    dq = b.tmp(tag + "_dq")
    b.op("kv_dequant", ins, {"Out": [dq]})
    return dq


def _pow2_bucket(n: int, lo: int = 1, hi: Optional[int] = None) -> int:
    b = lo
    while b < n:
        b *= 2
    return min(b, hi) if hi is not None else b


# ==========================================================================
# The block builder
# ==========================================================================
#: a layer's mixer kind -> the part of the model its ops serve
MIXER_PARTS = {"mla": "mla_part", "kda": "kda_part", "full": "attn_full",
               "window": "attn_window", "linear": "gdn_part"}


class _MB:
    """:class:`_B` plus the composites of a block whose feed-forward half is
    a SwiGLU or the routed experts.  Parameters take the configuration's
    weights type."""

    def __init__(self, program: Program, cfg):
        self.b = _B(program)
        self.cfg = cfg
        self.wdt = convert_dtype(cfg.weights_dtype)
        for name, shape in cfg.param_specs().items():
            self.b.param(name, shape, dtype=self.wdt)

    def op(self, *a, **kw):
        self.b.op(*a, **kw)

    def tmp(self, tag):
        return self.b.tmp(tag)

    @contextlib.contextmanager
    def part(self, name):
        """Every op built inside serves this part of the model (``embed``,
        ``mla_part``, ``kda_part``, ``gdn_part``, ``attn_full``,
        ``attn_window``, ``moe_part``, ``dense_ffn``, ``head``, ``mtp``): its
        attr ``part``, which ``registry.run_op`` makes the
        op's outermost scope, so the compiled program says whose time each
        of its instructions is (``profiler.device_symbols``)."""
        was, self.b.part = self.b.part, name
        try:
            yield
        finally:
            self.b.part = was

    def mm(self, x, w, tag):
        o = self.tmp(tag)
        self.op("matmul_f32acc", {"X": [x], "Y": [w]}, {"Out": [o]})
        return o

    def norm(self, x, scale, tag):
        o = self.tmp(tag)
        self.op("rms_norm", {"X": [x], "Scale": [scale]}, {"Y": [o]},
                {"epsilon": float(self.cfg.rms_norm_eps)})
        return o

    def swiglu_ffn(self, x, gate, up, down, tag):
        g = self.mm(x, gate, tag + "_g")
        u = self.mm(x, up, tag + "_u")
        a = self.tmp(tag + "_act")
        self.op("swiglu", {"Gate": [g], "Up": [u]}, {"Out": [a]})
        return self.mm(a, down, tag + "_d")

    def block(self, i, hid, mix, valid, counts, routes=None, absent=None,
              choices=None):
        """One block over rows ``hid`` (n, hidden): pre-norm, ``h = x +
        Mix(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, or where the
        description says ``norm_after`` the same two scales on the OUTPUTS,
        ``h = x + RMSNorm(Mix(x))``, ``y = h + RMSNorm(FFN(h))``.  ``mix``
        maps ``(i, the mixer's input rows)`` to the mixer's output before its
        ``wo``, for a layer of any kind (``MIXER_PARTS``); ``valid`` (or
        None) marks the rows that are real tokens; an expert layer appends
        its per-expert counts to ``counts`` (and, where it holds a share of
        its experts, the number of rows none of whose experts it holds to
        ``absent``; where it has identity experts, its choices by kind to
        ``choices``).  Where the description says ``shortcut`` layer ``i`` is
        :meth:`shortcut_pair`."""
        cfg, b = self.cfg, self.b
        if getattr(cfg, "shortcut", False):
            return self.shortcut_pair(i, hid, mix, valid, counts, routes,
                                      absent, choices)
        hid = self._mixed(i, hid, mix)
        dense = i < cfg.first_k_dense
        with self.part("dense_ffn" if dense else "moe_part"):
            return b.add(hid, self._ffn(i, hid, dense, valid, counts, routes,
                                        absent, choices), f"l{i}_res2")

    def _mixed(self, i, hid, mix):
        """``hid`` plus (sub-)layer ``i``'s mixer over it, under the mixer's
        part."""
        cfg, p, b = self.cfg, f"dec_l{i}_", self.b
        with self.part(MIXER_PARTS[cfg.mixer(i)]):
            if getattr(cfg, "norm_after", False):
                out = self.norm(self.mm(mix(i, hid), p + "wo", f"l{i}_o"),
                                p + "attn_norm_scale", f"l{i}_an")
            else:
                hn = self.norm(hid, p + "attn_norm_scale", f"l{i}_an")
                out = self.mm(mix(i, hn), p + "wo", f"l{i}_o")
            return b.add(hid, out, f"l{i}_res1")

    def shortcut_pair(self, layer, hid, mix, valid, counts, routes, absent,
                      choices):
        """A layer of a shortcut-connected model (arXiv:2509.01322): two
        sub-blocks ``2 * layer`` and ``2 * layer + 1``, each a mixer and a
        dense SwiGLU with weights, norms and cache rows of its own, and ONE
        expert layer that reads the first sub-block's normed stream and is
        added after the second's dense half, so that it has the second mixer
        and two dense halves to run beside::

            h1 = x  + Mix_0(RMSNorm(x))      n1 = RMSNorm(h1)
            m  = Experts(n1)
            h2 = h1 + SwiGLU_0(n1)
            h3 = h2 + Mix_1(RMSNorm(h2))     n3 = RMSNorm(h3)
            y  = h3 + SwiGLU_1(n3) + m
        """
        b, first, second = self.b, 2 * layer, 2 * layer + 1
        p = f"dec_l{first}_"
        h1 = self._mixed(first, hid, mix)
        with self.part("dense_ffn"):
            n1 = self.norm(h1, p + "ffn_norm_scale", f"l{first}_fn")
        with self.part("moe_part"):
            m = self._experts(first, n1, valid, counts, routes, absent,
                              choices)
        with self.part("dense_ffn"):
            h2 = b.add(h1, self.swiglu_ffn(
                n1, p + "w_gate", p + "w_up", p + "w_down", f"l{first}_ff"),
                f"l{first}_res2")
        h3 = self._mixed(second, h2, mix)
        with self.part("dense_ffn"):
            y = b.add(h3, self._ffn(second, h3, True, valid, counts, routes,
                                    absent, choices), f"l{second}_res2")
        with self.part("moe_part"):
            return b.add(y, m, f"l{first}_short")

    def _ffn(self, i, hid, dense, valid, counts, routes, absent, choices):
        """Layer ``i``'s feed-forward half over ``hid``: its norm and the
        dense SwiGLU, or the router, the routed experts and the shared
        expert where there is one; with ``norm_after`` the dense SwiGLU and
        then the norm."""
        cfg, p, b = self.cfg, f"dec_l{i}_", self.b
        if getattr(cfg, "norm_after", False):
            if not dense:
                raise ValueError("norm_after is built for dense layers")
            return self.norm(
                self.swiglu_ffn(hid, p + "w_gate", p + "w_up", p + "w_down",
                                f"l{i}_ff"), p + "ffn_norm_scale", f"l{i}_fn")
        hn2 = self.norm(hid, p + "ffn_norm_scale", f"l{i}_fn")
        if dense:
            return self.swiglu_ffn(hn2, p + "w_gate", p + "w_up",
                                   p + "w_down", f"l{i}_ff")
        routed = self._experts(i, hn2, valid, counts, routes, absent,
                               choices)
        if not cfg.n_shared_experts:
            return routed
        shared = self.swiglu_ffn(hn2, p + "shared_gate", p + "shared_up",
                                 p + "shared_down", f"l{i}_sh")
        return b.add(routed, shared, f"l{i}_ff")

    def _experts(self, i, hn, valid, counts, routes, absent, choices):
        """The router and the routed experts of layer ``i`` over the normed
        rows ``hn``: the held experts' part of the sum and, where the model
        has zero-computation experts, their identity term."""
        cfg, p = self.cfg, f"dec_l{i}_"
        zero = getattr(cfg, "zero_experts", 0)
        idx, wgt = self.tmp(f"l{i}_ridx"), self.tmp(f"l{i}_rw")
        attrs = {"top_k": int(cfg.num_experts_per_tok),
                 "routed_scaling_factor": float(cfg.routed_scaling_factor),
                 "norm_topk_prob": bool(cfg.norm_topk_prob)}
        if getattr(cfg, "router_scoring", "sigmoid") != "sigmoid":
            attrs["scoring_func"] = str(cfg.router_scoring)
        self.op("moe_router",
                {"X": [hn], "Gate": [p + "router"],
                 "Bias": [p + "router_bias"]},
                {"Idx": [idx], "Weight": [wgt]}, attrs)
        routed, cnt = self.tmp(f"l{i}_moe"), self.tmp(f"l{i}_cnt")
        ins = {"X": [hn], "Idx": [idx], "Weight": [wgt],
               "WGate": [p + "experts_gate"], "WUp": [p + "experts_up"],
               "WDown": [p + "experts_down"]}
        if valid is not None:
            ins["Valid"] = [valid]
        outs = {"Out": [routed], "Counts": [cnt]}
        if cfg.experts_here < cfg.n_routed_experts:
            # this chip's share: the rows' other experts are elsewhere
            outs["Absent"] = [self.tmp(f"l{i}_absent")]
            absent.append(outs["Absent"][0])
        if zero:
            # outputs of the router past the routed experts are identity
            # experts: no weights, so every chip computes its own tokens'
            outs["Choices"] = [self.tmp(f"l{i}_choices")]
            choices.append(outs["Choices"][0])
        self.op("moe_experts", ins, outs,
                {"routed_experts": int(cfg.n_routed_experts)} if zero
                else None)
        counts.append(cnt)
        if routes is not None:
            routes.append(idx)
        return routed

    def stacked(self, per_layer, name):
        """The expert layers' small int32 results as one fetch, layers
        first."""
        out = self.b.blk.create_var(name=name, dtype=VarType.INT32).name
        self.op("stack", {"X": list(per_layer)}, {"Y": [out]}, {"axis": 0})
        return out


def ffn_specs(cfg, i: int, moe: bool) -> Dict[str, tuple]:
    """The feed-forward half of layer ``i``: its norm and the dense SwiGLU,
    or :func:`expert_specs` (what ``_MB._ffn`` builds, for any description
    with these fields)."""
    h, p = cfg.hidden, f"dec_l{i}_"
    specs = {p + "ffn_norm_scale": (h,)}
    if not moe:
        f = cfg.intermediate
        specs.update({p + "w_gate": (h, f), p + "w_up": (h, f),
                      p + "w_down": (f, h)})
        return specs
    specs.update(expert_specs(cfg, i))
    return specs


def expert_specs(cfg, i: int) -> Dict[str, tuple]:
    """The expert layer ``_MB._experts`` builds under layer ``i``'s names:
    the router over every output (the routed experts, then the
    zero-computation ones), this chip's experts and, where the model has
    one, the shared expert."""
    h, p = cfg.hidden, f"dec_l{i}_"
    f, held = cfg.moe_intermediate, cfg.experts_here
    e = cfg.n_routed_experts + getattr(cfg, "zero_experts", 0)
    specs = {
        p + "router": (h, e), p + "router_bias": (e,),
        p + "experts_gate": (held, h, f), p + "experts_up": (held, h, f),
        p + "experts_down": (held, f, h)}
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        specs.update({p + "shared_gate": (h, fs), p + "shared_up": (h, fs),
                      p + "shared_down": (fs, h)})
    return specs


#: the weights of a delta-rule mixer (``kda_*``, ``gdn_*``) that are no
#: matrix over sqrt(fan-in)
DELTA_RULE_SEEDS = ("_a_log", "_dt_bias", "kda_conv", "gdn_conv")


def delta_rule_seed(name: str, shape, rng) -> np.ndarray:
    """A delta-rule mixer's decay and taps, seeded: ``A_log`` the log of a
    rate uniform in [1, 16], ``dt_bias`` the inverse softplus of a step
    log-uniform in [0.001, 0.1], the convolution's taps normal over
    sqrt(taps)."""
    if name.endswith("_a_log"):
        return np.log(rng.uniform(1.0, 16.0, shape))
    if name.endswith("_dt_bias"):
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
        return dt + np.log(-np.expm1(-dt))
    return rng.randn(*shape) / np.sqrt(shape[-1])


def _gmm_walk(counts, *, rows: int, hidden: int):
    """What a call's ``moe_gmm`` kernels walked, from the tokens each expert
    received (``counts``, (expert layers, experts): the call's own
    ``FormExtras.counts``, on the host once ``moe_stats`` reads them) and the
    rows the call's dispatch sorts (token rows times ``k``), by the tile the
    kernel's wrapper uses (``mla_kernels.gmm_walk_counts``).  An expert
    layer's two calls walk the same list: the row tiles that hold a row an
    expert owns and the (row tile, expert) visits, summed over them.  And
    the rows around them: ``moe_rows_sorted`` the ``n * k`` choices a
    layer's dispatch sorts, ``moe_rows_moved`` those of them that were moved
    in and out of the matmuls: the rows the experts here own where
    ``moe_rows_in`` / ``moe_combine`` take the call, all of them where
    XLA's ``take`` does."""
    counts = np.asarray(counts)
    walked = [mla_kernels.gmm_walk_counts(sizes, rows) for sizes in counts]
    by_kernel = mla_kernels.moe_rows_engage(rows, counts.shape[1], hidden)
    sorted_rows = rows * len(walked)
    return {"moe_gmm_calls": 2 * len(walked),
            "moe_gmm_row_tiles": 2 * sum(t for t, _ in walked),
            "moe_gmm_visits": 2 * sum(v for _, v in walked),
            "moe_rows_sorted": sorted_rows,
            "moe_rows_moved": int(counts.sum()) if by_kernel
            else sorted_rows}


# ==========================================================================
# The form builder
# ==========================================================================
def open_form(b, mode: str, sampling) -> dict:
    """The feeds a program form of ``mode`` opens with, by name (``tables``
    the block tables under the form's own feed name), ``feeds`` their names
    in order and ``seeds`` the sampling lanes' feed or None.  A description
    adds its own feeds after these."""
    whole = mode in ("reference", "prefill")
    if whole:
        shapes = {"tokens": (1, -1), "positions": (1, -1),
                  "last_index": (1,)}
    elif mode == "decode":
        shapes = {"tokens": (-1,), "positions": (-1,),
                  "block_tables": (-1, -1), "context_lens": (-1,)}
    else:                                           # verify: rows (B, S)
        shapes = {"tokens": (-1, -1), "positions": (-1, -1)}
    if mode != "reference":
        shapes["slot_mapping"] = (-1,)
    if mode == "verify":
        shapes["verify_tables"] = (-1, -1)
    if _sampled(sampling):                     # a lane an emitting row
        shapes["sample_seeds"] = (1,) if whole else (-1,)
    f = {name: b.feed(name, shape, VarType.INT32)
         for name, shape in shapes.items()}
    f["tables"] = f.get("block_tables") or f.get("verify_tables")
    f["seeds"] = f.get("sample_seeds")
    f["feeds"] = list(shapes)
    return f


def embed_rows(m: "_MB", tokens, positions):
    """The rows' inputs under the part ``embed``: the flat positions and the
    float32 embeddings of the flat ids."""
    b = m.b
    with m.part("embed"):
        flat_tok = b.reshape(tokens, [-1], "tok_flat")
        flat_pos = b.reshape(positions, [-1], "pos_flat")
        hid = b.tmp("h0")
        m.op("lookup_table_v2", {"W": ["dec_embed"], "Ids": [flat_tok]},
             {"Out": [hid]})
        hid32 = b.tmp("h0_f32")
        m.op("cast", {"X": [hid]}, {"Out": [hid32]},
             {"in_dtype": int(m.wdt), "out_dtype": int(VarType.FP32)})
    return flat_pos, hid32


def close_form(m: "_MB", hid, last_index, routes, counts, absent, sampling,
               seeds, routes_all: bool = False, choices=()) -> tuple:
    """The end of a form, from the last block's rows ``hid``: the emitting
    row of a whole prompt (``last_index``; None: every row emits), the final
    norm, the head, the token and what rides on a call (with ``routes_all``
    every row's routing too).  Returns the token's name and the form's
    :class:`FormExtras`."""
    b, whole = m.b, last_index is not None
    hidden = hid
    # (expert layers, rows, k): every row's routing, a prompt's too.  In a
    # hybrid model a row's neighbours reach it undiluted (the convolution's
    # taps, the fast-decaying channels of a state), so a check of the served
    # logits follows the engine's routing on the prompt's rows as well
    with m.part("moe_part"):
        every = m.stacked(routes, "token_routes_all") \
            if routes_all and routes else None
    if whole:
        with m.part("head"):
            last = b.tmp("hlast")
            m.op("gather", {"X": [hid], "Index": [last_index]},
                 {"Out": [last]}, {"axis": 0})
            hid = last
        # the routing of the one row that emits
        picked = []
        with m.part("moe_part"):
            for j, r in enumerate(routes):
                o = b.tmp(f"route_last_{j}")
                m.op("gather", {"X": [r], "Index": [last_index]},
                     {"Out": [o]}, {"axis": 0})
                picked.append(o)
        routes = picked
    out_name = "next_token" if whole else "next_tokens"
    with m.part("head"):
        logits = m.mm(m.norm(hid, "dec_norm_scale", "fnorm"), "dec_head",
                      "logits")
        _emit_head(b, logits, out_name, sampling, seeds)
        score = b.blk.create_var(name="token_score", dtype=VarType.FP32).name
        m.op("token_score", {"Logits": [logits], "Token": [out_name]},
             {"Out": [score]})
    with m.part("moe_part"):
        # the tokens each expert received, the experts each emitting row was
        # routed to, the rows none of whose experts this chip holds
        counts = m.stacked(counts, "moe_counts") if counts else None
        routes = m.stacked(routes, "token_routes") if routes else None
        absent = m.stacked(absent, "moe_absent") if absent else None
        choices = m.stacked(choices, "moe_choices") if choices else None
    return out_name, FormExtras(logits=logits, hidden=hidden, score=score,
                                routes=routes, routes_all=every,
                                counts=counts, absent=absent,
                                choices=choices)


def live_rows(m: "_MB", slot_map, pool):
    """Rows whose slot lies in ``pool`` are real tokens; bucket padding
    carries the pad sentinel, the first slot past it."""
    o = m.tmp("valid")
    m.op("slot_is_live", {"SlotMapping": [slot_map], "Cache": [pool]},
         {"Out": [o]})
    return o


def add_feed(b: _B, f: dict, name: str, shape):
    """One of a model's own int32 feeds, after the form's."""
    f[name] = b.feed(name, shape, VarType.INT32)
    f["feeds"].append(name)


def build_form(cfg, mode: str, sampling, kv_dtype: str, *, modes, feeds,
               rows, walk, live_walk=None, routes_all=()) -> tuple:
    """One program form ``(program, feeds, fetches)`` of a decoder whose
    layers are :meth:`_MB.block`.  The model says which ``modes`` it builds
    and gives what is its own:

    * ``feeds(m, f)`` adds its feeds after the form's (``f``: the feeds by
      name, as :func:`open_form` leaves them);
    * ``rows(m, f, flat_pos)``, called once the rows are embedded, declares
      its pools and returns ``(valid, mix)``: the live-row mask (None where
      the form caches nothing) and the one mixer hook of ``block``;
    * ``walk(feed, kv_config, mode=, cfg=, routed=)`` is what a serving
      form's kernels walk, the form's ``FormExtras.kernel_stats``;
    * ``live_walk(kv_config, cfg=)``, where the model gives one, is its
      decode form's ``FormExtras.live_walk_pages``;
    * ``routes_all`` names the modes that offer every row's routing."""
    if mode not in modes:
        raise ValueError(f"this decoder builds no {mode!r} form: {modes}")
    if kv_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"bad kv_dtype {kv_dtype!r}")
    if _sampled(sampling) and mode == "reference":
        raise ValueError("the reference form is the greedy oracle; "
                         "sampling applies to serving forms only")
    prog = Program()
    prog._label = mode
    m = _MB(prog, cfg)
    f = open_form(m.b, mode, sampling)
    feeds(m, f)
    flat_pos, hid = embed_rows(m, f["tokens"], f["positions"])
    valid, mix = rows(m, f, flat_pos)
    counts: List[str] = []
    routes: List[str] = []
    absent: List[str] = []
    choices: List[str] = []
    for i in range(cfg.num_layers):
        hid = m.block(i, hid, mix, valid, counts, routes, absent, choices)
    out_name, extras = close_form(
        m, hid, f.get("last_index"), routes, counts, absent, sampling,
        f["seeds"], routes_all=mode in routes_all, choices=choices)
    if mode != "reference":
        extras = extras._replace(kernel_stats=functools.partial(
            walk, mode=mode, cfg=cfg, routed=bool(counts)))
    if mode == "decode" and live_walk is not None:
        extras = extras._replace(
            live_walk_pages=functools.partial(live_walk, cfg=cfg))
    prog._form_extras = extras
    return prog, f["feeds"], [out_name]


