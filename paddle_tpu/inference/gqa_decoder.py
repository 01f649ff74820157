"""A grouped-query decoder with window layers beside full ones, a gate on the
attention output and the sparse-expert feed-forward of
``decoder_program.py``: the Laguna-shaped block (``model_type: laguna``) as a
model description ``ServingEngine`` serves through the same seam as
``DecoderConfig`` and ``MLADecoderConfig`` (``decoder_program.ServedModel``):
parameter specs, program forms, cache pools.  The same
description holds the Olmo-Hybrid-shaped decoder (``model_type:
olmo_hybrid``; below): Gated DeltaNet layers beside full multi-head ones,
dense throughout, the norms on the outputs.

Per layer ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; the
feed-forward half is ``decoder_program._MB._ffn`` as it stands (the first
``first_k_dense`` layers a SwiGLU, the rest the router, this chip's share of
the experts and the shared expert).  ``Attn`` of a layer of kind ``"full"``
or ``"window"``: ``heads_full`` or ``heads_window`` query heads over
``num_kv_heads`` key/value heads of ``head_dim``, rotary in the half-rotated
form with the kind's own setting (:class:`Rope`: rotated lanes, base, YaRN's
numbers, the factor on cos and sin), causal, a window layer attending the
last ``window`` positions only; the output times ``sigmoid(x W_g)``, one
value a head (``gate``), then ``W_o``.  No biases, untied head.

The cache holds K and V rows ``(num_kv_heads, head_dim)`` a token and layer
in pools ``(num_kv_heads, pages, page_size, head_dim)`` (``kv_k_<i>`` /
``kv_v_<i>``, written by ``kv_cache_append``), in TWO GROUPS of pages
(``KVCacheConfig.groups``): the full layers' pools keep every page of a
sequence until it ends, the window layers' pools hold only the pages that
cover the last ``window`` (+ a page's) positions, with a table and a slot
mapping of their own among the feeds (``window_slot_mapping``, and for a
decode step ``window_tables`` and ``window_first``, each row's first held
position).

Forms: ``reference`` and ``prefill`` attend over one whole prompt
(``gqa_prefill``), ``decode`` one row a sequence over the pools
(``gqa_decode``).  ``chunk`` and ``verify`` are not built: the engine refuses
prefix caching, chunked prefill and speculative decoding for this model at
construction (a page freed behind a window cannot be brought back).

Types as the other expert decoders: parameters in ``weights_dtype``, every
matmul with operands of that type accumulated in float32; the residual
stream, norms, softmax, router scores and the gate float32.

**Linear layers** (``mixers`` naming ``"linear"``; arXiv:2412.06464,
``ops/kda_ops.py``'s ``gdn_mixer``).  Such a layer keeps no rows a token but
one float32 state ``(linear_heads, linear_key_dim, linear_value_dim)`` and the
last ``taps - 1`` inputs of its short convolution a sequence, in a SLOT of two
pools a layer (``state_pool_specs``: ``gdn_state_<i>`` as
``kda_kernels.gdn_state_shape`` stores it, ``gdn_conv_<i>``) beside the K/V
pools, which the attention layers alone have: the cache manager hands a
sequence its slot with its first pages, and the serving forms take the feed
``state_slots``.  One decay a head, ``beta`` up to 2 with
``linear_neg_eigval``, the output gated by ``SiLU(z)`` of full rank.  With
``norm_after`` the block's two RMSNorms sit on the outputs (``h = x +
RMSNorm(Mix(x))``, ``y = h + RMSNorm(FFN(h))``: ``_MB.block``), with
``qk_norm`` an RMSNorm over the whole of ``q`` and of ``k`` stands before the
heads are split, a rotary setting of no lanes turns nothing, and
``first_k_dense`` equal to the depth is a decoder with no expert layer: its
forms carry no counts and no routes.

**Why a description of its own** and not two more mixer kinds of
``MLADecoderConfig``: that description's fields are latent attention's (two
low-rank projections, nope/rope head dims, one head count, one rotary base),
none of which this model has, and this one's (K/V heads, heads by kind, two
rotary settings, the window, the gate) none of which that one has; the two
share the block and the forms' skeleton, which are functions of
``decoder_program.py`` (``build_form``, ``_MB.block`` / ``_ffn``,
``ffn_specs``) and are used from here, not copied.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

from ..framework.dtype import VarType, convert_dtype
from ..ops import gqa_kernels, kda_kernels, mla_kernels
from .decoder_program import (DELTA_RULE_SEEDS, _gmm_walk, _kv_append,
                              _kv_pool_params, add_feed, build_form,
                              delta_rule_seed,
                              ffn_specs, live_rows)
from .kv_cache import KVCacheConfig

__all__ = ["GQADecoderConfig", "Rope", "init_gqa_weights"]


@dataclass(frozen=True)
class Rope:
    """One rotary setting: the first ``lanes`` of a head turn (half-rotated),
    at frequencies ``base^(-2i/lanes)`` or, with ``yarn_factor``, YaRN's blend
    of those and those over the factor; cos and sin times
    ``attention_factor``."""
    lanes: int = 0
    base: float = 10000.0
    yarn_factor: float = 0.0         # 0: plain rotary
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self) -> np.ndarray:
        yarn = {"factor": self.yarn_factor,
                "original_max_position_embeddings":
                    self.original_max_position,
                "beta_fast": self.beta_fast, "beta_slow": self.beta_slow} \
            if self.yarn_factor else None
        return gqa_kernels.rope_frequencies(self.lanes, self.base, yarn)

    @classmethod
    def from_source(cls, p: dict, head_dim: int) -> "Rope":
        """From one entry of the source's ``rope_parameters``."""
        lanes = int(round(head_dim * float(p.get("partial_rotary_factor",
                                                 1.0))))
        if p.get("rope_type", "default") != "yarn":
            return cls(lanes=lanes, base=float(p["rope_theta"]))
        return cls(lanes=lanes, base=float(p["rope_theta"]),
                   yarn_factor=float(p["factor"]),
                   original_max_position=int(
                       p["original_max_position_embeddings"]),
                   beta_fast=float(p["beta_fast"]),
                   beta_slow=float(p["beta_slow"]),
                   attention_factor=float(p.get("attention_factor", 1.0)))

    def to_source(self, head_dim: int) -> dict:
        out = {"rope_theta": self.base,
               "partial_rotary_factor": self.lanes / head_dim,
               "rope_type": "yarn" if self.yarn_factor else "default"}
        if self.yarn_factor:
            out.update(factor=self.yarn_factor,
                       original_max_position_embeddings=
                       self.original_max_position,
                       beta_fast=self.beta_fast, beta_slow=self.beta_slow,
                       attention_factor=self.attention_factor)
        return out


_KINDS = {"full_attention": "full", "sliding_attention": "window",
          "linear_attention": "linear"}
_KIND_NAMES = {ours: theirs for theirs, ours in _KINDS.items()}


@dataclass(frozen=True)
class GQADecoderConfig:
    vocab_size: int = 128
    hidden: int = 64
    num_layers: int = 4
    mixers: Tuple[str, ...] = ("full", "window", "window", "window")
    heads_full: int = 6
    heads_window: int = 8
    num_kv_heads: int = 2
    head_dim: int = 16
    window: int = 8
    gate: bool = True                # sigmoid(x W_g), one value a head
    rope_full: Rope = field(default_factory=lambda: Rope(lanes=8))
    rope_window: Rope = field(default_factory=lambda: Rope(lanes=16))
    first_k_dense: int = 1
    intermediate: int = 128          # the dense layers' SwiGLU width
    moe_intermediate: int = 32       # one expert's (and the shared one's)
    n_routed_experts: int = 8
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    experts_held: int = 0            # experts 0..held-1 of a layer; 0: all
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 256
    eos_id: int = -1
    weights_dtype: str = "float32"
    # -- the layers of kind "linear" (Gated DeltaNet) and the Olmo block
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_taps: int = 4
    linear_neg_eigval: bool = False  # beta = 2 sigmoid(b)
    linear_l2_eps: float = 1e-6
    norm_after: bool = False         # the block's norms on the outputs
    qk_norm: bool = False            # RMSNorm over all of q and of k

    # -- the seam ServingEngine asks a model description through ---------
    @property
    def param_dtype(self) -> str:
        return self.weights_dtype

    def mixer(self, i: int) -> str:
        return self.mixers[i]

    def heads(self, kind: str) -> int:
        return self.heads_full if kind == "full" else self.heads_window

    def rope(self, kind: str) -> Rope:
        return self.rope_full if kind == "full" else self.rope_window

    @property
    def full_layers(self) -> List[int]:
        return [i for i, k in enumerate(self.mixers) if k == "full"]

    @property
    def window_layers(self) -> List[int]:
        return [i for i, k in enumerate(self.mixers) if k == "window"]

    @property
    def linear_layers(self) -> List[int]:
        return [i for i, k in enumerate(self.mixers) if k == "linear"]

    @property
    def attn_layers(self) -> List[int]:
        """The layers that keep K and V rows."""
        return [i for i, k in enumerate(self.mixers) if k != "linear"]

    @property
    def experts_here(self) -> int:
        return self.experts_held or self.n_routed_experts

    def param_specs(self) -> Dict[str, tuple]:
        h, d = self.hidden, self.head_dim
        specs = {"dec_embed": (self.vocab_size, h),
                 "dec_head": (h, self.vocab_size), "dec_norm_scale": (h,)}
        for i, kind in enumerate(self.mixers):
            p = f"dec_l{i}_"
            specs[p + "attn_norm_scale"] = (h,)
            if kind == "linear":
                specs.update({p + name: shape
                              for name, shape in self._linear_specs().items()})
            else:
                heads, kv = self.heads(kind), self.num_kv_heads * d
                specs.update({p + "wq": (h, heads * d), p + "wk": (h, kv),
                              p + "wv": (h, kv), p + "wo": (heads * d, h)})
                if self.gate:
                    specs[p + "wg"] = (h, heads)
                if self.qk_norm:
                    specs.update({p + "q_norm_scale": (heads * d,),
                                  p + "k_norm_scale": (kv,)})
            specs.update(ffn_specs(self, i, moe=i >= self.first_k_dense))
        return specs

    def _linear_specs(self) -> Dict[str, tuple]:
        """A Gated DeltaNet mixer's weights: ``[q | k | v | z]`` in one
        projection and ``[b | a]`` in another, the convolution's taps a
        channel of ``[q | k | v]``, ``A_log`` and ``dt_bias`` a head, the
        per-head output norm, and ``W_o``."""
        h, n = self.hidden, self.linear_heads
        dk, dv = self.linear_key_dim, self.linear_value_dim
        return {"gdn_wqkvz": (h, 2 * n * (dk + dv)), "gdn_wba": (h, 2 * n),
                "gdn_conv": (n * (2 * dk + dv), self.linear_conv_taps),
                "gdn_a_log": (n,), "gdn_dt_bias": (n,),
                "gdn_onorm_scale": (dv,), "wo": (n * dv, h)}

    def build_program(self, mode: str, sampling=None,
                      kv_dtype: str = "float32", tp: int = 1) -> tuple:
        return build_gqa_program(self, mode, sampling=sampling,
                                 kv_dtype=kv_dtype)

    def validate(self, tp: int = 1, kv_dtype: str = "float32",
                 prefix_cache: bool = False, prefill_chunk: int = 0,
                 spec_k: int = 0):
        """What this model is not served with, refused at construction."""
        if len(self.mixers) != self.num_layers or \
                set(self.mixers) - {"full", "window", "linear"}:
            raise ValueError(f"mixers must name 'full' or 'window' or "
                             f"'linear' for each of the {self.num_layers} "
                             f"layers: {self.mixers}")
        if "full" not in self.mixers:
            raise ValueError("the decoder needs a full-attention layer: the "
                             "engine sizes its page pool by it")
        if self.linear_layers and min(self.linear_heads, self.linear_key_dim,
                                      self.linear_value_dim) < 1:
            raise ValueError("linear layers need linear_heads, "
                             "linear_key_dim and linear_value_dim")
        if self.norm_after and self.first_k_dense < self.num_layers:
            raise ValueError("norm_after is built for dense layers: "
                             "first_k_dense must be the depth")
        for kind in set(self.mixers) - {"linear"}:
            if self.heads(kind) % self.num_kv_heads:
                raise ValueError(f"{self.heads(kind)} query heads of a "
                                 f"{kind} layer do not divide into "
                                 f"{self.num_kv_heads} K/V heads")
        if self.window_layers and self.window < 1:
            raise ValueError("window layers need a window")
        if int(tp or 1) != 1:
            raise ValueError("the grouped-query decoder has no "
                             "tensor-parallel rules: serving_tp must be 1")
        if kv_dtype == "int8":
            raise ValueError("the grouped-query decoder's pools have no int8 "
                             "storage: kv_dtype must be float32 or bfloat16")
        if prefix_cache or prefill_chunk:
            raise ValueError(
                "the grouped-query decoder builds no 'chunk' program form: "
                "prefix caching and chunked prefill are refused for this "
                "model")
        if spec_k:
            raise ValueError(
                "a model with window or linear layers is not served with "
                "speculative decoding: truncate_tokens cannot bring back a "
                "page freed behind a window, nor roll a recurrent state back "
                "without a snapshot")

    def tp_rules(self, kv_dtype: str = "float32") -> Dict[str, tuple]:
        return {}

    def kv_cache_config(self, num_pages: int, page_size: int,
                        kv_dtype: str) -> KVCacheConfig:
        """K and V rows of ``num_kv_heads`` heads a token and layer, in two
        groups of pages: ``num_pages`` for the full layers, and for the
        window layers what the engine's batch can hold
        (``window_pages_per_seq`` a sequence: ``_EngineCore`` sizes it)."""
        attn = self.attn_layers       # the cache's layers: those with rows
        return KVCacheConfig(
            num_pages=num_pages, page_size=page_size,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            num_layers=len(attn), dtype=kv_dtype,
            window=self.window if self.window_layers else 0,
            window_layers=tuple(attn.index(i) for i in self.window_layers))

    def cache_pool_names(self) -> List[str]:
        return [f"kv_{kind}_{i}" for i in self.attn_layers
                for kind in ("k", "v")]

    def window_pool_names(self) -> List[str]:
        """The pools of the window group, ``window_pages`` pages each."""
        return [f"kv_{kind}_{i}" for i in self.window_layers
                for kind in ("k", "v")]

    def kv_token_bytes(self, kv_dtype: str, tp: int = 1) -> int:
        """What a token costs in the full layers' pools, the group
        ``num_pages`` sizes; a window layer's rows are a constant a
        sequence."""
        return len(self.full_layers) * self.kv_layer_token_bytes(kv_dtype)

    def kv_layer_token_bytes(self, kv_dtype: str) -> int:
        return 2 * self.num_kv_heads * self.head_dim \
            * np.dtype(kv_dtype).itemsize

    def state_pool_specs(self, state_slots: int) -> Dict[str, tuple]:
        """name -> (shape, dtype) of the pools that hold one SLOT a sequence
        (and one more, the padding's): a linear layer's state, stored with
        ``d_k`` on sublanes under the lanes of as many heads as fill whole
        tiles (``kda_kernels.gdn_state_shape``), and its convolution's last
        inputs, float32.  Empty without linear layers."""
        n, dk, dv = self.linear_heads, self.linear_key_dim, \
            self.linear_value_dim
        specs = {}
        for i in self.linear_layers:
            specs[f"gdn_state_{i}"] = (
                (state_slots + 1,) + kda_kernels.gdn_state_shape(n, dk, dv),
                "float32")
            specs[f"gdn_conv_{i}"] = (
                (state_slots + 1, self.linear_conv_taps - 1,
                 n * (2 * dk + dv)), "float32")
        return specs

    def state_slot_bytes(self) -> int:
        """Bytes one sequence's slot takes over the linear layers, as the
        pools store it."""
        return sum(int(np.prod(shape[1:])) * np.dtype(dtype).itemsize
                   for shape, dtype in self.state_pool_specs(0).values())

    # -- the source's names (its config.json) ------------------------------
    _SOURCE_KEYS = {
        "vocab_size": "vocab_size", "hidden": "hidden_size",
        "num_layers": "num_hidden_layers",
        "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
        "window": "sliding_window", "gate": "gating",
        "intermediate": "intermediate_size",
        "moe_intermediate": "moe_intermediate_size",
        "num_experts_per_tok": "num_experts_per_tok",
        "routed_scaling_factor": "moe_routed_scaling_factor",
        "rms_norm_eps": "rms_norm_eps",
    }

    #: Olmo-Hybrid's config.json names the linear layers' sizes so (the
    #: keys of the open Gated DeltaNet), and says nothing of experts,
    #: windows or gates
    _LINEAR_KEYS = {
        "linear_heads": "linear_num_value_heads",
        "linear_key_dim": "linear_key_head_dim",
        "linear_value_dim": "linear_value_head_dim",
        "linear_conv_taps": "linear_conv_kernel_dim",
        "linear_neg_eigval": "linear_allow_neg_eigval",
    }
    _DENSE_KEYS = ("vocab_size", "hidden", "num_layers", "num_kv_heads",
                   "intermediate", "rms_norm_eps")

    def source_config(self) -> dict:
        """This model under the source's key names."""
        if self.linear_layers:
            out = {self._SOURCE_KEYS[ours]: getattr(self, ours)
                   for ours in self._DENSE_KEYS}
            out.update({theirs: getattr(self, ours)
                        for ours, theirs in self._LINEAR_KEYS.items()})
            out.update(
                num_attention_heads=self.heads_full,
                linear_num_key_heads=self.linear_heads,
                layer_types=[_KIND_NAMES[k] for k in self.mixers],
                rope_parameters={"rope_theta": None},
                norm_after=self.norm_after, qk_norm=self.qk_norm,
                linear_l2_eps=self.linear_l2_eps)
            return out
        out = {theirs: getattr(self, ours)
               for ours, theirs in self._SOURCE_KEYS.items()}
        out.update(
            num_experts=self.experts_here,
            router_experts=self.n_routed_experts,
            shared_expert_intermediate_size=
            self.moe_intermediate * self.n_shared_experts,
            layer_types=[_KIND_NAMES[k] for k in self.mixers],
            mlp_layer_types=["dense" if i < self.first_k_dense else "sparse"
                             for i in range(self.num_layers)],
            num_attention_heads_per_layer=[self.heads(k)
                                           for k in self.mixers],
            num_attention_heads=self.heads_full,
            rope_parameters={
                "full_attention": self.rope_full.to_source(self.head_dim),
                "sliding_attention":
                    self.rope_window.to_source(self.head_dim)})
        return out

    @classmethod
    def from_source(cls, source: dict, **ours) -> "GQADecoderConfig":
        """From a ``config.json`` of the source's shape; its per-layer lists
        may name more layers than ``num_hidden_layers`` holds (a cut model:
        the first are taken).  ``router_experts`` (ours): the experts the
        router scores, where ``num_experts`` is the share held.  ``ours``
        gives what it does not say (``max_seq_len``, ``weights_dtype``)."""
        layers = source["num_hidden_layers"]
        kinds = tuple(_KINDS[k] for k in source["layer_types"][:layers])
        if "linear" in kinds:
            return cls._from_linear_source(source, kinds, ours)
        per_layer = source["num_attention_heads_per_layer"][:layers]
        by_kind = {k: {h for h, kk in zip(per_layer, kinds) if kk == k}
                   for k in ("full", "window")}
        if any(len(v) > 1 for v in by_kind.values()):
            raise ValueError(f"layers of one kind differ in heads: {by_kind}")
        mlp = source["mlp_layer_types"][:layers]
        dense = next((i for i, t in enumerate(mlp) if t != "dense"), layers)
        if "dense" in mlp[dense:]:
            raise ValueError("dense feed-forward layers must lead")
        routed = source.get("router_experts", source["num_experts"])
        held = source["num_experts"]
        d = source["head_dim"]
        ropes = source["rope_parameters"]
        kw = {mine: source[theirs]
              for mine, theirs in cls._SOURCE_KEYS.items()}
        kw.update(
            mixers=kinds,
            heads_full=next(iter(by_kind["full"]),
                            source["num_attention_heads"]),
            heads_window=next(iter(by_kind["window"]),
                              source["num_attention_heads"]),
            first_k_dense=dense, n_routed_experts=routed,
            experts_held=held if held < routed else 0,
            n_shared_experts=source["shared_expert_intermediate_size"]
            // source["moe_intermediate_size"],
            gate=bool(source.get("gating", False)),
            rope_full=Rope.from_source(ropes["full_attention"], d),
            rope_window=Rope.from_source(ropes["sliding_attention"], d))
        kw.update(ours)
        return cls(**kw)


    @classmethod
    def _from_linear_source(cls, source: dict, kinds, ours: dict):
        """Olmo-Hybrid's shape of ``config.json``: one head count, no
        ``head_dim`` (hidden over heads), every feed-forward dense, no gate,
        ``rope_parameters`` one setting whose ``rope_theta`` may be null (no
        rotary); ``norm_after``, ``qk_norm`` and ``linear_l2_eps`` are ours
        (the configuration file's ``assumed``)."""
        if source["linear_num_key_heads"] != source["linear_num_value_heads"]:
            raise ValueError("the linear layers' key and value heads differ: "
                             "value heads sharing a key head are not built")
        heads, layers = source["num_attention_heads"], len(kinds)
        d = source.get("head_dim") or source["hidden_size"] // heads
        theta = source["rope_parameters"].get("rope_theta")
        rope = Rope(lanes=0) if theta is None else \
            Rope.from_source(source["rope_parameters"], d)
        kw = {mine: source[cls._SOURCE_KEYS[mine]]
              for mine in cls._DENSE_KEYS}
        kw.update({mine: source[theirs]
                   for mine, theirs in cls._LINEAR_KEYS.items()})
        kw.update(
            mixers=kinds, heads_full=heads, heads_window=heads, head_dim=d,
            window=0, gate=False, rope_full=rope, rope_window=rope,
            first_k_dense=layers, n_routed_experts=0, n_shared_experts=0,
            num_experts_per_tok=0,
            norm_after=bool(source.get("norm_after", False)),
            qk_norm=bool(source.get("qk_norm", False)),
            linear_l2_eps=float(source.get("linear_l2_eps", 1e-6)))
        kw.update(ours)
        return cls(**kw)


def init_gqa_weights(cfg: GQADecoderConfig, seed: int = 0
                     ) -> Dict[str, np.ndarray]:
    """Seeded weights for tests and smokes: norm scales 1, the router's
    correction bias small, the embedding normal, every matrix normal over
    sqrt(fan-in); a linear layer's decay as ``init_mla_weights`` seeds a KDA
    layer's."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape in cfg.param_specs().items():
        if name.endswith("_scale"):
            w = np.ones(shape, np.float32)
        elif name.endswith("router_bias"):
            w = 0.01 * rng.randn(*shape)
        elif name.endswith(DELTA_RULE_SEEDS):
            w = delta_rule_seed(name, shape, rng)
        elif name == "dec_embed":
            w = rng.randn(*shape)
        else:
            w = rng.randn(*shape) / np.sqrt(shape[-2])
        out[name] = w.astype(np.dtype(cfg.weights_dtype))
    return out


# ==========================================================================
# Program builder
# ==========================================================================
#: a Gated DeltaNet mixer's weights: the ``gdn_mixer`` op's input slot of each
_GDN_SLOTS = {"gdn_wqkvz": "WQKVZ", "gdn_wba": "WBA", "gdn_conv": "Conv",
              "gdn_a_log": "ALog", "gdn_dt_bias": "DtBias",
              "gdn_onorm_scale": "ONormScale"}


def _form_walk(feed, kv_config, *, mode: str, cfg: GQADecoderConfig,
               routed: bool):
    """``FormExtras.kernel_stats`` of a serving form: what its attention
    kernels walk, from the feed and the sizes the kernels' wrappers use
    (``gqa_kernels.prefill_walk`` / ``decode_walk_counts``), summed over the
    layers; the linear layers' ``gdn_prefill`` / ``gdn_decode`` calls with
    the real tokens and the chunks of a prompt's bucket, or the live
    sequences (rows whose slot is not the padding's); under ``from_counts``
    what its grouped matmuls will have walked (``decoder_program.
    _gmm_walk``)."""
    full, win = len(cfg.full_layers), len(cfg.window_layers)
    lin = len(cfg.linear_layers) if kda_kernels.gdn_engages(
        cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim) else 0
    d, page = cfg.head_dim, kv_config.page_size
    out = {}
    if mode == "prefill":
        s = int(np.size(feed["tokens"]))
        n = int(np.asarray(feed["last_index"])[0]) + 1
        if lin:
            chunks = kda_kernels.gdn_prefill_grid(s, cfg.linear_heads)[2][1]
            out.update(gdn_prefill_calls=lin, gdn_prefill_tokens=lin * n,
                       gdn_prefill_chunks=lin * chunks)
        if gqa_kernels.prefill_engages(s, d):
            seen, causal = gqa_kernels.prefill_walk(s)[3:]
            seen_w, causal_w = gqa_kernels.prefill_walk(s, cfg.window)[3:]
            kvh = cfg.num_kv_heads
            inside = min(n, cfg.window)       # rows whose window is not full
            out.update(
                gqa_prefill_calls=full + win,
                gqa_prefill_tokens=(full + win) * n,
                gqa_prefill_blocks_visited=kvh * (full * seen + win * seen_w),
                # both in each layer kind's own blocks (the keys of a
                # block differ by kind: ``prefill_walk``)
                gqa_prefill_blocks_causal=kvh * (full * causal
                                                 + win * causal_w),
                # the unmasked (query, key) pairs of the real tokens, a
                # head: what the attention needs whatever walks it
                gqa_prefill_pairs_full=full * n * (n + 1) // 2,
                gqa_prefill_pairs_window=win * (
                    inside * (inside + 1) // 2 + (n - inside) * cfg.window))
    else:
        if lin:
            live = int((np.asarray(feed["state_slots"])
                        < kv_config.state_slots).sum())
            out.update(gdn_decode_calls=lin, gdn_decode_sequences=lin * live)
        if gqa_kernels.decode_engages(page, d):
            ctx = np.asarray(feed["context_lens"])
            live = int((np.asarray(feed["slot_mapping"])
                        < kv_config.pad_slot).sum())
            _, walked, held = gqa_kernels.decode_walk_counts(
                ctx, np.zeros_like(ctx), feed["block_tables"].shape[1], page,
                0)
            walked_w = 0
            if win:
                _, walked_w, _ = gqa_kernels.decode_walk_counts(
                    ctx, np.asarray(feed["window_first"]),
                    feed["window_tables"].shape[1], page, cfg.window)
            out.update(
                gqa_decode_calls=full + win,
                gqa_decode_sequences=(full + win) * live,
                gqa_decode_pages_walked=full * walked + win * walked_w,
                gqa_decode_pages_in_context=(full + win) * held)
    if routed and mla_kernels.gmm_engages(cfg.hidden, cfg.moe_intermediate):
        out["from_counts"] = functools.partial(
            _gmm_walk, hidden=cfg.hidden,
            rows=int(np.size(feed["tokens"])) * cfg.num_experts_per_tok)
    return out


def _live_walk(kv_config, *, cfg: GQADecoderConfig):
    """``FormExtras.live_walk_pages`` of the decode form: where
    ``gqa_decode`` runs its kernel (the predicate of :func:`_form_walk`) a
    full layer's grid is the chunks of each row's walk, whatever the tables
    span; the window group's table has one width already."""
    if gqa_kernels.decode_engages(kv_config.page_size, cfg.head_dim):
        return gqa_kernels.DECODE_TABLE_PAGES
    return None


def build_gqa_program(cfg: GQADecoderConfig, mode: str, sampling=None,
                      kv_dtype: str = "float32") -> tuple:
    """One program form of the decoder: ``(program, feeds, fetches)``
    through ``decoder_program.build_form``, with what rides on a call as
    ``close_form`` leaves it and, on the serving forms, ``kernel_stats``
    (:func:`_form_walk`)."""
    whole = mode in ("reference", "prefill")
    cached = mode != "reference"

    def feeds(m, f):
        if cached and cfg.window_layers:
            # the window group's own slots, and for a decode step its table
            # (the pages from each row's first held position on) and that
            # position
            add_feed(m.b, f, "window_slot_mapping", (-1,))
            if not whole:
                add_feed(m.b, f, "window_tables", (-1, -1))
                add_feed(m.b, f, "window_first", (-1,))
        if cached and cfg.linear_layers:
            # the slot of the sequence (a prompt) or of each row (a decode
            # batch) in the linear layers' pools; the padding's is the last
            add_feed(m.b, f, "state_slots", (1,) if whole else (-1,))

    def rows(m, f, flat_pos):
        pools = {i: _kv_pool_params(m.b, i, False, kv_dtype)[:2]
                 for i in cfg.attn_layers} if cached else {}
        valid = None
        if cached:
            with m.part("embed"):
                valid = live_rows(m, f["slot_mapping"],
                                  pools[cfg.full_layers[0]][0])
        return valid, _mixer(m, f, mode, kv_dtype, flat_pos, valid, pools)

    return build_form(cfg, mode, sampling, kv_dtype,
                      modes=("reference", "prefill", "decode"), feeds=feeds,
                      rows=rows, walk=_form_walk, live_walk=_live_walk,
                      routes_all=("prefill",))


def _mixer(m, f, mode: str, kv_dtype: str, flat_pos, valid, pools):
    """The one hook of ``block``: ``mix(i, x)``, layer ``i``'s attention
    (full or windowed) or Gated DeltaNet mixer over the rows ``x``, before
    its ``wo``; ``f`` the form's feeds, ``pools`` the attention layers' K and
    V pools where the form caches."""
    cfg, b = m.cfg, m.b
    whole = mode in ("reference", "prefill")
    cached = mode != "reference"
    kv_type = convert_dtype(kv_dtype)

    def heads_of(x, heads, tag):
        return b.reshape(x, [-1, heads, cfg.head_dim], tag)

    def turned(x, rope, tag):
        if not rope.lanes:
            return x
        o = m.tmp(tag)
        m.op("rope_half", {"X": [x], "Positions": [flat_pos]}, {"Out": [o]},
             {"inv_freq": [float(v) for v in rope.inv_freq()],
              "factor": float(rope.attention_factor)})
        return o

    def stored(x, tag):
        """``x`` in the pools' type: what the append writes, and what the
        prompt's own attention reads, so both phases see the same rows."""
        o = m.tmp(tag)
        m.op("cast", {"X": [x]}, {"Out": [o]},
             {"in_dtype": int(VarType.FP32), "out_dtype": int(kv_type)})
        return o

    def linear(i, hn):
        """Layer ``i``'s Gated DeltaNet mixer: one op, its state and its
        convolution's tail in the layer's two slot pools where the form
        caches."""
        p, out = f"dec_l{i}_", m.tmp(f"l{i}_gdn")
        ins = {"X": [hn]}
        ins.update({slot: [p + name] for name, slot in _GDN_SLOTS.items()})
        outs = {"Out": [out]}
        if cached:
            state, conv = (b.param(f"gdn_{kind}_{i}", (), dtype=VarType.FP32)
                           for kind in ("state", "conv"))
            ins.update({"Valid": [valid], "StateSlots": [f["state_slots"]],
                        "State": [state], "ConvState": [conv]})
            if whole:
                ins["LastIndex"] = [f["last_index"]]
            outs.update({"StateOut": [state], "ConvStateOut": [conv]})
        m.op("gdn_mixer", ins, outs,
             {"mode": mode, "heads": int(cfg.linear_heads),
              "key_dim": int(cfg.linear_key_dim),
              "value_dim": int(cfg.linear_value_dim),
              "neg_eigval": bool(cfg.linear_neg_eigval),
              "epsilon": float(cfg.rms_norm_eps),
              "l2_epsilon": float(cfg.linear_l2_eps)})
        return out

    def projected(i, hn, name, heads, rope):
        """``q`` or ``k``: ``hn W_name`` normed whole (``qk_norm``), by
        heads, turned."""
        x = m.mm(hn, f"dec_l{i}_w{name}", f"l{i}_{name}")
        if cfg.qk_norm:
            x = m.norm(x, f"dec_l{i}_{name}_norm_scale", f"l{i}_{name}n")
        return turned(heads_of(x, heads, f"l{i}_{name}3"), rope,
                      f"l{i}_{name}r")

    def mixer(i, hn):
        kind, p = cfg.mixer(i), f"dec_l{i}_"
        if kind == "linear":
            return linear(i, hn)
        heads, rope, window = cfg.heads(kind), cfg.rope(kind), \
            cfg.window if kind == "window" else 0
        q = projected(i, hn, "q", heads, rope)
        k = projected(i, hn, "k", cfg.num_kv_heads, rope)
        v = heads_of(m.mm(hn, p + "wv", f"l{i}_v"), cfg.num_kv_heads,
                     f"l{i}_v3")
        attrs = {"scale": float(cfg.head_dim ** -0.5), "window": int(window)}
        out = m.tmp(f"l{i}_att")
        ins = {"Q": [q]}
        if cfg.gate:
            ins["Gate"] = [m.mm(hn, p + "wg", f"l{i}_g")]
        if cached:
            k, v = stored(k, f"l{i}_ks"), stored(v, f"l{i}_vs")
            kc, vc = pools[i]
            _kv_append(b, k, v, f["window_slot_mapping" if window
                                  else "slot_mapping"], kc, vc, None, None)
        if whole:
            ins.update({"K": [k], "V": [v]})
            m.op("gqa_prefill_attention", ins, {"Out": [out]}, attrs)
            return out
        ins.update({"KCache": [kc], "VCache": [vc],
                    "ContextLens": [f["context_lens"]],
                    "BlockTables": [f["window_tables" if window
                                      else "tables"]]})
        if window:
            ins["First"] = [f["window_first"]]
        m.op("gqa_paged_attention", ins, {"Out": [out]}, attrs)
        return out

    return mixer
