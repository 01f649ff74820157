"""A latent-attention (MLA) decoder with sigmoid-routed experts, a shared
expert and a multi-token-prediction head: the DeepSeek-V3-shaped block
(arXiv:2405.04434 section 2.1, arXiv:2412.19437 sections 2.1-2.2) as a
model description ``ServingEngine`` serves through the same seam as
``DecoderConfig`` (``decoder_program.ServedModel``): parameter specs,
program forms (``decoder_program.build_form``), cache pools.

Per layer ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; the
first ``first_k_dense`` layers' FFN is a SwiGLU of width ``intermediate``,
the rest the routed experts plus the shared expert.  No biases, untied
head.  The cache holds one row ``[c_kv | k_r]`` a token and layer
(``kv_lora_rank + qk_rope_head_dim`` values), shared by every head:
``c_kv`` after its norm, ``k_r`` after RoPE.

Forms: ``reference`` and ``prefill`` run the EXPANDED attention (``W_kvb``
widens the latent rows to per-head keys and values) over one whole prompt;
``decode`` and ``verify`` run the ABSORBED attention over the paged latent
pool (``mla_decode``), a verify row being a decode row whose context ends
at its own position — so a drafted token is scored by the very kernel
that would have decoded it.  ``chunk`` (prefix cache, chunked prefill) is
not built: the engine refuses those flags for this model at construction.

Types: parameters in ``weights_dtype``; every matmul takes operands of
that type and accumulates in float32; the residual stream, norms, router
scores and softmax are float32.

**Hybrid layers** (``mixers``).  Each layer names its mixer: ``"mla"`` (the
block above) or ``"kda"``, the gated delta-rule mixer of Kimi-Linear
(arXiv:2510.26692; ``ops/kda_ops.py``), which keeps no rows a token but one
float32 state ``(heads, d_k, d_v)`` and the last ``taps - 1`` inputs of a
short convolution a sequence, in a SLOT of two pools a layer beside the
paged latent pools of the MLA layers (``state_pool_specs``; the cache
manager hands a sequence its slot with its first pages).  ``mixers`` empty is
"every mixer MLA": the programs of such a configuration are what they were.
``q_lora_rank`` 0 projects the queries directly (``wq``), ``rope`` false
leaves ``q_r`` and ``k_r`` unrotated (NoPE), and ``experts_held`` under
``n_routed_experts`` is one chip's share of an expert-parallel layer: the
router scores all experts, the weights are normalised over all chosen, and
the layer computes the part of the sum whose experts it holds (plus the
shared expert, whole).

**Shortcut-connected layers** (``shortcut``, LongCat-Flash,
arXiv:2509.01322).  A layer is TWO sub-blocks, each a latent attention and a
dense SwiGLU with weights, norms and cache rows of its own (sub-layers ``2l``
and ``2l + 1``: ``mla_layers`` counts sub-layers, two latent pools a layer),
and one expert layer that reads the first sub-block's normed stream and is
added after the second's dense half (``decoder_program._MB.shortcut_pair``).
Its router scores by a softmax over ALL its outputs (``router_scoring``),
``n_routed_experts`` with weights and then ``zero_experts`` identity experts
that compute nothing: a token's weight on them multiplies its own normed row.
``scale_q_lora`` / ``scale_kv_lora`` multiply the normed low-rank streams by
``sqrt(hidden / rank)``.  No shared expert (``n_shared_experts`` 0), no
leading dense layer.

The multi-token-prediction module (``mtp_layers`` 1) is one more block
with its own cache rows (layer index ``num_layers``) behind ``h' = W_p
[RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]``; :class:`MTPDrafter` runs it as
an engine drafter on the speculative path.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from ..framework.core import Program
from ..framework.dtype import VarType, convert_dtype
from ..ops import kda_kernels, mla_kernels
from .decoder_program import (DELTA_RULE_SEEDS, _MB, FormExtras, _emit_head,
                              _gmm_walk, _pow2_bucket, add_feed, build_form,
                              delta_rule_seed, expert_specs, ffn_specs,
                              live_rows)
from .kv_cache import KVCacheConfig
from .spec_decode import Proposer

__all__ = ["MLADecoderConfig", "MTPDrafter", "init_mla_weights",
           "seed_fan_in"]


@dataclass(frozen=True)
class MLADecoderConfig:
    vocab_size: int = 128
    hidden: int = 64
    num_heads: int = 4
    num_layers: int = 3
    first_k_dense: int = 1
    intermediate: int = 128          # the dense layers' SwiGLU width
    moe_intermediate: int = 32       # one expert's (and the shared one's)
    n_routed_experts: int = 8
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 256
    eos_id: int = -1
    weights_dtype: str = "float32"
    mtp_layers: int = 0              # 1: the MTP block's weights and cache
    # -- the hybrid description: empty / 0 / True is the plain MLA decoder
    mixers: Tuple[str, ...] = ()     # per layer "mla" | "kda"; (): all MLA
    rope: bool = True                # False: NoPE, q_r and k_r unrotated
    experts_held: int = 0            # experts 0..held-1 of a layer; 0: all
    kda_heads: int = 0
    kda_head_dim: int = 0            # d_k = d_v
    kda_conv_taps: int = 4
    kda_gate_rank: int = 0           # inner width of the two low-rank gates
    kda_l2_eps: float = 1e-6
    # -- the shortcut-connected description: False / 0 / "sigmoid" is the
    # block above
    shortcut: bool = False           # a layer: two sub-blocks, one shortcut
    router_scoring: str = "sigmoid"  # | "softmax", over every output
    zero_experts: int = 0            # identity experts after the routed ones
    scale_q_lora: bool = False       # c_q  * sqrt(hidden / q_lora_rank)
    scale_kv_lora: bool = False      # c_kv * sqrt(hidden / kv_lora_rank)

    # -- the seam ServingEngine asks a model description through ---------
    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The width a latent row is STORED at: whole 128-lane tiles where
        it is wider than one (576 -> 640: the chip pads the lanes of a
        576-wide array to 640 anyway, and cannot copy 576 of them)."""
        w = self.latent_width
        return w if w <= 128 else -(-w // 128) * 128

    @property
    def param_dtype(self) -> str:
        return self.weights_dtype

    def mixer(self, i: int) -> str:
        """Layer ``i``'s mixer; the MTP block's (``num_layers``) is MLA, and
        so is every sub-layer of a shortcut-connected model."""
        return self.mixers[i] if i < len(self.mixers) else "mla"

    @property
    def sub_layers(self) -> int:
        """The mixers over the depth: two a layer where shortcut-connected."""
        return self.num_layers * (2 if self.shortcut else 1)

    @property
    def mla_layers(self) -> List[int]:
        """The layers that keep latent rows, the MTP block's included; of a
        shortcut-connected model its sub-layers, two a layer."""
        if self.shortcut:
            return list(range(self.sub_layers))
        return [i for i in range(self.num_layers) if self.mixer(i) == "mla"] \
            + list(range(self.num_layers, self.num_layers + self.mtp_layers))

    @property
    def kda_layers(self) -> List[int]:
        return [i for i in range(self.num_layers) if self.mixer(i) == "kda"]

    @property
    def experts_here(self) -> int:
        return self.experts_held or self.n_routed_experts

    def param_specs(self) -> Dict[str, tuple]:
        return mla_param_specs(self)

    def build_program(self, mode: str, sampling=None,
                      kv_dtype: str = "float32", tp: int = 1) -> tuple:
        return build_mla_program(self, mode, sampling=sampling,
                                 kv_dtype=kv_dtype)

    def validate(self, tp: int = 1, kv_dtype: str = "float32",
                 prefix_cache: bool = False, prefill_chunk: int = 0,
                 spec_k: int = 0):
        """What this model is not served with, refused at construction."""
        if self.mixers and (len(self.mixers) != self.num_layers or
                            set(self.mixers) - {"mla", "kda"}):
            raise ValueError(f"mixers must name 'mla' or 'kda' for each of "
                             f"the {self.num_layers} layers: {self.mixers}")
        if self.kda_layers:
            if self.mtp_layers:
                raise ValueError(
                    "a model with KDA layers is not served with speculative "
                    "decoding (a recurrent state cannot be rolled back "
                    "without a snapshot): mtp_layers must be 0")
            if "mla" not in self.mixers:
                raise ValueError("a hybrid model needs an MLA layer: the "
                                 "engine sizes its page pool by it")
        if self.router_scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"router_scoring is 'sigmoid' or 'softmax': "
                             f"{self.router_scoring!r}")
        if self.zero_experts and self.router_scoring != "softmax":
            raise ValueError(
                "zero-computation experts are published with a softmax "
                "router over all outputs (a sigmoid scores each output "
                "alone, so an identity expert would take weight from none): "
                "router_scoring must be 'softmax'")
        if self.shortcut and (self.mixers or self.mtp_layers
                              or self.first_k_dense):
            raise ValueError(
                "a shortcut-connected layer is two MLA sub-blocks around one "
                "expert layer: it is not built with KDA mixers, an MTP "
                "block or leading dense layers")
        if int(tp or 1) != 1:
            raise ValueError("the MLA decoder has no tensor-parallel rules: "
                             "serving_tp must be 1")
        if kv_dtype == "int8":
            raise ValueError("the latent pool has no int8 storage: "
                             "kv_dtype must be float32 or bfloat16")
        if prefix_cache or prefill_chunk:
            raise ValueError(
                "the MLA decoder builds no 'chunk' program form: prefix "
                "caching and chunked prefill are refused for this model")
        if self.kda_layers and spec_k:
            raise ValueError(
                "a model with KDA layers is not served with speculative "
                "decoding: a recurrent state cannot be rolled back without "
                "a snapshot")

    def tp_rules(self, kv_dtype: str = "float32") -> Dict[str, tuple]:
        return {}

    def kv_cache_config(self, num_pages: int, page_size: int,
                        kv_dtype: str) -> KVCacheConfig:
        """One latent row a token and MLA layer: a single 'head' of
        ``latent_row``."""
        return KVCacheConfig(
            num_pages=num_pages, page_size=page_size, num_kv_heads=1,
            head_dim=self.latent_row, num_layers=len(self.mla_layers),
            dtype=kv_dtype)

    def cache_pool_names(self) -> List[str]:
        return [f"kv_lat_{i}" for i in self.mla_layers]

    def window_pool_names(self) -> List[str]:
        return []

    def kv_token_bytes(self, kv_dtype: str, tp: int = 1) -> int:
        return len(self.mla_layers) * self.latent_row \
            * np.dtype(kv_dtype).itemsize

    def state_pool_specs(self, state_slots: int) -> Dict[str, tuple]:
        """name -> (shape, dtype) of the pools that hold one SLOT a
        sequence (and one more, the padding's): a KDA layer's state and its
        convolution's last inputs, float32.  Empty without KDA layers."""
        h, d = self.kda_heads, self.kda_head_dim
        specs = {}
        for i in self.kda_layers:
            specs[f"kda_state_{i}"] = ((state_slots + 1, h, d, d), "float32")
            specs[f"kda_conv_{i}"] = (
                (state_slots + 1, self.kda_conv_taps - 1, 3 * h * d),
                "float32")
        return specs

    def state_slot_bytes(self) -> int:
        """Bytes one sequence's slot holds over the KDA layers."""
        return sum(int(np.prod(shape[1:])) * np.dtype(dtype).itemsize
                   for shape, dtype in self.state_pool_specs(0).values())

    # -- the source's names (its config.json), which the configuration
    # file, the plain reference and the tests speak ----------------------
    _SOURCE_KEYS = {
        "vocab_size": "vocab_size", "hidden": "hidden_size",
        "num_heads": "num_attention_heads",
        "num_layers": "num_hidden_layers",
        "first_k_dense": "first_k_dense_replace",
        "intermediate": "intermediate_size",
        "moe_intermediate": "moe_intermediate_size",
        "n_routed_experts": "n_routed_experts",
        "n_shared_experts": "n_shared_experts",
        "num_experts_per_tok": "num_experts_per_tok",
        "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
        "routed_scaling_factor": "routed_scaling_factor",
        "norm_topk_prob": "norm_topk_prob",
    }

    # Kimi-Linear's config.json names the same things otherwise, and adds
    # the layers' kinds (``linear_attn_config``, 1-based lists)
    _HYBRID_KEYS = {
        "vocab_size": "vocab_size", "hidden": "hidden_size",
        "num_heads": "num_attention_heads",
        "num_layers": "num_hidden_layers",
        "first_k_dense": "first_k_dense_replace",
        "intermediate": "intermediate_size",
        "moe_intermediate": "moe_intermediate_size",
        "n_shared_experts": "num_shared_experts",
        "num_experts_per_tok": "num_experts_per_token",
        "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
        "routed_scaling_factor": "routed_scaling_factor",
        "norm_topk_prob": "moe_renormalize",
    }

    # LongCat-Flash's config.json: its own names, a layer two sub-layers
    _SHORTCUT_KEYS = {
        "vocab_size": "vocab_size", "hidden": "hidden_size",
        "num_heads": "num_attention_heads", "num_layers": "num_layers",
        "intermediate": "ffn_hidden_size",
        "moe_intermediate": "expert_ffn_hidden_size",
        "num_experts_per_tok": "moe_topk", "zero_experts": "zero_expert_num",
        "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
        "routed_scaling_factor": "routed_scaling_factor",
        "scale_q_lora": "mla_scale_q_lora",
        "scale_kv_lora": "mla_scale_kv_lora",
    }

    def source_config(self) -> dict:
        """This model under the source's key names."""
        if self.shortcut:
            out = {theirs: getattr(self, ours)
                   for ours, theirs in self._SHORTCUT_KEYS.items()}
            out.update(n_routed_experts=self.experts_here,
                       router_experts=self.n_routed_experts,
                       zero_expert_type="identity")
            return out
        if not self.mixers:
            return {theirs: getattr(self, ours)
                    for ours, theirs in self._SOURCE_KEYS.items()}
        out = {theirs: getattr(self, ours)
               for ours, theirs in self._HYBRID_KEYS.items()}
        out.update(
            num_experts=self.experts_here,
            router_experts=self.n_routed_experts,
            q_lora_rank=self.q_lora_rank or None,
            mla_use_nope=not self.rope, kda_l2_eps=self.kda_l2_eps,
            linear_attn_config={
                "kda_layers": [i + 1 for i in self.kda_layers],
                "full_attn_layers": [i + 1 for i in self.mla_layers],
                "head_dim": self.kda_head_dim, "num_heads": self.kda_heads,
                "short_conv_kernel_size": self.kda_conv_taps})
        return out

    @classmethod
    def from_source(cls, source: dict, **ours) -> "MLADecoderConfig":
        """From a ``config.json`` of the source's shape (JoyAI's,
        Kimi-Linear's where it holds ``linear_attn_config``, LongCat-Flash's
        where it holds ``zero_expert_num``); ``ours`` gives what it does not
        say (``max_seq_len``, ``weights_dtype``, ...)."""
        if "zero_expert_num" in source:
            if source.get("zero_expert_type", "identity") != "identity":
                raise ValueError("zero-computation experts are built as the "
                                 "identity: zero_expert_type "
                                 f"{source['zero_expert_type']!r}")
            held = source["n_routed_experts"]
            routed = source.get("router_experts", held)
            kw = {mine: source[theirs]
                  for mine, theirs in cls._SHORTCUT_KEYS.items()}
            # the published router: softmax over every output, the chosen
            # weights not normalised, no shared expert, no dense layer
            kw.update(shortcut=True, router_scoring="softmax",
                      norm_topk_prob=False, n_shared_experts=0,
                      first_k_dense=0, n_routed_experts=routed,
                      experts_held=held if held < routed else 0)
            kw.update(ours)
            return cls(**kw)
        if "linear_attn_config" not in source:
            return cls(**{mine: source[theirs]
                          for mine, theirs in cls._SOURCE_KEYS.items()},
                       **ours)
        lin = source["linear_attn_config"]
        layers = source["num_hidden_layers"]
        routed = source.get("router_experts", source["num_experts"])
        held = source["num_experts"]
        kw = {mine: source[theirs]
              for mine, theirs in cls._HYBRID_KEYS.items()}
        kw.update(
            n_routed_experts=routed,
            experts_held=held if held < routed else 0,
            q_lora_rank=source.get("q_lora_rank") or 0,
            rope=not source.get("mla_use_nope", False),
            mixers=tuple("kda" if i + 1 in lin["kda_layers"] else "mla"
                         for i in range(layers)),
            kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
            kda_conv_taps=lin["short_conv_kernel_size"],
            kda_gate_rank=source.get("kda_gate_rank", lin["head_dim"]))
        kw.update(ours)
        return cls(**kw)


def _layer_specs(cfg: MLADecoderConfig, i: int, moe: bool) -> Dict[str, tuple]:
    """(Sub-)layer ``i``: its mixer and its feed-forward half; the first
    sub-layer of a shortcut-connected layer holds the layer's experts beside
    its dense half."""
    h, heads = cfg.hidden, cfg.num_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = f"dec_l{i}_"
    specs = {p + "attn_norm_scale": (h,)}
    if cfg.mixer(i) == "kda":
        specs.update({p + name: shape
                      for name, shape in _kda_specs(cfg).items()})
    else:
        if cfg.q_lora_rank:
            specs.update({p + "wq_a": (h, cfg.q_lora_rank),
                          p + "q_norm_scale": (cfg.q_lora_rank,),
                          p + "wq_b": (cfg.q_lora_rank, heads * qk)})
        else:
            specs[p + "wq"] = (h, heads * qk)
        specs.update({
            p + "wkv_a": (h, cfg.latent_width),
            p + "kv_norm_scale": (cfg.kv_lora_rank,),
            p + "wkv_b": (cfg.kv_lora_rank,
                          heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            p + "wo": (heads * cfg.v_head_dim, h),
        })
    specs.update(ffn_specs(cfg, i, moe and not cfg.shortcut))
    if cfg.shortcut and moe:
        specs.update(expert_specs(cfg, i))
    return specs


#: a KDA mixer's weights: the ``kda_mixer`` op's input slot of each
_KDA_SLOTS = {"kda_wqkv": "WQKV", "kda_conv": "Conv", "kda_wfa": "WFA",
              "kda_wfb": "WFB", "kda_a_log": "ALog", "kda_dt_bias": "DtBias",
              "kda_wbeta": "WBeta", "kda_wga": "WGA", "kda_wgb": "WGB",
              "kda_onorm_scale": "ONormScale"}


def _kda_specs(cfg: MLADecoderConfig) -> Dict[str, tuple]:
    """``[q | k | v]`` in one projection, the convolution's taps a channel,
    the decay's low-rank projection with ``A_log`` a head and ``dt_bias`` a
    channel, the write strength, the output gate's low-rank projection, the
    per-head output norm, and ``W_o``."""
    h, heads, d, r = cfg.hidden, cfg.kda_heads, cfg.kda_head_dim, \
        cfg.kda_gate_rank
    return {"kda_wqkv": (h, 3 * heads * d),
            "kda_conv": (3 * heads * d, cfg.kda_conv_taps),
            "kda_wfa": (h, r), "kda_wfb": (r, heads * d),
            "kda_a_log": (heads,), "kda_dt_bias": (heads * d,),
            "kda_wbeta": (h, heads), "kda_wga": (h, r),
            "kda_wgb": (r, heads * d), "kda_onorm_scale": (d,),
            "wo": (heads * d, h)}


def mla_param_specs(cfg: MLADecoderConfig) -> Dict[str, tuple]:
    """name -> shape of every weight, the MTP module's included where the
    configuration holds it."""
    h = cfg.hidden
    specs = {"dec_embed": (cfg.vocab_size, h), "dec_head": (h, cfg.vocab_size),
             "dec_norm_scale": (h,)}
    if cfg.shortcut:
        for i in range(cfg.sub_layers):
            specs.update(_layer_specs(cfg, i, moe=i % 2 == 0))
    else:
        for i in range(cfg.num_layers):
            specs.update(_layer_specs(cfg, i, moe=i >= cfg.first_k_dense))
    if cfg.mtp_layers:
        specs.update({"mtp_hnorm_scale": (h,), "mtp_enorm_scale": (h,),
                      "mtp_proj": (2 * h, h), "mtp_norm_scale": (h,)})
        specs.update(_layer_specs(cfg, cfg.num_layers, moe=True))
    return specs


def init_mla_weights(cfg: MLADecoderConfig, seed: int = 0
                     ) -> Dict[str, np.ndarray]:
    """Seeded weights for tests and smokes: norm scales 1, the router's
    correction bias small and seeded (against a softmax router's scores of
    order ``1 / outputs``: a tenth of that), the rest normal over
    sqrt(fan-in) (the fan-in is the second-to-last axis: weights multiply on
    the right; :func:`seed_fan_in` where a low-rank stream is scaled)."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape in mla_param_specs(cfg).items():
        if name.endswith("_scale"):
            w = np.ones(shape, np.float32)
        elif name.endswith("router_bias"):
            size = 0.01 if cfg.router_scoring == "sigmoid" \
                else 0.1 / shape[0]
            w = (size * rng.randn(*shape)).astype(np.float32)
        elif name.endswith(DELTA_RULE_SEEDS):
            w = delta_rule_seed(name, shape, rng).astype(np.float32)
        elif name == "dec_embed":
            w = rng.randn(*shape).astype(np.float32)
        else:
            w = (rng.randn(*shape) / np.sqrt(seed_fan_in(cfg, name, shape))
                 ).astype(np.float32)
        out[name] = w.astype(np.dtype(cfg.weights_dtype))
    return out


def seed_fan_in(cfg: MLADecoderConfig, name: str, shape) -> float:
    """The fan-in a seeded matrix is drawn over: its rows, or, for the two
    matrices that read a low-rank stream the description scales by
    ``sqrt(hidden / rank)`` (``wq_b``, ``wkv_b``), ``hidden``: the scales
    exist to align those paths' variance with the full-width paths'
    (arXiv:2509.01322, scale-correction for MLA), so ``q``, ``k_nope`` and
    ``v`` come out at the variance of ``k_r``, as without the scales.  Drawn
    over the rank the scores would have six times the spread and the softmax
    would be an argmax that bfloat16 rounding flips."""
    if (name.endswith("wq_b") and cfg.scale_q_lora) or \
            (name.endswith("wkv_b") and cfg.scale_kv_lora):
        return float(cfg.hidden)
    return float(shape[-2])


# ==========================================================================
# Program builders
# ==========================================================================
def _rope(m: _MB, x, positions, tag):
    o = m.tmp(tag)
    m.op("rope_interleaved", {"X": [x], "Positions": [positions]},
         {"Out": [o]}, {"theta": float(m.cfg.rope_theta)})
    return o


def _times(m: _MB, x, factor: float, tag):
    o = m.tmp(tag)
    m.op("scale", {"X": [x]}, {"Out": [o]},
         {"scale": float(factor), "bias": 0.0, "bias_after_scale": True})
    return o


def _split(m: _MB, x, sizes, tag):
    outs = [m.tmp(f"{tag}_{j}") for j in range(len(sizes))]
    m.op("split", {"X": [x]}, {"Out": outs},
         {"axis": -1, "sections": list(sizes), "num": 0})
    return outs


def _latents(m: _MB, i, hn, positions):
    """Layer ``i``'s queries and latent row of the normed rows ``hn``
    (n, hidden): ``(q_nope, q_rope) (n, heads, dn | dr)``, ``c_kv``
    (n, r) normed, ``k_r`` (n, dr) after RoPE.  Where the description says
    so the normed low-rank streams ``c_q`` and ``c_kv`` are multiplied by
    ``sqrt(hidden / rank)`` (hence both halves of ``q``, and ``k_nope`` and
    ``v``; never ``k_r``)."""
    cfg, p, b = m.cfg, f"dec_l{i}_", m.b
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = m.norm(m.mm(hn, p + "wq_a", f"l{i}_cq"),
                    p + "q_norm_scale", f"l{i}_cqn")
        if cfg.scale_q_lora:
            cq = _times(m, cq, (cfg.hidden / cfg.q_lora_rank) ** 0.5,
                        f"l{i}_cqs")
        q = m.mm(cq, p + "wq_b", f"l{i}_q")
    else:
        q = m.mm(hn, p + "wq", f"l{i}_q")
    q = b.reshape(q, [-1, cfg.num_heads, dn + dr], f"l{i}_q3")
    q_nope, q_rope = _split(m, q, [dn, dr], f"l{i}_qs")
    if cfg.rope:
        q_rope = _rope(m, q_rope, positions, f"l{i}_qr")
    c_kv, k_r = _split(m, m.mm(hn, p + "wkv_a", f"l{i}_kva"),
                       [cfg.kv_lora_rank, dr], f"l{i}_kvs")
    c_kv = m.norm(c_kv, p + "kv_norm_scale", f"l{i}_ckv")
    if cfg.scale_kv_lora:
        c_kv = _times(m, c_kv, (cfg.hidden / cfg.kv_lora_rank) ** 0.5,
                      f"l{i}_ckvs")
    if not cfg.rope:      # NoPE: dr more key lanes that all heads share
        return q_nope, q_rope, c_kv, k_r
    k_r = b.reshape(_rope(m, b.reshape(k_r, [-1, 1, dr], f"l{i}_kr3"),
                          positions, f"l{i}_krr"),
                    [-1, dr], f"l{i}_kr")
    return q_nope, q_rope, c_kv, k_r


def _pool(m: _MB, i, kv_dtype):
    return m.b.param(f"kv_lat_{i}", (), dtype=convert_dtype(kv_dtype))


def _mla(m: _MB, i, hn, positions, kv_dtype, slot_map=None, tables=None,
         ctx_lens=None):
    """Layer ``i``'s latent attention over the normed rows ``hn``: the rows'
    latents enter the layer's pool first where the form caches
    (``slot_map``), then the expanded attention over the whole prompt or,
    given ``tables``, the absorbed one over the paged pool."""
    cfg = m.cfg
    q_nope, q_rope, c_kv, k_r = _latents(m, i, hn, positions)
    out = m.tmp(f"l{i}_att")
    ins = {"QNope": [q_nope], "QRope": [q_rope],
           "WKVB": [f"dec_l{i}_wkv_b"]}
    if slot_map is not None:
        pool = _pool(m, i, kv_dtype)
        m.op("latent_cache_append",
             {"CKV": [c_kv], "KRope": [k_r], "SlotMapping": [slot_map],
              "Cache": [pool]}, {"CacheOut": [pool]})
    if tables is None:
        kind = "mla_prefill_attention"
        ins.update({"CKV": [c_kv], "KRope": [k_r]})
    else:
        kind = "mla_paged_attention"
        ins.update({"Cache": [pool], "BlockTables": [tables],
                    "ContextLens": [ctx_lens]})
    m.op(kind, ins, {"Out": [out]},
         {"v_head_dim": int(cfg.v_head_dim), "scale": float(
             (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)})
    return out


def _kda(m: _MB, i, hn, mode, valid=None, state_slots=None, last_index=None):
    """Layer ``i``'s KDA mixer over the normed rows ``hn``: one op (the
    projections, the convolution, the recurrence, the gated output
    norm), its state and its convolution's tail in the layer's two slot
    pools where the form caches."""
    cfg, p = m.cfg, f"dec_l{i}_"
    out = m.tmp(f"l{i}_kda")
    ins = {"X": [hn]}
    ins.update({slot: [p + name] for name, slot in _KDA_SLOTS.items()})
    outs = {"Out": [out]}
    if mode != "reference":
        state, conv = (m.b.param(f"kda_{kind}_{i}", (), dtype=VarType.FP32)
                       for kind in ("state", "conv"))
        ins.update({"Valid": [valid], "StateSlots": [state_slots],
                    "State": [state], "ConvState": [conv]})
        if last_index is not None:
            ins["LastIndex"] = [last_index]
        outs.update({"StateOut": [state], "ConvStateOut": [conv]})
    m.op("kda_mixer", ins, outs,
         {"mode": mode, "heads": int(cfg.kda_heads),
          "head_dim": int(cfg.kda_head_dim),
          "epsilon": float(cfg.rms_norm_eps),
          "l2_epsilon": float(cfg.kda_l2_eps)})
    return out


def _decode_walk(feed, kv_config, *, verify: bool, heads: int, layers: int):
    """``FormExtras.kernel_stats`` of the decode and verify forms: what the
    call's ``mla_decode`` kernels walk, from the contexts the call is fed
    and the sizes the kernel's wrapper uses (``mla_kernels.
    decode_chunks``), summed over the layers: the grid's steps (the chunks
    that hold context) and the chunks the tables span (what every row
    walking its whole table would take).  None where the kernel does not
    engage."""
    if not mla_kernels.decode_engages(kv_config.page_size, heads):
        return None
    if verify:      # a verify row's context ends at its own position
        ctx = np.asarray(feed["positions"]).reshape(-1) + 1
        width = feed["verify_tables"].shape[1]
    else:
        ctx = np.asarray(feed["context_lens"])
        width = feed["block_tables"].shape[1]
    steps, spanned = mla_kernels.decode_walk_counts(ctx, width,
                                                    kv_config.page_size)
    return {"mla_decode_calls": layers,
            "mla_decode_grid_steps": layers * steps,
            "mla_decode_table_chunks": layers * spanned}


def _hybrid_walk(feed, kv_config, *, mode: str, cfg: MLADecoderConfig):
    """``FormExtras.kernel_stats`` of a hybrid model's prefill and decode
    forms: the KDA kernels' calls, and the real tokens (prefill) or live
    sequences (decode: rows whose slot is not the padding's) they took,
    summed over the KDA layers; a prefill's grid steps, from the bucket it
    is fed by the sizes the kernel's wrapper uses (``kda_kernels.
    prefill_grid``); beside ``_decode_walk``'s counts of the MLA layers."""
    kda = len(cfg.kda_layers)
    if mode == "prefill":
        _, _, (groups, chunks) = kda_kernels.prefill_grid(
            int(np.size(feed["tokens"])), cfg.kda_heads, cfg.kda_head_dim)
        return {"kda_prefill_calls": kda, "kda_prefill_tokens":
                kda * (int(np.asarray(feed["last_index"])[0]) + 1),
                "kda_prefill_grid_steps": kda * groups * chunks}
    live = int((np.asarray(feed["state_slots"])
                < kv_config.state_slots).sum())
    out = {"kda_decode_calls": kda, "kda_decode_sequences": kda * live}
    out.update(_decode_walk(feed, kv_config, verify=False,
                            heads=cfg.num_heads,
                            layers=len(cfg.mla_layers)) or {})
    return out


def _live_walk(kv_config, *, cfg: MLADecoderConfig):
    """``FormExtras.live_walk_pages`` of the decode form: where
    ``mla_decode`` runs its kernel (the predicate of :func:`_decode_walk`)
    its grid is the chunks that hold context, whatever the tables span."""
    if mla_kernels.decode_engages(kv_config.page_size, cfg.num_heads):
        return mla_kernels.DECODE_TABLE_PAGES
    return None


def _form_walk(feed, kv_config, *, mode: str, cfg: MLADecoderConfig,
               routed: bool):
    """``FormExtras.kernel_stats`` of a serving form: what its attention and
    state kernels walk, from the feed (:func:`_decode_walk`,
    :func:`_hybrid_walk`), and under ``from_counts`` what its grouped
    matmuls will have walked, a function of the call's expert counts
    (:func:`_gmm_walk`), where the form routes and the kernel engages."""
    if cfg.kda_layers:
        out = _hybrid_walk(feed, kv_config, mode=mode, cfg=cfg)
    elif mode == "prefill":
        out = {}
    else:
        out = _decode_walk(
            feed, kv_config, verify=mode == "verify", heads=cfg.num_heads,
            layers=cfg.sub_layers) or {}
    if routed and mla_kernels.gmm_engages(cfg.hidden, cfg.moe_intermediate):
        out["from_counts"] = functools.partial(
            _gmm_walk, hidden=cfg.hidden,
            rows=int(np.size(feed["tokens"])) * cfg.num_experts_per_tok)
    return out


def build_mla_program(cfg: MLADecoderConfig, mode: str, sampling=None,
                      kv_dtype: str = "float32") -> tuple:
    """One program form of the decoder: ``(program, feeds, fetches)``
    through ``decoder_program.build_form``.  Its ``FormExtras`` offer the
    logits (the parity hook), the rows' last hidden state before the final
    norm (what the MTP drafter consumes), the tokens per expert by expert
    layer and each emitted token's logit and the row's log-sum-exp, two
    floats a row; the serving forms' ``kernel_stats`` is
    :func:`_form_walk`."""
    if mode == "mtp":
        return _build_mtp_program(cfg, sampling, kv_dtype)
    hybrid = bool(cfg.kda_layers)
    whole = mode in ("reference", "prefill")

    def feeds(m, f):
        if hybrid and mode != "reference":
            # the slot of the sequence (a prompt) or of each row (a decode
            # batch) in the KDA layers' pools; the padding's is the last
            add_feed(m.b, f, "state_slots", (1,) if whole else (-1,))

    def rows(m, f, flat_pos):
        slot_map, ctx_lens = f.get("slot_mapping"), f.get("context_lens")
        with m.part("embed"):     # the rows' contexts and liveness
            if mode == "verify":
                # a verify row's context ends at its own position
                ctx_lens = m.tmp("ctx_from_pos")
                m.op("scale", {"X": [flat_pos]}, {"Out": [ctx_lens]},
                     {"scale": 1.0, "bias": 1.0, "bias_after_scale": True})
            valid = None if slot_map is None else live_rows(
                m, slot_map, _pool(m, cfg.mla_layers[0], kv_dtype))

        def mix(i, hn):
            if cfg.mixer(i) == "kda":
                return _kda(m, i, hn, mode, valid, f.get("state_slots"),
                            f["last_index"] if mode == "prefill" else None)
            return _mla(m, i, hn, flat_pos, kv_dtype, slot_map,
                        f.get("tables"), ctx_lens)
        return valid, mix

    # no 'chunk' form; with KDA layers no 'verify' form either (a recurrent
    # state cannot be rolled back), and every row's routing with a prompt
    prompts = ("reference", "prefill")
    return build_form(
        cfg, mode, sampling, kv_dtype, feeds=feeds, rows=rows,
        walk=_form_walk, live_walk=_live_walk,
        routes_all=prompts if hybrid else (),
        modes=prompts + (("decode",) if hybrid else ("decode", "verify")))


def _build_mtp_program(cfg: MLADecoderConfig, sampling, kv_dtype: str):
    """The drafter's program, rows flat: row ``n`` is position ``p`` of
    some sequence with the main model's hidden state there, the token at
    ``p + 1``, its own block table and a context that ends at ``p``.  Every
    row's latent enters the MTP layer's pool first, so the rows of one
    prompt (contexts 1, 2, ...) are its causal prefill and the rows of a
    batch its decode step.  Emits each row's draft of the token at ``p +
    2`` (greedy: a draft is a guess, the verify samples)."""
    if not cfg.mtp_layers:
        raise ValueError("this configuration holds no MTP module")
    prog = Program()
    prog._label = "mtp"
    m = _MB(prog, cfg)
    b = m.b
    hidden = b.feed("hidden", (-1, cfg.hidden), VarType.FP32)
    tokens = b.feed("tokens", (-1,), VarType.INT32)
    positions = b.feed("positions", (-1,), VarType.INT32)
    tables = b.feed("block_tables", (-1, -1), VarType.INT32)
    ctx_lens = b.feed("context_lens", (-1,), VarType.INT32)
    slot_map = b.feed("slot_mapping", (-1,), VarType.INT32)
    feeds = ["hidden", "tokens", "positions", "block_tables",
             "context_lens", "slot_mapping"]
    with m.part("embed"):
        emb = b.tmp("mtp_emb")
        m.op("lookup_table_v2", {"W": ["dec_embed"], "Ids": [tokens]},
             {"Out": [emb]})
        emb32 = b.tmp("mtp_emb_f32")
        m.op("cast", {"X": [emb]}, {"Out": [emb32]},
             {"in_dtype": int(m.wdt), "out_dtype": int(VarType.FP32)})
    with m.part("mtp"):
        both = b.tmp("mtp_cat")
        m.op("concat", {"X": [m.norm(hidden, "mtp_hnorm_scale", "mtp_hn"),
                              m.norm(emb32, "mtp_enorm_scale", "mtp_en")]},
             {"Out": [both]}, {"axis": -1})
        hid = m.mm(both, "mtp_proj", "mtp_in")
    layer = cfg.num_layers
    with m.part("embed"):
        valid = live_rows(m, slot_map, _pool(m, layer, kv_dtype))
    hid = m.block(layer, hid, lambda i, hn: _mla(
        m, i, hn, positions, kv_dtype, slot_map, tables, ctx_lens), valid, [])
    with m.part("head"):
        logits = m.mm(m.norm(hid, "mtp_norm_scale", "mtp_fnorm"), "dec_head",
                      "mtp_logits")
        _emit_head(b, logits, "draft_tokens", None, None)
    prog._form_extras = FormExtras(logits=logits)
    return prog, feeds, ["draft_tokens"]


# ==========================================================================
# The MTP drafter
# ==========================================================================
class MTPDrafter(Proposer):
    """The model's own multi-token-prediction module as an engine drafter
    (k = 1).  Unlike a token-history proposer it consumes the main model's
    hidden states, which the engine hands it after each prefill and each
    verify call (``after_prefill`` / ``after_verify``); ``propose`` returns
    the draft those left behind.  Greedy acceptance is exact match, so the
    served tokens are those of plain decode whatever it drafts."""

    name = "mtp"
    wants_hidden = True

    def __init__(self):
        self.core = None
        self._draft: Dict[object, int] = {}
        self.last_logits = None          # (rows, vocab) of the last call

    def bind(self, core):
        """Build the drafter's program on the engine's scope and pools."""
        self.core = core
        self.prog, self.feeds, self.fetch = core.cfg.build_program(
            "mtp", kv_dtype=core.kv_dtype)
        core.keep_hidden = True

    def propose(self, req, k: int) -> List[int]:
        d = self._draft.get(req.req_id)
        return [d] if d is not None and k > 0 else []

    def _run(self, hidden, tokens, positions, tables, ctx, slots,
             keep_logits: bool = False):
        core, n = self.core, len(tokens)
        width = tables.shape[1]
        npad = _pow2_bucket(max(n, 1))
        feed = {
            "hidden": np.zeros((npad, core.cfg.hidden), np.float32),
            "tokens": np.zeros(npad, np.int32),
            "positions": np.zeros(npad, np.int32),
            "block_tables": np.zeros((npad, width), np.int32),
            "context_lens": np.ones(npad, np.int32),
            "slot_mapping": np.full(npad, core.kv_config.pad_slot, np.int32),
        }
        for key, val in (("hidden", hidden), ("tokens", tokens),
                         ("positions", positions), ("block_tables", tables),
                         ("context_lens", ctx), ("slot_mapping", slots)):
            feed[key][:n] = val
        fetch = list(self.fetch) + ([self.prog._form_extras.logits]
                                    if keep_logits else [])
        out = core.exe.run(self.prog, feed=feed, fetch_list=fetch,
                           scope=core.scope)
        if keep_logits:
            self.last_logits = np.asarray(out[1])[:n]
        return np.asarray(out[0])[:n]

    def _slots(self, req_id, positions):
        core = self.core
        ps = core.kv_config.page_size
        width = -(-(int(max(positions)) + 1) // ps)
        table = core.kv.block_table(req_id, width)
        pos = np.asarray(positions)
        return table[pos // ps] * ps + pos % ps

    def after_prefill(self, req, hidden, first_token: int,
                      keep_logits: bool = False):
        """``hidden`` (L, hidden): the prompt's rows.  Row ``i`` pairs with
        the token at ``i + 1`` (the first generated token for the last
        row); the last row's output drafts the second generated token."""
        core, L = self.core, len(req.prompt)
        tokens = list(req.prompt[1:]) + [int(first_token)]
        pos = np.arange(L, dtype=np.int32)
        W = _pow2_bucket(core.kv.num_pages_of(req.req_id))
        tables = np.broadcast_to(core.kv.block_table(req.req_id, W), (L, W))
        out = self._run(np.asarray(hidden)[:L], tokens, pos, tables, pos + 1,
                        self._slots(req.req_id, pos), keep_logits)
        self._draft[req.req_id] = int(out[-1])

    def after_verify(self, items, hidden, chunk: int, accepts, emits):
        """``items`` as ``verify_batch`` took them, ``hidden`` its rows
        ``(B * chunk, hidden)``; sequence ``i`` emitted ``emits[i]`` after
        accepting ``accepts[i]`` drafts.  The rows of the positions that
        now hold served tokens enter the MTP layer; the last one's output
        drafts the next step."""
        core = self.core
        alive = set(core.kv.live_sequences())    # a finished one was freed
        live = [(i, st) for i, (st, _d) in enumerate(items)
                if st.req.req_id in alive]
        if not live:
            return
        W = _pow2_bucket(max(core.kv.num_pages_of(st.req.req_id)
                             for _, st in live))
        rows, tokens, pos, tabs, slots, owners = [], [], [], [], [], []
        for i, st in live:
            rid = st.req.req_id
            n = min(accepts[i] + 1, len(emits[i]))
            # after the verify's roll-back the context ends at the last
            # accepted position: the served rows are its last n
            at = core.kv.context_len(rid) - n + np.arange(n, dtype=np.int32)
            rows += [i * chunk + j for j in range(n)]
            tokens += [int(t) for t in emits[i][:n]]
            pos += at.tolist()
            tabs += [core.kv.block_table(rid, W)] * n
            slots += self._slots(rid, at).tolist()
            owners += [rid] * n
        pos = np.asarray(pos, np.int32)
        out = self._run(np.asarray(hidden)[rows], tokens, pos,
                        np.stack(tabs), pos + 1, slots)
        for k, rid in enumerate(owners):
            self._draft[rid] = int(out[k])       # the last row of each wins

    def forget(self, req_id):
        self._draft.pop(req_id, None)
