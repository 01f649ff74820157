"""Inference / serving stack (SURVEY.md §2.7).

Reference: paddle/fluid/inference/ — AnalysisPredictor + AnalysisConfig
+ C API + engines.  TPU-native shape: XLA is the engine; the predictor
compiles the pruned program per input signature, the deployment artifact
is StableHLO + a flat weights container, and the native C API
(native/predictor_capi.cpp) serves that artifact through the PJRT C API
with no Python dependency.
"""
from .config import AnalysisConfig, Config, NativeConfig
from .predictor import (
    AnalysisPredictor,
    PaddlePredictor,
    PaddleTensor,
    ZeroCopyTensor,
    create_paddle_predictor,
    create_predictor,
)
from .export import export_stablehlo, load_ptw, save_ptw
from . import native_runtime
from .native_runtime import NativePredictor
from .kv_cache import KVCacheConfig, PagedKVCache
from .admission import (
    AdmissionPolicy,
    FIFOPolicy,
    RequestRejected,
    SLOAwarePolicy,
    get_policy,
)
from .gpt2_decoder import DecoderConfig, export_decoder
from .serving import Request, ServingEngine

__all__ = [
    "AnalysisConfig", "Config", "NativeConfig", "AnalysisPredictor",
    "PaddlePredictor", "PaddleTensor", "ZeroCopyTensor",
    "create_paddle_predictor", "create_predictor", "export_stablehlo",
    "load_ptw", "save_ptw",
    # serving runtime (r12)
    "KVCacheConfig", "PagedKVCache", "DecoderConfig", "Request",
    "ServingEngine", "export_decoder",
    # admission/preemption policy engine (r18)
    "AdmissionPolicy", "FIFOPolicy", "SLOAwarePolicy", "RequestRejected",
    "get_policy",
]
