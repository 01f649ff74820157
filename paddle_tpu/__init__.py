"""paddle_tpu: a TPU-native deep-learning framework with the capabilities
of PaddlePaddle (Fluid era).  See SURVEY.md for the blueprint.

Two API surfaces, mirroring the reference:
* ``paddle_tpu.fluid`` — the Fluid static-graph + dygraph API
  (reference: python/paddle/fluid/).
* top-level 2.0-preview style aliases (reference: python/paddle/).
"""
import os as _os

# Persistent XLA compilation cache.  JAX_COMPILATION_CACHE_DIR, when set,
# is read by JAX itself and nothing is set here; otherwise the cache has
# ONE fixed home inside the checkout (the path is part of the cache key,
# so a directory that moves never hits).
import jax as _jax

COMPILE_CACHE_DIR = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
if not COMPILE_CACHE_DIR:
    COMPILE_CACHE_DIR = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")
    _jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

from . import framework
from .framework import (
    CPUPlace,
    TPUPlace,
    CUDAPlace,
    CUDAPinnedPlace,
    Program,
    Variable,
    program_guard,
    default_main_program,
    default_startup_program,
    in_dygraph_mode,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
)
from . import ops
from . import inference
from . import tensor
from . import nn
from . import metric
from . import distribution
from . import static
from . import incubate
from .incubate import complex  # noqa: A004  (paddle.complex preview API)
import sys as _sys

# make `import paddle_tpu.complex` work as a module path too, not just
# attribute access (users import it both ways)
_sys.modules[__name__ + ".complex"] = complex
from .tensor import (
    to_tensor, full, full_like, zeros, ones, zeros_like, ones_like,
    arange, linspace, matmul, concat, reshape, transpose, stack, split,
    squeeze, unsqueeze, flatten, cast, add, subtract, multiply, divide,
    maximum, minimum, clip, rand, randn, randint, uniform, normal,
    argmax, argmin, topk, where, tile, expand, flip, roll, gather,
    allclose, equal_all, bmm, dot, norm, tril, triu, numel,
)
from .executor import Executor
from .utils.memory import memory_stats, memory_summary
from .backward import append_backward, gradients
from .framework.scope import global_scope, scope_guard, LoDTensor, Scope


def grad(*args, **kwargs):
    """``paddle.grad`` — eager partial grad (PartialGradEngine analog);
    see dygraph.base.grad."""
    from .dygraph.base import grad as _g

    return _g(*args, **kwargs)


def enable_dygraph(place=None):
    from .dygraph.base import enable_dygraph as _e

    return _e(place)


def disable_dygraph():
    from .dygraph.base import disable_dygraph as _d

    return _d()


__version__ = "0.1.0"
