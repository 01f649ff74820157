"""The `fluid` namespace — API-compatible surface with the reference's
python/paddle/fluid package, assembled from the TPU-native implementation.

A reference-era script should run with `import paddle_tpu.fluid as fluid`
and a Place swap (the north star in BASELINE.json).
"""
from ..framework.core import (
    Program,
    Variable,
    Operator,
    Block,
    default_main_program,
    default_startup_program,
    program_guard,
    name_scope,
    in_dygraph_mode,
)
from ..framework.place import (
    CPUPlace,
    TPUPlace,
    CUDAPlace,
    CUDAPinnedPlace,
    TPUPinnedPlace,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
)
from ..framework.scope import Scope, LoDTensor, global_scope, scope_guard
from ..framework.dtype import VarType
from ..framework import unique_name
from ..executor import Executor
from ..backward import append_backward, gradients
from ..param_attr import ParamAttr, WeightNormParamAttr
from .. import initializer
from .. import layers
from .. import metrics
from .. import optimizer
from .. import regularizer
from .. import clip
from ..clip import (
    GradientClipByGlobalNorm,
    GradientClipByNorm,
    GradientClipByValue,
)
from ..initializer import set_global_initializer
from .. import dygraph
from ..dygraph.base import enable_dygraph, disable_dygraph
from ..parallel.compiled_program import (
    BuildStrategy,
    CompiledProgram,
    ExecutionStrategy,
)
from .. import io
from ..io import (
    save,
    load,
    save_params,
    load_params,
    save_persistables,
    load_persistables,
    save_inference_model,
    load_inference_model,
)
from .. import backward
from .. import nets
from ..reader import DataFeeder
from .. import reader
from .. import data_feed as dataset
from ..data_feed import (
    DatasetFactory,
    InMemoryDataset,
    QueueDataset,
    DataFeedDesc,
)

# framework module alias (scripts do fluid.framework.xxx)
from .. import framework
from .. import contrib

# data layers at fluid level (fluid.data = shape-verbatim variant)
def data(name, shape, dtype="float32", lod_level=0):
    return layers.data(name, shape, dtype=dtype, lod_level=lod_level,
                       append_batch_size=False)


embedding = layers.embedding
one_hot = layers.one_hot


class core:
    """Placeholder for reference's `fluid.core` pybind module: common
    attributes scripts touch."""

    VarDesc = None
    from ..framework.scope import LoDTensor, Scope
    from ..framework.place import CPUPlace, CUDAPlace, TPUPlace

    @staticmethod
    def get_tpu_device_count():
        import jax

        return jax.device_count() if is_compiled_with_tpu() else 0

    get_cuda_device_count = get_tpu_device_count


def cuda_places(device_ids=None):
    n = core.get_tpu_device_count()
    if device_ids is None:
        device_ids = list(range(max(n, 1)))
    return [TPUPlace(i) for i in device_ids]


tpu_places = cuda_places


def cpu_places(device_count=None):
    import os

    if device_count is None:
        device_count = int(os.environ.get("CPU_NUM", 1))
    return [CPUPlace() for _ in range(device_count)]


from ..framework.core import device_guard  # noqa: F401


_flags = {}


def set_flags(d):
    """reference: framework.py:5480 fluid.set_flags (gflags bridge)."""
    from ..utils import flags as flag_mod

    flag_mod.set_flags(d)


def get_flags(keys):
    from ..utils import flags as flag_mod

    return flag_mod.get_flags(keys)


from .. import profiler  # noqa: F401  (reference: fluid/profiler.py)
from .. import inference  # noqa: F401  (reference: fluid.core inference api)
from ..inference import (  # noqa: F401
    AnalysisConfig,
    AnalysisPredictor,
    PaddleTensor,
    create_paddle_predictor,
)


from ..utils.custom_op import load_op_library, register_op  # noqa: F401
