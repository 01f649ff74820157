"""Op registry + lowerings (the analog of paddle/fluid/operators/).

Importing this package registers the full op corpus.
"""
from . import registry
from .registry import (
    op,
    grad_maker,
    infer_for,
    get_op_def,
    is_registered,
    run_op,
    make_grad_ops,
    has_grad,
    LowerCtx,
)

# registration side effects
from . import math_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import dgc_ops  # noqa: F401
from . import control_ops  # noqa: F401
from . import ps_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import detection_extra_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import fused_ops  # noqa: F401
from . import vision_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import extra_ops  # noqa: F401
from . import py_func_op  # noqa: F401
from . import compat_ops  # noqa: F401
from . import long_tail_ops  # noqa: F401
from . import parity_ops  # noqa: F401
from . import paged_ops  # noqa: F401
from . import sampling_ops  # noqa: F401
from . import mla_ops  # noqa: F401
from . import kda_ops  # noqa: F401
from . import gqa_ops  # noqa: F401
