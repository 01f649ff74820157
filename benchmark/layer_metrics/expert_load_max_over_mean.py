"""Experts: the fullest expert's tokens over the mean tokens an expert, per
expert layer and decode step, averaged over the window, from the program's
counters (``moe_expert_load_max_over_mean`` over ``moe_layer_steps``, phase
``decode``).  1 is an even load; the grouped matmul's row tiles and a
deployment's expert-parallel chips wait for the fullest."""
from benchmark.layer_metrics.experts_touched_mean import _delta


def read(record, trace, cell):
    if "moe_close" not in record:
        return None
    steps = _delta(record, "layer_steps")
    return _delta(record, "expert_load_max_over_mean") / steps if steps else None
