"""Experts: of the real tokens' choices in decode steps, the share that fell
on the experts this chip holds (``choices_held`` over ``choices_all``, phase
``decode``): the rows the dispatch, the grouped matmuls and the combine move,
which is what the expert layer's buffers and tiles are sized against."""
from benchmark.layer_metrics.zero_expert_choice_pct import share


def read(record, trace, cell):
    return share(record, "choices_held")
