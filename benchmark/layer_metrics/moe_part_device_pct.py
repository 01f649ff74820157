"""Step execution: the share of device 0's busy time in the traced window
under the part ``moe_part`` of the program's tables (``lib/device_symbols.
py``): the expert layers' norm, router, dispatch, both ``moe_gmm`` calls,
combine, shared expert and residual add, kernels and XLA operations alike."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "moe_part")
