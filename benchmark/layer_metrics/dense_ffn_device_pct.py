"""Step execution: the share of device 0's busy time in the traced window
under the part ``dense_ffn`` of the program's tables (``lib/device_symbols.
py``): the dense SwiGLU halves' norm, three projections, activation and
residual add (two halves a layer in a shortcut-connected model)."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "dense_ffn")
