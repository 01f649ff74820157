"""Step execution: the share of device 0's busy time in the traced window
under the part ``gdn_part`` of the program's tables (``lib/device_symbols.
py``): the linear layers' projections, convolution, normalisation, decay,
``gdn_prefill`` / ``gdn_decode``, output norm and gate, output projection,
the block's norm after it and the residual add."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "gdn_part")
