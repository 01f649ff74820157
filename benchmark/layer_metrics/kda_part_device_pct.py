"""Step execution: the share of device 0's busy time in the traced window
under the part ``kda_part`` of the program's tables (``lib/device_symbols.
py``): the KDA layers' norm, projections, convolution, gates, ``kda_prefill``
/ ``kda_decode``, output norm, output projection and residual add."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "kda_part")
