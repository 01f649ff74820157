"""Step execution: the share of device 0's busy time in the traced window
that the program's tables give to no part (``lib/device_symbols.py``): rows no
table knows, keys on which the compiled steps disagree, instructions the
compiler left without a scope.  The gauge of the by-part and by-form shares:
what they cannot see."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "unnamed")
