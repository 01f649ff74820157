"""Step execution: the share of device 0's busy time in the traced window
that the compiled steps labelled ``prefill`` took: every row whose instruction
name and shape the program's tables (``lib/device_symbols.py``) give to
prefill steps alone.  Read from the device's own rows, so two steps in flight
do not blur it as they blur a host span."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_program", "prefill")
