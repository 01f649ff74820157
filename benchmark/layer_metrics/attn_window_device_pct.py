"""Step execution: the share of device 0's busy time in the traced window
under the part ``attn_window`` of the program's tables (``lib/device_symbols.
py``): the window layers' norm, projections, rotary, append, ``gqa_prefill`` /
``gqa_decode`` over the last 512 positions, gate, output projection and
residual add."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "attn_window")
