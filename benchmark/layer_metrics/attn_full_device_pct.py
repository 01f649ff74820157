"""Step execution: the share of device 0's busy time in the traced window
under the part ``attn_full`` of the program's tables (``lib/device_symbols.
py``): the full-attention layers' norm, projections, rotary, append,
``gqa_prefill`` / ``gqa_decode`` over the whole context, gate, output
projection and residual add."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "attn_full")
