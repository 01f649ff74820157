"""Step execution: the share of device 0's busy time in the traced window
under the part ``mla_part`` of the program's tables (``lib/device_symbols.
py``): the latent-attention layers' norm, projections, rope, ``mla_prefill`` /
``mla_decode`` / ``latent_append``, absorb, output projection and residual
add."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    return device_symbols.share(device_symbols.of_run(record, trace),
                                "by_part", "mla_part")
