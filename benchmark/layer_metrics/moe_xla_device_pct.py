"""Step execution: ``moe_part`` (``moe_part_device_pct``) less its ``moe_gmm``
kernels, as a share of device 0's busy time in the traced window: the
gathers, selects, converts, router and shared expert around the grouped
matmuls, which is what folding dispatch and combine into the kernel, or a
dispatch that drops absent rows first, can take."""
from benchmark.lib import device_symbols


def read(record, trace, cell):
    found = device_symbols.of_run(record, trace)
    if found is None:
        return None
    part = found["by_part"].get("moe_part", 0.0)
    kernels = device_symbols.kernel_seconds(found, "moe_part", "moe_gmm")
    return 100.0 * (part - kernels) / found["busy_s"]
