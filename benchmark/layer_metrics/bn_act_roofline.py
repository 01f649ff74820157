"""Kernels: the fused batch-norm epilogues' share of their memory roofline:
the bytes the ``bn_act_fwd`` and ``bn_act_bwd`` calls of the traced steps
need, over the chip's memory bandwidth, over their summed device time."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import bn_act


def read(record, trace, cell):
    if not trace:
        return None
    need = bn_act.needed_bytes_per_step(
        cell.config["depth"], cell.config["image_size"],
        cell.traffic["per_chip_batch"], cell.config["amp_itemsize"])
    least_s = spent_s = 0.0
    for kernel, per_step in need.items():
        events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                       trace["window"], kernel)
        if not events:
            return None
        steps = len(events) / per_step["calls"]
        least_s += steps * per_step["bytes"] \
            / record["harness"]["peaks"]["hbm_bytes_per_s"]
        spent_s += sum(events) / 1e9
    return 100.0 * least_s / spent_s
