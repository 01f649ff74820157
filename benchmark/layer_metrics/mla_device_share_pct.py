"""Step execution: the share of device 0's busy time in the traced window
that the mla part of the blocks took: kernels and XLA operations whose
profile text names it (``lib/scopes.py``: the program scopes every op by its
type and by the part of the block it serves)."""


def read(record, trace, cell):
    parts = record.get("device_parts")
    if not parts or not parts["busy_s"]:
        return None
    return 100.0 * parts["seconds"].get("mla", 0.0) / parts["busy_s"]
