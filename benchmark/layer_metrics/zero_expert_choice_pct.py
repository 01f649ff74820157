"""Experts: of the real tokens' choices in decode steps (``moe_topk`` a token
and expert layer), the share that fell on zero-computation (identity)
experts, from the program's counters (``choices_identity`` over
``choices_all``, phase ``decode``, as the engine's core sums them over the
run): what the architecture exists to vary, a third where the router is
indifferent.  A program that counts no such choice reads nothing."""


def share(record, key, phase="decode"):
    sums = (record.get("choices") or {}).get(phase) or {}
    return 100.0 * sums[key] / sums["choices_all"] \
        if sums.get("choices_all") and key in sums else None


def read(record, trace, cell):
    return share(record, "choices_identity")
