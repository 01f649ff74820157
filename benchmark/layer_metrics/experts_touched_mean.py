"""Experts: the mean number of experts of an expert layer that received a
token in a decode step of the window, from the program's counters
(``moe_experts_touched`` over ``moe_layer_steps``, phase ``decode``, as the
engine's core sums them; the change between the window's opening and its
close).  At 256 it streams every expert's weights every step."""


def _delta(record, key):
    a = record.get("moe_open", {}).get("decode", {})
    b = record.get("moe_close", {}).get("decode", {})
    return b.get(key, 0.0) - a.get(key, 0.0)


def read(record, trace, cell):
    if "moe_close" not in record:
        return None
    steps = _delta(record, "layer_steps")
    return _delta(record, "experts_touched") / steps if steps else None
