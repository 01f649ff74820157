"""Scheduler: the most state slots that live sequences owned at once, as a
share of the slots the configuration reserves (``kv.stats()``'s
``state_slots``: a sequence of a model with recurrent layers owns one slot
beside its pages, and admission waits for a free one).  A cache manager with
no slots reads nothing."""


def read(record, trace, cell):
    slots = (record.get("kv") or {}).get("state_slots")
    return 100.0 * slots["peak"] / slots["total"] if slots else None
