"""Scheduler: the most pages of the KV pool that held live tokens at once,
as a share of the pages the configuration reserves (``kv.stats()``'s
``peak_pages`` over ``pages_total``).  A cell that fills a small share pays
for the whole pool all the same where a step's cost follows the reserved
size."""


def read(record, trace, cell):
    kv = record.get("kv")
    return 100.0 * kv["peak_pages"] / kv["pages_total"] if kv else None
