"""Scheduler: the most pages of the WINDOW group's pools that live sequences
held at once, as a share of the pages the engine reserves for it
(``kv.stats()``'s ``groups``: the window layers keep only the pages that cover
a sequence's last 512 (+ a page's) positions and give back what lies behind).
``kv_pool_peak_pct`` reads the full layers' group.  A cache manager with one
group reads nothing."""


def read(record, trace, cell):
    group = ((record.get("kv") or {}).get("groups") or {}).get("window")
    return 100.0 * group["peak_pages"] / group["pages_total"] if group \
        else None
