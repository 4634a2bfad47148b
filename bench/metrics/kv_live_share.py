"""Share of the KV pages the steps' gathers touched over the traced window
that held a written position (engine counters ``kv_pages_live`` over
``kv_pages_read``), in percent."""


def reduce(rec):
    c = rec["counts"]
    if c.get("kv_pages_read", 0) <= 0:
        return None
    return 100.0 * c["kv_pages_live"] / c["kv_pages_read"]
