"""Share of the expert buffer rows that the expert launches computed over
the traced window that held a routed row (engine counters
``moe_rows_routed`` over ``moe_rows_computed``: rows routed to the experts
held here, and each launch's experts times its dispatch capacity, every
MoE layer), in percent.  A program without these counters reads nothing."""


def reduce(rec):
    c = rec["counts"]
    if c.get("moe_rows_computed", 0) <= 0 or "moe_rows_routed" not in c:
        return None
    return 100.0 * c["moe_rows_routed"] / c["moe_rows_computed"]
