"""Active over total slots per decode step, averaged over the traced
window's decode steps (engine counts), in percent."""


def reduce(rec):
    c = rec["counts"]
    if c["decode_steps"] <= 0:
        return None
    return 100.0 * c["active_slot_steps"] / (c["decode_steps"] * rec["slots"])
