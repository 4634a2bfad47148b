"""Device self time under the ``weight_program`` scope (bf16 weights to the
kernel's codes) per run of the main step program (the one with the most
device time in the traced window), ms."""


def reduce(rec):
    return rec["scopes"].get(rec["main_program"], {}).get("weight_program")
