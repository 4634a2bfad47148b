"""Device self time under the ``kv.read`` and ``kv.write`` scopes per run of
the main step program (the one with the most device time in the traced
window), from the trace with scopes read from the program's optimised
HLO (``scopes.py``), ms."""


def reduce(rec):
    ms = rec["scopes"].get(rec["main_program"], {})
    if "kv.read" not in ms and "kv.write" not in ms:
        return None
    return ms.get("kv.read", 0.0) + ms.get("kv.write", 0.0)
