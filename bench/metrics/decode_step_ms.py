"""Device time of one decode step program run, from the trace's program
events (the program that ran as often as the engine's decode steps), ms."""


def reduce(rec):
    prog = rec["decode_program"]
    return None if prog is None else 1e3 * prog[2] / prog[1]
