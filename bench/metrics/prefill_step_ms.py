"""Device time of one prefill chunk program run, from the trace's program
events (the program that ran as often as the engine's prefill steps), ms."""


def reduce(rec):
    prog = rec["prefill_program"]
    return None if prog is None else 1e3 * prog[2] / prog[1]
