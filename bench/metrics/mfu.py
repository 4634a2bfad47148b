"""The whole step's share of the chip's peak: the model's matmul work over
the traced window (analog sites at the int8 peak; attention at its causal
context and a digital head at the bf16 peak; ``work.model_seconds``)
divided by the window, in percent."""


def reduce(rec):
    t = rec["trace"]
    if rec["model_s"] <= 0 or t["window_s"] <= 0:
        return None
    return 100.0 * rec["model_s"] / t["window_s"]
