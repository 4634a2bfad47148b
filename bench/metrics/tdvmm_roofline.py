"""Share of its roofline the TD-VMM kernel reached over the traced window:
the least time of every launch the window's steps made (``work.py``: the
larger of int8 ops over the int8 peak and bytes over the HBM bandwidth)
over the device time of the kernel's own events, in percent.

Its events are the ops that the compiled step programs name after the
kernel's entry point (``tdvmm_fused_kernel.<n>``), not any Mosaic call: a
later Pallas kernel is not counted; ``xplane.kernel_time`` refuses a count
of them other than the window's launches.
"""
from bench import xplane

KERNEL = "tdvmm_fused_kernel"


def reduce(rec):
    if rec["kernel_launches"] == 0:
        xplane.kernel_time(rec["trace"]["ops"], KERNEL, 0)
        return None
    spent = xplane.kernel_time(rec["trace"]["ops"], KERNEL, rec["kernel_launches"])
    return 100.0 * rec["least_kernel_s"] / spent
