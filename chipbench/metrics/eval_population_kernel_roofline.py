"""Kernel (`eval_population_kernel`): share of its HBM roofline.  The
least time the chip could take for the kernel's calls is the bytes they
must move between HBM and the chip (each operand read once, each result
written once, at the shapes in each call's HLO text) over the chip's
published HBM bandwidth; the share is that over the kernel's measured
device time.  The kernel's work is integer VPU ops, for which no peak is
published, so the share is bounded by HBM alone."""

from harness import peaks

KERNEL = "eval_population_kernel"


def read(run):
    k = run.trace["kernels"].get(KERNEL)
    if not k or k["seconds"] <= 0:
        return None
    least_s = k["bytes"] / peaks.peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * least_s / k["seconds"]
