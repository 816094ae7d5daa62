"""Percent of the device's busy time per call that the call's least
work would take at the chips' peaks.

The least work is ``min_work`` of the program family, from the shapes
alone; the least time is the larger of FLOPs over peak FLOP/s and bytes
over peak HBM bytes/s, with the peaks times the number of chips."""


def bound(r):
    """``(seconds, "bytes" | "flops")``: the least time of one call and
    what sets it."""
    flops_s = r.work["flops"] / (r.peaks["flops_per_s"] * r.chips)
    bytes_s = r.work["bytes"] / (r.peaks["hbm_bytes_per_s"] * r.chips)
    return (bytes_s, "bytes") if bytes_s >= flops_s else (flops_s, "flops")


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * bound(r)[0] / (r.trace.busy_s / r.window.calls)
