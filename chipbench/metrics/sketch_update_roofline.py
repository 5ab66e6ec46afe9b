"""Roofline share of the count-min sketch kernel, %: the least time one
step's sketch updates could take on the chip (``flops.sketch_update_cost``
over the peaks; memory-bound) over the device time of the kernel's events
in the traced steps.  Nothing to read (None) where the kernel did not run."""
import flops

KERNEL = "sketch_update"


def read(ctx):
    seconds, count = ctx["trace"].kernel_seconds(KERNEL)
    if count == 0 or seconds <= 0:
        return None
    f, b = flops.sketch_update_cost(ctx["param_shapes"], ctx["optimizer"])
    n = ctx["steps_traced"]
    return flops.roofline_share(n * f, n * b, seconds, ctx["peaks"])[0]
