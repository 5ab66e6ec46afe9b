"""Roofline share of pass 2 of the fused Adapprox update, %: the least
time one step's pass-2 calls could take (``flops.fused_apply_cost``,
memory-bound) over the kernel's device time in the traced steps.  None
where the kernel did not run."""
import flops

KERNEL = "fused_apply"


def read(ctx):
    seconds, count = ctx["trace"].kernel_seconds(KERNEL)
    if count == 0 or seconds <= 0:
        return None
    f, b = flops.fused_apply_cost(ctx["param_shapes"], ctx["optimizer"])
    n = ctx["steps_traced"]
    return flops.roofline_share(n * f, n * b, seconds, ctx["peaks"])[0]
