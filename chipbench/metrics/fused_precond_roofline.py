"""Roofline share of pass 1 of the fused Adapprox update, %: the least
time one step's pass-1 calls could take (``flops.fused_precond_cost`` at
the stored factor width, with the fold projection the amortized refresh
cadence computes every step) over the kernel's device time in the traced
steps.  None where the kernel did not run."""
import flops

KERNEL = "fused_precond"
RANK = 128        # stored factor width: min(k_max = 128, min(m, n) / 4)


def read(ctx):
    seconds, count = ctx["trace"].kernel_seconds(KERNEL)
    if count == 0 or seconds <= 0:
        return None
    knobs = ctx["optimizer"].get("knobs", {})
    f, b = flops.fused_precond_cost(ctx["param_shapes"], ctx["optimizer"],
                                    RANK, knobs.get("refresh_every", 1) > 1)
    n = ctx["steps_traced"]
    return flops.roofline_share(n * f, n * b, seconds, ctx["peaks"])[0]
