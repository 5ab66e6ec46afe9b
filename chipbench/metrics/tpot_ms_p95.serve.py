"""95th percentile of the time per output token after the first, ms, over
the requests of the traced run that the profiler left alone: (finish less
first token) / (output tokens less 1) on the benchmark's clock, for every
request whose decode ended before the profiled window opened or began
after it closed; a miss counts as infinite.  The engine reads every step's
tokens back before the next, so a pause of the host (the profiler's start
and stop among them) lands on every request in flight: on short ones most."""
import math

import harness


def read(ctx):
    w0, w1 = ctx["window"]                       # seconds from run start
    t0, stamps = ctx["t0"], ctx["stamps"]
    tpot = []
    for r in ctx["requests"]:
        first, done = stamps.first.get(r.uid), stamps.done.get(r.uid)
        if r.rejected or not r.done or first is None or done is None:
            tpot.append(math.inf)
        elif len(r.out_tokens) > 1 and (done - t0 < w0 or first - t0 > w1):
            tpot.append((done - first) / (len(r.out_tokens) - 1))
    if not tpot:
        return None
    return 1e3 * harness.quantile(tpot, 0.95)
