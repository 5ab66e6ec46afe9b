"""95th percentile of the time requests waited in the engine's queue, ms:
the ``queued`` spans ``ContinuousEngine`` records per request (arrival to
admission), over every request of the traced run."""
import harness


def read(ctx):
    waits = [e["dur_s"] for e in ctx["spans"] if e["name"] == "queued"]
    if not waits:
        return None
    return 1e3 * harness.quantile(waits, 0.95)
