"""Host time per engine step in the traced window, ms: each
``engine_step`` span of ``ContinuousEngine`` that lies inside the window,
less its ``prefill_readback`` and ``decode_readback`` children (the host
waiting for the device's tokens), mean over those steps.  What is left is
the scheduler's own work: admission, dispatch, building the decode
inputs, committing tokens.  None where the engine records no readback
spans."""

READBACK = ("prefill_readback", "decode_readback")


def read(ctx):
    spans = ctx["spans"]
    if not any(e["name"] in READBACK for e in spans):
        return None
    w0, w1 = ctx["window"]                   # seconds from run start
    t_open = ctx["tracer_open"]              # window open, tracer clock
    t_close = t_open + (w1 - w0)
    steps = {e["span"]: e["dur_s"] for e in spans
             if e["name"] == "engine_step" and e["t0_s"] >= t_open
             and e["t0_s"] + e["dur_s"] <= t_close}
    if not steps:
        return None
    for e in spans:
        if e["name"] in READBACK and e.get("parent") in steps:
            steps[e["parent"]] -= e["dur_s"]
    return 1e3 * sum(steps.values()) / len(steps)
