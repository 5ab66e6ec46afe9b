"""Host time per train step, ms: the loop's own ``train_step`` span less
its ``device_sync`` child (data wait and dispatch), mean over the traced
steps.  Source: the train loop's spans (``repro.train.train`` tracer)."""


def read(ctx):
    steps = set(ctx["traced_steps"])
    spans = ctx["spans"]
    top = {e["span"]: e for e in spans
           if e["name"] == "train_step" and e.get("step") in steps}
    sync = {e["parent"]: e["dur_s"] for e in spans
            if e["name"] == "device_sync" and e.get("parent") in top}
    host = [e["dur_s"] - sync[sid] for sid, e in top.items() if sid in sync]
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
