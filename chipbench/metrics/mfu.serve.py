"""Model FLOP/s utilisation of serving in the traced window, %: forward
FLOPs of the prompt tokens prefilled and the tokens decoded in the window
(the configuration's reference's ``forward_flops``, each token attending
to its own context) over the window times the chip's bf16 peak.  Prefill
tokens come from the engine's ``prefill_chunk`` spans; a request's decode
tokens are taken as spread evenly between its first token and its finish,
on the benchmark's clock."""


def read(ctx):
    tr = ctx["trace"]
    model = ctx["model"]
    forward_flops = ctx["reference"].forward_flops
    w0, w1 = ctx["window"]                       # seconds from run start
    open_s = ctx["tracer_open"]
    work = 0.0
    for e in ctx["spans"]:
        if e["name"] != "prefill_chunk":
            continue
        t = e["t0_s"] - open_s
        if 0.0 <= t <= w1 - w0:
            a = e.get("attrs", {})
            p0, n = a.get("p0", 0), a.get("tokens", 0)
            for i in range(n):
                work += forward_flops(model, p0 + i + 1)
    t0, stamps = ctx["t0"], ctx["stamps"]
    for r in ctx["requests"]:
        first, done = stamps.first.get(r.uid), stamps.done.get(r.uid)
        k = len(r.out_tokens) - 1
        if first is None or done is None or k <= 0:
            continue
        a, b = first - t0, done - t0
        for j in range(k):
            t = a + (b - a) * (j + 1) / k
            if w0 <= t <= w1:
                work += forward_flops(model, len(r.prompt) + j + 1)
    return 100.0 * work / (tr.window_s * tr.n_devices
                           * ctx["peaks"]["bf16_flops_per_s"])
