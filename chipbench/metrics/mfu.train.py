"""Model FLOP/s utilisation of the traced train steps, %: the steps'
model FLOPs (the configuration's reference's ``train_flops_per_token``;
GPT-2's: 6 per matmul weight plus causal attention, no recomputation)
over the traced window times the chip's bf16 peak."""


def read(ctx):
    tr = ctx["trace"]
    n_tokens = ctx["steps_traced"] * ctx["batch"] * ctx["seq"]
    work = n_tokens * ctx["reference"].train_flops_per_token(ctx["model"],
                                                             ctx["seq"])
    return 100.0 * work / (tr.window_s * tr.n_devices
                           * ctx["peaks"]["bf16_flops_per_s"])
