"""The whole training step's share of the chips' bf16 peak: model FLOPs of
the steps completed in the traced window (the cell's reference's
``flops_per_token``, no recomputation) over window x chips x peak."""


def read(ctx):
    seconds = (ctx["hi"] - ctx["lo"]) / 1e9
    flops = ctx["steps"] * ctx["flops_per_step"]
    return 100.0 * flops / (seconds * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
