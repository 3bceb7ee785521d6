"""Forward and backward's own share of the chips' bf16 peak: the model
FLOPs of the steps in the traced window (the reference's
``flops_per_token``) over the device seconds of the round's ``grad`` phase
summed over the chips, times the peak (``scopes.grad_flops_pct``).
Nothing is read where no ``grad`` op ran."""

from perfbench import scopes


def read(ctx):
    return scopes.grad_flops_pct(ctx)
