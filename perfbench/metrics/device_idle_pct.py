"""Share of the traced window in which no op runs on the device, averaged
over the cell's chips: 100 x (1 - union of op intervals / window)."""

from perfbench import trace as trace_lib


def read(ctx):
    ops = ctx["trace"].devices
    if not ctx["device_ids"]:
        return None
    span = ctx["hi"] - ctx["lo"]
    busy = [trace_lib.busy_ns(ops[i], ctx["lo"], ctx["hi"])
            for i in ctx["device_ids"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
