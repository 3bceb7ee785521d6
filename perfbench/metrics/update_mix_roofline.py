"""The fused SGD update+mix kernel's share of its HBM roofline: the bytes
one call must move (perfbench/counts.py) times its calls, over its summed
device time times the chip's HBM bandwidth.  The kernel is the round's
one Mosaic kernel (``tpu_custom_call`` in the compiled HLO); nothing is
read where the round has none or the trace shows no call."""

from perfbench import trace as trace_lib


def read(ctx):
    if ctx["update_mix_bytes"] is None or len(ctx["kernels"]) != 1:
        return None
    ops = ctx["trace"].devices[ctx["device_ids"][0]]
    ns, calls = trace_lib.kernel_ns(ops, set(ctx["kernels"]), ctx["lo"],
                                    ctx["hi"])
    if not calls or not ns:
        return None
    return 100.0 * ctx["update_mix_bytes"] * calls / (
        ns / 1e9 * ctx["peaks"]["hbm_bytes_per_s"])
