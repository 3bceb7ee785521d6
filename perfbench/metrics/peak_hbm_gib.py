"""The largest ``peak_bytes_in_use`` over the cell's chips after the
window, in GiB."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 2 ** 30
