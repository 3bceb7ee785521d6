"""Seconds from process start to the first timed dispatch."""


def read(ctx):
    return ctx["setup_s"]
