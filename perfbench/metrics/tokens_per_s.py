"""All agents' training tokens of the rounds completed in the window, over
the window's seconds on the host clock."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"]
