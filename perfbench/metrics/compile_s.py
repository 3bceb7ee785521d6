"""JAX's trace + lower + backend-compile seconds during set-up, summed from
the ``jax.monitoring`` duration events."""


def read(ctx):
    return ctx["compile_s"]
