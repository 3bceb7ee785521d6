"""Share of the traced window in which the chips run the flat buffer's
copies, the round's ``unflatten`` and ``flatten`` phases, averaged over
the chips (``scopes.flat_copy_pct``); 0 where the round has none."""

from perfbench import scopes


def read(ctx):
    return scopes.flat_copy_pct(ctx)
