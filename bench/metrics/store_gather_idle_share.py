"""Share of the window in which the device sat idle while the host was
inside the client store's gather (``fl.store.gather``: the rows looked up,
stacked and copied to the chip), averaged over the chips used."""
from bench import spans


def read(ctx):
    return spans.idle_in(ctx, "fl.store.gather")
