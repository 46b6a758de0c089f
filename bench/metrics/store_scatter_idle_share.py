"""Share of the window in which the device sat idle while the host was
inside the client store's scatter (``fl.store.scatter``: the mixed rows
copied back from the chip and written into the store), averaged over the
chips used. The copy waits for the round's program first; the device is
busy then, so the idle time is the copy and the writes."""
from bench import spans


def read(ctx):
    return spans.idle_in(ctx, "fl.store.scatter")
