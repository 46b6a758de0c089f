"""Share of the window in which no operation ran on the device, averaged
over the chips used."""
from bench import trace


def read(ctx):
    devs = ctx["trace"].devices
    if not devs:
        return None
    span = ctx["hi"] - ctx["lo"]
    idle = [1.0 - trace.busy_ns(d, ctx["lo"], ctx["hi"]) / span for d in devs]
    return 100.0 * sum(idle) / len(idle)
