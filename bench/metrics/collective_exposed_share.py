"""Share of the window in which a collective (all-reduce and kin) ran on a
device with no other operation beside it, averaged over the chips used."""
from bench import trace


def read(ctx):
    devs = ctx["trace"].devices
    if not devs or not any(d.collective.any() for d in devs):
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    exp = [trace.exposed_ns(d, lo, hi) for d in devs]
    return 100.0 * sum(exp) / len(exp) / (hi - lo)
