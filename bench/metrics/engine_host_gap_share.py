"""Share of the window in which the device sat idle while the host was
inside an engine call (selection, the client store, staging, dispatch),
averaged over the chips used. Idle time between calls is the benchmark's
own loop and is not counted."""
from bench import trace


def read(ctx):
    tr = ctx["trace"]
    if not tr.devices or not tr.calls:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    calls = [list(c) for c in tr.calls]
    inside = [trace.overlap_ns([list(g) for g in trace.idle_gaps(d, lo, hi)],
                               calls) for d in tr.devices]
    return 100.0 * sum(inside) / len(inside) / (hi - lo)
