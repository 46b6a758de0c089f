"""Device time of local training per round: the union of the operations
compiled under ``jax.named_scope("local_train")`` in the window, over the
rounds completed in it, averaged over the chips used (each chip trains its
own clients)."""
from bench import spans, trace


def read(ctx):
    scoped = spans.of(ctx).scoped
    if not any(scoped) or not ctx["rounds"]:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    ns = [sum(e - s for s, e in trace.union([a for a, _ in ev],
                                              [b for _, b in ev], lo, hi))
          for ev in scoped]
    return sum(ns) / len(ns) / ctx["rounds"] / 1e6
