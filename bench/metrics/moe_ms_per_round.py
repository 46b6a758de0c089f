"""Device time of the MoE blocks per round: the union of the operations
compiled under ``jax.named_scope("moe")`` (router, dispatch, experts,
shared expert, combine; forward, recomputation and backward) and of the
held experts' ragged dots, whose operations carry no scope on the TPU
(``scopes.RAGGED_DOT``; only the MoE blocks run one), in the window, over
the rounds completed in it, averaged over the chips."""
from bench import scopes


def read(ctx):
    ns = scopes.busy_ns(ctx, "moe", scopes.RAGGED_DOT)
    if not ns or not ctx["rounds"]:
        return None
    return sum(ns) / len(ns) / ctx["rounds"] / 1e6
