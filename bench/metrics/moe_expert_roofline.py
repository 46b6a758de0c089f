"""The held experts' share of their roofline: the least time the chip
allows for their work in the window, over the device time of the
operations compiled under ``jax.named_scope("moe_experts")`` (the
expert matmuls and their activation) and of the ragged dots' operations,
which carry no scope on the TPU (``scopes.RAGGED_DOT``; only the held
experts run one), averaged over the chips.

The least time is the larger of the counted assignments' FLOPs (12 d ff
each, forward and backward) over the bfloat16 peak, and the bytes over
the HBM bandwidth: the held float32 expert weights of the chip's own
client read by the forward and the backward and their gradient written,
once per expert block and local step, plus each assignment's activation
rows
(``mesh_hybrid_rounds.Driver.expert_work``, from
``bench/models/nemotron_h.py``)."""
from bench import scopes


def read(ctx):
    work = getattr(ctx["driver"], "expert_work", None)
    if work is None or not ctx["calls"]:
        return None
    ns = scopes.busy_ns(ctx, "moe_experts", scopes.RAGGED_DOT)
    if not ns or not sum(ns):
        return None
    flops, bytes_ = work(ctx["calls"])
    peaks = ctx["peaks"]
    least = max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(ns) / len(ns) / 1e9)
