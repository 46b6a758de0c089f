"""The segment-mixing kernels' share of their roofline: the least time the
chip's HBM bandwidth allows for the bytes one mix must move (read the new
and old rows, write the mixed rows, write and read the [L, P] segment
sums), times the mixes in the window, over the summed device time of the
kernels' events. The bound is bandwidth: the mix does 2 FLOP per byte
read at most."""
from bench import trace

#: the two Pallas kernels of ``kernels/fed_mix_sparse.fed_mix_segment``
#: (its segment reduce and its gather-broadcast); both carry the jitted
#: wrapper's name, so they are summed together
KERNELS = r"^fed_mix_segment"


def read(ctx):
    per_round = ctx["driver"].mix_bytes_per_round()
    if not per_round or not ctx["trace"].devices:
        return None
    ns = sum(trace.named_ns(d, KERNELS, ctx["lo"], ctx["hi"])
             for d in ctx["trace"].devices)
    if ns == 0:
        return None
    least = ctx["rounds"] * per_round / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ns / len(ctx["trace"].devices) / 1e9)
