"""The whole round's share of the chips' peak: the training operations
the window's calls require (counted from the configuration's shapes by the
driver: forward and backward, real samples only, no recomputation) over
window seconds x chips x the bfloat16 peak of the device kind."""


def read(ctx):
    if ctx["calls"] == 0:
        return None
    need = ctx["calls"] * ctx["driver"].required_flops_per_call()
    return 100.0 * need / (ctx["window_s"] * ctx["chips"]
                           * ctx["peaks"]["bf16_flops"])
