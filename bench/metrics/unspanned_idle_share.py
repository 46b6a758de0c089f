"""Share of the window in which the device sat idle inside an engine call
(``bench.call``) and outside every stage span of the program, averaged over
the chips used: the host work the program's spans do not yet explain. A
stage span is an ``fl.*`` span with no ``fl.*`` span inside it but the
store's copies (``bench.spans.stages``); the engine runs only inside the
benchmark's calls, so its spans lie inside them."""
from bench import spans


def read(ctx):
    tr = ctx["trace"]
    sp = spans.of(ctx)
    if not sp.spans or not tr.devices or not tr.calls:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    calls = spans.idle_share(ctx, [list(c) for c in tr.calls])
    staged = spans.idle_share(ctx, spans.intervals(spans.stages(sp), lo, hi))
    return calls - staged
