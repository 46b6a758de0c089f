"""Bytes the client store moved between host and chip per client update:
the ``bytes`` of every ``fl.store.to_device`` and ``fl.store.to_host`` span
in the window, over the window's client updates."""
from bench import spans

COPIES = ("fl.store.to_device", "fl.store.to_host")


def read(ctx):
    got = [s for s in spans.of(ctx).spans if s.name in COPIES]
    updates = ctx["calls"] * ctx["driver"].updates_per_call
    if not got or not updates:
        return None
    return sum(int(s.args["bytes"]) for s in got) / updates
