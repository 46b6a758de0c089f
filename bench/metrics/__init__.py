"""One module per per-layer metric; each has ``read(ctx)`` returning the
metric's value, or None where the trace holds nothing for it to read.

``ctx`` holds ``trace`` (a ``bench.trace.Trace``), ``lo``/``hi`` (the traced
window in ns), ``window_s``, ``calls`` and ``rounds`` completed in it,
``driver``, ``peaks`` and ``chips``."""
import importlib


def load(name: str):
    return importlib.import_module(f"bench.metrics.{name}").read
