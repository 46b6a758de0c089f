"""One module per driver; ``load(name)`` finds the one a cell names."""
import importlib


def load(name: str):
    return importlib.import_module(f"bench.drivers.{name}").Driver
