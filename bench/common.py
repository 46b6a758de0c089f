"""Paths, the manifest, and seed handling shared by every part of the
benchmark."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(MANIFEST)


def resolve_cell(name: str, bench: dict | None = None) -> dict:
    """The cell ``name`` with its configuration, traffic mix and cell file
    read: ``{"entry", "config", "traffic", "cell", "chips"}``."""
    bench = bench or manifest()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return {
        "entry": entry,
        "config": load_json(ROOT / cfg_entry["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "cell": load_json(BENCH / "workloads" / f"{name}.json"),
        "chips": int(entry["chips"]),
    }


def seed_words(seed: int, stream: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words for one named stream of a run, from a seed of any
    size (seeds may exceed 32 bits)."""
    return np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        n, np.uint32)


def raw_key(seed: int, stream: int):
    """A raw threefry key ``uint32[2]`` for one stream of a run."""
    import jax.numpy as jnp
    return jnp.asarray(seed_words(seed, stream, 2), jnp.uint32)


def numpy_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, stream, 4))


#: stream ids: one per independent use of the seed
STREAM_DATA, STREAM_WEIGHTS, STREAM_CALLS, STREAM_TOKENS = 1, 2, 3, 4
