"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

Row = Tuple[str, float, str]     # (name, us_per_call_or_metric, derived)


def wallclock(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-clock (us) of one ``fn(*args)`` call: the shared timing
    discipline of every benchmark module. ``warmup`` calls are discarded
    (compilation, store warming), then the median of ``iters`` timed calls
    is reported; every call — warmup included — is fenced with
    ``jax.block_until_ready`` on its return value, so async-dispatched
    device work is charged to the call that issued it, never to the next
    measurement."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def print_rows(rows: List[Row]) -> None:
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")
