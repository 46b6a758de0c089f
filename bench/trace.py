"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced window. In it each
TPU is a plane ``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event
per operation executed on the chip, named by its whole HLO instruction
(``%fusion.271 = f32[...] fusion(...)``); the host threads are lines of
the plane ``/host:CPU``, where the benchmark's ``TraceAnnotation`` around
each engine call appears as an event named ``bench.call``. Times are
nanoseconds on one clock.

An operation is kept under its instruction name (``fusion.271``). The
control-flow operations (``while``, ``conditional``, ``call``) are dropped:
each spans the operations of its body, which are on the line themselves.
Pallas kernels are custom calls named after the jitted function that
launched them (``fed_mix_segment.14``).

``Trace`` keeps, per device, the operations as (start, end, name) and the
host's call spans; the functions below reduce them: the union of busy
intervals, the idle time inside the call spans, the summed time of named
events, and the time of collectives during which nothing else runs.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CALL_SPAN = "bench.call"
#: a collective, by its HLO opcode (the instruction's name may be the JAX
#: primitive's, e.g. ``%psum.3 = f32[...] all-reduce(...)``)
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
#: operations that only contain others
CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%") if " = " in hlo else hlo


@dataclass
class Device:
    index: int
    starts: np.ndarray          # int64 ns
    ends: np.ndarray
    names: list                 # instruction names
    collective: np.ndarray      # bool: the op is a collective


@dataclass
class Trace:
    devices: list = field(default_factory=list)
    calls: list = field(default_factory=list)     # [(start, end)] ns


def from_profile(data) -> Trace:
    """Build a ``Trace`` from ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ev = []
                for e in line.events:
                    name = short_name(e.name)
                    if op_kind(name) not in CONTAINERS:
                        ev.append((int(e.start_ns),
                                   int(e.start_ns + e.duration_ns), name,
                                   bool(COLLECTIVE.search(e.name))))
                ev.sort()
                tr.devices.append(Device(
                    int(m.group(1)),
                    np.array([e[0] for e in ev], np.int64),
                    np.array([e[1] for e in ev], np.int64),
                    [e[2] for e in ev],
                    np.array([e[3] for e in ev], bool)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == CALL_SPAN:
                        tr.calls.append((int(e.start_ns),
                                         int(e.start_ns + e.duration_ns)))
    tr.devices.sort(key=lambda d: d.index)
    tr.calls.sort()
    return tr


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    newest = max(files, key=os.path.getmtime)
    return from_profile(ProfileData.from_file(newest))


def union(starts, ends, lo: int, hi: int) -> list:
    """Merged [(start, end)] of the intervals, clipped to [lo, hi]."""
    out = []
    for s, e in zip(starts, ends):
        s, e = max(int(s), lo), min(int(e), hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(dev: Device, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(dev.starts, dev.ends, lo, hi))


def idle_gaps(dev: Device, lo: int, hi: int) -> list:
    """[(start, end)] of the device's idle stretches inside [lo, hi]."""
    gaps, t = [], lo
    for s, e in union(dev.starts, dev.ends, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def overlap_ns(a: list, b: list) -> int:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def named_ns(dev: Device, pattern, lo: int, hi: int) -> int:
    """Summed durations of the device's events whose name matches."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return sum(min(int(e), hi) - max(int(s), lo)
               for s, e, n in zip(dev.starts, dev.ends, dev.names)
               if rx.search(n) and min(int(e), hi) > max(int(s), lo))


def exposed_ns(dev: Device, lo: int, hi: int) -> int:
    """Time inside [lo, hi] covered by the device's collectives and by no
    other operation."""
    coll = union(dev.starts[dev.collective], dev.ends[dev.collective], lo, hi)
    other = union(dev.starts[~dev.collective], dev.ends[~dev.collective],
                  lo, hi)
    return sum(e - s for s, e in coll) - overlap_ns(coll, other)


def window(tr: Trace):
    """[lo, hi] ns: from the start of the first call span to the end of the
    last one."""
    if not tr.calls:
        raise ValueError("the trace holds no bench.call span")
    return tr.calls[0][0], max(e for _, e in tr.calls)


def op_kind(name: str) -> str:
    """An operation's name without its numeric suffix: ``fusion.12`` and
    ``fusion.7`` are one kind."""
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(tr: Trace, lo: int, hi: int, n: int = 10) -> list:
    """[[kind, seconds]] of the ``n`` operation kinds that took most device
    time, averaged over the devices."""
    tot = {}
    for dev in tr.devices:
        for s, e, name in zip(dev.starts, dev.ends, dev.names):
            d = min(int(e), hi) - max(int(s), lo)
            if d > 0:
                k = op_kind(name)
                tot[k] = tot.get(k, 0) + d
    nd = max(1, len(tr.devices))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def top_gaps(tr: Trace, lo: int, hi: int, n: int = 10) -> list:
    """[[what the host was doing, seconds]] of the ``n`` longest idle
    stretches of the first device: inside an engine call, or between calls
    (the benchmark's own loop)."""
    if not tr.devices:
        return []
    dev = tr.devices[0]
    calls = [list(c) for c in tr.calls]
    out = []
    for s, e in idle_gaps(dev, lo, hi):
        inside = overlap_ns([[s, e]], calls)
        where = "inside_call" if inside * 2 >= (e - s) else "between_calls"
        out.append([f"{where}@{(s - lo) / 1e6:.3f}ms", (e - s) / 1e9])
    out.sort(key=lambda g: -g[1])
    return out[:n]
