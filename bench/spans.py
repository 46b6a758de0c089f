"""The program's own spans and its ``local_train`` scope, from the trace of
the window.

The engines and the client store mark their host work with
``jax.profiler.TraceAnnotation`` spans named ``fl.*``, whose arguments
(counts: ``rows``, ``bytes``, ...) are stats of the event. They run on the
thread that calls the engine, so they sit on the host line that holds the
benchmark's ``bench.call`` spans, nested: ``fl.run_rounds`` >
``fl.round`` > ``fl.select`` | ``fl.store.gather`` (> ``fl.store.to_device``)
| ``fl.window`` | ``fl.store.scatter`` (> ``fl.store.to_host``).

Local training is compiled under ``jax.named_scope("local_train")``. On a
TPU the scope path of an operation (``jit(_window_round)/vmap(local_train)
/while/body/...``) is the ``tf_op`` stat of the metadata of its ``XLA Ops``
event, not of the event, and ``jax.profiler.ProfileData`` shows only the
event's own stats; so the scope is read from the serialized ``XSpace``
directly (``_scoped_ops``), with no library beyond JAX.

``load()`` parses the newest ``.xplane.pb`` under ``bench.run.TRACE_DIR``
once per process; the metrics call ``of(ctx)``, which a test may answer
with ``ctx["spans"]``.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass

from bench import trace

#: the scope as a segment of an operation's scope path, bare or under
#: transformations (``local_train/``, ``vmap(local_train)/``), and not as
#: part of a longer name
SCOPE = re.compile(r"(?:^|/)(?:\w+\()*local_train\)*/")
#: spans that only split their parent's work (the store's copies): a span
#: whose ``fl.*`` children are all of these still explains its idle time
SPLITS = ("fl.store.to_device", "fl.store.to_host")


@dataclass
class Span:
    name: str
    start: int                  # ns
    end: int
    args: dict


class Spans:
    """The ``fl.*`` spans on the ``bench.call`` line (``spans``, by start,
    parents before the children that start with them) and, per device by
    index, [(start, end)] of the operations in the scope (``scoped``). The
    scope is decoded from ``raw`` on first use: a four-chip trace holds
    millions of operations, and only the cells that read the scope pay
    for it."""

    def __init__(self, spans: list, raw: bytes = b"", scoped=None):
        self.spans, self._raw, self._scoped = spans, raw, scoped

    @property
    def scoped(self) -> list:
        if self._scoped is None:
            self._scoped = _scoped_ops(memoryview(self._raw))
        return self._scoped


def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, i: int, end: int):
    """(field number, value) of the protobuf message in ``b[i:end]``: a
    varint as its int, a length-delimited field as its (start, stop)."""
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} in the trace")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode()


def _plane(b, span):
    """Name, lines, event metadata ({id: (name, {stat id: str_value})}) and
    stat names ({id: name}) of one ``XPlane``."""
    name, lines, meta, stat_names = "", [], {}, {}
    for g, v in _fields(b, *span):
        if g == 2:
            name = _text(b, v)
        elif g == 3:
            lines.append(v)
        elif g in (4, 5):
            entry = dict(_fields(b, *v))          # a map entry: key 1, value 2
            value = entry.get(2, (0, 0))
            label = _text(b, dict(_fields(b, *value)).get(2, (0, 0)))
            if g == 5:
                stat_names[entry[1]] = label
                continue
            stats = (dict(_fields(b, *w)) for h, w in _fields(b, *value)
                     if h == 5)
            meta[entry[1]] = (label, {st.get(1): st[5] for st in stats
                                      if 5 in st})
    return name, lines, meta, stat_names


def _line_events(b, lines, want: str, ids) -> list:
    """Sorted [(start, end)] ns of the events of line ``want`` whose
    metadata id is in ``ids``."""
    ev = []
    for line in lines:
        head = {g: v for g, v in _fields(b, *line) if g != 4}
        if _text(b, head.get(2, (0, 0))) != want:
            continue
        for g, e in _fields(b, *line):
            if g == 4:
                e = dict(_fields(b, *e))
                if e.get(1) in ids:
                    start = head.get(3, 0) + e.get(2, 0) // 1000
                    ev.append((start, start + e.get(3, 0) // 1000))
    return sorted(ev)


def _scoped_ops(b) -> list:
    """Per device, by index: [(start, end)] ns of the ``XLA Ops`` events
    whose operation's ``tf_op`` stat holds the ``SCOPE`` segment, containers
    left out. ``tf_op`` is a stat of the event's metadata, which
    ``ProfileData`` does not expose, so this walks the ``XSpace`` message
    itself: planes (1) > name (2), lines (3), event_metadata (4, a map:
    key 1, value 2), stat_metadata (5, alike); a line > name (2),
    timestamp_ns (3), events (4); an event > metadata_id (1),
    offset_ps (2), duration_ps (3); metadata > id (1), name (2),
    stats (5); a stat > metadata_id (1), str_value (5)."""
    out = []
    for f, span in _fields(b, 0, len(b)):
        if f != 1:
            continue
        name, lines, meta, stat_names = _plane(b, span)
        m = trace.DEVICE_PLANE.match(name)
        if not m:
            continue
        tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        ids = {k for k, (hlo, stats) in meta.items()
               if tf_op in stats and SCOPE.search(_text(b, stats[tf_op]))
               and trace.op_kind(trace.short_name(hlo)) not in trace.CONTAINERS}
        out.append((int(m.group(1)), _line_events(b, lines, trace.OPS_LINE, ids)))
    return [ev for _, ev in sorted(out)]


def _host_spans(data) -> list:
    """The ``fl.*`` events of the host line that holds ``bench.call``."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name == trace.CALL_SPAN for e in events):
                out += [Span(e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns), dict(e.stats))
                        for e in events if e.name.startswith("fl.")]
    return sorted(out, key=lambda s: (s.start, -s.end))


def from_xspace(raw: bytes) -> Spans:
    """``Spans`` of a serialized ``XSpace`` (the ``.xplane.pb`` file)."""
    from jax.profiler import ProfileData
    return Spans(_host_spans(ProfileData.from_serialized_xspace(raw)), raw)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> Spans:
    with open(path, "rb") as f:
        return from_xspace(f.read())


def load(trace_dir: str | None = None) -> Spans:
    """The spans of the newest ``.xplane.pb`` under ``trace_dir`` (default:
    the benchmark's trace directory), parsed once per file."""
    if trace_dir is None:
        from bench.run import TRACE_DIR as trace_dir
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    newest = max(files, key=os.path.getmtime)
    return _load(newest, os.path.getmtime(newest))


def of(ctx) -> Spans:
    return ctx["spans"] if "spans" in ctx else load()


def named(sp: Spans, name: str) -> list:
    return [s for s in sp.spans if s.name == name]


def stages(sp: Spans) -> list:
    """The spans that explain the idle time inside them: those with no
    ``fl.*`` span inside them but the store's copies (``SPLITS``). On one
    thread a child starts inside its parent, so only the spans that start
    before a span ends need a look."""
    out, s = [], sp.spans
    for i, a in enumerate(s):
        j, parent = i + 1, False
        while j < len(s) and s[j].start < a.end and not parent:
            parent = s[j].end <= a.end and s[j].name not in SPLITS
            j += 1
        if not parent:
            out.append(a)
    return out


def intervals(spans: list, lo: int, hi: int) -> list:
    """Merged [[start, end]] of the spans, clipped to [lo, hi]."""
    s = sorted(spans, key=lambda x: x.start)
    return trace.union([x.start for x in s], [x.end for x in s], lo, hi)


def idle_share(ctx, where: list):
    """Share (%) of the window in which the device sat idle inside the
    intervals ``where``, averaged over the chips; None without devices."""
    devs = ctx["trace"].devices
    if not devs:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    ns = [trace.overlap_ns([list(g) for g in trace.idle_gaps(d, lo, hi)],
                           where) for d in devs]
    return 100.0 * sum(ns) / len(ns) / (hi - lo)


def idle_in(ctx, name: str):
    """``idle_share`` inside the spans called ``name``; None where the
    trace holds none."""
    got = named(of(ctx), name)
    if not got:
        return None
    return idle_share(ctx, intervals(got, ctx["lo"], ctx["hi"]))
