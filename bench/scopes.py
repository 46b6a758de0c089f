"""Device operations by the ``jax.named_scope`` they were compiled under,
for scopes other than ``local_train`` (which ``bench/spans.py`` reads):
the same walk of the serialized ``XSpace``, with the scope given.

A scope is a segment of an operation's ``tf_op`` path, bare or under
transformations (``moe/``, ``transpose(jvp(moe))/``), and not part of a
longer name: ``moe`` does not match ``moe_experts/``.

The operations the TPU compiler writes when it expands
``jax.lax.ragged_dot`` (``ragged-dot-none``, ``ragged-dot-metadata``)
carry no scope path: their ``tf_op`` is their own name. A reader names
such kinds with the scope they belong to.
"""
from __future__ import annotations

import re

from bench import spans, trace


def pattern(scope: str):
    return re.compile(r"(?:^|/)(?:\w+\()*" + re.escape(scope) + r"\)*/")


#: the kinds of a ragged dot's operations on the TPU
RAGGED_DOT = ("ragged-dot",)


def scoped_ops(raw: bytes, scope: str, kinds: tuple = ()) -> list:
    """Per device, by index: [(start, end)] ns of the ``XLA Ops`` events
    compiled under ``scope``, and of those whose name starts with one of
    ``kinds`` whatever their scope, containers left out."""
    rx, b, out = pattern(scope), memoryview(raw), []
    for f, span in spans._fields(b, 0, len(b)):
        if f != 1:
            continue
        name, lines, meta, stat_names = spans._plane(b, span)
        m = trace.DEVICE_PLANE.match(name)
        if not m:
            continue
        tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        ids = {k for k, (hlo, stats) in meta.items()
               if (trace.short_name(hlo).startswith(kinds)
                   or tf_op in stats
                   and rx.search(spans._text(b, stats[tf_op])))
               and trace.op_kind(trace.short_name(hlo)) not in trace.CONTAINERS}
        out.append((int(m.group(1)),
                    spans._line_events(b, lines, trace.OPS_LINE, ids)))
    return [ev for _, ev in sorted(out)]


def busy_ns(ctx, scope: str, kinds: tuple = ()) -> list:
    """Per device, the union of the scope's operations (and of ``kinds``)
    in the window (ns); [] where the trace holds none."""
    per_dev = scoped_ops(spans.of(ctx)._raw, scope, kinds)
    if not any(per_dev):
        return []
    lo, hi = ctx["lo"], ctx["hi"]
    return [sum(e - s for s, e in trace.union([a for a, _ in ev],
                                               [b for _, b in ev], lo, hi))
            for ev in per_dev]
