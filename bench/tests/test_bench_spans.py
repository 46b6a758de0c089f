"""The span and scope readers (``bench/spans.py``) and the metrics that read
them, on a small recorded trace (``data/span_trace.pbtxt``) whose sums are
worked out by hand. Window [0, 220] us, two chips.

TPU:0 busy [0, 5] + [14, 30] + [44, 75] + [85, 88] + [131, 215]; idle
[5, 14], [30, 44], [75, 85], [88, 131], [215, 220]: 81 us, 61 of them
inside the calls ([100, 120] lies between them). TPU:1 is idle only
between the calls.

Stage spans (no fl.* span inside but the store's copies): fl.select
[6, 10], fl.store.gather [12, 40], fl.window [42, 46], fl.store.scatter
[48, 90], the resident call's fl.run_rounds [125, 130], and the copies
inside gather and scatter. fl.round and the sampled call's fl.run_rounds
hold other stages. TPU:0's idle time inside them: gather [12, 14] +
[30, 40] = 12, scatter [75, 85] + [88, 90] = 12, select 4, window 2, the
resident fl.run_rounds 5; outside every stage but inside a call: [5, 6],
[10, 12], [40, 42], [90, 100], [120, 125], [130, 131], [215, 220] = 26.
12 + 12 + 4 + 2 + 5 + 26 = 61.

Local training on TPU:0: [14, 30] + [44, 70] + [131, 210] = 121 us (the
while op around it is dropped; [85, 88] names ``local_train`` only inside
a longer name); on TPU:1: 100 us.
"""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import peaks, spans, trace  # noqa: E402
from bench.metrics import load  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data", "span_trace.pbtxt")
US = 1000
T0 = 1_000_000          # the lines' timestamp_ns


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def tr(raw):
    from jax.profiler import ProfileData
    return trace.from_profile(ProfileData.from_serialized_xspace(raw))


@pytest.fixture(scope="module")
def sp(raw):
    return spans.from_xspace(raw)


@pytest.fixture
def ctx(tr, sp):
    lo, hi = trace.window(tr)

    class Stub:
        rounds_per_call = 1
        updates_per_call = 4

    return {"trace": tr, "spans": sp, "lo": lo, "hi": hi,
            "window_s": (hi - lo) / 1e9, "calls": 2, "rounds": 2,
            "driver": Stub(), "peaks": peaks.peaks("TPU v5 lite"),
            "chips": 2}


def _us(pairs):
    return [((s - T0) / US, (e - T0) / US) for s, e in pairs]


def share(us_per_chip):
    """Percent of the window, averaged over the two chips."""
    return pytest.approx(100 * sum(us_per_chip) / 2 / 220)


def test_host_spans_of_the_call_line(sp):
    # the prefetch worker's line holds fl.* spans but no bench.call
    assert [s.name for s in sp.spans] == [
        "fl.run_rounds", "fl.round", "fl.select", "fl.store.gather",
        "fl.store.to_device", "fl.window", "fl.store.scatter",
        "fl.store.to_host", "fl.run_rounds"]
    by = {s.name: s for s in sp.spans}
    assert by["fl.store.gather"].args == {"rows": 8, "cold_rows": 3}
    assert by["fl.store.to_host"].args == {"bytes": 800}
    assert _us([(s.start, s.end) for s in spans.named(sp, "fl.run_rounds")]) == [
        (2, 98), (125, 130)]


def test_stages_follow_the_leaf_rule(sp):
    assert [(s.name, _us([(s.start, s.end)])[0]) for s in spans.stages(sp)] == [
        ("fl.select", (6, 10)), ("fl.store.gather", (12, 40)),
        ("fl.store.to_device", (36, 40)), ("fl.window", (42, 46)),
        ("fl.store.scatter", (48, 90)), ("fl.store.to_host", (48, 80)),
        ("fl.run_rounds", (125, 130))]


def test_scoped_operations(sp):
    assert [_us(ev) for ev in sp.scoped] == [
        [(14, 30), (44, 70), (131, 210)], [(120, 220)]]


def test_readers(ctx):
    assert load("store_gather_idle_share")(ctx) == share([12, 0])
    assert load("store_scatter_idle_share")(ctx) == share([12, 0])
    assert load("unspanned_idle_share")(ctx) == share([26, 0])
    # 800 B each way, over 2 calls x 4 updates
    assert load("store_bytes_per_update")(ctx) == pytest.approx(1600 / 8)
    # (121 + 100) / 2 us of local training over 2 rounds, in ms
    assert load("local_train_ms_per_round")(ctx) == pytest.approx(
        110.5e-3 / 2)
    assert load("engine_host_gap_share")(ctx) == share([61, 0])


def test_idle_inside_the_calls_is_split_without_remainder(ctx, sp):
    parts = [spans.idle_in(ctx, n) for n in ("fl.store.gather",
                                             "fl.store.scatter", "fl.select",
                                             "fl.window")]
    resident = spans.intervals(spans.named(sp, "fl.run_rounds")[1:],
                               ctx["lo"], ctx["hi"])
    parts += [spans.idle_share(ctx, resident),
              load("unspanned_idle_share")(ctx)]
    assert parts[:5] == [share([12, 0]), share([12, 0]), share([4, 0]),
                         share([2, 0]), share([5, 0])]
    assert sum(parts) == pytest.approx(load("engine_host_gap_share")(ctx))


def test_readers_find_nothing(ctx, tr):
    # a program without spans or scope (the parent of this reader)
    bare = dict(ctx, spans=spans.Spans(spans=[], scoped=[[], []]))
    for name in ("store_gather_idle_share", "store_scatter_idle_share",
                 "unspanned_idle_share", "store_bytes_per_update",
                 "local_train_ms_per_round"):
        assert load(name)(bare) is None
    # a resident or mesh call: spans, but no store
    resident = [s for s in ctx["spans"].spans if s.start > 120 * US + T0]
    dense = dict(ctx, spans=spans.Spans(spans=resident,
                                        scoped=ctx["spans"].scoped))
    for name in ("store_gather_idle_share", "store_scatter_idle_share",
                 "store_bytes_per_update"):
        assert load(name)(dense) is None
    assert load("unspanned_idle_share")(dense) == share([61 - 5, 0])
    no_devices = dict(ctx, trace=trace.Trace(devices=[], calls=tr.calls))
    for name in ("store_gather_idle_share", "store_scatter_idle_share",
                 "unspanned_idle_share"):
        assert load(name)(no_devices) is None


def test_load_reads_the_newest_file_once(raw, tmp_path):
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    got, want = spans.load(str(tmp_path)), spans.from_xspace(raw)
    assert (got.spans, got.scoped) == (want.spans, want.scoped)
    assert spans.load(str(tmp_path)) is got
    with pytest.raises(FileNotFoundError):
        spans.load(str(tmp_path / "empty"))
