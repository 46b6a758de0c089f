"""The trace reduction and the per-layer readers on a small recorded trace
(``data/small_trace.pbtxt``) whose sums are worked out by hand:

device 0, window [0, 220] us, calls [0, 100] and [120, 220]:
  busy [5, 60] + [70, 95] + [125, 210] = 165 us, idle 55 us, of which
  35 us inside calls ([0, 5], [60, 70], [95, 100], [120, 125], [210, 220])
  and 20 us between them; the segment kernel runs 10 us; the all-reduce
  runs [190, 210], 10 us of it beside no other op. A while op spanning
  the whole window is dropped: its body's operations are on the line.
device 1: busy all 220 us; its all-reduce is hidden under fusion.9.
"""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import peaks, trace  # noqa: E402
from bench.metrics import load  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.pbtxt")
US = 1000


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        return trace.from_profile(ProfileData.from_text_proto(f.read()))


@pytest.fixture
def ctx(tr):
    lo, hi = trace.window(tr)

    class Stub:
        rounds_per_call = 3

        def required_flops_per_call(self):
            return 2.0e6

        def mix_bytes_per_round(self):
            return 819.0

    return {"trace": tr, "lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
            "calls": 2, "rounds": 6, "driver": Stub(),
            "peaks": peaks.peaks("TPU v5 lite"), "chips": 2}


def test_planes_and_window(tr):
    assert [d.index for d in tr.devices] == [0, 1]
    assert len(tr.calls) == 2
    lo, hi = trace.window(tr)
    assert (hi - lo) == 220 * US
    # the XLA Modules line is not read, and the while op is dropped
    assert tr.devices[0].names == ["fusion.1", "convolution.2",
                                   "fed_mix_segment.14", "fusion.4",
                                   "fusion.5", "all-reduce.6"]


def test_busy_idle_and_gaps(tr):
    lo, hi = trace.window(tr)
    d0, d1 = tr.devices
    assert trace.busy_ns(d0, lo, hi) == 165 * US
    assert trace.busy_ns(d1, lo, hi) == 220 * US
    gaps = trace.idle_gaps(d0, lo, hi)
    assert [(s - lo, e - lo) for s, e in gaps] == [
        (0, 5 * US), (60 * US, 70 * US), (95 * US, 125 * US), (210 * US, 220 * US)]
    inside = trace.overlap_ns([list(g) for g in gaps], [list(c) for c in tr.calls])
    assert inside == 35 * US


def test_named_and_exposed(tr):
    lo, hi = trace.window(tr)
    d0, d1 = tr.devices
    assert trace.named_ns(d0, r"^fed_mix_segment", lo, hi) == 10 * US
    assert trace.exposed_ns(d0, lo, hi) == 10 * US
    # a collective is known by its opcode, whatever the instruction's name
    assert d1.names[1] == "psum.7" and d1.collective.tolist() == [False, True]
    assert trace.exposed_ns(d1, lo, hi) == 0


def test_breakdown(tr):
    lo, hi = trace.window(tr)
    ops = dict(trace.top_ops(tr, lo, hi))
    # fusion: (35 + 15 + 75 + 220) us over two devices
    assert ops["fusion"] == pytest.approx(345e-6 / 2)
    gaps = trace.top_gaps(tr, lo, hi)
    assert gaps[0] == ["between_calls@0.095ms", pytest.approx(30e-6)]
    assert [g[0].split("@")[0] for g in gaps[1:]] == ["inside_call"] * 3


def test_readers(ctx):
    assert load("device_idle_share")(ctx) == pytest.approx(100 * 55 / 220 / 2)
    assert load("engine_host_gap_share")(ctx) == pytest.approx(100 * 35 / 220 / 2)
    assert load("collective_exposed_share")(ctx) == pytest.approx(100 * 10 / 220 / 2)
    # 6 rounds x 819 B at 819 GB/s = 6 ns, over 10 us of kernel on 2 devices
    assert load("mix_kernel_roofline")(ctx) == pytest.approx(100 * 6e-9 / 5e-6)
    # 2 calls x 2 MFLOP over 220 us x 2 chips x 197 TFLOP/s
    assert load("mfu")(ctx) == pytest.approx(100 * 4e6 / (220e-6 * 2 * 197e12))


def test_readers_find_nothing(ctx):
    ctx["driver"].mix_bytes_per_round = lambda: None
    assert load("mix_kernel_roofline")(ctx) is None
    empty = dict(ctx, trace=trace.Trace(devices=[], calls=ctx["trace"].calls))
    quiet = [trace.Device(d.index, d.starts, d.ends, d.names,
                          d.collective & False) for d in ctx["trace"].devices]
    assert load("collective_exposed_share")(
        dict(ctx, trace=trace.Trace(devices=quiet, calls=ctx["trace"].calls))) is None
    for name in ("device_idle_share", "engine_host_gap_share",
                 "collective_exposed_share"):
        assert load(name)(empty) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
