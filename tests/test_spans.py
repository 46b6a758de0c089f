"""The program's host spans and its local-training scope, read back on the
CPU: a sampled round on the checkpoint tier records its ``fl.*`` spans
nested as the benchmark's readers expect (``bench/spans.py``), with their
counts, and the same number of them in every round; a dense run is one
``fl.run_rounds`` span; local training compiles under the ``local_train``
scope; and with the profiler off a run's numbers are bit-identical to the
same run with no spans at all."""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import FLConfig
from repro.configs.paper_models import LOGREG_SYN
from repro.core.simulator import Simulator
from repro.data.federated import pack_clients
from repro.data.synthetic import syncov
from repro.models.paper_nets import init_paper_net
from repro.protocols import get
from repro.protocols.engine import DenseEngine, SampledEngine, make_local_trainer

D, K, T = 24, 8, 3
#: the spans of one sampled round, by start
ROUND = ["fl.round", "fl.select", "fl.store.gather", "fl.store.to_device",
         "fl.window", "fl.store.scatter", "fl.store.to_host"]


def _fl(**kw):
    base = dict(num_clients=D, num_clusters=2, devices_per_cluster=4,
                participation=K, local_epochs=1, batch_size=10, lr=0.05,
                straggler_rate=0.0, num_enrolled=D,
                participants_per_round=K)
    base.update(kw)
    return FLConfig(**base)


@pytest.fixture(scope="module")
def data_dev():
    xs, ys = syncov(num_clients=D, seed=0)
    return Simulator(LOGREG_SYN, pack_clients(xs, ys, 10, seed=0),
                     _fl()).data_dev


def _sampled(data_dev):
    se = SampledEngine(LOGREG_SYN, data_dev, _fl(), get("fedp2p"))
    se.init_store(se.init_params(0), tier="checkpoint")
    return se


def _dense(data_dev):
    return DenseEngine(LOGREG_SYN, data_dev, _fl(), get("fedp2p"))


def _fl_events(trace_dir) -> list:
    """(name, start, end, args) of the ``fl.*`` events, by start, from the
    one host line that holds them."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            ev = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                   dict(e.stats)) for e in line.events
                  if e.name.startswith("fl.")]
            if ev:
                lines.append(ev)
    assert len(lines) == 1
    return sorted(lines[0], key=lambda e: (e[1], -e[2]))


def _inside(outer, ev) -> list:
    return [e for e in ev if e is not outer
            and outer[1] <= e[1] and e[2] <= outer[2]]


@pytest.fixture(scope="module")
def traced(data_dev, tmp_path_factory):
    """The ``fl.*`` events of one sampled and one dense run, each traced
    after a first run that compiled its programs."""
    out = {}
    for name, make, run in (
            ("sampled", _sampled,
             lambda e: e.run_rounds(jax.random.PRNGKey(1), T)),
            ("dense", _dense,
             lambda e: e.run_rounds(e.init_params(0), jax.random.PRNGKey(1),
                                    T))):
        eng = make(data_dev)
        jax.block_until_ready(run(eng))
        d = str(tmp_path_factory.mktemp(name))
        with jax.profiler.trace(d):
            jax.block_until_ready(run(eng))
        out[name] = (_fl_events(d), eng)
    return out


def test_sampled_spans_nest_in_every_round(traced):
    ev, _ = traced["sampled"]
    (top,) = [e for e in ev if e[0] == "fl.run_rounds"]
    assert top[3] == {"rounds": T}
    rounds = [e for e in ev if e[0] == "fl.round"]
    assert [r[3] for r in rounds] == [{"round": t} for t in range(T)]
    # a fixed number of spans a round: none sits in a per-row loop
    assert len(ev) == 1 + len(ROUND) * T
    assert _inside(top, ev) == ev[1:]
    for r in rounds:
        inner = _inside(r, ev)
        assert [e[0] for e in [r] + inner] == ROUND
        by = {e[0]: e for e in inner}
        assert _inside(by["fl.store.gather"], ev) == [by["fl.store.to_device"]]
        assert _inside(by["fl.store.scatter"], ev) == [by["fl.store.to_host"]]
        for e in (by["fl.select"], by["fl.window"]):
            assert _inside(e, ev) == []


def test_sampled_span_counts(traced):
    ev, eng = traced["sampled"]
    width = eng.store.width
    gathers = [e[3] for e in ev if e[0] == "fl.store.gather"]
    assert all(g["rows"] == K and 0 <= g["cold_rows"] <= K for g in gathers)
    scatters = [e[3] for e in ev if e[0] == "fl.store.scatter"]
    assert [{"rows": s["rows"]} for s in scatters] == [{"rows": K}] * T
    # the traced run repeats the first run's selections: every row it
    # writes already has its slot
    assert [s["new_rows"] for s in scatters] == [0] * T
    assert eng.store.num_touched > 0
    for name in ("fl.store.to_device", "fl.store.to_host"):
        assert [e[3] for e in ev if e[0] == name] == [
            {"bytes": K * width * 4}] * T


def test_sampled_cold_rows_count_rows_from_the_base(data_dev, tmp_path):
    se = _sampled(data_dev)
    ids = np.arange(K)
    se.store.scatter(ids[:3], np.zeros((3, se.store.width), np.float32))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(se.store.gather(ids))
    (g,) = [e for e in _fl_events(str(tmp_path)) if e[0] == "fl.store.gather"]
    assert g[3] == {"rows": K, "cold_rows": K - 3}


def test_dense_run_is_one_span(traced):
    ev, _ = traced["dense"]
    assert [(e[0], e[3]) for e in ev] == [("fl.run_rounds", {"rounds": T})]


def test_local_training_compiles_under_its_scope():
    train = make_local_trainer(LOGREG_SYN, _fl())
    n = 20
    hlo = jax.jit(train).lower(
        init_paper_net(jax.random.PRNGKey(0), LOGREG_SYN),
        np.zeros((n, LOGREG_SYN.input_dim), np.float32),
        np.zeros((n,), np.int32), np.ones((n,), np.float32),
        jax.random.PRNGKey(0)).compile().as_text()
    # the op_name the device trace carries for each operation
    assert 'op_name="jit(local_train)/local_train/' in hlo


class _NoSpan(contextlib.nullcontext):
    """``TraceAnnotation`` with nothing behind it."""

    def __init__(self, *a, **k):
        super().__init__(self)

    def set_metadata(self, **k):
        pass


def _numbers(data_dev):
    se = _sampled(data_dev)
    m = se.run_rounds(jax.random.PRNGKey(2), T)
    rows = np.asarray(se.store.gather(np.arange(D)))
    dense = _dense(data_dev)
    params, dm = dense.run_rounds(dense.init_params(0),
                                  jax.random.PRNGKey(2), T)
    return ([m["train_loss"], rows, np.asarray(dm["train_loss"])]
            + [np.asarray(p) for p in jax.tree.leaves(params)])


def test_spans_change_no_number(data_dev, monkeypatch):
    spanned = _numbers(data_dev)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _NoSpan)
    plain = _numbers(data_dev)
    assert len(spanned) == len(plain)
    for a, b in zip(spanned, plain):
        np.testing.assert_array_equal(a, b)
