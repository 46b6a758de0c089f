"""Pipelined SampledEngine rounds + the store prefetch API.

The tentpole's correctness bar: ``run_rounds`` at ``pipeline_depth`` 2-3
is BIT-FOR-BIT the depth-1 serial loop — store rows, residual tier,
losses, and staleness — even under forced id-overlap conflicts (every
round colliding on the whole window), on both store tiers, stateful
``topk`` codec included. Plus: the ``CheckpointStore`` prefetch thread's
ordering semantics (reads queued behind a scatter return post-scatter
rows), the ``resident_flat``/``consensus`` readout contract, the
``gather_rows_dev``/``scatter_rows_dev`` device seams, and the traced
store programs' ``no-host-transfer``/``donation-integrity`` audit.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.configs.paper_models import LOGREG_SYN
from repro.core.simulator import Simulator
from repro.data.federated import pack_clients
from repro.data.synthetic import syncov
from repro.kernels import ops as kernel_ops
from repro.protocols import get
from repro.protocols.engine import SampledEngine
from repro.protocols.store import (
    CheckpointStore, ClientStateStore, MemoryStore, PrefetchHandle,
)

D = 24
K = 8


def _fl(**kw):
    base = dict(num_clients=D, num_clusters=2, devices_per_cluster=8,
                participation=D, local_epochs=1, batch_size=10, lr=0.05,
                straggler_rate=0.3, num_enrolled=D,
                participants_per_round=K)
    base.update(kw)
    return FLConfig(**base)


@pytest.fixture(scope="module")
def data_dev():
    xs, ys = syncov(num_clients=D, seed=0)
    data = pack_clients(xs, ys, 10, seed=0)
    return Simulator(LOGREG_SYN, data, _fl()).data_dev


def _engine(data_dev, depth, *, algo="gossip", codec=None, tier="auto",
            select=None):
    se = SampledEngine(LOGREG_SYN, data_dev, _fl(), get(algo), codec=codec,
                       pipeline_depth=depth)
    params = se.init_params(0)
    se.init_store(params, tier=tier)
    if select is not None:
        se.select_fn = select
    return se


def _store_state(se):
    """Everything the store owns, as host arrays, for bit comparison."""
    st = se.store
    out = {"last_round": st.last_round.copy()}
    if isinstance(st, MemoryStore):
        out["flat"] = np.asarray(st.flat)
        if st._residual is not None:
            out["residual"] = np.asarray(st._residual)
    else:
        for name, residual in (("rows", False), ("res_rows", True)):
            ids, rows = st.touched_rows(residual=residual)
            out[name] = dict(zip(ids.tolist(), rows))
    return out


def _assert_state_equal(got, ref):
    assert set(got) == set(ref)
    for k, v in ref.items():
        if isinstance(v, dict):
            assert set(got[k]) == set(v)
            for c in v:
                np.testing.assert_array_equal(got[k][c], v[c])
        else:
            np.testing.assert_array_equal(got[k], v)


# ---- depth semantics ------------------------------------------------------


def test_pipeline_depth_validation(data_dev):
    with pytest.raises(ValueError, match="pipeline_depth"):
        SampledEngine(LOGREG_SYN, data_dev, _fl(), get("fedavg"),
                      pipeline_depth=0)
    se = _engine(data_dev, 1)
    with pytest.raises(ValueError, match="pipeline_depth"):
        se.run_rounds(jax.random.PRNGKey(0), 1, pipeline_depth=-2)


def test_depth1_is_the_serial_round_loop(data_dev):
    """run_rounds at depth 1 is literally round() per fold_in(key, t) —
    the historical serial program, pinned bit-for-bit."""
    key = jax.random.PRNGKey(3)
    ref = _engine(data_dev, 1)
    losses = [ref.round(jax.random.fold_in(key, t), round_index=t)
              for t in range(4)]
    se = _engine(data_dev, 1)
    out = se.run_rounds(key, 4)
    np.testing.assert_array_equal(out["train_loss"],
                                  np.asarray(jax.device_get(losses)))
    _assert_state_equal(_store_state(se), _store_state(ref))


# ---- pipelined == serial, bit for bit -------------------------------------


@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("tier", ["memory", "checkpoint"])
def test_pipelined_bit_exact_under_natural_overlap(data_dev, depth, tier):
    """K=8 of D=24 over 6 rounds: consecutive windows overlap with high
    probability (asserted, not assumed) and the pipelined store state
    still matches serial exactly."""
    key = jax.random.PRNGKey(5)
    ref = _engine(data_dev, 1, tier=tier)
    out_ref = ref.run_rounds(key, 6)
    # prove this key really exercises the conflict path
    ids = [np.asarray(ref.select_fn(jax.random.split(
        jax.random.fold_in(key, t), 4)[0])) for t in range(6)]
    overlaps = sum(len(np.intersect1d(ids[t], ids[t + 1]))
                   for t in range(5))
    assert overlaps > 0, "selection produced no cross-round collisions"
    se = _engine(data_dev, depth, tier=tier)
    out = se.run_rounds(key, 6)
    np.testing.assert_array_equal(out["train_loss"], out_ref["train_loss"])
    _assert_state_equal(_store_state(se), _store_state(ref))
    assert se.prefetch_fallbacks == 0


def test_broken_prefetch_is_counted_without_a_fault_plan(data_dev):
    """A prefetch that raises falls back to a synchronous gather — counted
    with no fault plan at all, so a broken pipeline cannot hide behind
    results that stay bit-exact."""
    class Broken(PrefetchHandle):
        def result(self, timeout=None):
            raise RuntimeError("prefetch worker died")

    key = jax.random.PRNGKey(5)
    ref = _engine(data_dev, 1, tier="memory")
    out_ref = ref.run_rounds(key, 4)
    se = _engine(data_dev, 2, tier="memory")
    se.store.prefetch = lambda ids: Broken()
    out = se.run_rounds(key, 4)
    assert se.faults is None and se.prefetch_fallbacks == 4
    np.testing.assert_array_equal(out["train_loss"], out_ref["train_loss"])
    _assert_state_equal(_store_state(se), _store_state(ref))


@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("tier", ["memory", "checkpoint"])
def test_pipelined_bit_exact_adversarial_full_collision(data_dev, depth,
                                                        tier):
    """Worst case: every round samples the SAME window, so every row of
    every in-flight round conflicts — the whole window rides the patch
    path, on both store tiers."""
    sel = jax.jit(lambda k: jnp.arange(K, dtype=jnp.int32) + 2)
    key = jax.random.PRNGKey(9)
    ref = _engine(data_dev, 1, tier=tier, select=sel)
    out_ref = ref.run_rounds(key, 5)
    se = _engine(data_dev, depth, tier=tier, select=sel)
    out = se.run_rounds(key, 5)
    np.testing.assert_array_equal(out["train_loss"], out_ref["train_loss"])
    _assert_state_equal(_store_state(se), _store_state(ref))


@pytest.mark.parametrize("depth", [2, 3])
def test_pipelined_topk_residual_bit_exact(data_dev, depth):
    """Stateful ``topk`` error feedback: the residual tier rides the same
    prefetch/patch discipline and must stay bit-identical too."""
    key = jax.random.PRNGKey(7)
    ref = _engine(data_dev, 1, algo="fedavg", codec="topk")
    out_ref = ref.run_rounds(key, 5)
    se = _engine(data_dev, depth, algo="fedavg", codec="topk")
    out = se.run_rounds(key, 5)
    np.testing.assert_array_equal(out["train_loss"], out_ref["train_loss"])
    _assert_state_equal(_store_state(se), _store_state(ref))


# ---- store prefetch API ---------------------------------------------------


def test_base_prefetch_is_eager_and_reusable(data_dev):
    se = _engine(data_dev, 1, tier="memory")
    ids = np.array([3, 0, 5], np.int32)
    h = se.store.prefetch(ids)
    assert isinstance(h, PrefetchHandle)
    np.testing.assert_array_equal(np.asarray(h.wait()),
                                  np.asarray(se.store.gather(ids)))
    np.testing.assert_array_equal(np.asarray(h.wait()),
                                  np.asarray(h.wait()))   # idempotent


def test_checkpoint_prefetch_runs_on_background_thread():
    st = CheckpointStore(np.zeros((4,), np.float32), 16)
    seen = {}

    orig = st.gather

    def spy(ids):
        seen["thread"] = threading.current_thread().name
        return orig(ids)

    st.gather = spy
    rows = st.prefetch(np.array([1, 2])).wait()
    assert rows.shape == (2, 4)
    assert seen["thread"].startswith("store-prefetch")


def test_checkpoint_prefetch_after_scatter_reads_post_scatter_rows(tmp_path):
    """Ordering pin for the fetch thread: a prefetch QUEUED behind the
    worker when a conflicting scatter lands must observe the written row
    (post-scatter), not the stale ``load_leaves`` base row — the arena's
    slots are read at fetch time."""
    from repro.checkpoint.io import save_checkpoint
    base = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    path = save_checkpoint(str(tmp_path), 0, {"state": base})
    st = CheckpointStore(path, 16)
    gate = threading.Event()
    st._fetch_pool().submit(gate.wait)        # occupy the single worker
    ids = np.array([2, 7], np.int32)
    h = st.prefetch(ids)                      # queued behind the gate
    new = np.full((2, 4), -1.0, np.float32)
    st.scatter(ids, new)                      # lands BEFORE the fetch runs
    gate.set()
    np.testing.assert_array_equal(np.asarray(h.wait()), new)


def test_checkpoint_scatter_converts_once():
    """The store consumes device arrays directly — one host conversion at
    the seam (the engine no longer pre-converts)."""
    st = CheckpointStore(np.zeros((3,), np.float32), 8)
    rows = jnp.ones((2, 3), jnp.float32) * 2.5
    st.scatter(np.array([0, 4]), rows)        # a DEVICE array, not np
    np.testing.assert_array_equal(np.asarray(st.gather(np.array([4]))),
                                  np.full((1, 3), 2.5, np.float32))


# ---- resident_flat / consensus contract -----------------------------------


def test_resident_flat_contract(data_dev):
    mem = _engine(data_dev, 1, tier="memory").store
    assert mem.resident_flat() is mem.flat
    ck = CheckpointStore(np.zeros((4,), np.float32), 16)
    assert ck.resident_flat() is None
    base = ClientStateStore(4, 2)
    assert base.resident_flat() is None
    with pytest.raises(NotImplementedError):
        base.consensus()


def test_global_params_dispatches_on_resident_flat(data_dev):
    """Cold tier: global_params must route through ``consensus()`` (no
    ``flat`` attribute exists to duck-type on)."""
    se = _engine(data_dev, 1, tier="checkpoint")
    se.round(jax.random.PRNGKey(0), 0)
    got = kernel_ops.pack_tree(
        jax.tree.map(lambda p: p[None], se.global_params()))[0][0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(se.store.consensus()), rtol=1e-6)


# ---- device gather/scatter seams ------------------------------------------


def test_dev_seam_validation_and_roundtrip():
    from repro.kernels.ops import gather_rows_dev, scatter_rows_dev
    flat = jnp.arange(12.0).reshape(4, 3)
    with pytest.raises(ValueError, match="packed"):
        gather_rows_dev(jnp.zeros((4,)), jnp.array([0]))
    with pytest.raises(ValueError, match="1-D"):
        gather_rows_dev(flat, jnp.array([[0]]))
    with pytest.raises(ValueError, match="width"):
        scatter_rows_dev(flat, jnp.array([0]), jnp.zeros((1, 2)))
    with pytest.raises(ValueError, match="ids"):
        scatter_rows_dev(flat, jnp.array([0, 1]), jnp.zeros((1, 3)))
    win = gather_rows_dev(flat, jnp.array([2, 0]))
    np.testing.assert_array_equal(np.asarray(win),
                                  np.asarray(flat)[[2, 0]])
    out = scatter_rows_dev(flat, jnp.array([1]), jnp.ones((1, 3)),
                           donate=False)
    np.testing.assert_array_equal(np.asarray(out[1]), np.ones(3))
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(flat)[0])


def test_store_programs_pass_transfer_and_donation_audit():
    """The traced device gather/scatter programs: zero host transfers
    inside, and the scatter's donated state buffer aliases its output."""
    from repro.analysis import base as analysis_base
    from repro.analysis.programs import store_programs
    progs = store_programs()
    assert {p.name for p in progs} == {"store/memory/dev/none/gather",
                                       "store/memory/dev/none/scatter"}
    rules = [analysis_base.get("no-host-transfer"),
             analysis_base.get("donation-integrity")]
    assert analysis_base.run_rules(progs, rules) == []
