"""protocols/store: the persistent client-state tiers behind sampled
participation — window gather/scatter round-trips, residual gating, the
slab-arena cold tier (incl. the load_leaves-backed path) against a dict
oracle, its reused staging window, staleness counters, and make_store tier
selection."""
import contextlib
import os
import sys
import threading
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.protocols import (
    CheckpointStore, MemoryStore, make_store,
)
from repro.protocols import store as store_mod
from repro.protocols.store import MEMORY_TIER_MAX_BYTES

D, W, K = 32, 7, 5


def _flat(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(D, W)).astype(np.float32))


def _ids():
    return np.array([4, 0, 31, 9, 4], np.int32)   # unordered + repeated


# ---- MemoryStore --------------------------------------------------------


def test_memory_gather_scatter_roundtrip():
    store = MemoryStore(_flat())
    ids = _ids()
    win = store.gather(ids)
    np.testing.assert_array_equal(np.asarray(win),
                                  np.asarray(store.flat)[ids])
    new = win + 1.0
    store.scatter(ids, new)
    np.testing.assert_array_equal(np.asarray(store.gather(ids[:4])),
                                  np.asarray(new)[:4])
    # untouched rows unchanged
    untouched = np.setdiff1d(np.arange(D), ids)
    np.testing.assert_array_equal(np.asarray(store.flat)[untouched],
                                  np.asarray(_flat())[untouched])


def test_memory_requires_packed_2d():
    with pytest.raises(ValueError, match=r"packed \[D, sum\(sizes\)\]"):
        MemoryStore(jnp.zeros((D,)))


def test_memory_residual_gated():
    store = MemoryStore(_flat())
    with pytest.raises(ValueError, match="without residual=True"):
        store.gather_residual(_ids())
    store = MemoryStore(_flat(), residual=True)
    np.testing.assert_array_equal(np.asarray(store.gather_residual(_ids())),
                                  np.zeros((K, W), np.float32))
    store.scatter_residual(_ids()[:2], np.ones((2, W)))
    assert float(store.gather_residual(np.array([4]))[0, 0]) == 1.0


def test_memory_consensus_is_row_mean():
    store = MemoryStore(_flat())
    np.testing.assert_allclose(store.consensus(),
                               np.asarray(_flat()).mean(axis=0), rtol=1e-6)


@pytest.mark.parametrize("ids,err", [
    (np.array([0, D]), IndexError),           # out of range
    (np.array([[0, 1]]), ValueError),         # not 1-D
])
def test_store_id_validation(ids, err):
    with pytest.raises(err):
        MemoryStore(_flat()).gather(ids)


# ---- CheckpointStore ----------------------------------------------------


def test_checkpoint_overlay_gather_scatter():
    base = np.arange(W, dtype=np.float32)
    store = CheckpointStore(base, D)
    ids = _ids()
    # cold gather: every row is the base row
    np.testing.assert_array_equal(np.asarray(store.gather(ids)),
                                  np.broadcast_to(base, (K, W)))
    rows = np.random.default_rng(1).normal(size=(K, W)).astype(np.float32)
    store.scatter(ids, rows)
    assert store.num_touched == 4                  # id 4 written twice
    got = np.asarray(store.gather(ids))
    # the LAST write for the duplicated id wins
    np.testing.assert_array_equal(got[0], rows[4])
    np.testing.assert_array_equal(got[1:4], rows[1:4])
    # untouched clients still read base
    np.testing.assert_array_equal(
        np.asarray(store.gather(np.array([7]))), base[None])


def test_checkpoint_consensus_analytic():
    base = np.ones((W,), np.float32)
    store = CheckpointStore(base, D)
    store.scatter(np.array([0, 1]), np.full((2, W), 3.0, np.float32))
    want = (2 * 3.0 + (D - 2) * 1.0) / D
    np.testing.assert_allclose(store.consensus(), np.full((W,), want),
                               rtol=1e-6)


def test_checkpoint_save_then_partial_read(tmp_path):
    """save() materializes [D, W]; a path-backed store over that file
    gathers cold rows via load_leaves partial-row reads."""
    base = np.arange(W, dtype=np.float32)
    store = CheckpointStore(base, D)
    rows = np.full((2, W), 5.0, np.float32)
    store.scatter(np.array([3, 8]), rows)
    path = store.save(str(tmp_path), 0)
    cold = CheckpointStore(path, D)
    assert cold.width == W and cold.dtype == np.float32
    got = np.asarray(cold.gather(np.array([3, 7, 8])))
    np.testing.assert_array_equal(got[0], rows[0])
    np.testing.assert_array_equal(got[1], base)
    np.testing.assert_array_equal(got[2], rows[1])
    with pytest.raises(NotImplementedError, match="full +pass"):
        cold.consensus()


def test_checkpoint_scatter_shape_mismatch():
    store = CheckpointStore(np.zeros((W,), np.float32), D)
    with pytest.raises(ValueError, match="does not match"):
        store.scatter(np.array([0, 1]), np.zeros((2, W + 1)))


def test_checkpoint_residual_defaults_zero():
    store = CheckpointStore(np.zeros((W,), np.float32), D)
    ids = _ids()
    np.testing.assert_array_equal(np.asarray(store.gather_residual(ids)),
                                  np.zeros((K, W), np.float32))
    store.scatter_residual(ids[:1], np.ones((1, W)))
    assert float(store.gather_residual(ids[:1]).sum()) == W


# ---- the slab arena against a dict oracle --------------------------------


def _base_store(base_kind, tmp_path, d=D, w=W):
    """A CheckpointStore over a base row, or over an npz of [d, w]
    distinct rows; returns (store, [d, w] base rows)."""
    rng = np.random.default_rng(7)
    if base_kind == "row":
        row = rng.normal(size=(w,)).astype(np.float32)
        return CheckpointStore(row, d), np.broadcast_to(row, (d, w))
    full = rng.normal(size=(d, w)).astype(np.float32)
    path = save_checkpoint(str(tmp_path), 0, {"state": full})
    return CheckpointStore(path, d), full


@pytest.mark.parametrize("tier", ["state", "residual"])
@pytest.mark.parametrize("base_kind", ["row", "npz"])
def test_checkpoint_arena_matches_dict_oracle(base_kind, tier, tmp_path,
                                              monkeypatch):
    """Rounds of random gathers and scatters (repeated, cold and hot ids,
    slabs of 3 rows so windows straddle slabs) read exactly what a plain
    {id: row} dict over the base reads, and the readouts agree."""
    monkeypatch.setattr(store_mod, "SLAB_ROWS", 3)
    store, base = _base_store(base_kind, tmp_path)
    residual = tier == "residual"
    gather = store.gather_residual if residual else store.gather
    scatter = store.scatter_residual if residual else store.scatter
    default = np.zeros((D, W), np.float32) if residual else base
    oracle = {}
    rng = np.random.default_rng(11)
    for _ in range(6):
        ids = rng.integers(0, D, size=K + 3).astype(np.int32)
        want = np.stack([oracle.get(int(c), default[c]) for c in ids])
        np.testing.assert_array_equal(np.asarray(gather(ids)), want)
        rows = rng.normal(size=(ids.size, W)).astype(np.float32)
        scatter(ids, rows)
        for c, r in zip(ids.tolist(), rows):
            oracle[c] = r
    got_ids, got_rows = store.touched_rows(residual=residual)
    assert sorted(got_ids.tolist()) == sorted(oracle)
    for c, r in zip(got_ids.tolist(), got_rows):
        np.testing.assert_array_equal(r, oracle[c])
    state = {} if residual else oracle
    assert store.num_touched == len(state)
    everyone = np.arange(D)
    full = np.array(base)
    for c, r in state.items():
        full[c] = r
    np.testing.assert_array_equal(np.asarray(store.gather(everyone)), full)
    if base_kind == "row":
        want = full.astype(np.float64).mean(axis=0).astype(np.float32)
        np.testing.assert_allclose(store.consensus(), want, rtol=1e-6)
    else:
        with pytest.raises(NotImplementedError):
            store.consensus()
    saved = store.save(str(tmp_path / "saved"), 1)
    restored, _ = load_checkpoint(str(tmp_path / "saved"),
                                  {"state": np.zeros((D, W), np.float32)})
    assert saved.endswith(".npz")
    np.testing.assert_array_equal(np.asarray(restored["state"]), full)


def _aligned(shape, dtype, align=64):
    """An empty array whose data starts on an ``align``-byte boundary."""
    n, item = int(np.prod(shape)), np.dtype(dtype).itemsize
    raw = np.empty(n * item + align, np.uint8)
    off = -raw.ctypes.data % align
    return raw[off:off + n * item].view(dtype).reshape(shape)


@pytest.mark.parametrize("aligned", [False, True])
def test_checkpoint_gather_windows_own_their_memory(aligned):
    """Gathers stage through one reused host buffer, yet a window they
    returned is bit-unchanged by later gathers and scatters: the CPU
    backend must not alias the staging memory, which it would do for an
    aligned buffer. A warm gather plus scatter allocates nothing near a
    window's size on the host."""
    w = 4096
    store = CheckpointStore(np.zeros((w,), np.float32), D)
    if aligned:
        store._staging[np.dtype(np.float32)] = _aligned((K, w), np.float32)
    rng = np.random.default_rng(3)
    ids = np.arange(K)
    store.scatter(ids, rng.normal(size=(K, w)).astype(np.float32))
    store.scatter_residual(ids, rng.normal(size=(K, w)).astype(np.float32))
    win = store.gather(ids)
    res = store.gather_residual(ids)
    kept = [np.array(win), np.array(res)]
    staging = store._staging[np.dtype(np.float32)]
    for k in range(3):
        ids2 = rng.permutation(D)[:K]
        store.scatter(ids2, rng.normal(size=(K, w)).astype(np.float32))
        store.scatter_residual(ids2, np.full((K, w), k, np.float32))
        jax.block_until_ready((store.gather(ids2),
                               store.gather_residual(ids2)))
    np.testing.assert_array_equal(np.asarray(win), kept[0])
    np.testing.assert_array_equal(np.asarray(res), kept[1])
    assert store._staging[np.dtype(np.float32)] is staging
    assert not np.shares_memory(np.asarray(win), staging)
    rows = rng.normal(size=(K, w)).astype(np.float32)
    cold = np.flatnonzero(store._rows.slot < 0)
    mixed = np.concatenate([ids[:2], cold[:K - 2]])    # hot and cold rows
    tracemalloc.start()
    try:
        jax.block_until_ready((store.gather(mixed),
                               store.gather_residual(mixed)))
        store.scatter(ids, rows)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert peak < rows.nbytes // 4


class _Recorder(contextlib.nullcontext):
    """``TraceAnnotation`` that keeps each span's name and args."""
    spans: list = []

    def __init__(self, name, **args):
        super().__init__(self)
        self.args = dict(args)
        _Recorder.spans.append((name, self.args))

    def set_metadata(self, **args):
        self.args.update(args)


def test_checkpoint_scatter_counts_new_rows(monkeypatch):
    """``fl.store.scatter``'s ``new_rows``: first-written ids take a slot
    each (a repeat within one scatter takes one), rewrites take none and
    grow no slab."""
    monkeypatch.setattr(store_mod, "SLAB_ROWS", 4)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(_Recorder, "spans", [])
    store = CheckpointStore(np.zeros((W,), np.float32), D)

    def scatter(ids):
        store.scatter(np.asarray(ids), np.ones((len(ids), W), np.float32))
        name, args = _Recorder.spans[-2]         # the span, then to_host
        assert name == "fl.store.scatter" and args["rows"] == len(ids)
        return args["new_rows"]

    assert scatter([4, 0, 31, 9, 4]) == 4
    assert len(store._rows._slabs) == 1
    assert scatter([9, 4, 1, 2, 3]) == 3          # 1, 2, 3 are new
    slabs = list(store._rows._slabs)
    assert len(slabs) == 2
    assert scatter([0, 1, 2, 3, 4, 9, 31]) == 0
    assert [id(s) for s in store._rows._slabs] == [id(s) for s in slabs]
    assert store.num_touched == 7


def test_checkpoint_concurrent_gathers_read_whole_rows():
    """Stress: more gathering threads than cores, on a short switch
    interval, against a thread writing every client once. Each row a
    gather returns is the base row or the whole row written for that id:
    a slot is published only after its row, and the staging buffer is
    never shared by two gathers at once."""
    d, w, k = 256, 1 << 16, 8
    store = CheckpointStore(np.full((w,), -1.0, np.float32), d)
    n_threads = min((os.cpu_count() or 1) + 2, 34)
    bad, done = [], threading.Event()

    def gatherer(seed):
        rng = np.random.default_rng(seed)
        while not done.is_set() and not bad:
            ids = rng.integers(0, d, size=k)
            win = np.asarray(store.gather(ids))
            lo, hi = win.min(axis=1), win.max(axis=1)
            ok = (lo == hi) & ((lo == -1) | (lo == ids + 1))
            if not ok.all():
                bad.append((ids[~ok], lo[~ok], hi[~ok]))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    threads = [threading.Thread(target=gatherer, args=(s,), daemon=True)
               for s in range(n_threads)]
    try:
        for t in threads:
            t.start()
        for c in np.random.default_rng(0).permutation(d).tolist():
            store.scatter(np.array([c]), np.full((1, w), c + 1, np.float32))
    finally:
        done.set()
        sys.setswitchinterval(old)
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert not bad, bad[:3]
    assert store.num_touched == d


def test_arena_publishes_a_slot_after_its_row():
    """A first-written client's slot is still -1 while its row is being
    copied in, so a concurrent gather reads the base row, never a slot
    whose row is not there yet."""
    arena = store_mod._RowArena(D, W, np.float32)
    seen = []

    class Row:
        def __init__(self, c):
            self.c = c

        def __array__(self, dtype=None, copy=None):
            seen.append(int(arena.slot[self.c]))
            return np.full((W,), self.c, np.float32)

    assert arena.write(np.array([5, 9]), [Row(5), Row(9)]) == 2
    assert seen == [-1, -1]
    np.testing.assert_array_equal(arena.row(int(arena.slot[9])), 9.0)


# ---- staleness ----------------------------------------------------------


def test_staleness_counters():
    store = MemoryStore(_flat())
    # never-touched clients are stale since before round 0
    np.testing.assert_array_equal(store.staleness(0), np.ones(D, np.int32))
    store.touch(np.array([1, 2]), 0)
    store.touch(np.array([2]), 3)
    s = store.staleness(4)
    assert s[1] == 4 and s[2] == 1 and s[0] == 5


# ---- make_store tiering -------------------------------------------------


def test_make_store_auto_tiers_by_footprint():
    small = make_store(jnp.zeros((W,), jnp.float32), D)
    assert isinstance(small, MemoryStore)
    big_d = MEMORY_TIER_MAX_BYTES // (W * 4) + 1
    big = make_store(jnp.zeros((W,), jnp.float32), big_d)
    assert isinstance(big, CheckpointStore)
    assert big.num_enrolled == big_d


def test_make_store_forced_tiers_and_errors():
    row = jnp.zeros((W,), jnp.float32)
    assert isinstance(make_store(row, D, tier="checkpoint"), CheckpointStore)
    assert isinstance(make_store(row, D, tier="memory"), MemoryStore)
    with pytest.raises(ValueError, match="unknown store tier"):
        make_store(row, D, tier="cold")
    with pytest.raises(ValueError, match="base_row"):
        make_store(jnp.zeros((2, W)), D)


def test_make_store_residual_counts_toward_footprint():
    # D*W*(4+4) just over the line only WITH the residual tier riding along
    d = MEMORY_TIER_MAX_BYTES // (W * 8) + 1
    assert isinstance(make_store(jnp.zeros((W,), jnp.float32), d),
                      MemoryStore)
    assert isinstance(
        make_store(jnp.zeros((W,), jnp.float32), d, residual=True),
        CheckpointStore)


# ---- prefetch-worker lifecycle (fault-tolerance satellite) --------------


def _poll(pred, timeout=5.0):
    import time
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_worker_error_collected_via_result_is_not_rethrown():
    st = CheckpointStore(np.zeros((W,), np.float32), D)

    def boom(ids):
        raise ValueError("fetch exploded")

    st.gather = boom
    h = st.prefetch(np.array([1], np.int32))
    with pytest.raises(ValueError, match="fetch exploded"):
        h.result()
    # collecting consumed the error: the store is healthy again
    del st.gather
    np.testing.assert_array_equal(
        np.asarray(st.prefetch(np.array([2], np.int32)).result()),
        np.zeros((1, W), np.float32))


def test_uncollected_worker_error_rethrows_on_next_use():
    """A prefetch whose handle is dropped must NOT lose its exception —
    the store re-raises it at the next submit instead of silently
    serving stale data forever."""
    st = CheckpointStore(np.zeros((W,), np.float32), D)

    def boom(ids):
        raise ValueError("lost in the worker")

    st.gather = boom
    st.prefetch(np.array([0], np.int32))          # handle dropped
    assert _poll(lambda: st._worker_error is not None)
    del st.gather
    with pytest.raises(RuntimeError, match="never collected"):
        st.prefetch(np.array([1], np.int32))
    # the rethrow drained it: the store recovers
    h = st.prefetch(np.array([1], np.int32))
    assert np.asarray(h.result()).shape == (1, W)


def test_close_is_idempotent_and_pool_restarts_lazily():
    from repro.protocols.store import _LIVE_FETCH_POOLS
    st = CheckpointStore(np.zeros((W,), np.float32), D)
    st.prefetch(np.array([0], np.int32)).result()
    pool = st._executor
    assert pool in _LIVE_FETCH_POOLS             # atexit shutdown covers it
    st.close()
    assert st._executor is None and pool not in _LIVE_FETCH_POOLS
    st.close()                                   # idempotent
    # a later prefetch lazily restarts the pool
    rows = st.prefetch(np.array([3], np.int32)).result()
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.zeros((1, W), np.float32))
    assert st._executor is not None
    st.close()
