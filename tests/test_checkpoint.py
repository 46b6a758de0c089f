"""checkpoint/io: save/load round-trip, structure-mismatch errors (a real
exception, not a strippable assert), and step retention edge cases."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import (
    _retain, latest_step, load_checkpoint, load_leaves, save_checkpoint,
)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(3,)).astype(np.float32)
                             ).astype(jnp.bfloat16)}


def test_save_load_roundtrip(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 7, tree, metadata={"lr": 0.1})
    out, meta = load_checkpoint(str(tmp_path), tree)
    assert meta["step"] == 7 and meta["metadata"] == {"lr": 0.1}
    for key in tree:
        np.testing.assert_array_equal(np.asarray(out[key], np.float32),
                                      np.asarray(tree[key], np.float32))


def test_load_structure_mismatch_raises_value_error(tmp_path):
    """A bare assert would vanish under ``python -O``; must be ValueError."""
    save_checkpoint(str(tmp_path), 1, _tree())
    with pytest.raises(ValueError, match="structure mismatch"):
        load_checkpoint(str(tmp_path), {"only_one_leaf": jnp.zeros((2,))})


def test_retention_keeps_newest(tmp_path):
    for step in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), step, _tree(), keep=2)
    assert latest_step(str(tmp_path)) == 4
    with pytest.raises(FileNotFoundError):
        load_checkpoint(str(tmp_path), _tree(), step=1)
    load_checkpoint(str(tmp_path), _tree(), step=3)


@pytest.mark.parametrize("keep", [0, -1])
def test_retention_keep_nonpositive_keeps_nothing(tmp_path, keep):
    """keep=0 must retain NOTHING (ckpts[:-0] is [] and used to keep all)."""
    save_checkpoint(str(tmp_path), 1, _tree())
    save_checkpoint(str(tmp_path), 2, _tree())
    _retain(str(tmp_path), keep)
    assert latest_step(str(tmp_path)) is None


def test_save_checkpoint_rejects_nonpositive_keep(tmp_path):
    """save_checkpoint(keep=0) would delete its own freshly-written file."""
    with pytest.raises(ValueError, match="keep >= 1"):
        save_checkpoint(str(tmp_path), 1, _tree(), keep=0)
    assert latest_step(str(tmp_path)) is None


# ---- load_leaves: partial-row reads (the CheckpointStore cold-tier I/O) --


def _rowy_tree(rows=16, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(rows, 5)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(rows,)).astype(np.float32)
                         ).astype(jnp.bfloat16),
    }


def test_load_leaves_matches_full_load(tmp_path):
    tree = _rowy_tree()
    path = save_checkpoint(str(tmp_path), 1, tree)
    idx = [3, 0, 11, 3]                       # out of order + repeated
    leaves, meta = load_leaves(path, idx)
    full, _ = load_checkpoint(str(tmp_path), tree)
    # leaves come back in tree_flatten order (sorted keys: b, w)
    np.testing.assert_array_equal(np.asarray(leaves[0], np.float32),
                                  np.asarray(full["b"], np.float32)[idx])
    np.testing.assert_array_equal(leaves[1], np.asarray(full["w"])[idx])
    assert meta["step"] == 1


def test_load_leaves_restores_bf16_dtype(tmp_path):
    """bf16 leaves are stored as uint16 views; partial reads must hand back
    bf16 (bit-identical to the saved rows), not the storage view."""
    import ml_dtypes
    tree = _rowy_tree()
    path = save_checkpoint(str(tmp_path), 2, tree)
    leaves, _ = load_leaves(path, np.arange(16))
    assert leaves[0].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(
        leaves[0].view(np.uint16),
        np.asarray(tree["b"]).view(np.uint16))


def test_load_leaves_fills_caller_rows(tmp_path):
    """``out``/``at``: requested rows land at the given positions of the
    caller's arrays (bf16 included), other rows stay as they were; a
    destination of the wrong row shape or dtype is refused."""
    tree = _rowy_tree()
    path = save_checkpoint(str(tmp_path), 5, tree)
    idx, at = [3, 0, 11], [4, 1, 2]
    b = np.full((6,), 7.0, np.float32).astype(tree["b"].dtype)
    w = np.full((6, 5), 7.0, np.float32)
    leaves, _ = load_leaves(path, idx, out=[b, w], at=at)
    assert leaves[0] is b and leaves[1] is w
    want_b = np.asarray(tree["b"], np.float32)
    want_w = np.asarray(tree["w"])
    np.testing.assert_array_equal(np.asarray(b, np.float32)[at], want_b[idx])
    np.testing.assert_array_equal(w[at], want_w[idx])
    np.testing.assert_array_equal(w[[0, 3, 5]], 7.0)
    # only the first leaf has a destination: the second comes back fresh
    leaves, _ = load_leaves(path, idx, out=[b], at=at)
    np.testing.assert_array_equal(leaves[1], want_w[idx])
    with pytest.raises(ValueError, match="C-contiguous rows"):
        load_leaves(path, idx, out=[b, np.zeros((6, 4), np.float32)], at=at)
    with pytest.raises(ValueError, match="at has shape"):
        load_leaves(path, idx, out=[b], at=at[:2])


def test_load_leaves_out_of_range_raises(tmp_path):
    path = save_checkpoint(str(tmp_path), 3, _rowy_tree())
    with pytest.raises(IndexError, match="out of range"):
        load_leaves(path, [0, 16])


def test_load_leaves_requires_1d_indices(tmp_path):
    path = save_checkpoint(str(tmp_path), 4, _rowy_tree())
    with pytest.raises(ValueError, match="1-D"):
        load_leaves(path, [[0, 1]])


# ---- corruption surfaces (fault-tolerance satellite) --------------------


def _truncated_leaf_npz(tmp_path, cut=8):
    """A hand-built STORED npz whose leaf_0 member is ``cut`` bytes short
    of its npy header's promise — a mid-write crash or bad sector."""
    import io
    import json
    import zipfile

    arr = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    buf = io.BytesIO()
    np.lib.format.write_array(buf, arr)
    meta = {"step": 0, "names": ["state"], "dtypes": ["float32"],
            "metadata": {}}
    mbuf = io.BytesIO()
    np.lib.format.write_array(mbuf, np.array(json.dumps(meta)))
    path = str(tmp_path / "step_00000000.npz")
    with zipfile.ZipFile(path, "w", zipfile.ZIP_STORED) as zf:
        zf.writestr("__meta__.npy", mbuf.getvalue())
        zf.writestr("leaf_0.npy", buf.getvalue()[:-cut])
    return path


def test_load_leaves_truncated_file_names_path(tmp_path):
    from repro.checkpoint import CheckpointCorruptionError
    path = save_checkpoint(str(tmp_path), 0, _tree())
    with open(path, "r+b") as fh:
        fh.truncate(100)                       # destroy the zip directory
    with pytest.raises(CheckpointCorruptionError,
                       match="corrupt or truncated") as ei:
        load_leaves(path, [0])
    assert path in str(ei.value)


def test_load_leaves_truncated_leaf_names_row_range(tmp_path):
    from repro.checkpoint import CheckpointCorruptionError
    path = _truncated_leaf_npz(tmp_path)
    # early rows are intact — partial reads before the damage still work
    leaves, _ = load_leaves(path, [0, 3])
    np.testing.assert_array_equal(leaves[0][1], np.arange(12, 16))
    with pytest.raises(CheckpointCorruptionError) as ei:
        load_leaves(path, [2, 15])
    msg = str(ei.value)
    assert path in msg and "truncated" in msg
    assert "row 15" in msg and "2..15" in msg  # offending row + range


def test_corruption_is_not_retried(tmp_path):
    """Retry-with-backoff is for TRANSIENT errors; corrupt bytes re-read
    as the same corrupt bytes, so the store must raise immediately."""
    from repro.checkpoint import CheckpointCorruptionError
    from repro.protocols import CheckpointStore
    st = CheckpointStore(_truncated_leaf_npz(tmp_path), 16,
                         read_retries=5, read_backoff=10.0)
    with pytest.raises(CheckpointCorruptionError):
        st.gather(np.array([15], np.int32))
    assert st.read_retry_count == 0            # no backoff sleeps burned
