"""Pytree checkpointing: flattened-leaf ``.npz`` + JSON treedef/metadata.

No orbax in this container; this is a dependency-free implementation with
atomic writes and step-based retention, sufficient for single-host drivers
(multi-host would swap in a sharded writer behind the same API).
"""
from __future__ import annotations

import json
import os
import tempfile
import zipfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint file is truncated or structurally corrupt.

    Raised instead of the raw ``zipfile``/``struct`` errors so callers can
    tell a PERMANENT failure (bad bytes on disk — retrying cannot help;
    ``CheckpointStore`` deliberately excludes this from its read-retry
    loop) from a transient one, and so the message names the offending
    path and row range instead of an opaque zip offset."""


def _key_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def save_checkpoint(ckpt_dir: str, step: int, tree: Any,
                    metadata: Optional[Dict] = None, keep: int = 3) -> str:
    if keep < 1:
        # _retain(keep<=0) deletes everything — including the checkpoint
        # this very call just wrote; refuse rather than self-destruct
        raise ValueError(f"save_checkpoint requires keep >= 1, got {keep}")
    os.makedirs(ckpt_dir, exist_ok=True)
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    arrays, dtypes = {}, []
    for i, (_, v) in enumerate(leaves_with_paths):
        a = np.asarray(v)
        dtypes.append(str(a.dtype))
        if a.dtype.kind == "V" or str(a.dtype) == "bfloat16":
            a = a.view(np.uint16)            # npz can't store ml_dtypes
        arrays[f"leaf_{i}"] = a
    names = [_key_str(p) for p, _ in leaves_with_paths]
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    meta = {"step": step, "names": names, "dtypes": dtypes,
            "metadata": metadata or {}}
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    with os.fdopen(fd, "wb") as f:
        np.savez(f, __meta__=json.dumps(meta), **arrays)
    os.replace(tmp, path)
    _retain(ckpt_dir, keep)
    return path


def _retain(ckpt_dir: str, keep: int) -> None:
    ckpts = sorted(f for f in os.listdir(ckpt_dir)
                   if f.startswith("step_") and f.endswith(".npz"))
    # keep <= 0 means retain nothing (ckpts[:-0] would be [] and keep all).
    # Deliberately stricter than save_checkpoint, which rejects keep < 1:
    # a purge is meaningful for a standalone cleanup call, but never as the
    # retention policy of the write that just happened.
    drop = ckpts if keep <= 0 else ckpts[:-keep]
    for old in drop:
        os.remove(os.path.join(ckpt_dir, old))


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(f[5:13]) for f in os.listdir(ckpt_dir)
             if f.startswith("step_") and f.endswith(".npz")]
    return max(steps) if steps else None


def _restore_dtype(a: np.ndarray, dt: Optional[str]) -> np.ndarray:
    """Undo the uint16 storage view for ml_dtypes leaves (save_checkpoint
    stores bf16 as uint16 because npz cannot hold ml_dtypes)."""
    if dt == "bfloat16":
        import ml_dtypes
        a = a.view(ml_dtypes.bfloat16)
    return a


def load_leaves(path: str, indices: Sequence[int], *,
                out: Optional[Sequence[np.ndarray]] = None,
                at: Optional[np.ndarray] = None
                ) -> Tuple[List[np.ndarray], Dict]:
    """Partial-row reads: fetch only the given leading-axis rows of every
    leaf in one checkpoint file, without materializing the full arrays.

    ``np.savez`` writes *stored* (uncompressed) zip members, so each
    ``leaf_i.npy`` member is seekable: we parse its npy header, then seek
    straight to the byte range of each requested row. This is the cold-tier
    I/O path of ``protocols.store.CheckpointStore`` — a K=1024 gather out
    of a D=10^6-row state file reads K rows, not D.

    Returns ``(leaves, meta)`` where ``leaves[i]`` has shape
    ``[len(indices), *trailing_i]`` with the checkpointed dtype restored
    (bf16 leaves come back as bf16, not their uint16 storage view).

    ``out`` fills caller-owned arrays instead of fresh ones: ``out[i]``
    (for the first ``len(out)`` leaves) receives requested row ``j`` at
    ``out[i][at[j]]`` (``at`` defaults to ``0..len(indices)-1``) and is
    returned as ``leaves[i]``; its trailing shape and dtype must be the
    leaf's, and it must be C-contiguous.
    """
    idx = np.asarray(indices, dtype=np.int64)
    if idx.ndim != 1:
        raise ValueError(f"load_leaves: indices must be 1-D, got shape "
                         f"{idx.shape}")
    fresh = np.arange(idx.size)
    at = fresh if at is None else np.asarray(at, dtype=np.int64)
    if at.shape != idx.shape:
        raise ValueError(f"load_leaves: at has shape {at.shape}, indices "
                         f"{idx.shape}")
    out = list(out or ())
    try:
        zf_ctx = zipfile.ZipFile(path)
    except zipfile.BadZipFile as e:
        raise CheckpointCorruptionError(
            f"checkpoint {path!r} is corrupt or truncated: {e}") from e
    with zf_ctx as zf:
        try:
            with zf.open("__meta__.npy") as fh:
                meta = json.loads(str(np.lib.format.read_array(
                    fh, allow_pickle=False)))
        except (KeyError, zipfile.BadZipFile, ValueError) as e:
            raise CheckpointCorruptionError(
                f"checkpoint {path!r} is corrupt: cannot read its "
                f"__meta__ record ({e})") from e
        dtypes = meta.get("dtypes", [None] * len(meta["names"]))
        leaves: List[np.ndarray] = []
        for i, dt in enumerate(dtypes):
            member = f"leaf_{i}.npy"
            info = zf.getinfo(member)
            if info.compress_type != zipfile.ZIP_STORED:
                # compressed members are not seekable in O(1); fall back to
                # a full read of this leaf only
                with zf.open(member) as fh:
                    full = np.lib.format.read_array(fh, allow_pickle=False)
                rows = _restore_dtype(full[idx], dt)
                if i < len(out):
                    _check_out(out[i], rows.shape[1:], rows.dtype, i)
                    out[i][at] = rows
                    rows = out[i]
                leaves.append(rows)
                continue
            with zf.open(member) as fh:
                version = np.lib.format.read_magic(fh)
                readers = {(1, 0): np.lib.format.read_array_header_1_0,
                           (2, 0): np.lib.format.read_array_header_2_0}
                if version not in readers:
                    raise ValueError(
                        f"load_leaves: leaf {i} in {path!r} uses npy format "
                        f"{version}; expected 1.0 or 2.0")
                shape, fortran, dtype = readers[version](fh)
                if fortran:
                    raise ValueError(
                        f"load_leaves: leaf {i} in {path!r} is "
                        "Fortran-ordered; partial-row reads need C order")
                if not shape:
                    raise ValueError(
                        f"load_leaves: leaf {i} in {path!r} is a scalar — "
                        "no leading row axis to index")
                data_start = fh.tell()
                row_shape = shape[1:]
                row_bytes = int(np.prod(row_shape, dtype=np.int64)
                                ) * dtype.itemsize
                bad = idx[(idx < 0) | (idx >= shape[0])]
                if bad.size:
                    raise IndexError(
                        f"load_leaves: indices {bad[:4].tolist()} out of "
                        f"range for leaf {i} with {shape[0]} rows")
                if i < len(out):
                    dst, pos = out[i], at
                    _check_out(dst, row_shape,
                               _restore_dtype(np.empty(0, dtype), dt).dtype, i)
                else:
                    dst, pos = _restore_dtype(
                        np.empty((idx.size,) + row_shape, dtype), dt), fresh
                flat = dst.reshape(dst.shape[0], -1).view(dtype)
                for j, r in zip(pos.tolist(), idx.tolist()):
                    fh.seek(data_start + r * row_bytes)
                    buf = fh.read(row_bytes)
                    if len(buf) != row_bytes:
                        raise CheckpointCorruptionError(
                            f"checkpoint {path!r} is truncated: leaf {i} "
                            f"row {r} (requested rows "
                            f"{int(idx.min())}..{int(idx.max())} of "
                            f"{shape[0]}) yielded {len(buf)} of "
                            f"{row_bytes} bytes")
                    flat[j] = np.frombuffer(buf, dtype)
                leaves.append(dst)
    return leaves, meta


def _check_out(dst: np.ndarray, row_shape, dtype, leaf: int) -> None:
    if (dst.shape[1:] != tuple(row_shape) or dst.dtype != dtype
            or not dst.flags.c_contiguous):
        raise ValueError(
            f"load_leaves: out[{leaf}] must be C-contiguous rows of "
            f"{tuple(row_shape)} {np.dtype(dtype)}, got {dst.shape[1:]} "
            f"{dst.dtype}")


def load_checkpoint(ckpt_dir: str, tree_like: Any,
                    step: Optional[int] = None) -> Tuple[Any, Dict]:
    """Restore into the structure of ``tree_like`` (shapes must match)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        leaves = []
        for i, dt in enumerate(meta.get("dtypes",
                                        [None] * len(meta["names"]))):
            a = z[f"leaf_{i}"]
            if dt == "bfloat16":
                import ml_dtypes
                a = a.view(ml_dtypes.bfloat16)
            leaves.append(a)
    ref_leaves, treedef = jax.tree_util.tree_flatten(tree_like)
    if len(ref_leaves) != len(leaves):
        raise ValueError(
            f"checkpoint/model structure mismatch: {path} holds "
            f"{len(leaves)} leaves, tree_like expects {len(ref_leaves)}")
    out = treedef.unflatten([np.asarray(leaf) for leaf in leaves])
    return out, meta
