"""ClientStateStore — the persistent [D, sum(sizes)] client state behind
sampled participation.

The resident engines (``DenseEngine``/``MeshEngine``) hold the WHOLE
federated state as the scan carry: every enrolled client is a live row of
the compiled program, so D is capped by device memory and every round pays
O(D) compute even when only K << D clients train. This module inverts that:
client state lives in a host-owned store, and each round the
``SampledEngine`` gathers a K-row *active window*, runs the compiled
window round on [K, sum(sizes)] only, and scatters the mixed rows back.
Enrollment D then only prices storage — the compiled per-round program is
D-independent (the ``state-residency`` analysis rule pins this).

Tiers (``make_store`` picks by footprint):

* ``MemoryStore``     — one packed [D, sum(sizes)] device buffer;
                        gather/scatter are the ``kernels.ops``
                        ``gather_rows``/``scatter_rows`` seam. Optionally
                        sharded over the mesh data axes (multi-host
                        placement is ROADMAP item 5).
* ``CheckpointStore`` — cold tier for D where [D, sum(sizes)] can never
                        materialize (D=10^6 x a 2M-param model is ~8 TB):
                        untouched clients implicitly hold a single shared
                        ``base_row`` (or a row of an on-disk npz checkpoint
                        read via ``checkpoint.io.load_leaves`` partial-row
                        reads), and only rows a round actually touched are
                        held, in a host slab arena (``_RowArena``). Memory
                        scales with the touched rows, not D; gathers stage
                        through one reused host window.

Both tiers carry per-client error-feedback/codec residuals (same
gather/scatter window discipline, f32, zeros for untouched clients) and
round-staleness counters (``last_round``/``staleness``) — the bookkeeping
async/debiasing extensions need lives with the state, not the engine.
"""
from __future__ import annotations

import atexit
import os
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Dict, List, Optional, Tuple


import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import (
    CheckpointCorruptionError, load_leaves, save_checkpoint,
)
from repro.kernels import ops as kernel_ops

#: footprint (bytes of [D, sum(sizes)] at f32) above which ``make_store``
#: refuses to materialize a resident buffer and drops to the cold tier
MEMORY_TIER_MAX_BYTES = 2 ** 31

#: rows per slab of a ``_RowArena``: the unit its host memory grows by
SLAB_ROWS = 64

#: every live prefetch pool, so interpreter exit can never hang on a
#: forgotten non-daemon fetch thread (the lifecycle bug this replaces:
#: a lazily-created ThreadPoolExecutor nobody ever shut down). WeakSet —
#: registration must not keep collected stores' pools alive.
_LIVE_FETCH_POOLS: "weakref.WeakSet[ThreadPoolExecutor]" = weakref.WeakSet()


@atexit.register
def _shutdown_fetch_pools() -> None:
    for pool in list(_LIVE_FETCH_POOLS):
        pool.shutdown(wait=False, cancel_futures=True)


class PrefetchHandle:
    """An in-flight window read issued by ``ClientStateStore.prefetch``.
    ``result(timeout=)`` blocks until the [K, width] rows are available
    and returns them (``TimeoutError`` if the fetch is stuck past the
    timeout; a worker-side exception re-raises here); calling it twice
    returns the same rows. ``wait()`` is the historical no-timeout alias."""

    def result(self, timeout: Optional[float] = None) -> jnp.ndarray:
        raise NotImplementedError

    def wait(self) -> jnp.ndarray:
        return self.result()


class _ReadyPrefetch(PrefetchHandle):
    """Eager tier: the gather was already dispatched (device work is
    async under JAX's dispatch model, so 'eager' still overlaps)."""

    def __init__(self, rows):
        self._rows = rows

    def result(self, timeout: Optional[float] = None):
        return self._rows


class _ThreadPrefetch(PrefetchHandle):
    """Cold tier: the gather runs on a background fetch thread so
    ``load_leaves`` partial-row file reads overlap the compiled window.
    A worker-side exception re-raises out of ``result()`` — and is marked
    CONSUMED on the owning store, so the store's rethrow-on-next-use
    safety net (for callers that never collect the handle) does not
    raise the same failure twice."""

    def __init__(self, future, owner=None):
        self._future = future
        self._owner = owner

    def result(self, timeout: Optional[float] = None):
        try:
            return self._future.result(timeout)
        except (_FutureTimeout, TimeoutError):
            raise
        except BaseException as e:
            if self._owner is not None:
                self._owner._consume_worker_error(e)
            raise


class ClientStateStore:
    """Base contract: [D, width] persistent per-client rows + residuals +
    staleness. ``gather``/``scatter`` move [K, width] windows; ids are
    concrete host arrays (selection runs OUTSIDE the compiled window
    program — that is the whole point)."""

    #: optional ``repro.faults.FaultInjector`` (fault-injection harness);
    #: tiers with real failure surfaces (file reads, fetch threads) call
    #: its hooks. None = no injection — the default on every tier.
    fault_injector = None
    #: cumulative count of retried store reads (checkpoint tier only;
    #: resident tiers never retry — the buffer is device memory)
    read_retry_count = 0

    def __init__(self, num_enrolled: int, width: int):
        if num_enrolled <= 0:
            raise ValueError(f"ClientStateStore: num_enrolled must be "
                             f"positive, got {num_enrolled}")
        self.num_enrolled = int(num_enrolled)
        self.width = int(width)
        #: [D] round index each client last trained in; -1 = never touched
        self.last_round = np.full((self.num_enrolled,), -1, np.int32)

    def close(self) -> None:
        """Release background resources (fetch threads). No-op on tiers
        without any; safe to call twice."""

    # -- window movement ------------------------------------------------
    def gather(self, ids) -> jnp.ndarray:
        """[K, width] rows for the active ids."""
        raise NotImplementedError

    def scatter(self, ids, rows) -> None:
        """Write the mixed [K, width] window back at the active ids."""
        raise NotImplementedError

    # -- async prefetch (the pipelined engine's stage-A seam) -----------
    def prefetch(self, ids) -> PrefetchHandle:
        """Start fetching the [K, width] window for ``ids``; returns a
        handle whose ``wait()`` yields the rows. The base implementation
        dispatches the gather eagerly — correct for every tier, and
        already overlapping for device-backed tiers (JAX async dispatch).
        Tiers whose gather blocks the host (file reads) override this
        with a background thread."""
        return _ReadyPrefetch(self.gather(ids))

    def prefetch_residual(self, ids) -> PrefetchHandle:
        """``prefetch`` for the codec residual tier."""
        return _ReadyPrefetch(self.gather_residual(ids))

    # -- readout contract -----------------------------------------------
    def resident_flat(self) -> Optional[jnp.ndarray]:
        """The live [D, width] buffer if this tier keeps one resident,
        else ``None`` — callers dispatch on this instead of duck-typing
        (``global_params`` reads rows directly when a buffer exists and
        falls back to ``consensus()`` otherwise)."""
        return None

    def consensus(self) -> np.ndarray:
        """[width] mean over all enrolled rows (the global-model
        readout). Every tier must provide this, resident or not."""
        raise NotImplementedError

    # -- per-client codec residuals ------------------------------------
    def gather_residual(self, ids) -> jnp.ndarray:
        """[K, width] f32 error-feedback residuals (zeros for clients the
        wire never touched)."""
        raise NotImplementedError

    def scatter_residual(self, ids, rows) -> None:
        raise NotImplementedError

    # -- staleness bookkeeping -----------------------------------------
    def _check_ids(self, ids) -> np.ndarray:
        ids = np.asarray(ids)
        if ids.ndim != 1:
            raise ValueError(f"store ids must be 1-D, got shape {ids.shape}")
        bad = ids[(ids < 0) | (ids >= self.num_enrolled)]
        if bad.size:
            raise IndexError(
                f"store ids {bad[:4].tolist()} out of range for "
                f"num_enrolled={self.num_enrolled}")
        return ids

    def touch(self, ids, round_index: int) -> None:
        """Mark the active ids as trained in ``round_index``."""
        self.last_round[self._check_ids(ids)] = int(round_index)

    def staleness(self, round_index: int) -> np.ndarray:
        """[D] rounds since each client last trained (never-touched clients
        read ``round_index + 1`` — stale since before round 0)."""
        return np.asarray(int(round_index) - self.last_round, np.int32)


class MemoryStore(ClientStateStore):
    """Resident tier: the full [D, width] packed state as ONE device
    buffer, windowed through the shared ``gather_rows``/``scatter_rows``
    seam. ``mesh_info`` shards the row axis over the data mesh axes."""

    def __init__(self, flat: jnp.ndarray, *, mesh_info=None,
                 residual: bool = False):
        if getattr(flat, "ndim", 0) != 2:
            raise ValueError(
                f"MemoryStore: expected a packed [D, sum(sizes)] buffer, "
                f"got shape {getattr(flat, 'shape', ())}")
        super().__init__(flat.shape[0], flat.shape[1])
        self._sharding = None
        if mesh_info is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            ax = (mesh_info.dp_axes if len(mesh_info.dp_axes) > 1
                  else mesh_info.dp_axes[0])
            self._sharding = NamedSharding(mesh_info.mesh, P(ax, None))
            flat = jax.device_put(flat, self._sharding)
        flat = jnp.asarray(flat)
        self._flat = flat
        self._residual = (jnp.zeros(flat.shape, jnp.float32)
                          if residual else None)
        #: accelerator-resident buffers take the jitted
        #: ``gather_rows_dev``/``scatter_rows_dev`` fast path: windows
        #: move device↔device with the state buffer donated through the
        #: scatter — no host round-trip at all
        self._device_resident = all(d.platform != "cpu"
                                    for d in flat.devices())

    @property
    def flat(self) -> jnp.ndarray:
        """The live [D, width] buffer (resident tier only)."""
        return self._flat

    def resident_flat(self) -> jnp.ndarray:
        return self._flat

    def gather(self, ids) -> jnp.ndarray:
        ids = self._check_ids(ids)
        with jax.profiler.TraceAnnotation("fl.store.gather", rows=int(ids.size)):
            ids = jnp.asarray(ids)
            if self._device_resident:
                return kernel_ops.gather_rows_dev(self._flat, ids)
            return kernel_ops.gather_rows(self._flat, ids)

    def scatter(self, ids, rows) -> None:
        # ``rows`` arrives as whatever the engine produced (usually the
        # still-device-resident window output); jnp.asarray is zero-copy
        # for device arrays — the ONE conversion happens here, at the seam
        ids = self._check_ids(ids)
        with jax.profiler.TraceAnnotation("fl.store.scatter", rows=int(ids.size)):
            ids = jnp.asarray(ids)
            if self._device_resident:
                self._flat = kernel_ops.scatter_rows_dev(
                    self._flat, ids, jnp.asarray(rows))
            else:
                self._flat = kernel_ops.scatter_rows(self._flat, ids,
                                                     jnp.asarray(rows))

    def gather_residual(self, ids) -> jnp.ndarray:
        if self._residual is None:
            raise ValueError("MemoryStore was built without residual=True; "
                             "no codec residual tier to gather")
        return kernel_ops.gather_rows(self._residual,
                                      jnp.asarray(self._check_ids(ids)))

    def scatter_residual(self, ids, rows) -> None:
        if self._residual is None:
            raise ValueError("MemoryStore was built without residual=True; "
                             "no codec residual tier to scatter")
        self._residual = kernel_ops.scatter_rows(
            self._residual, jnp.asarray(self._check_ids(ids)),
            jnp.asarray(rows, jnp.float32))

    def consensus(self) -> np.ndarray:
        """[width] mean over all enrolled rows (the global-model readout)."""
        return np.asarray(jnp.mean(self._flat.astype(jnp.float32), axis=0))


class _RowArena:
    """The written rows of a [D, width] host table, in slabs of
    ``SLAB_ROWS`` rows allocated as clients are first written and reused
    ever after. ``slot[c]`` is client c's row in the arena, -1 while c
    was never written: host memory grows with written rows (plus the [D]
    int64 index), never with D x width.

    One thread writes (the store's scatters); gathers may read from the
    fetch thread meanwhile. A new slot is published in ``slot`` only after
    its row is written, so a reader sees either -1 or a whole first row;
    an overwrite of a published row may be read torn (numpy copies without
    the GIL) — see ``CheckpointStore.prefetch``."""

    def __init__(self, num_rows: int, width: int, dtype):
        self.slot = np.full((num_rows,), -1, np.int64)
        self.width = int(width)
        self.dtype = np.dtype(dtype)
        self._slabs: List[np.ndarray] = []
        self._count = 0

    def __len__(self) -> int:
        return self._count

    def row(self, s: int) -> np.ndarray:
        return self._slabs[s // SLAB_ROWS][s % SLAB_ROWS]

    def read_into(self, out: np.ndarray, slots: np.ndarray) -> None:
        """``out[i] = row(slots[i])`` wherever ``slots[i] >= 0``; rows of
        never-written clients are left as they are."""
        for i in np.flatnonzero(slots >= 0).tolist():
            out[i] = self.row(int(slots[i]))

    def write(self, ids: np.ndarray, rows: np.ndarray) -> int:
        """Write ``rows[i]`` as client ``ids[i]``'s row, in order (a
        repeated id keeps its last row). Returns how many ids took a new
        slot."""
        new = 0
        for c, row in zip(ids.tolist(), rows):
            s = int(self.slot[c])
            if s >= 0:
                self.row(s)[...] = row
                continue
            s = self._count
            if s // SLAB_ROWS == len(self._slabs):
                self._slabs.append(
                    np.empty((SLAB_ROWS, self.width), self.dtype))
            self.row(s)[...] = row
            self._count += 1
            self.slot[c] = s                 # published after the write
            new += 1
        return new

    def written(self) -> Tuple[np.ndarray, np.ndarray]:
        """(ids, slots) of every written client, in first-write order."""
        ids = np.flatnonzero(self.slot >= 0)
        slots = self.slot[ids]
        order = np.argsort(slots)
        return ids[order], slots[order]


class CheckpointStore(ClientStateStore):
    """Cold tier: untouched clients hold a shared base row implicitly;
    touched rows live in a host slab arena. ``base`` is either a [width]
    row (fresh enrollment: every client starts at the global init) or a
    path to an npz checkpoint holding one [D, width] leaf, whose rows are
    fetched on demand with ``checkpoint.io.load_leaves`` partial-row reads
    — a K-row gather out of a D=10^6-row file reads K rows, not D.

    A gather stacks its window in a store-owned [K, width] host staging
    buffer, allocated at the first gather of K rows and reused by every
    later one (its pages stay mapped and warm), and ships a copy of it to
    the device before the buffer can be written again."""

    def __init__(self, base, num_enrolled: int, *, width: Optional[int] = None,
                 dtype=jnp.float32, read_retries: int = 0,
                 read_backoff: float = 0.0):
        if isinstance(base, (str, os.PathLike)):
            self._base_path: Optional[str] = os.fspath(base)
            self._base_row: Optional[np.ndarray] = None
            if width is None:
                probe, _ = load_leaves(self._base_path, np.array([0]))
                width = probe[0].shape[-1]
                dtype = probe[0].dtype
        else:
            row = np.asarray(base)
            if row.ndim != 1:
                raise ValueError(
                    f"CheckpointStore: base must be a [sum(sizes)] row or an "
                    f"npz path, got shape {row.shape}")
            self._base_path = None
            self._base_row = row
            width, dtype = row.shape[0], row.dtype
        super().__init__(num_enrolled, width)
        self.dtype = np.dtype(dtype)
        #: touched rows only, state and codec residual
        self._rows = _RowArena(self.num_enrolled, self.width, self.dtype)
        self._residual_rows = _RowArena(self.num_enrolled, self.width,
                                        np.float32)
        #: {dtype -> [rows, width] staging buffer} of the state and the
        #: residual gathers; the lock keeps the fetch thread and the
        #: caller's thread (a synchronous fallback, a readout) apart
        self._staging: Dict[np.dtype, np.ndarray] = {}
        self._staging_lock = threading.Lock()
        #: lazily-started background fetch thread for prefetch(): the
        #: ``load_leaves`` partial-row file reads block the host, so they
        #: run off-thread to overlap the compiled window. One worker —
        #: prefetches are issued one round ahead and must stay ordered.
        self._executor: Optional[ThreadPoolExecutor] = None
        #: transient-read resilience: a failed base read is retried up to
        #: ``read_retries`` times with exponential backoff (base seconds
        #: ``read_backoff``); ``CheckpointCorruptionError`` is permanent
        #: and never retried. ``read_retry_count`` accumulates across the
        #: store's lifetime (engines snapshot per-round deltas).
        self.read_retries = int(read_retries)
        self.read_backoff = float(read_backoff)
        self.read_retry_count = 0
        #: a fetch-worker exception nobody collected via ``result()``:
        #: recorded by the future's done-callback and re-raised at the
        #: store's NEXT use instead of being silently lost
        self._worker_error: Optional[BaseException] = None
        self._error_lock = threading.Lock()

    def _fetch_pool(self) -> ThreadPoolExecutor:
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="store-prefetch")
            _LIVE_FETCH_POOLS.add(self._executor)
        return self._executor

    def close(self) -> None:
        """Shut down the background fetch pool (queued fetches are
        cancelled, a running one completes). Idempotent; a later
        ``prefetch`` lazily restarts the pool. Also registered via
        ``atexit`` so a forgotten store cannot hang interpreter exit."""
        if self._executor is not None:
            self._executor.shutdown(wait=True, cancel_futures=True)
            _LIVE_FETCH_POOLS.discard(self._executor)
            self._executor = None

    # -- worker-error bookkeeping (rethrow-on-next-use) ----------------
    def _on_fetch_done(self, future) -> None:
        if future.cancelled():
            return
        exc = future.exception()
        if exc is not None:
            with self._error_lock:
                if self._worker_error is None:
                    self._worker_error = exc

    def _consume_worker_error(self, exc: BaseException) -> None:
        with self._error_lock:
            if self._worker_error is exc:
                self._worker_error = None

    def _raise_pending_worker_error(self) -> None:
        with self._error_lock:
            exc, self._worker_error = self._worker_error, None
        if exc is not None:
            raise RuntimeError(
                "CheckpointStore: a previous prefetch worker died and its "
                "error was never collected (call PrefetchHandle.result())"
            ) from exc

    def _submit_fetch(self, fn, ids) -> PrefetchHandle:
        self._raise_pending_worker_error()
        future = self._fetch_pool().submit(self._fetch_job, fn, ids)
        future.add_done_callback(self._on_fetch_done)
        return _ThreadPrefetch(future, self)

    def _fetch_job(self, fn, ids):
        """Runs ON the fetch worker: fault hooks first (an injected delay
        or worker death lands here), then the id materialization + gather."""
        if self.fault_injector is not None:
            self.fault_injector.on_prefetch()
        if isinstance(ids, jax.Array):
            ids = np.asarray(ids)
        return fn(ids)

    def prefetch(self, ids) -> PrefetchHandle:
        """Background-thread gather, racing the caller's ``scatter``s.
        The gather reads each id's slot once; a first-written client's
        slot appears only after its row is whole, so the read gets the
        base row or that whole row. An overwrite of an existing row copies
        without the GIL, so a racing gather may read that row torn — but
        only rows of a round still in flight when the prefetch was issued
        can be written meanwhile, and the pipelined engine's
        ``_acquire_window`` rewrites every such row from its in-flight
        output (``shadow``/``pending``) before use.

        ``ids`` may be a still-computing DEVICE array (e.g. the jitted
        selection's output): the host materialization then happens on the
        fetch thread too, so an O(D) selection never blocks the caller —
        the whole id->rows chain overlaps the compiled window."""
        if not isinstance(ids, jax.Array):
            ids = self._check_ids(ids)
        return self._submit_fetch(self.gather, ids)

    def prefetch_residual(self, ids) -> PrefetchHandle:
        if not isinstance(ids, jax.Array):
            ids = self._check_ids(ids)
        return self._submit_fetch(self.gather_residual, ids)

    @property
    def num_touched(self) -> int:
        return len(self._rows)

    def touched_rows(self, residual: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """(ids, rows): every client the state tier (``residual``: the
        codec residual tier) holds a written row for, in first-write
        order, and a copy of those [n, width] rows."""
        arena = self._residual_rows if residual else self._rows
        ids, slots = arena.written()
        rows = np.empty((ids.size, self.width), arena.dtype)
        arena.read_into(rows, slots)
        return ids, rows

    def _stage(self, dtype, n: int) -> np.ndarray:
        """The [n, width] prefix of the staging buffer for ``dtype``,
        grown (never shrunk) to the largest window asked for. Call under
        ``_staging_lock``."""
        buf = self._staging.get(np.dtype(dtype))
        if buf is None or buf.shape[0] < n:
            buf = np.empty((n, self.width), dtype)
            self._staging[buf.dtype] = buf
        return buf[:n]

    @staticmethod
    def _ship(win: np.ndarray) -> jnp.ndarray:
        """A device copy of a staged window that shares no memory with it
        (the window program donates its input), complete on return so
        the caller may reuse the buffer. The CPU backend wraps a suitably
        aligned host array in place whatever ``may_alias`` says; such a
        window is copied once more, device to device, where a jax.Array
        input does honour it."""
        out = jax.device_put(win, may_alias=False)
        if (next(iter(out.devices())).platform == "cpu"
                and out.unsafe_buffer_pointer() == win.ctypes.data):
            out = jax.device_put(out, may_alias=False)
        out.block_until_ready()
        return out

    def _base_rows(self, ids: np.ndarray, out: np.ndarray,
                   at: np.ndarray) -> None:
        """One base read into ``out[at]``, retried: transient ``OSError``s
        (a flaky disk, an injected fault) are retried up to
        ``read_retries`` times with exponential backoff;
        ``CheckpointCorruptionError`` is permanent (bad bytes — a retry
        re-reads the same bytes) and raises through immediately."""
        attempt = 0
        while True:
            try:
                return self._base_rows_once(ids, out, at)
            except CheckpointCorruptionError:
                raise
            except OSError:
                if attempt >= self.read_retries:
                    raise
                if self.read_backoff > 0.0:
                    time.sleep(self.read_backoff * (2 ** attempt))
                attempt += 1
                self.read_retry_count += 1

    def _base_rows_once(self, ids: np.ndarray, out: np.ndarray,
                        at: np.ndarray) -> None:
        if self.fault_injector is not None:
            self.fault_injector.on_read()
        if self._base_row is not None:
            out[at] = self._base_row
        else:
            load_leaves(self._base_path, ids, out=[out], at=at)

    def gather(self, ids) -> jnp.ndarray:
        ids = self._check_ids(ids)
        with jax.profiler.TraceAnnotation("fl.store.gather",
                                          rows=int(ids.size)) as span:
            slots = self._rows.slot[ids]
            cold = np.flatnonzero(slots < 0)
            span.set_metadata(cold_rows=int(cold.size))
            with self._staging_lock:
                out = self._stage(self.dtype, ids.size)
                if cold.size:
                    self._base_rows(ids[cold], out, cold)
                self._rows.read_into(out, slots)
                with jax.profiler.TraceAnnotation("fl.store.to_device",
                                                  bytes=int(out.nbytes)):
                    return self._ship(out)

    def scatter(self, ids, rows) -> None:
        ids = self._check_ids(ids)
        with jax.profiler.TraceAnnotation("fl.store.scatter",
                                          rows=int(ids.size)) as span:
            # the read waits for the program that computes ``rows``: the
            # device is busy then, so idle time inside is the transfer
            with jax.profiler.TraceAnnotation("fl.store.to_host",
                                              bytes=int(rows.nbytes)):
                rows = np.asarray(rows, self.dtype)
            if rows.shape != (ids.size, self.width):
                raise ValueError(
                    f"CheckpointStore.scatter: window shape {rows.shape} "
                    f"does not match ({ids.size}, {self.width})")
            span.set_metadata(new_rows=self._rows.write(ids, rows))

    def gather_residual(self, ids) -> jnp.ndarray:
        ids = self._check_ids(ids)
        slots = self._residual_rows.slot[ids]
        with self._staging_lock:
            out = self._stage(np.float32, ids.size)
            out[slots < 0] = 0.0
            self._residual_rows.read_into(out, slots)
            return self._ship(out)

    def scatter_residual(self, ids, rows) -> None:
        ids = self._check_ids(ids)
        rows = np.asarray(rows, np.float32)
        if rows.shape != (ids.size, self.width):
            raise ValueError(
                f"CheckpointStore.scatter_residual: window shape "
                f"{rows.shape} does not match ({ids.size}, {self.width})")
        self._residual_rows.write(ids, rows)

    def consensus(self) -> np.ndarray:
        """[width] mean over all enrolled rows without materializing them:
        touched rows sum explicitly, the (D - touched) untouched clients
        contribute the base row analytically. Requires a base *row* (a
        checkpoint-backed base would need a full-file pass)."""
        if self._base_row is None:
            raise NotImplementedError(
                "consensus over a checkpoint-backed base requires a full "
                "pass over the state file; hold a base row instead")
        acc = np.zeros((self.width,), np.float64)
        for s in self._rows.written()[1].tolist():
            np.add(acc, self._rows.row(s), out=acc)
        acc += (self.num_enrolled - len(self._rows)) * np.asarray(
            self._base_row, np.float64)
        return (acc / self.num_enrolled).astype(self.dtype)

    def save(self, ckpt_dir: str, step: int) -> str:
        """Materialize touched rows + base into one [D, width] checkpoint
        — ONLY sensible at small D (tests, tier migration); at cold-tier D
        this would allocate the very buffer the tier exists to avoid."""
        full = np.empty((self.num_enrolled, self.width), self.dtype)
        if self._base_row is not None:
            full[...] = self._base_row
        else:
            load_leaves(self._base_path, np.arange(self.num_enrolled),
                        out=[full])
        self._rows.read_into(full, self._rows.slot)
        return save_checkpoint(ckpt_dir, step, {"state": full},
                               metadata={"num_enrolled": self.num_enrolled})


def make_store(base_row, num_enrolled: int, *, tier: str = "auto",
               mesh_info=None, residual: bool = False,
               read_retries: int = 0, read_backoff: float = 0.0
               ) -> ClientStateStore:
    """Build the right tier for D=``num_enrolled`` clients all starting at
    ``base_row`` ([sum(sizes)], the packed global init): a resident
    ``MemoryStore`` while [D, width] fits ``MEMORY_TIER_MAX_BYTES``, the
    arena-backed ``CheckpointStore`` beyond (where materializing the
    buffer is exactly the failure mode the store exists to remove)."""
    if tier not in ("auto", "memory", "checkpoint"):
        raise ValueError(f"unknown store tier {tier!r}; expected one of "
                         "auto, memory, checkpoint")
    row = jnp.asarray(base_row)
    if row.ndim != 1:
        raise ValueError(f"make_store: base_row must be a packed "
                         f"[sum(sizes)] row, got shape {row.shape}")
    nbytes = int(num_enrolled) * int(row.shape[0]) * row.dtype.itemsize
    if residual:                       # f32 residual tier rides along
        nbytes += int(num_enrolled) * int(row.shape[0]) * 4
    if tier == "memory" or (tier == "auto" and nbytes <= MEMORY_TIER_MAX_BYTES):
        flat = jnp.broadcast_to(row[None], (int(num_enrolled), row.shape[0]))
        return MemoryStore(jnp.array(flat), mesh_info=mesh_info,
                           residual=residual)
    return CheckpointStore(np.asarray(row), num_enrolled,
                           read_retries=read_retries,
                           read_backoff=read_backoff)
