"""What every driver shares: the call keys, the readings of the first
steps, and the interface ``bench/run.py`` drives.

A driver builds one object of the program (its engine with its state),
and ``call()`` runs one call into the engine's round entry, blocked until
its outputs are ready, returning the client updates it completed. The
first ``check_steps`` calls are the set-up's; their readings go to the
comparison with the reference, which ``reference()`` runs once the program's
state is freed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.common import STREAM_CALLS, raw_key


class Driver:
    #: set by subclasses: client updates per call, rounds per call
    updates_per_call = 0
    rounds_per_call = 0

    def __init__(self, config: dict, traffic: dict, cell: dict, seed: int,
                 devices):
        self.config, self.traffic, self.cell = config, traffic, cell
        self.seed, self.devices = int(seed), list(devices)
        self.calls = 0
        #: the last call's per-round losses and accuracies, as the
        #: call returned them (read on the host only by ``readings``)
        self.last_losses = self.last_acc = None
        #: seconds spent reading the state for the comparison (not set-up)
        self.read_seconds = 0.0
        self._call_key = raw_key(seed, STREAM_CALLS)

    def call_key(self, c: int):
        return jax.random.fold_in(self._call_key, c)

    # -- readings of the first steps -----------------------------------
    def readings(self, steps: int) -> dict:
        """Drive the first ``steps`` calls and read them: the rounds'
        losses, the evaluated accuracies, and the per-leaf change of the
        state after the first and after the last call."""
        out = {"losses": [], "acc": None}
        for s in range(steps):
            self.call()
            out["losses"].extend(float(v) for v in np.asarray(self.last_losses))
            if self.last_acc is not None:
                out["acc"] = (out["acc"] or []) + [
                    float(np.asarray(self.last_acc)[-1])]
            t = time.perf_counter()
            if s == 0:
                out["d1"] = check.norms(self.change_sq())
            if s == steps - 1:
                out["d3"] = check.norms(self.change_sq())
            self.read_seconds += time.perf_counter() - t
        self.drop_initial()
        return out

    # -- to be provided ------------------------------------------------
    def setup(self):
        raise NotImplementedError

    def call(self) -> int:
        raise NotImplementedError

    def change_sq(self) -> dict:
        """Per leaf, sum of squares of (state now - initial state)."""
        raise NotImplementedError

    def drop_initial(self):
        """Release the copy of the initial state the readings needed."""

    def free(self):
        """Release the program's state and compiled programs."""

    def reference(self, steps: int, *, dtype=jnp.float32, fault=None) -> dict:
        raise NotImplementedError

    def required_flops_per_call(self) -> float:
        raise NotImplementedError

    def mix_bytes_per_round(self):
        """Bytes the segment mix must move per round, or None where the
        cell does not run it."""
        return None
