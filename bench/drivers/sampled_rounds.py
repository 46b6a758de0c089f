"""``SampledEngine.run_rounds``: K active clients of D enrolled, each with
its own state in the engine's client store (tier and pipeline depth are
the program's own choices), T rounds per call."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.drivers.base import Driver as Base
from bench.drivers.dense_rounds import (
    _loss, _param_count, cnn_weights, fl_config, paper_net, segment_mix_bytes,
)
from bench.models import cnn
from bench.reference import fedp2p as ref
from bench.traffic import generate


def leaf_layout(params) -> list:
    """[(name, offset, size)] of each leaf in a packed row: leaves in the
    tree's flattening order, each raveled."""
    out, off = [], 0
    for name, leaf in zip(check.leaf_names(params), jax.tree.leaves(params)):
        out.append((name, off, int(leaf.size)))
        off += int(leaf.size)
    return out


@functools.partial(jax.jit, static_argnames=("layout",))
def _row_change_sq(rows, init_row, *, layout):
    d = rows.astype(jnp.float32) - init_row[None].astype(jnp.float32)
    return [jnp.sum(jnp.square(d[:, o:o + n])) for _, o, n in layout]


class Driver(Base):
    #: rows read back from the store at a time for the comparison
    READ_BLOCK = 256

    def setup(self):
        from repro import protocols
        from repro.protocols.engine import SampledEngine
        cfg, r = self.config, self.cell["round"]
        self.data = generate.image_clients(
            self.traffic, self.seed, cfg["image_size"], cfg["channels"],
            with_test=False)
        self.D, self.K = int(r["enrolled"]), int(r["active"])
        self.T = int(self.cell["rounds_per_call"])
        self.rounds_per_call = self.T
        self.updates_per_call = self.K * self.T
        data_dev = {k: self.data[k] for k in ("x", "y", "mask", "counts")}
        fl = fl_config(self.cell, int(self.traffic["data_clients"]),
                       num_enrolled=self.D, participants_per_round=self.K)
        self.engine = SampledEngine(paper_net(cfg), data_dev, fl,
                                    protocols.get(r["protocol"]))
        p0 = self._init()
        self.layout = tuple(leaf_layout(p0))
        self.init_row = jnp.concatenate([a.ravel() for a in jax.tree.leaves(p0)])
        self.engine.init_store(p0)

    def _init(self, dtype=jnp.float32):
        return cnn_weights(self.config, self.seed, dtype)

    def _ids(self, c: int, t: int):
        return ref.sampled_ids(jax.random.fold_in(self.call_key(c), t),
                               enrolled=self.D, active=self.K)

    def call(self) -> int:
        m = self.engine.run_rounds(self.call_key(self.calls), self.T)
        self.calls += 1
        self.last_losses = m["train_loss"]
        return self.updates_per_call

    def change_sq(self) -> dict:
        """Over every client the rounds so far selected: the store's rows
        against the initial row."""
        ids = np.unique(np.concatenate([
            np.asarray(self._ids(c, t)) for c in range(self.calls)
            for t in range(self.T)]))
        tot = np.zeros(len(self.layout))
        for i in range(0, ids.size, self.READ_BLOCK):
            rows = self.engine.store.gather(ids[i:i + self.READ_BLOCK])
            tot += np.asarray(jax.device_get(_row_change_sq(
                rows, self.init_row, layout=self.layout)))
        return {name: float(v) for (name, _, _), v in zip(self.layout, tot)}

    def free(self):
        if self.engine is not None and hasattr(self.engine.store, "close"):
            self.engine.store.close()
        self.engine = None

    def reference(self, steps, *, dtype=jnp.float32, fault=None):
        r = self.cell["round"]
        fl = (int(self.traffic["data_clients"]), r["clusters"],
              r["local_epochs"], r["batch"], float(r["lr"]))
        p0 = self._init(dtype)
        state = ref.SampledState(p0, self.D, 1 + steps * self.T * self.K)
        out = {"losses": [], "acc": None}
        with jax.default_matmul_precision(ref.PRECISION):
            for s in range(steps):
                for t in range(self.T):
                    key = jax.random.fold_in(self.call_key(s), t)
                    ids = ref.sampled_ids(key, enrolled=self.D, active=self.K)
                    ids_np = np.asarray(ids)
                    mixed, loss = ref.sampled_round(
                        state.gather(ids_np), ids, key, self.data,
                        model=(_loss,), fl=fl, dtype=dtype, fault=fault)
                    state.scatter(ids_np, mixed)
                    out["losses"].append(float(loss))
                if s == 0:
                    out["d1"] = _state_norms(state, p0)
        out["d3"] = _state_norms(state, p0)
        return out

    def required_flops_per_call(self) -> float:
        """Each round: one epoch per local epoch over the real training
        samples of K clients of the population's mean size, forward and
        backward."""
        r = self.cell["round"]
        mean_n = float(self.data["counts_np"].mean())
        return (self.T * self.K * r["local_epochs"] * mean_n
                * cnn.train_flops_per_sample(self.config))

    def mix_bytes_per_round(self):
        return segment_mix_bytes(self.K, _param_count(self.config), segments=1)


def _state_norms(state, p0) -> dict:
    """Per leaf, the norm over every client of (state - initial)."""
    sq = jax.device_get(state.change_sq(p0))
    return check.norms(dict(zip(check.leaf_names(p0), (float(v) for v in sq))))
