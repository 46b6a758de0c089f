"""``DenseEngine.run_rounds``: the paper's resident round over every client,
T rounds per call, the global model carried from call to call, evaluation
at each call's last round."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.common import STREAM_WEIGHTS, raw_key
from bench.drivers.base import Driver as Base
from bench.models import cnn
from bench.reference import fedp2p as ref
from bench.traffic import generate

PAPER_NET_KEYS = ("name", "kind", "image_size", "channels", "hidden",
                  "num_classes")
def fl_config(cell: dict, clients: int, **extra):
    from repro.config import FLConfig
    r = cell["round"]
    return FLConfig(num_clients=clients, num_clusters=r["clusters"],
                    devices_per_cluster=r["active"] // r["clusters"],
                    participation=r["active"], local_epochs=r["local_epochs"],
                    batch_size=r["batch"], lr=r["lr"],
                    mix_path=r["mix_path"], **extra)


def paper_net(config: dict):
    from repro.configs.paper_models import PaperNetConfig
    return PaperNetConfig(**{k: config[k] for k in PAPER_NET_KEYS})


class Driver(Base):

    def setup(self):
        from repro import protocols
        from repro.protocols.engine import DenseEngine
        cfg, r = self.config, self.cell["round"]
        self.data = generate.image_clients(
            self.traffic, self.seed, cfg["image_size"], cfg["channels"])
        N = int(self.traffic["data_clients"])
        self.T = int(self.cell["rounds_per_call"])
        self.rounds_per_call = self.T
        self.updates_per_call = r["active"] * self.T
        data_dev = {k: self.data[k] for k in
                    ("x", "y", "mask", "counts", "test_x", "test_y",
                     "test_mask")}
        self.engine = DenseEngine(paper_net(cfg), data_dev, fl_config(
            self.cell, N), protocols.get(r["protocol"]))
        self.params = self._init()
        self.p0 = self.params

    def _init(self, dtype=jnp.float32):
        return cnn_weights(self.config, self.seed, dtype)

    def call(self) -> int:
        key = self.call_key(self.calls)
        self.params, m = self.engine.run_rounds(self.params, key, self.T,
                                                eval_every=self.T)
        jax.block_until_ready((self.params, m))
        self.calls += 1
        self.last_losses, self.last_acc = m["train_loss"], m["acc"]
        return self.updates_per_call

    def change_sq(self) -> dict:
        return check.change_sq(self.params, self.p0)

    def drop_initial(self):
        self.p0 = None

    def free(self):
        self.engine = self.params = self.p0 = None

    def reference(self, steps, *, dtype=jnp.float32, fault=None):
        r, N = self.cell["round"], int(self.traffic["data_clients"])
        fl = (N, r["clusters"], r["active"] // r["clusters"],
              r["local_epochs"], r["batch"], float(r["lr"]))
        model = (_loss, _acc)
        p0 = self._init(dtype)
        params, out = p0, {"losses": [], "acc": []}
        with jax.default_matmul_precision(ref.PRECISION):
            for s in range(steps):
                params, ls, acc = ref.dense_call(
                    params, self.call_key(s), self.data, model=model,
                    rounds=self.T, fl=fl, dtype=dtype, fault=fault,
                    eval_last=True)
                out["losses"].extend(float(v) for v in np.asarray(ls))
                out["acc"].append(float(acc))
                if s == 0:
                    out["d1"] = check.norms(check.change_sq(params, p0))
        out["d3"] = check.norms(check.change_sq(params, p0))
        return out

    def required_flops_per_call(self) -> float:
        """Each round: one epoch per local epoch over every participant's
        real training samples, forward and backward; each call: one forward
        over every real test sample."""
        r = self.cell["round"]
        train = (self.T * r["local_epochs"] * float(self.data["counts_np"].sum())
                 * cnn.train_flops_per_sample(self.config))
        test = (float(self.data["test_counts_np"].sum())
                * cnn.forward_flops_per_sample(self.config))
        return train + test

    def mix_bytes_per_round(self):
        return segment_mix_bytes(self.cell["round"]["active"],
                                 _param_count(self.config),
                                 segments=1)


def cnn_weights(config: dict, seed: int, dtype=jnp.float32):
    """The seeded weights, made on the device in one jitted call."""
    make = jax.jit(functools.partial(cnn.init, cfg=config, dtype=dtype))
    return make(raw_key(seed, STREAM_WEIGHTS))


def segment_mix_bytes(clients: int, params: int, segments: int) -> float:
    """Least HBM traffic of one segment mix in float32: read the new and
    the old rows, write the mixed rows, and write then read the [L, P]
    segment sums."""
    return 4.0 * (3 * clients * params + 2 * segments * params)


def _param_count(config: dict) -> int:
    return int(sum(np.prod(s) for s in cnn.shapes(config).values()))


def _loss(params, x, y, m):
    return cnn.loss(params, x, y, m)


def _acc(params, x, y, m):
    return cnn.accuracy(params, x, y, m)
