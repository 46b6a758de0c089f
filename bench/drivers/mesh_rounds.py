"""``MeshEngine.run_rounds`` on a ("data", "model") = (clients, 1) mesh: one
language-model client per chip, local steps then the protocol's grouped
psums, T rounds per call. Each call stages its chunk of the token pool
onto the chips with the batch sharding, as ``run_federated_training`` does."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import check
from bench.common import STREAM_WEIGHTS, raw_key
from bench.drivers.base import Driver as Base
from bench.models import mamba2
from bench.reference import fedp2p as ref
from bench.traffic import generate

def model_config(config: dict):
    from repro.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in config.items() if k in fields})


class Driver(Base):

    def setup(self):
        from repro.config import FLConfig
        from repro.models.model import build_model
        from repro.protocols.engine import MeshEngine
        from repro.sharding.rules import make_mesh_info
        r, cfg = self.cell["round"], self.config
        self.D, self.steps = int(r["clients"]), int(r["local_steps"])
        self.T = int(self.cell["rounds_per_call"])
        self.rounds_per_call = self.T
        self.updates_per_call = self.D * self.T
        self.mesh = Mesh(np.array(self.devices[:self.D]).reshape(self.D, 1),
                         ("data", "model"))
        self.batch_sh = NamedSharding(self.mesh, P(None, "data"))
        mcfg = model_config(cfg)
        fl = FLConfig(num_clusters=r["clusters"], lr=r["lr"],
                      sync_period=r["sync_period"], algorithm=r["protocol"])
        self.engine = MeshEngine(build_model(mcfg), fl, self.D, self.steps,
                                 algorithm=r["protocol"],
                                 mesh_info=make_mesh_info(mcfg, self.mesh))
        pool = generate.token_rounds(self.traffic, self.seed, clients=self.D,
                                     local_steps=self.steps,
                                     vocab_size=cfg["vocab_size"])
        R = pool["tokens"].shape[0]
        if R < 3 * self.T or R % self.T:
            raise ValueError(f"pool_rounds={R} must be a multiple of "
                             f"rounds_per_call={self.T}, and hold 3 calls")
        self.chunks = [{k: v[i:i + self.T] for k, v in pool.items()}
                       for i in range(0, R, self.T)]
        self.f = self._init()
        self.f0 = self.f

    def _state_sharding(self, tree):
        return jax.tree.map(lambda a: NamedSharding(
            self.mesh, P("data", *([None] * (a.ndim - 1)))), tree)

    def _init(self, dtype=jnp.float32):
        """Seeded weights, one copy per client, made on the chips in one
        jitted call."""
        def make(key):
            p = mamba2.init(key, self.config, dtype)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (self.D,) + a.shape), p)
        key = raw_key(self.seed, STREAM_WEIGHTS)
        shapes = jax.eval_shape(make, key)
        return jax.jit(make, out_shardings=self._state_sharding(shapes))(key)

    def call(self) -> int:
        c = self.calls
        batches = jax.device_put(self.chunks[c % len(self.chunks)],
                                 self.batch_sh)
        self.f, losses = self.engine.run_rounds(self.f, self.call_key(c),
                                                self.T, batches)
        jax.block_until_ready((self.f, losses))
        self.calls += 1
        self.last_losses = losses
        return self.updates_per_call

    def change_sq(self) -> dict:
        return check.change_sq(self.f, self.f0)

    def drop_initial(self):
        self.f0 = None

    def free(self):
        self.engine = self.f = self.f0 = None

    def reference(self, steps, *, dtype=jnp.float32, fault=None):
        r = self.cell["round"]
        loss_fn = functools.partial(mamba2.loss, cfg=self.config)
        client_sh = NamedSharding(self.mesh, P("data"))
        f0 = self._init(dtype)
        # every client's state stays on its own chip from round to round
        one = jax.jit(functools.partial(
            ref.mesh_round, loss_fn=loss_fn, lr=float(r["lr"]),
            num_clusters=r["clusters"], fault=fault),
            out_shardings=(self._state_sharding(f0),
                           NamedSharding(self.mesh, P())))
        f, out = f0, {"losses": [], "acc": None}
        with jax.default_matmul_precision(ref.PRECISION):
            for s in range(steps):
                chunk = self.chunks[s % len(self.chunks)]
                for t in range(self.T):
                    tok = jax.device_put(chunk["tokens"][t], client_sh)
                    lab = jax.device_put(chunk["labels"][t], client_sh)
                    f, loss = one(f, tok, lab)
                    out["losses"].append(float(loss))
                if s == 0:
                    out["d1"] = check.norms(check.change_sq(f, f0))
        out["d3"] = check.norms(check.change_sq(f, f0))
        return out

    def required_flops_per_call(self) -> float:
        B, S = int(self.traffic["batch"]), int(self.traffic["seq"])
        return (self.T * self.D * self.steps * B * S
                * mamba2.train_flops_per_token(self.config, S))
