"""``mesh_rounds`` with the Nemotron-H client of ``bench/models/nemotron_h.py``:
the same ``MeshEngine.run_rounds`` call, faults, first steps and reference
round, with that module's weights and loss in place of mamba2's
(``mesh_rounds`` names ``bench/models/mamba2.py`` inside ``_init`` and
``reference``, so those two are repeated here with the module swapped).

Besides:

* the initial state is not held beside the round: at the cell's size the
  compiled round takes 14.0 GiB of a chip's 16 (XLA's buffer assignment
  for a v5e), so a third copy of the 2.86-GiB state would not fit. The
  readings make it again from the seed instead;
* each call keeps the engine's counters (``moe_assignments`` and
  ``moe_dropped`` per round, summed over clients, local steps and expert
  blocks) on the device, and nothing reads them inside the window:
  ``expert_work`` reads the assignments for ``moe_expert_roofline``, and
  freeing the program reads the dropped ones and stops the run if any
  assignment was dropped;
* set-up first checks that the program builds the reference's weight tree,
  so a program without this model's blocks fails before it compiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import check
from bench.common import STREAM_WEIGHTS, raw_key
from bench.drivers import mesh_rounds
from bench.models import nemotron_h
from bench.reference import fedp2p as ref


def program_matches_reference(config: dict) -> None:
    """Raise unless the program's model of ``config`` has the reference's
    weight tree, leaf for leaf (names, shapes, dtypes)."""
    from repro.models.model import build_model
    mcfg = mesh_rounds.model_config(config)
    key = jax.random.PRNGKey(0)
    prog = jax.eval_shape(build_model(mcfg).init, key)
    mine = jax.eval_shape(lambda k: nemotron_h.init(k, config), key)
    got = [(check.leaf_names(prog), [(a.shape, a.dtype)
                                     for a in jax.tree.leaves(prog)])]
    want = [(check.leaf_names(mine), [(a.shape, a.dtype)
                                      for a in jax.tree.leaves(mine)])]
    if got != want:
        raise SystemExit(
            f"the program does not build {config['name']}: its weight tree "
            "differs from the reference's (bench/models/nemotron_h.py)")


class Driver(mesh_rounds.Driver):

    def setup(self):
        program_matches_reference(self.config)
        #: per call, the engine's counters ({name: [T]}, on the device)
        self.counts = []
        super().setup()
        self.f0 = None

    def _init(self, dtype=jnp.float32):
        """Seeded weights, one copy per client, made on the chips in one
        jitted call."""
        def make(key):
            p = nemotron_h.init(key, self.config, dtype)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (self.D,) + a.shape), p)
        key = raw_key(self.seed, STREAM_WEIGHTS)
        shapes = jax.eval_shape(make, key)
        return jax.jit(make, out_shardings=self._state_sharding(shapes))(key)

    def change_sq(self) -> dict:
        return check.change_sq(self.f, self._init())

    def call(self) -> int:
        updates = super().call()
        self.counts.append(self.engine.counters)
        return updates

    def free(self):
        dropped = int(np.sum(jax.device_get(
            [c["moe_dropped"] for c in self.counts])))
        super().free()
        if dropped:
            raise RuntimeError(
                f"the MoE dropped {dropped} assignments: not dropless")

    def reference(self, steps, *, dtype=jnp.float32, fault=None):
        r = self.cell["round"]
        loss_fn = functools.partial(nemotron_h.loss, cfg=self.config)
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
                * nemotron_h.train_flops_per_token(self.config, S))

    def expert_work(self, calls: int):
        """(FLOPs, bytes) the held experts need on one chip over the last
        ``calls`` calls, at the least: every computed assignment's FLOPs
        and activation bytes, and the weight traffic of each client's
        expert blocks once per local step (``expert_weight_bytes``). The
        assignments are summed over the clients, and so over the chips."""
        cfg, chips = self.config, self.mesh.devices.size
        assignments = float(np.sum(jax.device_get(
            [c["moe_assignments"] for c in self.counts[-calls:]])))
        block_steps = (calls * self.T * self.D * self.steps
                       * nemotron_h.pattern(cfg).count("E"))
        flops = assignments * nemotron_h.expert_flops_per_assignment(cfg)
        bytes_ = (block_steps * nemotron_h.expert_weight_bytes(cfg)
                  + assignments
                  * nemotron_h.expert_activation_bytes_per_assignment(cfg))
        return flops / chips, bytes_ / chips
