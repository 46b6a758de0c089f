"""Faults planted under the timed path, for the fault tests: each patches
the program so that a run goes wrong in one way the comparison must
catch, and ``run(name, fault)`` drives a whole run past the look for a
chip (``bench.run.run_cell``) at a CPU size and returns its result line."""
import contextlib
import io
import json
import types

import jax
import jax.numpy as jnp

import bench_tiny
from bench import common
import bench.run as bench_run

FAULTS = ("unchanged", "half_batch", "no_exchange", "answer_altered")


def _half_batch_loss(loss):
    def half(params, batch, *a, **k):
        m = batch["mask"]
        keep = (jnp.arange(m.shape[0]) < m.shape[0] // 2).astype(m.dtype)
        return loss(params, dict(batch, mask=m * keep), *a, **k)
    return half


def plant(mp, fault: str):
    """Break the program in one way (``mp``: pytest's monkeypatch)."""
    from repro.models import model as model_mod
    from repro.protocols import engine, fedp2p
    if fault == "unchanged":
        orig_d, orig_m = engine.DenseEngine.run_rounds, engine.MeshEngine.run_rounds
        mp.setattr(engine.DenseEngine, "run_rounds",
                   lambda self, p, *a, **k: (p, orig_d(self, p, *a, **k)[1]))
        mp.setattr(engine.MeshEngine, "run_rounds",
                   lambda self, f, *a, **k: (f, orig_m(self, f, *a, **k)[1]))
        from repro.protocols import store
        for cls in (store.MemoryStore, store.CheckpointStore):
            mp.setattr(cls, "scatter", lambda self, ids, rows: None)
    elif fault == "half_batch":
        mp.setattr(engine, "paper_net_loss",
                   _half_batch_loss(engine.paper_net_loss))
        build = model_mod.build_model

        def build_half(cfg):
            m = build(cfg)

            def loss_fn(params, batch, **k):
                half = {n: v[: v.shape[0] // 2] for n, v in batch.items()}
                return m.loss_fn(params, half, **k)
            return m.__class__(**dict(m.__dict__, loss_fn=loss_fn))
        mp.setattr(model_mod, "build_model", build_half)
    elif fault == "no_exchange":
        mp.setattr(engine, "mix_flat",
                   lambda proto, new, old, ctx, cstate, **k: (new, cstate))
        mp.setattr(fedp2p.FedP2P, "psum_mix", lambda self, new, old, ctx: new)
    elif fault == "answer_altered":
        orig_d, orig_s = engine.DenseEngine.run_rounds, engine.SampledEngine.run_rounds
        orig_m = engine.MeshEngine.run_rounds

        def dense(self, *a, **k):
            p, m = orig_d(self, *a, **k)
            return p, dict(m, train_loss=m["train_loss"] * 1.05)

        def sampled(self, *a, **k):
            m = orig_s(self, *a, **k)
            return dict(m, train_loss=m["train_loss"] * 1.05)

        def mesh(self, *a, **k):
            f, losses = orig_m(self, *a, **k)
            return f, losses * 1.05
        mp.setattr(engine.DenseEngine, "run_rounds", dense)
        mp.setattr(engine.SampledEngine, "run_rounds", sampled)
        mp.setattr(engine.MeshEngine, "run_rounds", mesh)
    else:
        raise ValueError(fault)


def run(name: str, seed: int = 2**34 + 3) -> dict:
    """One whole run of the tiny cell ``name``; its result line."""
    args = types.SimpleNamespace(workload=name, seed=seed, seconds=0.5,
                                 trace=0)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.run_cell(args, common.manifest(),
                                bench_tiny.tiny(name), jax.devices()[:4])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
