"""End-to-end training drivers.

Two entry points:

  * ``run_lm_training``   — standard distributed LM training of any assigned
    architecture (used by examples/train_lm.py; CPU-friendly at reduced
    config, production mesh via --mesh).
  * ``run_federated_training`` — the paper's protocol at production scale:
    clients mapped onto the data axis, protocol sync via
    ``repro.protocols.MeshEngine``, straggler injection, per-round metrics.
    The whole T-round loop is ONE scan-compiled program
    (``MeshEngine.run_rounds``): batches for every round are staged up
    front, losses come back as a [T] on-device buffer — no per-round Python
    dispatch or ``float()`` host syncs.

Both share the substrates: data pipeline, optimizer, checkpointing.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import protocols
from repro.checkpoint import save_checkpoint
from repro.config import FLConfig, TrainConfig
from repro.configs import get_config
from repro.core.fedp2p import broadcast_to_clients
from repro.data.lm import token_stream_batches
from repro.launch.cache import enable_compile_cache
from repro.launch.steps import build_train_step
from repro.models.model import build_model
from repro.protocols.engine import MeshEngine


def run_lm_training(arch: str, *, steps: int = 100, batch: int = 8,
                    seq_len: int = 128, reduced: bool = True,
                    train_cfg: Optional[TrainConfig] = None,
                    ckpt_dir: Optional[str] = None, log_every: int = 10,
                    seed: int = 0, verbose: bool = True) -> Dict:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(num_layers=4, max_d_model=256)
    model = build_model(cfg)
    tc = train_cfg or TrainConfig(lr=3e-3, schedule="warmup_cosine",
                                  warmup_steps=max(10, steps // 10),
                                  total_steps=steps, remat=False)
    step_fn, opt = build_train_step(model, tc)
    step_fn = jax.jit(step_fn)

    params = model.init(jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    stream = token_stream_batches(cfg.vocab_size, batch, seq_len, seed=seed)
    losses = []
    t0 = time.time()
    for i in range(steps):
        batch_np = next(stream)
        params, opt_state, metrics = step_fn(params, opt_state,
                                             {k: jnp.asarray(v) for k, v in batch_np.items()})
        losses.append(float(metrics["loss"]))
        if verbose and ((i + 1) % log_every == 0 or i == 0):
            print(f"  step {i+1:5d} loss={losses[-1]:.4f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
        if ckpt_dir and (i + 1) % max(1, steps // 2) == 0:
            save_checkpoint(ckpt_dir, i + 1, {"params": params})
    return {"losses": losses, "final_loss": losses[-1],
            "first_loss": losses[0], "steps": steps}


def stage_rounds(streams, rounds: int, local_steps: int
                 ) -> Dict[str, np.ndarray]:
    """Draw ``rounds`` rounds of batches from per-client token streams (one
    ``token_stream_batches`` iterator per client) in the layout
    ``MeshEngine.run_rounds`` takes: leaves [rounds, D, local_steps, B, S]."""
    staged = [[[next(s) for _ in range(local_steps)] for s in streams]
              for _ in range(rounds)]
    return {k: np.stack([[np.stack([b[k] for b in client]) for client in rnd]
                         for rnd in staged])
            for k in ("tokens", "labels")}


def run_federated_training(arch: str, *, rounds: int = 20,
                           num_clients: int = 4, num_clusters: int = 2,
                           local_steps: int = 4, batch: int = 4,
                           seq_len: int = 64, algorithm: str = "fedp2p",
                           codec: str = "none",
                           sync_period: int = 1, straggler_rate: float = 0.0,
                           lr: float = 5e-3, seed: int = 0,
                           counts=None, reduced: bool = True,
                           verbose: bool = True) -> Dict:
    """Paper protocol over LM clients with heterogeneous token streams.
    ``algorithm`` is any ``repro.protocols`` registry name; ``counts``
    carries non-uniform per-client |D_i| weights onto the mesh path;
    ``codec`` is any ``repro.compression`` name — the lossy wire format
    of every exchanged update. ``reduced=False`` trains the architecture
    at its published width and depth."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(num_layers=2, max_d_model=128)
    model = build_model(cfg)
    fl = FLConfig(num_clusters=num_clusters, lr=lr,
                  straggler_rate=straggler_rate, sync_period=sync_period,
                  algorithm=protocols.get(algorithm).name, codec=codec)
    engine = MeshEngine(model, fl, num_clients, local_steps,
                        algorithm=algorithm, counts=counts)
    params = model.init(jax.random.PRNGKey(seed))
    f_params = broadcast_to_clients(params, num_clients)
    # non-IID: each client gets a stream with a different successor table.
    # Batches are staged in sync_period-aligned chunks of ~64 rounds
    # ([n, D, steps, B, S]) so staging memory stays bounded in T while each
    # chunk still runs as one scan-compiled program (at most two compiled
    # shapes: the full chunk and the final remainder).
    streams = [token_stream_batches(cfg.vocab_size, batch, seq_len, seed=100 + c)
               for c in range(num_clients)]
    sp = max(1, sync_period)
    chunk_rounds = max(sp, (64 // sp) * sp)
    key = jax.random.PRNGKey(seed + 1)
    losses = []
    done = 0
    # stateful codecs (error feedback): the residual must survive the
    # chunked staging, or every chunk boundary drops the feedback mass
    stateful = engine.codec is not None and engine.codec.stateful
    cstate = None
    while done < rounds:
        n = min(chunk_rounds, rounds - done)
        bt = jax.tree.map(jnp.asarray, stage_rounds(streams, n, local_steps))
        key, kc = jax.random.split(key)
        if stateful:
            f_params, loss_buf, cstate = engine.run_rounds(
                f_params, kc, n, bt, codec_state=cstate)
        else:
            f_params, loss_buf = engine.run_rounds(f_params, kc, n, bt)
        losses.extend(float(x) for x in np.asarray(loss_buf))
        done += n
    if verbose:
        for t in range(4, rounds, 5):
            print(f"  [{algorithm}] round {t+1:4d} loss={losses[t]:.4f}")
    return {"losses": losses, "final_loss": losses[-1],
            "first_loss": losses[0]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--mode", choices=("lm", "federated"), default="lm")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--algorithm", default="fedp2p",
                    choices=protocols.names())
    from repro import compression
    ap.add_argument("--codec", default="none", choices=compression.names(),
                    help="lossy wire format for federated exchange")
    ap.add_argument("--straggler-rate", type=float, default=0.0)
    ap.add_argument("--full", action="store_true", help="full (unreduced) config")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "lm":
        out = run_lm_training(args.arch, steps=args.steps,
                              reduced=not args.full, ckpt_dir=args.ckpt_dir)
    else:
        out = run_federated_training(args.arch, rounds=args.rounds,
                                     algorithm=args.algorithm,
                                     codec=args.codec,
                                     straggler_rate=args.straggler_rate,
                                     reduced=not args.full)
    print(f"loss {out['first_loss']:.4f} -> {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
