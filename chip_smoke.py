"""Run the federated round on a TPU through the program's own entry points.

    python chip_smoke.py             # phases a-c on one chip
    python chip_smoke.py --chips 4   # the sharded mesh round on four chips

Phases (one chip):

  a. paper path   — ``DenseEngine.run_rounds`` on the paper's FEMNIST CNN
                    (246,590 params) over 200 seeded pseudo-FEMNIST clients,
                    fedp2p and gossip, through the sparse kernels
                    (``mix_path="auto"``), ``fed_mix`` (``"dense"``) and
                    ``fed_mix_q`` (``"dense"`` + int8 wire). Each run is
                    compared with the jnp oracle (``mix_use_pallas=False``)
                    at the kernel tests' tolerances, and the round program
                    must hold a native Mosaic kernel (``tpu_custom_call``).
  b. LM path      — ``run_federated_training("mamba2-130m")`` at its
                    published width (24 layers, d_model 768), 4 clients.
  c. sampled path — ``SampledEngine`` over a device-resident ``MemoryStore``
                    (2048 enrolled CNN clients, ~2 GB; 256 active), at
                    ``pipeline_depth`` 1 and 2: bit-identical, and no
                    prefetch ever falls back to a synchronous gather.

``--chips 4`` runs only the production path: ``MeshEngine`` with a
("data", "model") = (4, 1) mesh, one mamba2-130m client per chip, fedp2p and
gossip_async, against the same engine's no-mesh dense fallback.

Every phase prints ``phase=...`` lines (platform, device kind, compile and
run seconds, the check made). The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script refuses to run phases off a TPU and exits non-zero when any
phase fails.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import protocols  # noqa: E402
from repro.config import FLConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.paper_models import CNN_FEMNIST  # noqa: E402
from repro.core.fedp2p import (  # noqa: E402
    broadcast_to_clients, federated_state_specs,
)
from repro.core.simulator import Simulator  # noqa: E402
from repro.data.federated import pseudo_femnist_federated  # noqa: E402
from repro.data.lm import token_stream_batches  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.train import run_federated_training, stage_rounds  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.protocols.engine import (  # noqa: E402
    DenseEngine, MeshEngine, SampledEngine,
)
from repro.sharding.rules import make_mesh_info  # noqa: E402

#: the monitoring event JAX records around every backend compile
#: (a persistent-cache read included, so a warm cache shows up as less)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the event JAX records for each program read from the persistent cache
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: (mix_path, codec) of phase a: the structured kernels, fed_mix, fed_mix_q
PAPER_VARIANTS = (("auto", None), ("dense", None), ("dense", "int8"))
#: kernel-vs-oracle tolerances: tests/test_fed_mix.py (f32 wire) and
#: tests/test_compression.py (int8 wire)
ORACLE_TOL = {None: dict(rtol=1e-5, atol=1e-6),
              "int8": dict(rtol=1e-4, atol=1e-5)}
#: mesh-vs-dense tolerance of tests/test_sharding_and_dryrun.py
MESH_TOL = dict(rtol=2e-3, atol=2e-4)


class CompileClock:
    """Wall and backend-compile seconds, backend compiles and
    persistent-cache hits, summed over every time the clock is entered."""

    def __init__(self):
        self.seconds = 0.0
        self.wall = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        self._t0 = time.perf_counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        self.wall += time.perf_counter() - self._t0

    @property
    def run_seconds(self) -> float:
        return max(0.0, self.wall - self.seconds)


def _row(phase: str, clock: CompileClock, ok: bool, check: str) -> dict:
    dev = jax.devices()[0]
    return {"phase": phase, "platform": dev.platform,
            "device_kind": dev.device_kind,
            "compile_s": round(clock.seconds, 3),
            "compiles": clock.compiles, "cache_hits": clock.cache_hits,
            "run_s": round(clock.run_seconds, 3), "ok": bool(ok),
            "check": check}


def _host(tree):
    return [np.asarray(leaf, np.float32) for leaf in jax.tree.leaves(tree)]


def _compare(got, ref, *, rtol, atol):
    """(all close, max |got - ref|, max excess over the tolerance) over
    matching leaf lists; excess <= 0 means within tolerance."""
    worst, excess = 0.0, -math.inf
    for a, b in zip(got, ref, strict=True):
        d = np.abs(a - b)
        worst = max(worst, float(d.max(initial=0.0)))
        excess = max(excess, float((d - (atol + rtol * np.abs(b)))
                                   .max(initial=-math.inf)))
    return excess <= 0, worst, excess


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_paper(*, net=CNN_FEMNIST, num_clients: int = 200,
                per_client: int = 120, rounds: int = 3,
                algorithms=("fedp2p", "gossip"), seed: int = 0):
    """a. The paper's own path: ``DenseEngine.run_rounds`` with the Pallas
    mixing kernels, every variant, a few rounds. Each round is checked
    against the jnp oracle run from the same model and key: compared over
    several rounds, the two runs' last-ulp differences would be amplified
    by the local training and the int8 wire's rounding, not by the kernel."""
    data = pseudo_femnist_federated(num_clients=num_clients,
                                    per_client=per_client, seed=seed)
    fl = FLConfig(num_clients=num_clients, num_clusters=10,
                  devices_per_cluster=num_clients // 10,
                  participation=num_clients, local_epochs=1, batch_size=10,
                  lr=0.05, seed=seed)
    sim = Simulator(net, data, fl)
    native_expected = jax.default_backend() == "tpu"
    for algo in algorithms:
        proto = protocols.get(algo)
        for mix_path, codec in PAPER_VARIANTS:
            engines = [DenseEngine(net, sim.data_dev, fl, proto,
                                   mix_use_pallas=use_pallas, codec=codec,
                                   mix_path=mix_path)
                       for use_pallas in (True, False)]
            params = sim.init_params(seed)
            # a Mosaic kernel lowers to this custom call; the interpreter
            # lowers to plain XLA loops
            native = "tpu_custom_call" in engines[0].round_fn.lower(
                params, jax.random.PRNGKey(0)).as_text()
            clocks = [CompileClock(), CompileClock()]
            close, worst, excess, losses, accs = True, 0.0, -math.inf, [], []
            for t in range(rounds):
                key = jax.random.PRNGKey(seed + 1 + t)
                outs = []
                for eng, clock in zip(engines, clocks):
                    with clock:
                        final, metrics = eng.run_rounds(params, key, 1)
                        jax.block_until_ready((final, metrics))
                    outs.append((final, _host(final) + _host(
                        [metrics["train_loss"], metrics["acc"]])))
                ok_t, worst_t, excess_t = _compare(
                    outs[0][1], outs[1][1], **ORACLE_TOL[codec])
                close &= ok_t and all(np.isfinite(a).all()
                                      for a in outs[0][1])
                worst, excess = max(worst, worst_t), max(excess, excess_t)
                losses.append(float(outs[0][1][-2][0]))
                accs.append(float(outs[0][1][-1][0]))
                params = outs[0][0]          # the kernel path's trajectory
            yield _row(
                f"a.paper/{algo}/{mix_path}/{codec or 'f32'}", clocks[0],
                close and native == native_expected,
                f"pallas~oracle rounds={rounds} max_abs={worst:.3e} "
                f"excess={excess:.3e} native={native} "
                f"oracle_compile_s={clocks[1].seconds:.3f} "
                f"oracle_run_s={clocks[1].run_seconds:.3f} "
                f"losses={[round(x, 4) for x in losses]} "
                f"accs={[round(x, 4) for x in accs]}")


def phase_lm(*, arch: str = "mamba2-130m", reduced: bool = False,
             num_clients: int = 4, rounds: int = 3, local_steps: int = 4,
             batch: int = 4, seq_len: int = 64):
    """b. ``run_federated_training`` at the architecture's published width:
    finite losses that end no higher than they start."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(num_layers=2, max_d_model=128)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    with CompileClock() as clock:
        out = run_federated_training(
            arch, rounds=rounds, num_clients=num_clients, num_clusters=2,
            local_steps=local_steps, batch=batch, seq_len=seq_len,
            algorithm="fedp2p", reduced=reduced, verbose=False)
    losses = out["losses"]
    ok = all(math.isfinite(x) for x in losses) and losses[-1] <= losses[0]
    yield _row(
        f"b.lm/{arch}/fedp2p", clock, ok,
        f"layers={cfg.num_layers} d_model={cfg.d_model} params={n_params} "
        f"clients={num_clients} losses={[round(x, 4) for x in losses]}")


def phase_sampled(*, net=CNN_FEMNIST, num_enrolled: int = 2048,
                  active: int = 256, data_clients: int = 200,
                  per_client: int = 120, rounds: int = 4, seed: int = 0):
    """c. ``SampledEngine`` over an accelerator-resident ``MemoryStore``
    (donated gather/scatter live): depth 2 bit-identical to depth 1, and
    zero prefetch fallbacks."""
    data = pseudo_femnist_federated(num_clients=data_clients,
                                    per_client=per_client, seed=seed)
    fl = FLConfig(num_clients=data_clients, num_clusters=8,
                  devices_per_cluster=active // 8, participation=active,
                  local_epochs=1, batch_size=10, lr=0.05, seed=seed,
                  num_enrolled=num_enrolled, participants_per_round=active)
    sim = Simulator(net, data, fl)
    params = sim.init_params(seed)
    key = jax.random.PRNGKey(seed + 1)
    resident_expected = jax.default_backend() != "cpu"
    runs = {}
    for depth in (1, 2):
        se = SampledEngine(net, sim.data_dev, fl, protocols.get("fedp2p"),
                           pipeline_depth=depth)
        store = se.init_store(params, tier="memory")
        with CompileClock() as clock:
            metrics = se.run_rounds(key, rounds)
            jax.block_until_ready(store.flat)
        runs[depth] = (se, metrics)
        resident = store._device_resident
        yield _row(
            f"c.sampled/fedp2p/depth{depth}", clock,
            resident == resident_expected and se.prefetch_fallbacks == 0
            and np.isfinite(metrics["train_loss"]).all(),
            f"D={num_enrolled} K={active} store_bytes={store.flat.nbytes} "
            f"device_resident={resident} "
            f"prefetch_fallbacks={se.prefetch_fallbacks} "
            f"losses={metrics['train_loss'].round(4).tolist()}")
    (s1, m1), (s2, m2) = runs[1], runs[2]
    with CompileClock() as clock:
        same = (bool(jnp.array_equal(s1.store.flat, s2.store.flat))
                and np.array_equal(m1["train_loss"], m2["train_loss"])
                and np.array_equal(s1.store.last_round, s2.store.last_round))
    yield _row("c.sampled/fedp2p/depth2==depth1", clock, same,
               f"bit_identical={same}")


def phase_mesh(*, arch: str = "mamba2-130m", reduced: bool = False,
               rounds: int = 2, local_steps: int = 2, batch: int = 4,
               seq_len: int = 64, algorithms=("fedp2p", "gossip_async"),
               seed: int = 0):
    """The production path: ``MeshEngine`` over a (n, 1) data mesh of every
    device, one client per device, against the same engine's no-mesh
    dense fallback (the jnp [D, D] oracle) on the first device."""
    devices = jax.devices()
    n = len(devices)
    mesh = jax.sharding.Mesh(np.array(devices).reshape(n, 1),
                             ("data", "model"))
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(num_layers=2, max_d_model=128)
    model = build_model(cfg)
    info = make_mesh_info(cfg, mesh)
    fl = FLConfig(num_clusters=2, lr=5e-3)
    streams = [token_stream_batches(cfg.vocab_size, batch, seq_len,
                                    seed=100 + c) for c in range(n)]
    batches = stage_rounds(streams, rounds, local_steps)
    key = jax.random.PRNGKey(seed + 1)
    f_params = broadcast_to_clients(model.init(jax.random.PRNGKey(seed)), n)
    for algo in algorithms:
        ref_eng = MeshEngine(model, fl, n, local_steps, algorithm=algo,
                             mix_path="dense", mix_use_pallas=False)
        with CompileClock() as clock_r:
            out, losses = ref_eng.run_rounds(
                jax.device_put(f_params, devices[0]), key, rounds,
                jax.device_put(batches, devices[0]))
            ref = _host(out) + _host([losses])
        del out, losses
        mesh_eng = MeshEngine(model, fl, n, local_steps, algorithm=algo,
                              mesh_info=info)
        batch_sh = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, "data"))
        with CompileClock() as clock:
            out, losses = mesh_eng.run_rounds(
                jax.device_put(f_params, federated_state_specs(
                    f_params, mesh, info.dp_axes)),
                key, rounds, jax.device_put(batches, batch_sh))
            got = _host(out) + _host([losses])
        # one client row per device: every leaf's shards sit on n distinct
        # devices, one [1, ...] row each
        spread = all(
            len({s.device for s in leaf.addressable_shards}) == n
            and all(s.data.shape[0] == 1 for s in leaf.addressable_shards)
            for leaf in jax.tree.leaves(out))
        del out, losses
        close, worst, excess = _compare(got, ref, **MESH_TOL)
        finite = all(np.isfinite(a).all() for a in got)
        yield _row(
            f"mesh/{arch}/{algo}", clock, close and finite and spread,
            f"mesh~no-mesh max_abs={worst:.3e} excess={excess:.3e} "
            f"devices={n} spread={spread} "
            f"ref_compile_s={clock_r.seconds:.3f} "
            f"ref_run_s={clock_r.run_seconds:.3f} "
            f"losses={[round(float(x), 4) for x in got[-1]]}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _print_row(row: dict) -> None:
    print(f"phase={row['phase']} platform={row['platform']} "
          f"device_kind={row['device_kind']!r} compile_s={row['compile_s']} "
          f"compiles={row['compiles']} cache_hits={row['cache_hits']} "
          f"run_s={row['run_s']} "
          f"ok={row['ok']} check: {row['check']}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-c on one chip; 4: only the sharded "
                         "mesh round, over four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile_cache={enable_compile_cache()}", flush=True)
    phases = ((phase_mesh,) if args.chips == 4
              else (phase_paper, phase_lm, phase_sampled))
    ok = True
    for phase in phases:
        try:
            for row in phase():
                _print_row(row)
                ok &= row["ok"]
        except Exception:
            # report the failure and go on: the run still exits non-zero
            traceback.print_exc()
            print(f"phase={phase.__name__} ok=False check: raised",
                  flush=True)
            ok = False
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
