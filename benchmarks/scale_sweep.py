"""Scale sweep: per-round mixing wall-clock and peak temp memory vs client
count D, dense [D, D] oracle vs structured-sparse MixingSpec path.

This is the tracked evidence for the fast path's O(D²·n) -> O(D·n) claim:
for every protocol with a structured spec it times ONE full mixing
application (context -> operator -> flat [D, n] mix, compiled as one jit
program, including the operator construction) on both paths at growing D,
and reads the compiled program's temp-buffer footprint — the dense path
materializes two [D, D] f32 matrices (128 MiB at D=4096), the sparse path
O(D) index/weight vectors.

Rows (``name,value,derived`` — the speedup row is the CI-tracked one):

    scale/<proto>/D<D>/dense_round_us
    scale/<proto>/D<D>/sparse_round_us
    scale/<proto>/D<D>/speedup
    scale/<proto>/D<D>/dense_temp_mib | sparse_temp_mib

Quick mode sweeps D ∈ {64, 256, 1024}; ``--full`` adds D=4096 (the dense
oracle at D=4096 is exactly the wall the sparse path removes — expect
seconds per round there).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import wallclock
from repro import protocols
from repro.config import FLConfig
from repro.protocols import apply_spec_flat, make_context

# protocols with a structured spec, one per spec family + the rank-1 server
# forms (fedp2p_topo shares fedp2p's spec; it would only duplicate rows)
SWEEP_PROTOCOLS = ("fedavg", "fedp2p", "gossip", "gossip_async")
QUICK_DS = (64, 256, 1024)
FULL_DS = (64, 256, 1024, 4096)
# largest D whose DENSE oracle is even worth materializing per protocol:
# gossip_async's dense form indexes a precomputed [R, D, D] matching stack —
# O(D³) bytes (4.3 GiB at D=1024), the very wall the MatchingSpec removes —
# so past this cap only the sparse path is measured.
DENSE_MAX_D = {"gossip_async": 256}


def _temp_mib(fn, *args) -> float:
    try:
        mem = jax.jit(fn).lower(*args).compile().memory_analysis()
        return float(getattr(mem, "temp_size_in_bytes", 0.0)) / 2 ** 20
    except Exception:  # noqa: BLE001 — memory analysis is best-effort
        return 0.0


def sweep_one(name: str, D: int, n: int, *, iters: int = 3):
    """(dense_us, sparse_us, dense_mib, sparse_mib) for one (protocol, D)."""
    proto = protocols.get(name)
    fl = FLConfig(num_clusters=min(8, D), participation=D)
    cids = jnp.asarray(proto.mesh_cluster_ids(D, fl))
    L = int(np.asarray(cids).max()) + 1
    rng = np.random.default_rng(D)
    survive = jnp.asarray((rng.random(D) > 0.1).astype(np.float32))
    counts = jnp.asarray(rng.uniform(0.5, 5.0, D).astype(np.float32))

    def ctx_of(key):
        return make_context(key=key, survive=survive, counts=counts,
                            cluster_ids=cids, num_clusters=L,
                            do_global_sync=True)

    def dense_fn(xn, xo, key):
        M_new, M_old = proto.mixing_matrix(ctx_of(key))
        return (M_new @ xn + M_old @ xo).astype(xn.dtype)

    def sparse_fn(xn, xo, key):
        return apply_spec_flat(proto.mixing_spec(ctx_of(key)), xn, xo)

    xn = jnp.asarray(rng.normal(size=(D, n)).astype(np.float32))
    xo = jnp.asarray(rng.normal(size=(D, n)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    with_dense = D <= DENSE_MAX_D.get(name, FULL_DS[-1])
    dense_us = (wallclock(jax.jit(dense_fn), xn, xo, key, iters=iters)
                if with_dense else 0.0)
    sparse_us = wallclock(jax.jit(sparse_fn), xn, xo, key, iters=iters)
    dense_mib = _temp_mib(dense_fn, xn, xo, key) if with_dense else 0.0
    return dense_us, sparse_us, dense_mib, _temp_mib(sparse_fn, xn, xo, key)


# sampled-participation sweep: the active window is FIXED at K=1024 while
# enrollment D grows 100x — the compiled round must not notice. D=10^6 is
# cheap to include even in quick mode precisely BECAUSE the round is
# D-independent (only the host-side store gather sees D at all).
SAMPLED_K = 1024
SAMPLED_DS = (10 ** 4, 10 ** 6)


def sweep_sampled(name: str, D: int, K: int, n: int, *, iters: int = 3):
    """(window_us, store_us) for one (protocol, enrolled D): the compiled
    [K, n] window mix of a K-active-of-D-enrolled round, plus the host-side
    store gather+scatter that moves the window in and out."""
    from benchmarks.common import wallclock
    from repro.protocols import make_store

    proto = protocols.get(name)
    fl = FLConfig(num_clusters=min(8, K), participation=K,
                  num_enrolled=D, participants_per_round=K)
    cids = jnp.asarray(proto.mesh_cluster_ids(K, fl))
    L = int(np.asarray(cids).max()) + 1
    rng = np.random.default_rng(K)
    survive = jnp.asarray((rng.random(K) > 0.1).astype(np.float32))
    counts = jnp.asarray(rng.uniform(0.5, 5.0, K).astype(np.float32))

    def window_fn(xn, xo, ids, key):
        ctx = make_context(key=key, survive=survive, counts=counts,
                           cluster_ids=cids, num_clusters=L,
                           do_global_sync=True, active_ids=ids,
                           num_enrolled=D)
        return apply_spec_flat(proto.mixing_spec(ctx), xn, xo)

    xn = jnp.asarray(rng.normal(size=(K, n)).astype(np.float32))
    xo = jnp.asarray(rng.normal(size=(K, n)).astype(np.float32))
    ids_np = rng.choice(D, size=K, replace=False).astype(np.int32)
    key = jax.random.PRNGKey(0)
    # D reaches the compiled program only as VALUES of the [K] id vector —
    # the jit signature (and hence the compiled round cost) is D-free
    window_us = wallclock(jax.jit(window_fn), xn, xo, jnp.asarray(ids_np), key,
                      iters=iters)

    store = make_store(jnp.zeros((n,), jnp.float32), D)
    store.scatter(ids_np, np.asarray(xo))       # warm: rows take arena slots

    def store_roundtrip():
        win = store.gather(ids_np)
        jax.block_until_ready(win)
        store.scatter(ids_np, win)

    return window_us, wallclock(store_roundtrip, warmup=1, iters=iters)


# pipelined-round sweep: a REAL SampledEngine (local SGD + mix, not just
# the mixing op) driven through run_rounds at growing pipeline_depth —
# depth 1 is the serial baseline, depths 2-3 overlap store prefetch and
# retire/scatter with the compiled window. Tiers: the resident MemoryStore
# (device buffer, D=10^4) and the arena CheckpointStore (host-owned,
# D=10^6 — the regime where store I/O sits on the serial critical path).
PIPELINE_DEPTHS = (1, 2, 3)
PIPELINE_TIERS = (("resident", "memory", 10 ** 4),
                  ("checkpoint", "checkpoint", 10 ** 6))
PIPELINE_ROUNDS = 6


def sweep_pipeline(tier: str, D: int, K: int, *, rounds: int = PIPELINE_ROUNDS,
                   iters: int = 2):
    """{depth: per-round us} for one store tier at K active of D enrolled."""
    from benchmarks.common import wallclock
    from repro.configs.paper_models import LOGREG_SYN
    from repro.core.simulator import Simulator
    from repro.data.federated import pack_clients
    from repro.data.synthetic import syncov
    from repro.protocols.engine import SampledEngine

    data_clients = 64            # enrollment maps onto data rows cyclically
    xs, ys = syncov(num_clients=data_clients, seed=0)
    data = pack_clients(xs, ys, 10, seed=0)
    # local_epochs picked so the compiled window (stage B) is the same
    # order as the O(D) select + store fetch (stage A) at D=10^6 — the
    # regime where a depth-2 pipeline can hide one stage behind the other
    fl = FLConfig(num_clients=data_clients, num_clusters=8,
                  participation=data_clients, local_epochs=4, batch_size=10,
                  lr=0.05, straggler_rate=0.1, num_enrolled=D,
                  participants_per_round=K)
    data_dev = Simulator(LOGREG_SYN, data, fl).data_dev
    out = {}
    for depth in PIPELINE_DEPTHS:
        se = SampledEngine(LOGREG_SYN, data_dev, fl, protocols.get("fedavg"),
                           pipeline_depth=depth)
        se.init_store(se.init_params(0), tier=tier)
        key = jax.random.PRNGKey(0)
        out[depth] = wallclock(lambda: se.run_rounds(key, rounds),
                               warmup=1, iters=iters) / rounds
    return out


def run(quick: bool = True, n: int | None = None, verbose: bool = False):
    import sys
    import time

    ds = QUICK_DS if quick else FULL_DS
    n = n or (2048 if quick else 4096)
    rows = []
    resident_us = {}       # protocol -> sparse round us at resident D=1024
    for name in SWEEP_PROTOCOLS:
        for D in ds:
            t0 = time.time()
            iters = 1 if D >= 4096 else 3
            dense_us, sparse_us, dense_mib, sparse_mib = sweep_one(
                name, D, n, iters=iters)
            if D == SAMPLED_K:
                resident_us[name] = sparse_us
            tag = f"scale/{name}/D{D}"
            if dense_us > 0:
                rows.append((f"{tag}/dense_round_us", dense_us,
                             f"[D,D]@[D,{n}] oracle, ctx->matrix->mix"))
            else:
                rows.append((f"{tag}/dense_skipped", 1.0,
                             "dense oracle infeasible here: O(D^3) "
                             "matching-matrix stack"))
            rows.append((f"{tag}/sparse_round_us", sparse_us,
                         "MixingSpec fast path, same round"))
            if dense_us > 0:
                rows.append((f"{tag}/speedup",
                             dense_us / max(sparse_us, 1e-9),
                             "dense/sparse round-time ratio"))
                rows.append((f"{tag}/dense_temp_mib", dense_mib,
                             "compiled temp buffers"))
            rows.append((f"{tag}/sparse_temp_mib", sparse_mib,
                         "compiled temp buffers"))
            if verbose:
                print(f"# {tag}: dense={dense_us:.0f}us "
                      f"sparse={sparse_us:.0f}us ({time.time() - t0:.1f}s)",
                      file=sys.stderr)
    for name in SWEEP_PROTOCOLS:
        for D in SAMPLED_DS:
            t0 = time.time()
            window_us, store_us = sweep_sampled(name, D, SAMPLED_K, n)
            tag = f"scale/sampled/{name}/D{D}/K{SAMPLED_K}"
            rows.append((f"{tag}/round_us", window_us,
                         f"compiled [K,{n}] window mix, K of D enrolled"))
            rows.append((f"{tag}/store_us", store_us,
                         "host store gather+scatter of the window"))
            if resident_us.get(name):
                # the tentpole's acceptance ratio: a K-active round over a
                # 10^6 enrollment vs the SAME round resident at D=K
                rows.append((f"{tag}/vs_resident_D{SAMPLED_K}",
                             window_us / max(resident_us[name], 1e-9),
                             "sampled/resident compiled round-time ratio "
                             "(target: <= 2x, i.e. D-independent)"))
            if verbose:
                print(f"# {tag}: window={window_us:.0f}us "
                      f"store={store_us:.0f}us ({time.time() - t0:.1f}s)",
                      file=sys.stderr)
    for tier_name, tier, D in PIPELINE_TIERS:
        t0 = time.time()
        per_depth = sweep_pipeline(tier, D, SAMPLED_K)
        serial_us = per_depth[PIPELINE_DEPTHS[0]]
        for depth, us in per_depth.items():
            tag = (f"scale/pipeline/{tier_name}/D{D}/K{SAMPLED_K}/"
                   f"depth{depth}")
            rows.append((f"{tag}/round_us", us,
                         "full SampledEngine round (train+mix+store), "
                         f"{tier} tier"))
            if depth > 1:
                rows.append((f"{tag}/speedup_vs_serial",
                             serial_us / max(us, 1e-9),
                             "serial/pipelined round wall-clock ratio"))
                rows.append((f"{tag}/hidden_pct",
                             100.0 * max(serial_us - us, 0.0)
                             / max(serial_us, 1e-9),
                             "% of the serial round hidden behind "
                             "compute by the pipeline"))
        if verbose:
            depths = " ".join(f"d{d}={us:.0f}us"
                              for d, us in per_depth.items())
            print(f"# scale/pipeline/{tier_name}/D{D}: {depths} "
                  f"({time.time() - t0:.1f}s)", file=sys.stderr)
    return rows


def main():
    import argparse

    from benchmarks.common import print_rows
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--n", type=int, default=None,
                    help="packed params per client (flat row width)")
    args = ap.parse_args()
    rows = run(quick=not args.full, n=args.n, verbose=True)
    print_rows(rows)
    return rows


if __name__ == "__main__":
    main()
