"""Traced audit programs: the jaxprs the rules inspect.

A ``Program`` is one traced entry point — a single protocol round or a full
T-round ``run_rounds`` scan — on one engine, with the configuration
metadata the rules need (peer count for the dense-operator probe, the
spec-implied collective budget, the donation contract). Builders trace with
``jax.make_jaxpr`` over ``ShapeDtypeStruct``s / tiny concrete models, so
nothing executes and no real data is needed.

Both suites deliberately use peer counts and model widths that cannot
collide: the dense suite's packed width (610 for the logreg paper net) is
far from its participant count (8), so a float [P, P] hit really is the
dense mixing operator, never a training-shape coincidence.

Mesh-engine programs trace ``shard_map`` bodies against a (D, 1)
data×model mesh, which requires D visible devices — the CLI forces host
devices via XLA_FLAGS (``repro.analysis.__main__``); in-process callers on
a single device get a ``RuntimeError`` from ``mesh_programs`` and should
use the subprocess pattern of tests/test_sharding_and_dryrun.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro import protocols
from repro.config import FLConfig
from repro.configs.paper_models import LOGREG_SYN
from repro.faults import FaultPlan, FaultSpec
from repro.protocols.context import make_context
from repro.protocols.engine import DenseEngine, MeshEngine, SampledEngine


@dataclass
class Program:
    """One traced program plus the metadata rules audit it against."""
    name: str                 # "{engine}/{protocol}/{mix_path}/{codec}/{kind}"
    jaxpr: Any                # ClosedJaxpr from jax.make_jaxpr
    engine: str               # "dense" | "mesh"
    protocol: str
    mix_path: str             # resolved lowering: "sparse"|"dense"|"psum"
    codec: str
    kind: str                 # "round" | "run"
    meta: Dict[str, Any] = field(default_factory=dict)
    # meta keys the built-in rules read:
    #   num_peers      — D/P, the client-axis width ([D, D] probe shapes)
    #   sparse_path    — True -> no-dense-mixing applies
    #   census_budget  — {collective prim: count} implied by the protocol's
    #                    mixing structure for ONE round (dense engine: {})
    #   rounds         — census scale factor (T for "run" programs)
    #   donate_intent  — flat invar indices the engine donates on
    #                    accelerators (donation-integrity applies)
    #   stateful_codec — True for error-feedback codecs (residual carry)
    #   wire_model     — the protocol's declared §3.2 wire structure for
    #                    ONE round ((group_size, n_groups, copies) ring
    #                    terms; () for the network-free dense engine) —
    #                    wire-model-parity compares the static jaxpr byte
    #                    count against its CommParams pricing
    #   model_bytes    — per-client model bytes at full precision (M)


# ---------------------------------------------------------------------------
# dense (simulator / oracle) suite
# ---------------------------------------------------------------------------

DENSE_P = 8          # participants; far from the 610 packed logreg width


def _dense_fl(P: int) -> FLConfig:
    return FLConfig(num_clients=P, num_clusters=2,
                    devices_per_cluster=P // 2, participation=P,
                    local_epochs=1, batch_size=4, lr=0.05,
                    straggler_rate=0.1)


def _dense_data(P: int):
    z = jnp.zeros
    F = LOGREG_SYN.input_dim
    return {"x": z((P, 4, F)), "y": z((P, 4), jnp.int32), "mask": z((P, 4)),
            "counts": jnp.ones((P,)),
            "test_x": z((P, 2, F)), "test_y": z((P, 2), jnp.int32),
            "test_mask": z((P, 2))}


def _resolved_mix_path(proto, fl: FLConfig, mix_path: str) -> str:
    """Which lowering 'auto' lands on: probe ``mixing_spec`` on a concrete
    context built exactly the way the engine builds one."""
    if mix_path == "dense":
        return "dense"
    P = proto.num_participants(fl)
    _, cids = proto.partition(jax.random.PRNGKey(0), fl, None)
    ctx = make_context(key=jax.random.PRNGKey(0),
                       survive=jnp.ones((P,), jnp.float32),
                       counts=jnp.ones((P,), jnp.float32),
                       cluster_ids=cids,
                       num_clusters=proto.num_clusters(fl),
                       do_global_sync=True)
    if proto.mixing_spec(ctx) is not None:
        return "sparse"
    if mix_path == "sparse":
        raise ValueError(f"protocol {proto.name!r} provides no mixing_spec")
    return "dense"


def dense_programs(protocol: str, *, codec: str = "none",
                   mix_path: str = "auto", rounds: int = 3,
                   P: int = DENSE_P, kinds: Tuple[str, ...] = ("round", "run")
                   ) -> List[Program]:
    """Trace a DenseEngine round and/or T-round run program for one
    (protocol, codec, mix_path). Dense-engine programs have a ZERO
    collective budget — the simulator path never touches the network."""
    proto = protocols.get(protocol)
    fl = _dense_fl(P)
    resolved = _resolved_mix_path(proto, fl, mix_path)
    engine = DenseEngine(LOGREG_SYN, _dense_data(P), fl, proto,
                         codec=None if codec == "none" else codec,
                         mix_path=mix_path)
    params = engine.init_params(0)
    key = jax.random.PRNGKey(0)
    stateful = engine.codec is not None and engine.codec.stateful
    flat0, spec = engine._pack_params(params)
    # the simulator is network-free: its declared wire structure is EMPTY,
    # so wire-model-parity doubles as "the dense path moves zero bytes"
    base_meta = {"num_peers": P, "sparse_path": resolved == "sparse",
                 "census_budget": {}, "stateful_codec": stateful,
                 "wire_model": (),
                 "model_bytes": float(flat0.size * flat0.dtype.itemsize)}
    out: List[Program] = []
    if "round" in kinds:
        jaxpr = jax.make_jaxpr(engine._round)(params, key)
        out.append(Program(
            name=f"dense/{protocol}/{resolved}/{codec}/round",
            jaxpr=jaxpr, engine="dense", protocol=protocol,
            mix_path=resolved, codec=codec, kind="round",
            meta=dict(base_meta, rounds=1)))
    if "run" in kinds:
        run = engine._build_run(spec, rounds, 1)
        jaxpr = jax.make_jaxpr(run)(flat0, key)
        out.append(Program(
            name=f"dense/{protocol}/{resolved}/{codec}/run{rounds}",
            jaxpr=jaxpr, engine="dense", protocol=protocol,
            mix_path=resolved, codec=codec, kind="run",
            meta=dict(base_meta, rounds=rounds,
                      donate_intent=tuple(engine._donate_argnums))))
    return out


# ---------------------------------------------------------------------------
# sampled (persistent store + active window) suite
# ---------------------------------------------------------------------------

#: audited enrolled population — absurdly far from every toy shape, so ANY
#: dimension equal to it in the window program is a real residency leak
SAMPLED_D = 10 ** 6


def sampled_programs(protocol: str, *, codec: str = "none",
                     mix_path: str = "auto", K: int = DENSE_P,
                     num_enrolled: int = SAMPLED_D) -> List[Program]:
    """Trace a SampledEngine WINDOW round for one (protocol, codec,
    mix_path): the compiled program a K-active-of-D-enrolled round runs
    after the store gather. The trace takes only [K, sum(sizes)]-sized
    ``ShapeDtypeStruct``s — D enters exclusively as static metadata, which
    is exactly what the ``state-residency`` rule certifies."""
    proto = protocols.get(protocol)
    fl = FLConfig(num_clients=K, num_clusters=2,
                  devices_per_cluster=K // 2, participation=K,
                  local_epochs=1, batch_size=4, lr=0.05,
                  straggler_rate=0.1, num_enrolled=num_enrolled,
                  participants_per_round=K)
    resolved = _resolved_mix_path(proto, fl, mix_path)
    engine = SampledEngine(LOGREG_SYN, _dense_data(K), fl, proto,
                           codec=None if codec == "none" else codec,
                           mix_path=mix_path)
    # the store is host-side and never traced; init only supplies the
    # packed TreeSpec (auto tier lands on the checkpoint store at this D)
    engine.init_store(engine.init_params(0))
    width = engine.store.width
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    flat_sds = _sds((K, width))
    ids_sds = _sds((K,), jnp.int32)
    t_sds = _sds((), jnp.int32)
    stateful = engine._codec_stateful
    if stateful:
        jaxpr = jax.make_jaxpr(engine._window_round)(
            flat_sds, ids_sds, key, key, key, t_sds, _sds((K, width)))
    else:
        jaxpr = jax.make_jaxpr(engine._window_round)(
            flat_sds, ids_sds, key, key, key, t_sds)
    meta = {"num_peers": K, "sparse_path": resolved == "sparse",
            "census_budget": {}, "stateful_codec": stateful,
            "wire_model": (), "model_bytes": float(width * 4),
            "sampled_window": True, "num_enrolled": num_enrolled,
            "window": K, "rounds": 1,
            "donate_intent": tuple(engine._donate_argnums)}
    return [Program(
        name=f"sampled/{protocol}/{resolved}/{codec}/round",
        jaxpr=jaxpr, engine="sampled", protocol=protocol,
        mix_path=resolved, codec=codec, kind="round", meta=meta)]


# ---------------------------------------------------------------------------
# store (device-resident fast path) suite
# ---------------------------------------------------------------------------

STORE_D = 4096       # resident-tier population for the traced store programs
STORE_K = 64


def store_programs(*, D: int = STORE_D, K: int = STORE_K,
                   width: int | None = None) -> List[Program]:
    """Trace the ``MemoryStore`` device fast path's window movement
    (``kernels.ops.gather_rows_dev``/``scatter_rows_dev``): one compiled
    program each, moving the [K, width] window device<->device against the
    resident [D, width] state with NO host round-trip (``no-host-transfer``
    audits this) and the state buffer donated through the scatter
    (``donation-integrity`` audits the alias). Protocol-independent —
    every sampled round shares these two programs."""
    from repro.kernels.ops import _gather_rows_dev, _scatter_rows_dev
    if width is None:
        width = 610          # the packed logreg width, as the dense suite
    flat = _sds((D, width))
    ids = _sds((K,), jnp.int32)
    rows = _sds((K, width))
    base = {"num_peers": K, "sparse_path": False, "census_budget": {},
            "stateful_codec": False, "wire_model": (),
            "model_bytes": float(width * 4), "rounds": 1}
    return [
        Program(name="store/memory/dev/none/gather",
                jaxpr=jax.make_jaxpr(_gather_rows_dev)(flat, ids),
                engine="store", protocol="memory", mix_path="dev",
                codec="none", kind="gather", meta=dict(base)),
        Program(name="store/memory/dev/none/scatter",
                jaxpr=jax.make_jaxpr(_scatter_rows_dev)(flat, ids, rows),
                engine="store", protocol="memory", mix_path="dev",
                codec="none", kind="scatter",
                meta=dict(base, donate_intent=(0,))),
    ]


# ---------------------------------------------------------------------------
# mesh (production shard_map) suite
# ---------------------------------------------------------------------------

MESH_D = 8


class ToyMeshModel:
    """Minimal 2-leaf model satisfying the MeshEngine contract
    (``loss_fn(params, batch, remat=...) -> (loss, aux)``) so mesh-path
    programs trace in seconds."""
    F, K = 8, 4

    def init(self, key):
        k1, _ = jax.random.split(key)
        return {"w": 0.1 * jax.random.normal(k1, (self.F, self.K),
                                             jnp.float32),
                "b": jnp.zeros((self.K,), jnp.float32)}

    def loss_fn(self, params, batch, remat=False):
        logits = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((logits - batch["y"]) ** 2), {}


def _mesh_info(D: int):
    from repro.sharding.rules import MeshInfo
    if len(jax.devices()) < D:
        raise RuntimeError(
            f"mesh-engine analysis needs {D} devices, found "
            f"{len(jax.devices())}; run via `python -m repro.analysis` "
            "(which forces host devices through XLA_FLAGS) or the "
            "subprocess pattern of tests/test_sharding_and_dryrun.py")
    mesh = jax.make_mesh((D, 1), ("data", "model"))
    return MeshInfo(mesh=mesh, dp_axes=("data",), tp_axis="model",
                    strategy="dp")


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def mesh_budget(proto, fl: FLConfig, D: int, info, fp_sds) -> Dict[str, float]:
    """The spec-implied per-round collective budget: the census of the
    protocol's ``psum_mix`` traced ALONE, uncompressed. A full round must
    hit exactly this census — local training is client-diagonal (zero
    collectives) and codecs wrap the wire client-side (PR 4's 'zero extra
    collectives' claim, machine-checked by the collective-census rule)."""
    from repro.analysis.rules.collective_census import census
    ids = proto.mesh_cluster_ids(D, fl)
    L = int(ids.max()) + 1
    counts = jnp.ones((D,), jnp.float32)

    def mix(f_new, f_old, survive, key):
        ctx = make_context(key=key, survive=survive, counts=counts,
                           cluster_ids=ids, num_clusters=L,
                           do_global_sync=True, mesh_info=info)
        return proto.psum_mix(f_new, f_old, ctx)

    jaxpr = jax.make_jaxpr(mix)(
        fp_sds, fp_sds, _sds((D,)),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    return census(jaxpr)


def mesh_programs(protocol: str, *, codec: str = "none", rounds: int = 3,
                  D: int = MESH_D, local_steps: int = 2, batch: int = 2,
                  kinds: Tuple[str, ...] = ("round", "run")) -> List[Program]:
    """Trace a MeshEngine round and/or T-round run program for one
    (protocol, codec) against a (D, 1) data mesh, with the protocol's
    psum_mix-implied collective budget attached."""
    proto = protocols.get(protocol)
    info = _mesh_info(D)
    fl = FLConfig(num_clusters=2, lr=0.05)
    model = ToyMeshModel()
    engine = MeshEngine(model, fl, D, local_steps, algorithm=protocol,
                        mesh_info=info,
                        codec=None if codec == "none" else codec)
    F, K = model.F, model.K
    fp = {"w": _sds((D, F, K)), "b": _sds((D, K))}
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    budget = mesh_budget(proto, fl, D, info, fp)
    stateful = engine._codec_stateful
    ids = proto.mesh_cluster_ids(D, fl)
    L = int(ids.max()) + 1
    model_bytes = float(sum(
        (leaf.size // D) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(fp)))      # per-client leaf bytes
    base_meta = {"num_peers": D, "sparse_path": True,
                 "census_budget": budget, "stateful_codec": stateful,
                 "wire_model": proto.wire_model(D, L, do_global_sync=True),
                 "model_bytes": model_bytes}
    out: List[Program] = []
    if "round" in kinds:
        b1 = {"x": _sds((D, local_steps, batch, F)),
              "y": _sds((D, local_steps, batch, K))}
        jaxpr = jax.make_jaxpr(
            lambda f, b, s, k: engine._round(f, b, s, k,
                                             do_global_sync=True))(
            fp, b1, _sds((D,)), key)
        out.append(Program(
            name=f"mesh/{protocol}/psum/{codec}/round",
            jaxpr=jaxpr, engine="mesh", protocol=protocol, mix_path="psum",
            codec=codec, kind="round", meta=dict(base_meta, rounds=1)))
    if "run" in kinds:
        bT = {"x": _sds((rounds, D, local_steps, batch, F)),
              "y": _sds((rounds, D, local_steps, batch, K))}
        jaxpr = jax.make_jaxpr(
            lambda f, k, b: engine._run(f, k, b))(fp, key, bT)
        out.append(Program(
            name=f"mesh/{protocol}/psum/{codec}/run{rounds}",
            jaxpr=jaxpr, engine="mesh", protocol=protocol, mix_path="psum",
            codec=codec, kind="run", meta=dict(base_meta, rounds=rounds)))
    return out


#: the literal plan every fault-guarded audit program closes over: tiny,
#: explicit, and exercising all three corrupt modes plus a dropout — the
#: traced structure is what the contracts baseline pins, not the values
_FAULT_PLAN = FaultPlan(specs=(
    FaultSpec(0, drop=(1,), corrupt=((2, "nan"), (3, "bitflip"))),
    FaultSpec(2, corrupt=((0, "inf"),)),
))


def dense_fault_programs(protocol: str, *, mix_path: str = "auto",
                         rounds: int = 3, P: int = DENSE_P) -> List[Program]:
    """Trace the FAULT-GUARDED DenseEngine run program: the scan body with
    the plan's per-round drop/flag/mode xs, the corrupt wire, the
    receive-side exclusion, and the scatter-back guard. A separate program
    from the fault-free run — the baseline diff proving the zero-cost-when-
    disabled contract is exactly 'these programs appear, the others don't
    change'."""
    proto = protocols.get(protocol)
    fl = _dense_fl(P)
    resolved = _resolved_mix_path(proto, fl, mix_path)
    engine = DenseEngine(LOGREG_SYN, _dense_data(P), fl, proto,
                         mix_path=mix_path, faults=_FAULT_PLAN)
    flat0, spec = engine._pack_params(engine.init_params(0))
    run = engine._build_run(spec, rounds, 1)
    jaxpr = jax.make_jaxpr(run)(flat0, jax.random.PRNGKey(0))
    meta = {"num_peers": P, "sparse_path": resolved == "sparse",
            "census_budget": {}, "stateful_codec": False,
            "wire_model": (), "rounds": rounds, "faulted": True,
            "model_bytes": float(flat0.size * flat0.dtype.itemsize),
            "donate_intent": tuple(engine._donate_argnums)}
    return [Program(
        name=f"dense/{protocol}/{resolved}/none/faulty-run{rounds}",
        jaxpr=jaxpr, engine="dense", protocol=protocol,
        mix_path=resolved, codec="none", kind="run", meta=meta)]


def sampled_fault_programs(protocol: str, *, mix_path: str = "auto",
                           K: int = DENSE_P, num_enrolled: int = SAMPLED_D
                           ) -> List[Program]:
    """Trace the FAULT-GUARDED sampled window round (``_window_round_
    faulted``): per-slot drop/flag/mode operands, the corrupt wire, and
    the guard returning the rejected mask. Shares the fault-free window's
    residency discipline — D never enters the traced program."""
    proto = protocols.get(protocol)
    fl = FLConfig(num_clients=K, num_clusters=2,
                  devices_per_cluster=K // 2, participation=K,
                  local_epochs=1, batch_size=4, lr=0.05,
                  straggler_rate=0.1, num_enrolled=num_enrolled,
                  participants_per_round=K)
    resolved = _resolved_mix_path(proto, fl, mix_path)
    engine = SampledEngine(LOGREG_SYN, _dense_data(K), fl, proto,
                           mix_path=mix_path, faults=_FAULT_PLAN)
    engine.init_store(engine.init_params(0))
    width = engine.store.width
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(engine._window_round_faulted)(
        _sds((K, width)), _sds((K,), jnp.int32), key, key, key,
        _sds((K,)), _sds((K,)), _sds((K,), jnp.int32), _sds((), jnp.int32))
    meta = {"num_peers": K, "sparse_path": resolved == "sparse",
            "census_budget": {}, "stateful_codec": False,
            "wire_model": (), "model_bytes": float(width * 4),
            "sampled_window": True, "num_enrolled": num_enrolled,
            "window": K, "rounds": 1, "faulted": True,
            "donate_intent": tuple(engine._donate_argnums)}
    return [Program(
        name=f"sampled/{protocol}/{resolved}/none/faulty-round",
        jaxpr=jaxpr, engine="sampled", protocol=protocol,
        mix_path=resolved, codec="none", kind="round", meta=meta)]


# ---------------------------------------------------------------------------
# suite composition
# ---------------------------------------------------------------------------

def build_suite(protocol_names=None, *, engines=("dense", "mesh", "sampled"),
                mix_path: str = "auto", codecs=("none",), rounds: int = 3
                ) -> List[Program]:
    """Every (protocol x codec) program on the requested engines.

    ``mix_path='both'`` traces the dense AND sampled engines through BOTH
    lowerings (explicit dense and explicit sparse) — the full-coverage
    suite the contracts baseline snapshots. The mesh engine always lowers
    grouped psums, so mix_path only fans out the other suites."""
    names = list(protocol_names) if protocol_names else list(protocols.names())
    dense_paths = ("dense", "sparse") if mix_path == "both" else (mix_path,)
    out: List[Program] = []
    for name in names:
        for codec in codecs:
            if "dense" in engines:
                for mp in dense_paths:
                    out.extend(dense_programs(name, codec=codec,
                                              mix_path=mp, rounds=rounds))
            if "mesh" in engines:
                out.extend(mesh_programs(name, codec=codec, rounds=rounds))
            if "sampled" in engines:
                for mp in dense_paths:
                    out.extend(sampled_programs(name, codec=codec,
                                                mix_path=mp))
        # fault-guarded variants ride the uncompressed suite only: one
        # dense faulty-run and one sampled faulty-round per lowering —
        # their presence (and the fault-free programs' bit-identity) is
        # the baseline's zero-cost-when-disabled evidence
        if "dense" in engines and "none" in codecs:
            for mp in dense_paths:
                out.extend(dense_fault_programs(name, mix_path=mp,
                                                rounds=rounds))
        if "sampled" in engines and "none" in codecs:
            for mp in dense_paths:
                out.extend(sampled_fault_programs(name, mix_path=mp))
    if "sampled" in engines:
        # the device-resident store fast path rides the sampled suite:
        # ONE gather + ONE scatter program, shared by every protocol
        out.extend(store_programs())
    return out
