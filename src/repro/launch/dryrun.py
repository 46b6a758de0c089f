import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ^ MUST precede any jax-importing import: jax locks the device count at init,
#   and the 512 virtual devices are host devices — never the accelerator.

"""Multi-pod dry-run: lower + compile every (arch x input-shape) entry point
against the production mesh and extract memory/cost/collective analyses.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi --out out.json

Exit code != 0 if any combination fails to lower/compile — failures here are
sharding bugs in the framework, per the brief.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.config import TrainConfig
from repro.configs import ARCH_IDS, get_config, get_shape
from repro.configs.shapes import SHAPES
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, params_sds
from repro.launch.steps import entry_point
from repro.models.model import build_model
from repro.sharding.rules import make_mesh_info


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               verbose: bool = True, train_overrides=None):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    info = make_mesh_info(cfg, mesh)
    model = build_model(cfg)
    # tp archs: 4-way gradient accumulation; small-vocab seqtp/dp archs
    # train with the model axis folded into data parallelism (256-way) ->
    # no microbatching needed for memory (§Perf iteration 2/2b).
    from repro.sharding.rules import batch_dims
    pure_dp = len(batch_dims(info, shape.global_batch, shape.mode,
                             cfg.vocab_size)) > len(info.dp_axes)
    default_mb = 1 if pure_dp else 4
    tc = train_overrides or TrainConfig(microbatches=default_mb)

    t0 = time.time()
    kwargs = input_specs(cfg, shape, info, model)
    # weight-stationary decode pays when the decode batch saturates the data
    # axis; at batch 1 (long_500k) the ZeRO layout is comm-free already.
    p_mode = "decode" if (shape.mode == "decode"
                          and shape.global_batch >= 16) else "train"
    p_sds = params_sds(model, info, mode=p_mode)
    step = entry_point(model, shape.mode, tc, info, shape.global_batch)

    if shape.mode == "train":
        from repro.optim import make_optimizer
        opt = make_optimizer(tc)
        o_sds = jax.eval_shape(opt.init, p_sds)
        # optimizer-state shardings follow the parameter shardings
        o_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=_opt_sharding(s, p_sds, info)), o_sds)
        args = (p_sds, o_sds, kwargs["batch"])
    elif shape.mode == "prefill":
        args = (p_sds, kwargs["batch"], kwargs["cache"])
    else:
        args = (p_sds, kwargs["cache"], kwargs["batch"])

    flops_g, bytes_g = rl.program_cost(step, *args)
    # donate params/opt-state (train) or cache (decode): in/out buffers alias
    donate = {"train": (0, 1), "prefill": (2,), "decode": (1,)}[shape.mode]
    lowered = jax.jit(step, donate_argnums=donate).lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    report = rl.analyze(
        compiled, arch=arch, shape=shape_name,
        mesh_name="multi" if multi_pod else "single",
        chips=mesh.devices.size, cfg=cfg, params_sds=p_sds, tokens=tokens,
        mode=shape.mode, strategy=info.strategy,
        flops_global=flops_g, bytes_global=bytes_g)
    mem = compiled.memory_analysis()
    result = report.to_dict()
    result.update({
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "arg_bytes_per_device": float(getattr(mem, "argument_size_in_bytes", 0)),
        "temp_bytes_per_device": float(getattr(mem, "temp_size_in_bytes", 0)),
        "ok": True,
    })
    if verbose:
        print(f"[{arch} x {shape_name} x {result['mesh']}] "
              f"strategy={info.strategy} "
              f"mem={result['peak_mem_per_device_gib']:.2f}GiB/dev "
              f"compute={report.compute_s:.4f}s memory={report.memory_s:.4f}s "
              f"coll={report.collective_s:.4f}s dom={report.dominant} "
              f"useful={report.useful_flops_ratio:.2f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return result


def dryrun_protocol(arch: str, algorithm: str = "fedp2p", *,
                    multi_pod: bool = False, local_steps: int = 4,
                    client_batch: int = 2, seq_len: int = 4096,
                    num_clusters: int = 4, codec: str = "none",
                    mix_path: str = "dense",
                    verbose: bool = True):
    """Lower + compile one federated round of ANY registered protocol
    (``repro.protocols``) on the production mesh: one client group per
    data-axis slice, the protocol's grouped-psum ``psum_mix`` lowering for
    the sync step. The fedp2p row is the paper-representative entry in the
    roofline study; fedavg / gossip / gossip_async price the registry's
    other traffic patterns on identical hardware. ``codec`` lowers the
    quantized-exchange wire (``repro.compression``) into the same program
    and stamps the artifact with the codec-adjusted analytic wire bytes.
    ``mix_path`` != "dense" additionally lowers the protocol's
    structured-sparse ``mixing_spec`` fast path at production (D,
    n_params) scale, verifies the lowered program materializes no [D, D]
    operator, and stamps its analytic cost into the artifact."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import compression, protocols
    from repro.config import FLConfig
    from repro.core.fedp2p import make_federated_round
    proto = protocols.get(algorithm)
    codec_obj = compression.as_codec(codec)
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    info = make_mesh_info(cfg, mesh)
    model = build_model(cfg)
    D = info.dp_size
    fl = FLConfig(num_clusters=num_clusters, lr=0.01)

    dp = info.dp_axes
    dspec = dp if len(dp) > 1 else dp[0]

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    import jax.numpy as jnp
    p_shapes = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                              jax.random.key(0))
    f_params = jax.tree.map(
        lambda s: sds((D,) + s.shape, s.dtype,
                      P(*((dspec,) + (None,) * len(s.shape)))), p_shapes)
    out_specs = (jax.tree.map(lambda s: s.sharding, f_params),
                 NamedSharding(mesh, P()))
    round_fn = make_federated_round(model, fl, D, local_steps,
                                    algorithm=algorithm,
                                    out_shardings=out_specs, mesh_info=info,
                                    codec=codec_obj, mix_path=mix_path)
    bshape = (D, local_steps, client_batch, seq_len)
    batches = {"tokens": sds(bshape, jnp.int32, P(dspec, None, None, None)),
               "labels": sds(bshape, jnp.int32, P(dspec, None, None, None))}
    survive = sds((D,), jnp.float32, P(dspec))
    key = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=NamedSharding(mesh, P())),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)))

    t0 = time.time()
    flops_g, bytes_g = rl.program_cost(
        lambda fp, b, s, k: round_fn(fp, b, s, k, do_global_sync=True),
        f_params, batches, survive, key)
    lowered = round_fn.lower(f_params, batches, survive, key,
                             do_global_sync=True)
    compiled = lowered.compile()
    tokens = D * local_steps * client_batch * seq_len
    L_eff = int(proto.mesh_cluster_ids(D, fl).max()) + 1
    report = rl.analyze(
        compiled, arch=f"{arch}+{algorithm}", shape=f"round_{seq_len}",
        mesh_name="multi" if multi_pod else "single",
        chips=mesh.devices.size, cfg=cfg, params_sds=p_shapes, tokens=tokens,
        mode="train", strategy=f"{algorithm}(D={D},L={L_eff})",
        flops_global=flops_g, bytes_global=bytes_g)
    result = report.to_dict()
    mem = compiled.memory_analysis()
    # codec-adjusted analytic §3.2 wire cost of this round on the pod model
    from repro.core.comm_model import tpu_comm_params
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(p_shapes))
    cp = tpu_comm_params(4.0 * n_params).with_codec(codec_obj)
    result.update({"ok": True, "protocol": algorithm,
                   "codec": codec_obj.name,
                   "mix_path": mix_path,
                   "bits_per_param": codec_obj.bits_per_param(),
                   "wire_bytes_per_client": cp.wire_bytes,
                   "comm_model_h_s": proto.comm_time(cp, D),
                   "compile_s": round(time.time() - t0, 1),
                   "arg_bytes_per_device": float(mem.argument_size_in_bytes),
                   "temp_bytes_per_device": float(mem.temp_size_in_bytes)})
    if mix_path != "dense":
        result.update(_lower_sparse_mix(proto, fl, D, n_params))
    if verbose:
        print(f"[{arch}+{algorithm} x {result['mesh']}] "
              f"mem={result['peak_mem_per_device_gib']:.2f}GiB/dev "
              f"compute={report.compute_s:.4f}s memory={report.memory_s:.4f}s "
              f"coll={report.collective_s:.4f}s dom={report.dominant} "
              f"useful={report.useful_flops_ratio:.2f}")
    return result


def _lower_sparse_mix(proto, fl, D: int, n_params: int) -> dict:
    """Lower the protocol's structured-sparse mixing fast path at
    production scale — flat [D, n_params] buffers through the
    ``mixing_spec`` kernels — and stamp (a) that the lowered program
    materializes NO [D, D] operator (the O(D²) dense matrix is gone from
    the jaxpr, not just unexecuted) and (b) its analytic FLOP/byte cost
    next to the dense oracle's for the roofline artifact."""
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.walker import materializes_shape
    from repro.protocols import apply_spec_flat, make_context

    ids = proto.mesh_cluster_ids(D, fl)

    def ctx_of(key):
        return make_context(
            key=key, survive=jnp.ones((D,), jnp.float32),
            counts=jnp.ones((D,), jnp.float32),
            cluster_ids=jnp.asarray(ids),
            num_clusters=int(np.asarray(ids).max()) + 1,
            do_global_sync=True)

    if proto.mixing_spec(ctx_of(jax.random.PRNGKey(0))) is None:
        return {"mix_path_lowered": "dense",
                "sparse_mix_available": False}

    def sparse_mix(flat_new, flat_old, key):
        return apply_spec_flat(proto.mixing_spec(ctx_of(key)),
                               flat_new, flat_old)

    sds = jax.ShapeDtypeStruct((D, n_params), jnp.float32)
    key_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(sparse_mix)(sds, sds, key_sds)
    return {"mix_path_lowered": "sparse",
            "sparse_mix_available": True,
            "sparse_mix_no_dense_matrix":
                not materializes_shape(jaxpr, (D, D)),
            # analytic per-round mixing cost (the jaxpr cost model does not
            # price segment/gather ops): weighted combine + segment reduce
            # + gather-broadcast ~ O(D·n), vs the dense oracle's two
            # [D, D] @ [D, n] contractions and its [D, D] f32 operands
            "sparse_mix_flops": 6.0 * D * n_params,
            "sparse_mix_bytes": 3.0 * 4.0 * D * n_params,
            "dense_mix_flops": 4.0 * D * D * n_params,
            "dense_mix_matrix_bytes": 2.0 * 4.0 * D * D}


def dryrun_sampled(algorithm: str, *, arch: str = "qwen2-1.5b",
                   num_enrolled: int = 10 ** 6, active: int = 1024,
                   num_clusters: int = 4, codec: str = "none",
                   verbose: bool = True) -> dict:
    """Lower ONE sampled-participation round of a registered protocol at
    production scale — D=10^6 clients ENROLLED, K=1024 ACTIVE — and stamp
    the K-priced analytic cost into the roofline artifact.

    The window mix is traced (``jax.make_jaxpr``, nothing executes) over
    the [K, n_params] active window exactly as ``SampledEngine`` lowers it
    (structured ``mixing_spec`` kernels when the protocol has them, the
    [K, K] oracle otherwise), then audited: no array in the program may
    touch the enrolled dimension — the static proof that per-round compute
    is D-independent. Cost stamps price the round at K (what a sampled
    round actually moves/computes) with the resident-D figures alongside
    for contrast; state bytes contrast the resident [D, n] footprint the
    store replaces against the [K, n] window the round touches."""
    import jax.numpy as jnp
    import numpy as np

    from repro import compression, protocols
    from repro.analysis.walker import find_avals
    from repro.config import FLConfig
    from repro.core.comm_model import tpu_comm_params
    from repro.protocols import (
        apply_spec_flat, make_context, validate_participation,
    )
    from repro.kernels import ops as kernel_ops

    proto = protocols.get(algorithm)
    codec_obj = compression.as_codec(codec)
    cfg = get_config(arch)
    model = build_model(cfg)
    import jax.numpy as jnp  # noqa: F811
    p_shapes = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                              jax.random.key(0))
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(p_shapes))
    D, K = int(num_enrolled), int(active)
    fl = FLConfig(num_clusters=num_clusters,
                  devices_per_cluster=max(1, K // num_clusters),
                  participation=K, lr=0.01, num_enrolled=D,
                  participants_per_round=K)
    K = validate_participation(fl, proto)
    ids = proto.mesh_cluster_ids(K, fl)
    L = int(np.asarray(ids).max()) + 1

    def ctx_of(key, active_ids):
        return make_context(
            key=key, survive=jnp.ones((K,), jnp.float32),
            counts=jnp.ones((K,), jnp.float32),
            cluster_ids=jnp.asarray(ids), num_clusters=L,
            do_global_sync=True, active_ids=active_ids, num_enrolled=D)

    have_spec = proto.mixing_spec(
        ctx_of(jax.random.PRNGKey(0), jnp.arange(K))) is not None

    def window_mix(flat_new, flat_old, active_ids, key):
        ctx = ctx_of(key, active_ids)
        if have_spec:
            return apply_spec_flat(proto.mixing_spec(ctx),
                                   flat_new, flat_old)
        M_new, M_old = proto.mixing_matrix(ctx)
        return kernel_ops.fed_mix_flat(M_new, M_old, flat_new, flat_old)

    t0 = time.time()
    sds = jax.ShapeDtypeStruct((K, n_params), jnp.float32)
    ids_sds = jax.ShapeDtypeStruct((K,), jnp.int32)
    key_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(window_mix)(sds, sds, ids_sds, key_sds)
    touches = find_avals(
        jaxpr, lambda aval: any(int(s) == D
                                for s in getattr(aval, "shape", ())),
        max_sites=1)

    cp = tpu_comm_params(4.0 * n_params).with_codec(codec_obj)
    result = {
        "ok": True, "protocol": algorithm, "arch": arch,
        "shape": f"sampled_D{D}_K{K}", "codec": codec_obj.name,
        "participation": "sampled",
        "num_enrolled": D, "active": K, "num_clusters": L,
        "mix_path_lowered": "sparse" if have_spec else "dense",
        # the static residency proof: the traced window program holds no
        # D-sized array — per-round cost cannot depend on enrollment
        "window_no_population_array": not touches,
        # K-priced §3.2 analytics: what one SAMPLED round actually costs...
        "comm_model_h_s": proto.comm_time(cp, K),
        "window_mix_flops": 6.0 * K * n_params,
        "window_state_bytes": 4.0 * K * n_params,
        "wire_bytes_per_client": cp.wire_bytes,
        # ...with the resident-D figures alongside for contrast: the state
        # the store replaces and the round a resident engine would price
        "comm_model_h_s_resident": proto.comm_time(cp, D),
        "resident_state_bytes": 4.0 * D * n_params,
        "trace_s": round(time.time() - t0, 1),
    }
    if verbose:
        print(f"[{arch}+{algorithm} sampled D={D:.0e} K={K}] "
              f"mix={result['mix_path_lowered']} "
              f"no_pop_array={result['window_no_population_array']} "
              f"h(K)={result['comm_model_h_s']:.4f}s "
              f"h(D)={result['comm_model_h_s_resident']:.4f}s "
              f"window={result['window_state_bytes'] / 2**30:.1f}GiB "
              f"resident={result['resident_state_bytes'] / 2**40:.1f}TiB")
    return result


def dryrun_fedp2p(arch: str, **kwargs):
    """Back-compat alias: the paper-protocol row of ``dryrun_protocol``."""
    return dryrun_protocol(arch, "fedp2p", **kwargs)


def _opt_sharding(leaf_sds, p_sds, info):
    """Match m/v leaves to param shardings by shape; scalars replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if leaf_sds.ndim == 0:
        return NamedSharding(info.mesh, P())
    for _, p in jax.tree_util.tree_flatten_with_path(p_sds)[0]:
        if p.shape == leaf_sds.shape:
            return NamedSharding(info.mesh, p.sharding.spec)
    return NamedSharding(info.mesh, P())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fedp2p", action="store_true",
                    help="shorthand for --protocol fedp2p")
    ap.add_argument("--protocol", default=None, metavar="NAME",
                    help="lower one federated round of a registered "
                         "protocol (or 'all') instead of the train/serve "
                         "entry points")
    ap.add_argument("--codec", default="none", metavar="NAME",
                    help="repro.compression codec lowered into the "
                         "federated round (--protocol runs only)")
    ap.add_argument("--mix-path", default="dense", dest="mix_path",
                    choices=("dense", "sparse", "auto"),
                    help="mixing lowering stamped into the round; 'sparse' "
                         "also lowers the structured MixingSpec fast path "
                         "at production (D, n_params) scale and verifies "
                         "it materializes no [D, D] operator "
                         "(--protocol runs only)")
    ap.add_argument("--participation", choices=("resident", "sampled"),
                    default="resident",
                    help="'sampled' lowers one K-active-of-D-enrolled "
                         "round of every requested protocol at production "
                         "shapes (default D=10^6, K=1024) with K-priced "
                         "analytic cost stamped into the artifact")
    ap.add_argument("--enrolled", type=int, default=10 ** 6, metavar="D",
                    help="enrolled population for --participation sampled")
    ap.add_argument("--active", type=int, default=1024, metavar="K",
                    help="active window for --participation sampled")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.fedp2p and not args.protocol:
        args.protocol = "fedp2p"
    if args.participation == "sampled":
        from repro import protocols
        algos = (list(protocols.names())
                 if args.protocol in (None, "all")
                 else [protocols.get(args.protocol).name])
        results, failures = [], []
        for algo in algos:
            try:
                results.append(dryrun_sampled(
                    algo, arch=args.arch or "qwen2-1.5b",
                    num_enrolled=args.enrolled, active=args.active,
                    codec=args.codec))
            except Exception as e:  # noqa: BLE001 — report all failures
                traceback.print_exc()
                failures.append((algo, "sampled", repr(e)))
                results.append({"protocol": algo,
                                "participation": "sampled",
                                "ok": False, "error": repr(e)})
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
            print(f"wrote {args.out}")
        if failures:
            print(f"FAILURES ({len(failures)}):")
            for f in failures:
                print("  ", f)
        sys.exit(1 if failures else 0)
    if args.protocol:
        from repro import protocols
        algos = (list(protocols.names()) if args.protocol == "all"
                 else [protocols.get(args.protocol).name])
        results, failures = [], []
        for multi in {"single": [False], "multi": [True],
                      "both": [False, True]}[args.mesh]:
            for algo in algos:
                mesh_name = "multi" if multi else "single"
                try:
                    results.append(dryrun_protocol(args.arch or "qwen2-1.5b",
                                                   algo, multi_pod=multi,
                                                   codec=args.codec,
                                                   mix_path=args.mix_path))
                except Exception as e:  # noqa: BLE001 — report all failures
                    traceback.print_exc()
                    failures.append((algo, mesh_name, repr(e)))
                    results.append({
                        "arch": f"{args.arch or 'qwen2-1.5b'}+{algo}",
                        "shape": "round", "mesh": mesh_name,
                        "protocol": algo, "ok": False, "error": repr(e)})
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
        if failures:
            print(f"FAILURES ({len(failures)}):")
            for f in failures:
                print("  ", f)
        sys.exit(1 if failures else 0)

    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results, failures = [], []
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    results.append(dryrun_one(arch, shape, multi_pod=multi))
                except Exception as e:  # noqa: BLE001 — report all failures
                    traceback.print_exc()
                    failures.append((arch, shape, "multi" if multi else "single",
                                     repr(e)))
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "multi" if multi else "single",
                                    "ok": False, "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    if failures:
        print(f"FAILURES ({len(failures)}):")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print(f"all {len(results)} dry-runs compiled OK")


if __name__ == "__main__":
    main()
