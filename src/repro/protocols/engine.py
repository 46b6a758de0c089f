"""Unified round engines: one RoundContext-driven loop for both execution
layers.

``DenseEngine`` (simulator / CPU oracle: the paper's own model classes,
dense [P, P] mixing) and ``MeshEngine`` (production shard_map: one client
per data-axis slice, grouped psums) drive ANY registered protocol through
the same per-round recipe —

    build RoundContext  ->  local training  ->  protocol mixing

— and both expose ``run_rounds``, which compiles the WHOLE T-round training
loop into a single ``jax.lax.scan`` with on-device metric buffers. That
eliminates the per-round Python dispatch and per-metric ``float()`` host
syncs of the old ``Simulator.run`` loop: one jitted program per (protocol,
T) instead of 3T host round-trips. ``run_rounds`` is round-for-round
IDENTICAL to driving ``round_fn`` (+ ``evaluate``) from Python — pinned
bit-for-bit by tests/test_engine.py.

Because every round builds a fresh ``RoundContext`` (with a per-round PRNG
key and round index), stochastic protocols like ``gossip_async`` get new
mixing structure each scan iteration on both engines — the thing the old
positional API could not express on the production path.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compression
from repro import faults as fault_lib
from repro.config import FLConfig
from repro.configs.paper_models import PaperNetConfig
from repro.core.straggler import straggler_mask
from repro.core.topology import Topology
from repro.kernels import ops as kernel_ops
from repro.models.paper_nets import (
    init_paper_net, paper_net_accuracy, paper_net_loss,
)
from repro.protocols.base import Protocol, get
from repro.protocols.context import make_context
from repro.protocols.spec import apply_spec_flat

MIX_PATHS = ("dense", "sparse", "auto")


def _check_mix_path(mix_path: str) -> str:
    if mix_path not in MIX_PATHS:
        raise ValueError(f"unknown mix_path {mix_path!r}; expected one of "
                         f"{', '.join(MIX_PATHS)}")
    return mix_path


def _resolve_spec(proto: Protocol, ctx, mix_path: str):
    """The one mix_path dispatch rule all engines share: the protocol's
    structured MixingSpec unless the path is 'dense'; 'sparse' refuses to
    silently fall back when no spec exists."""
    if mix_path == "dense":
        return None
    spec = proto.mixing_spec(ctx)
    if spec is None and mix_path == "sparse":
        raise ValueError(
            f"protocol {proto.name!r} provides no mixing_spec; "
            "mix_path='sparse' is unavailable (use 'auto' or 'dense')")
    return spec


def mix_flat(proto: Protocol, flat_new, flat_old, ctx, codec_state, *,
             mix_path: str, codec, use_pallas):
    """One mixing application on a packed [P, sum(sizes)] buffer — the
    shared seam of ``DenseEngine`` (resident rounds) and ``SampledEngine``
    (active-window rounds): structured-spec kernels on the sparse path,
    the dense (M_new, M_old) contraction otherwise; the codec wire sits
    identically in front of both. Always returns ``(flat, codec_state)``."""
    spec = _resolve_spec(proto, ctx, mix_path)
    if spec is not None:
        if codec is None:
            out = apply_spec_flat(spec, flat_new, flat_old,
                                  use_pallas=use_pallas)
            return out, codec_state
        return apply_spec_flat(
            spec, flat_new, flat_old, codec=codec, codec_state=codec_state,
            key=jax.random.fold_in(ctx.key, 0x636F6465),
            use_pallas=use_pallas)
    M_new, M_old = proto.mixing_matrix(ctx)
    if codec is None:
        out = kernel_ops.fed_mix_flat(M_new, M_old, flat_new, flat_old,
                                      use_pallas=use_pallas)
        return out, codec_state
    return kernel_ops.fed_mix_flat(
        M_new, M_old, flat_new, flat_old, codec=codec,
        codec_state=codec_state, key=jax.random.fold_in(ctx.key, 0x636F6465),
        use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# Client-local training (vmapped) — simulator / paper-net path
# ---------------------------------------------------------------------------

def make_local_trainer(net: PaperNetConfig, fl: FLConfig):
    """Returns f(params, cx, cy, cmask, key) -> (params', mean_loss) for ONE
    client; callers vmap it over participants."""
    bs = fl.batch_size

    @jax.named_scope("local_train")
    def local_train(params, cx, cy, cmask, key):
        n_max = cy.shape[0]
        steps = max(1, -(-n_max // bs))               # ceil

        def epoch(carry, ekey):
            params, loss_sum, cnt = carry
            perm = jax.random.permutation(ekey, n_max)

            def step(carry, s):
                params, loss_sum, cnt = carry
                idx = jnp.take(perm, (jnp.arange(bs) + s * bs) % n_max)
                batch = {"x": cx[idx], "y": cy[idx], "mask": cmask[idx]}
                loss, grads = jax.value_and_grad(paper_net_loss)(params, batch, net)
                params = jax.tree.map(
                    lambda p, g: p - fl.lr * g.astype(p.dtype), params, grads)
                return (params, loss_sum + loss, cnt + 1), None

            (params, loss_sum, cnt), _ = jax.lax.scan(
                step, (params, loss_sum, cnt), jnp.arange(steps))
            return (params, loss_sum, cnt), None

        ekeys = jax.random.split(key, fl.local_epochs)
        (params, loss_sum, cnt), _ = jax.lax.scan(
            epoch, (params, jnp.zeros(()), jnp.zeros(())), ekeys)
        return params, loss_sum / jnp.maximum(cnt, 1.0)

    return local_train


def _gather_clients(data_dev, sel):
    return (jnp.take(data_dev["x"], sel, axis=0),
            jnp.take(data_dev["y"], sel, axis=0),
            jnp.take(data_dev["mask"], sel, axis=0),
            jnp.take(data_dev["counts"], sel, axis=0))


# ---------------------------------------------------------------------------
# Dense engine — simulator / oracle path
# ---------------------------------------------------------------------------

class DenseEngine:
    """Drives one protocol's rounds through its mixing operator on the
    paper's own model classes (§4.2), on a PACKED federated state: the
    [P, ...] client pytree lives as one flat [P, sum(sizes)] buffer
    (``kernels.ops.pack_tree`` layout) across the whole round — and across
    the whole ``run_rounds`` scan — so mixing, the codec wire, and
    error-feedback all run on the flat carry while local training vmaps
    over unpacked *views*. The global model is packed once per
    ``run_rounds`` call, not once per sub-round mix.

    One round (``round_fn``):

      1. partition  — the protocol picks P participants and their clusters;
      2. local SGD  — vmapped over participants;
      3. mixing     — via a fresh ``RoundContext``: the protocol's
         structured ``mixing_spec`` fast path (O(P·n) segment-reduce /
         permutation-gather, no [P, P] operator) when available and
         ``mix_path`` allows, else the dense (M_new, M_old) oracle; with
         ``sync_period > 1`` intermediate sub-rounds mix WITHOUT the
         global step;
      4. collapse   — the reported global model is the mean over the mixed
         client models (exact for server protocols, whose rows agree; the
         standard consensus-average readout for gossip).

    ``run_rounds(params, key, T)`` scan-compiles T rounds + per-round
    evaluation into one program with on-device [T] metric buffers and a
    donated flat carry.
    """

    def __init__(self, net: PaperNetConfig, data_dev: Dict, fl: FLConfig,
                 proto: Protocol, topology: Optional[Topology] = None, *,
                 mix_use_pallas: Optional[bool] = None, codec=None,
                 mix_path: Optional[str] = None, faults=None):
        self.net, self.fl, self.proto = net, fl, proto
        self.topology = topology
        self.data_dev = data_dev
        #: backend for the fused mixing primitive behind ``apply_mixing``:
        #: None = auto (Pallas on TPU, jnp oracle on CPU); True forces the
        #: kernel (interpret mode off-TPU); False forces the jnp oracle
        self.mix_use_pallas = mix_use_pallas
        #: which mixing lowering runs (default ``fl.mix_path``): "dense" =
        #: the [P, P] matrix oracle (bit-for-bit the pre-spec program),
        #: "sparse" = the protocol's structured ``mixing_spec`` kernels
        #: (raises if the protocol provides none), "auto" = sparse whenever
        #: a spec exists, dense otherwise
        self.mix_path = _check_mix_path(mix_path or fl.mix_path)
        #: quantized-exchange wire (``repro.compression`` name or Codec);
        #: stored in active form — None/"none" keeps every round bit-for-bit
        #: the uncompressed program. Stateful codecs (error feedback) make
        #: ``round_fn`` take/return a [P, sum(sizes)] f32 residual that
        #: ``run_rounds`` threads through the scan carry.
        self.codec = compression.active(codec)
        #: injected-failure schedule (``repro.faults.FaultPlan``); stored
        #: in active form — None/empty plans keep every round bit-for-bit
        #: the pre-fault program (the contracts baseline pins this, same
        #: discipline as ``codec="none"``). Active plans make
        #: ``run_rounds`` fold per-round dropout into the survive mask,
        #: poison flagged uploads, and run the scatter-back guard, with
        #: ``dropped``/``rejected_rows`` counters riding the scan's
        #: metric buffers.
        self.faults = fault_lib.active(faults)
        local_train = make_local_trainer(net, fl)
        self._vtrain = jax.vmap(local_train, in_axes=(None, 0, 0, 0, 0))
        self._vtrain_per = jax.vmap(local_train, in_axes=(0, 0, 0, 0, 0))
        self._veval = jax.vmap(self._eval_one, in_axes=(None, 0, 0, 0))
        #: jitted (params, key[, round_index]) -> (params', mean_loss)
        self.round_fn = jax.jit(self._round)
        #: jitted params -> (sample-weighted acc, client-mean acc)
        self.evaluate = jax.jit(self._eval)
        self._run_cache: Dict[int, callable] = {}

    def init_params(self, seed: int = 0):
        return init_paper_net(jax.random.PRNGKey(seed), self.net)

    # -- evaluation ----------------------------------------------------
    def _eval_one(self, params, tx, ty, tm):
        acc = paper_net_accuracy(params, {"x": tx, "y": ty, "mask": tm},
                                 self.net)
        return acc, jnp.sum(tm)

    def _eval(self, params):
        accs, ns = self._veval(params, self.data_dev["test_x"],
                               self.data_dev["test_y"],
                               self.data_dev["test_mask"])
        sample_weighted = jnp.sum(accs * ns) / jnp.maximum(jnp.sum(ns), 1.0)
        client_mean = jnp.mean(accs)
        return sample_weighted, client_mean

    # -- packed-state helpers ------------------------------------------
    def _pack_params(self, params):
        """Pack ONE global model into its flat [sum(sizes)] row + the
        TreeSpec that unpacks any [..., sum(sizes)] buffer back to
        [..., *leaf_shape] views."""
        flat, spec = kernel_ops.pack_tree(
            jax.tree.map(lambda p: p[None], params))
        return flat[0], spec

    def _mix_flat(self, flat_new, flat_old, ctx, cstate):
        """One mixing application on the packed [P, sum(sizes)] carry (the
        module-level ``mix_flat`` seam with this engine's knobs bound)."""
        return mix_flat(self.proto, flat_new, flat_old, ctx, cstate,
                        mix_path=self.mix_path, codec=self.codec,
                        use_pallas=self.mix_use_pallas)

    # -- one round -----------------------------------------------------
    def _round_rows(self, spec, flat_params, key, round_index=0,
                    codec_state=None, fault=None):
        """One protocol round on the packed carry, stopping BEFORE the
        consensus collapse: ``flat_params`` is the flat [sum(sizes)] global
        model, ``spec`` its TreeSpec. The round's federated state stays a
        flat [P, sum(sizes)] buffer end-to-end — the round-start state is a
        broadcast of the carry (packed once per run, not once per sub-round
        mix), every mixing / codec / error-feedback application runs on the
        flat buffer, and local training vmaps over unpacked views. Returns
        the mixed PER-CLIENT rows ``(flat_mixed [P, sum(sizes)], losses,
        codec_state)`` — the resident reference the sampled window round is
        pinned against bit-for-bit.

        ``fault`` (active plans only) is this round's ``(drop [P], flag
        [P], mode [P])`` triple from ``FaultPlan.dense_arrays``: dropped
        clients leave the survive mask for every sub-round, flagged
        clients' FINAL uploads are poisoned on the wire (``corrupt_flat``),
        detected non-finite rows are excluded from the mix like stragglers
        (and their bytes sanitized — a masked NaN row would still poison a
        dense contraction through 0 * nan), and the scatter-back guard
        reverts any rejected row to its pre-round value. The return then
        grows a 4th element: ``{'dropped', 'rejected_rows'}`` int32
        counters. ``fault=None`` traces the exact pre-fault program."""
        proto, fl = self.proto, self.fl
        P = proto.num_participants(fl)
        L = proto.num_clusters(fl)
        k_sel, k_tr, k_str, k_mix = jax.random.split(key, 4)
        sel, cids = proto.partition(k_sel, fl, self.topology)
        # gathered ONCE per round: the selection is fixed across sub-rounds
        cx, cy, cm, counts = _gather_clients(self.data_dev, sel)
        smask = straggler_mask(k_str, P, fl.straggler_rate)
        drop_t = flag_t = mode_t = None
        if fault is not None:
            drop_t, flag_t, mode_t = fault
            smask = smask * (1.0 - drop_t)
        flat_old = jnp.broadcast_to(flat_params[None],
                                    (P, flat_params.shape[0]))

        def ctx_for(sub_round: int, sync: bool, survive=None):
            return make_context(
                key=jax.random.fold_in(k_mix, sub_round),
                round_index=round_index,
                survive=smask if survive is None else survive,
                counts=counts, cluster_ids=cids, num_clusters=L,
                do_global_sync=sync, topology=self.topology,
                fault_drop=drop_t)

        flat_cp, losses = None, jnp.zeros(())
        cstate = codec_state
        sub_rounds = max(1, fl.sync_period)
        for r in range(sub_rounds):
            keys = jax.random.split(jax.random.fold_in(k_tr, r), P)
            if flat_cp is None:
                params0 = kernel_ops.unpack_tree(flat_params, spec)
                cp, losses = self._vtrain(params0, cx, cy, cm, keys)
            else:
                flat_start, cstate = self._mix_flat(flat_cp, flat_old,
                                                    ctx_for(r, False), cstate)
                start = kernel_ops.unpack_tree(flat_start, spec)
                cp, losses = self._vtrain_per(start, cx, cy, cm, keys)
            flat_cp = kernel_ops.pack_tree(cp)[0]

        if fault is None:
            flat_mixed, cstate = self._mix_flat(
                flat_cp, flat_old, ctx_for(sub_rounds, True), cstate)
            return flat_mixed, losses, cstate
        # the fault wire sits on the FINAL upload: poison flagged rows,
        # then receive-side validation — the finite check plus the
        # integrity flag (a bit-flipped row stays finite; without the
        # flag its huge-exponent values would enter the mix average and
        # contaminate every OTHER row). Detected rows are excluded from
        # the mix like stragglers and their bytes sanitized so 0 * nan
        # never reaches the contraction.
        flat_cp = fault_lib.corrupt_flat(flat_cp, flag_t, mode_t)
        ok = jnp.all(jnp.isfinite(flat_cp), axis=1) & (flag_t <= 0)
        flat_cp = jnp.where(ok[:, None], flat_cp, flat_old)
        flat_mixed, cstate = self._mix_flat(
            flat_cp, flat_old,
            ctx_for(sub_rounds, True,
                    survive=smask * ok.astype(smask.dtype)), cstate)
        # scatter-back guard: no flagged or non-finite row survives into
        # the carry — rejected clients keep their pre-round value
        guarded, bad = fault_lib.guard_flat(flat_mixed, flat_old, flag_t)
        counters = {"dropped": jnp.sum(drop_t).astype(jnp.int32),
                    "rejected_rows": jnp.sum(bad).astype(jnp.int32)}
        return guarded, losses, cstate, counters

    def _round_flat(self, spec, flat_params, key, round_index=0,
                    codec_state=None, fault=None):
        """``_round_rows`` + the consensus collapse: the reported global
        model is the mean over the mixed client rows. Returns ``(flat',
        mean_loss[, codec_state])``; with ``fault`` the per-round counter
        dict rides along as the last element."""
        out = self._round_rows(
            spec, flat_params, key, round_index, codec_state, fault=fault)
        flat_mixed, losses, cstate = out[:3]
        # consensus collapse in each LEAF's dtype (mean_packed), exactly as
        # the unpacked program computed it — a whole-buffer mean would
        # accumulate bf16 leaves in the promoted dtype
        new_flat = kernel_ops.mean_packed(flat_mixed, spec)
        base = ((new_flat, jnp.mean(losses)) if self.codec is None
                else (new_flat, jnp.mean(losses), cstate))
        return base if fault is None else base + (out[3],)

    def _round(self, params, key, round_index=0, codec_state=None):
        """One protocol round on pytree params (the jitted ``round_fn``
        API): pack, run the flat round, unpack. Without a codec:
        ``(params', mean_loss)`` — value-identical to the pre-packed-state
        program. With one, every mixing application puts the freshly-
        trained client models through the lossy wire and the return grows
        a third element: the threaded error-feedback residual."""
        flat, spec = self._pack_params(params)
        out = self._round_flat(spec, flat, key, round_index, codec_state)
        params_out = kernel_ops.unpack_tree(out[0], spec)
        if self.codec is None:
            return params_out, out[1]
        return params_out, out[1], out[2]

    # -- the scan-compiled training loop -------------------------------

    #: argnums of ``_build_run``'s closure that ``run_rounds`` donates on
    #: accelerators: the freshly-packed flat carry (invar 0). The
    #: donation-integrity analysis rule audits this contract.
    _donate_argnums = (0,)

    def _build_run(self, spec, T: int, eval_every: int):
        """The un-jitted T-round program ``run(flat, key)`` behind
        ``run_rounds`` — exposed so ``repro.analysis`` can trace the full
        scan-compiled training loop (``jax.make_jaxpr``) without executing
        it. ``spec`` is the TreeSpec of the packed carry the closure
        captures; arg 0 is the donation target (``_donate_argnums``)."""

        def eval_at(flat, t):
            p = kernel_ops.unpack_tree(flat, spec)
            if eval_every == 1:
                return self._eval(p)
            return jax.lax.cond(
                jnp.logical_or((t + 1) % eval_every == 0, t == T - 1),
                self._eval,
                lambda _: (jnp.zeros(()), jnp.zeros(())), p)

        if self.faults is not None:
            return self._build_run_faulted(spec, T, eval_at)

        if self.codec is None:
            def body(carry, t):
                flat, key = carry
                key, kr = jax.random.split(key)
                flat, loss = self._round_flat(spec, flat, kr, t)
                acc_w, acc_m = eval_at(flat, t)
                return (flat, key), (loss, acc_w, acc_m)

            def run(flat, key):
                (flat, _), (loss, acc_w, acc_m) = jax.lax.scan(
                    body, (flat, key), jnp.arange(T))
                return kernel_ops.unpack_tree(flat, spec), {
                    "train_loss": loss, "acc": acc_w,
                    "acc_client_mean": acc_m}
        else:
            # error-feedback residuals (stateful codecs) ride the scan
            # carry as one [P, sum(sizes)] f32 buffer per participant
            # slot; stateless codecs carry None (an empty pytree).
            def body(carry, t):
                flat, key, cstate = carry
                key, kr = jax.random.split(key)
                flat, loss, cstate = self._round_flat(spec, flat, kr, t,
                                                      cstate)
                acc_w, acc_m = eval_at(flat, t)
                return (flat, key, cstate), (loss, acc_w, acc_m)

            def run(flat, key):
                cstate = self._init_codec_state_flat(flat)
                (flat, _, _), (loss, acc_w, acc_m) = jax.lax.scan(
                    body, (flat, key, cstate), jnp.arange(T))
                return kernel_ops.unpack_tree(flat, spec), {
                    "train_loss": loss, "acc": acc_w,
                    "acc_client_mean": acc_m}

        return run

    def _build_run_faulted(self, spec, T: int, eval_at):
        """The faulted T-round program: the plan's dense per-round
        ``(drop, flag, mode)`` arrays ride the scan as xs alongside the
        round counter, every round runs the fault-wired ``_round_flat``,
        and the metric dict grows the four fault counters ([T] int32;
        ``retries``/``prefetch_fallbacks`` are store-tier counters — zeros
        here, the resident engine has no store)."""
        P = self.proto.num_participants(self.fl)
        drop, flag, mode = self.faults.dense_arrays(T, P)
        fault_xs = (jnp.asarray(drop), jnp.asarray(flag), jnp.asarray(mode))

        def metric_dict(flat, loss, acc_w, acc_m, dropped, rejected):
            zero = jnp.zeros((T,), jnp.int32)
            return kernel_ops.unpack_tree(flat, spec), {
                "train_loss": loss, "acc": acc_w, "acc_client_mean": acc_m,
                "dropped": dropped, "rejected_rows": rejected,
                "retries": zero, "prefetch_fallbacks": zero}

        if self.codec is None:
            def body(carry, xs):
                t, drop_t, flag_t, mode_t = xs
                flat, key = carry
                key, kr = jax.random.split(key)
                flat, loss, counters = self._round_flat(
                    spec, flat, kr, t, fault=(drop_t, flag_t, mode_t))
                acc_w, acc_m = eval_at(flat, t)
                return (flat, key), (loss, acc_w, acc_m,
                                     counters["dropped"],
                                     counters["rejected_rows"])

            def run(flat, key):
                (flat, _), ys = jax.lax.scan(
                    body, (flat, key), (jnp.arange(T),) + fault_xs)
                return metric_dict(flat, *ys)
        else:
            def body(carry, xs):
                t, drop_t, flag_t, mode_t = xs
                flat, key, cstate = carry
                key, kr = jax.random.split(key)
                flat, loss, cstate, counters = self._round_flat(
                    spec, flat, kr, t, cstate,
                    fault=(drop_t, flag_t, mode_t))
                acc_w, acc_m = eval_at(flat, t)
                return (flat, key, cstate), (loss, acc_w, acc_m,
                                             counters["dropped"],
                                             counters["rejected_rows"])

            def run(flat, key):
                cstate = self._init_codec_state_flat(flat)
                (flat, _, _), ys = jax.lax.scan(
                    body, (flat, key, cstate), (jnp.arange(T),) + fault_xs)
                return metric_dict(flat, *ys)

        return run

    def run_rounds(self, params, key, T: int, eval_every: int = 1):
        """Run T rounds as ONE compiled ``lax.scan`` program over the
        PACKED carry: the global model is packed into its flat
        [sum(sizes)] form once here, every round/mix/codec application
        inside the scan operates on flat buffers (training and evaluation
        unpack views), the carry is donated to the compiled program, and
        the final model is unpacked once on the way out. Returns
        (final_params, metrics) with metrics = {'train_loss', 'acc',
        'acc_client_mean'}, each a [T] on-device array; nothing syncs to
        host until the caller reads the buffers. With ``eval_every > 1``
        the accuracy entries are only computed at rounds where
        (t+1) % eval_every == 0 (and the last round) — the other slots are
        zeros the caller must not read.

        Stateful codecs: the error-feedback residual is per-run memory —
        zero-initialized at the start of the scan and internal to it (one
        ``run_rounds`` call == one training run on this engine; drive
        ``round_fn`` directly to thread residuals across calls)."""
        T, eval_every = int(T), max(1, int(eval_every))
        with jax.profiler.TraceAnnotation("fl.run_rounds", rounds=T):
            return self._run_rounds(params, key, T, eval_every)

    def _run_rounds(self, params, key, T: int, eval_every: int):
        flat0, spec = self._pack_params(params)      # packed ONCE per call
        # the compiled run closes over the TreeSpec, so the cache must key
        # on the params *structure* too — two layouts can share a packed
        # width and would otherwise unpack each other's column slices
        cache_key = (T, eval_every, spec)
        if cache_key not in self._run_cache:
            run = self._build_run(spec, T, eval_every)
            # the flat carry is ours (freshly packed) — donate it so the
            # scan state aliases the input buffer instead of copying it
            # (accelerators only: XLA:CPU can't alias and would just warn)
            donate = (() if jax.default_backend() == "cpu"
                      else self._donate_argnums)
            self._run_cache[cache_key] = jax.jit(run, donate_argnums=donate)
        return self._run_cache[cache_key](flat0, key)

    def _init_codec_state_flat(self, flat):
        if self.codec is None or not self.codec.stateful:
            return None
        P = self.proto.num_participants(self.fl)
        return jnp.zeros((P, flat.shape[-1]), jnp.float32)

    def init_codec_state(self, params):
        """Zero error-feedback residual for ``round_fn``/``run_rounds``:
        one f32 row per participant *slot* over the packed param size, or
        ``None`` for stateless codecs. (With random per-round participation
        the residual is slot-indexed — the standard sampled-client
        error-feedback memory.)"""
        if self.codec is None or not self.codec.stateful:
            return None
        P = self.proto.num_participants(self.fl)
        total = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
        return jnp.zeros((P, total), jnp.float32)


# ---------------------------------------------------------------------------
# Sampled engine — persistent store + per-round active window
# ---------------------------------------------------------------------------

class SampledEngine:
    """Drives protocol rounds over a persistent ``ClientStateStore``
    (``protocols.store``): D clients are ENROLLED but only K are ACTIVE per
    round. Each round —

      1. select    — the first-class participation strategy
                     (``fl.participation_strategy``) draws [K] active ids
                     from the D-client population; O(D) vector work that
                     runs OUTSIDE the compiled window program;
      2. gather    — the store yields the active [K, sum(sizes)] rows (and
                     their codec residuals) through the shared
                     ``kernels.ops`` gather seam;
      3. window    — ONE compiled round on [K, sum(sizes)] only: per-row
                     local SGD from each client's OWN persistent state (no
                     broadcast, no consensus collapse), then the
                     spec-lowered mix over the window via the same
                     ``mix_flat`` seam ``DenseEngine`` uses, with the
                     window's RoundContext carrying ``active_ids`` and
                     ``num_enrolled``;
      4. scatter   — mixed rows (and residuals) write back; the store's
                     ``last_round`` staleness counters advance.

    The compiled program never sees D: enrolling 10^6 clients costs
    storage, not compute — per-round compiled cost matches a RESIDENT
    K-client engine (the ``state-residency`` analysis rule and the
    benchmark's sampled sweep pin this).

    With ``active_ids = arange(D)`` (and the store freshly initialized from
    one global model) a window round is bit-for-bit the resident
    ``DenseEngine`` round at matching selections — pinned by
    tests/test_sampled_engine.py.

    ``pipeline_depth`` turns ``run_rounds`` into a software pipeline: at
    depth 1 (default) rounds run serially, exactly the historical program;
    at depth d >= 2 up to d windows are in flight at once — round t+1's
    selection + store prefetch (stage A) and round t's retire/scatter
    (stage C) overlap round t's compiled window (stage B). Results are
    bit-for-bit identical to serial at every depth: id overlaps between
    in-flight rounds are detected on the host id vectors and only the
    conflicting rows are patched from the in-flight outputs (see
    ``_acquire_window``). tests/test_pipeline.py pins this under forced
    collisions.
    """

    def __init__(self, net: PaperNetConfig, data_dev: Dict, fl: FLConfig,
                 proto: Protocol, topology: Optional[Topology] = None, *,
                 mix_use_pallas: Optional[bool] = None, codec=None,
                 mix_path: Optional[str] = None, pipeline_depth: int = 1,
                 faults=None, prefetch_timeout: Optional[float] = None):
        from repro.protocols.base import (
            get_participation, validate_participation)
        self.net, self.fl, self.proto = net, fl, proto
        self.topology = topology
        self.data_dev = data_dev
        self.mix_use_pallas = mix_use_pallas
        self.mix_path = _check_mix_path(mix_path or fl.mix_path)
        self.codec = compression.active(codec)
        #: injected-failure schedule (``repro.faults.FaultPlan``, active
        #: form — None/empty plans keep every round bit-for-bit the
        #: pre-fault program). Active plans route rounds through the
        #: fault-wired window program + scatter-back guard, attach a
        #: ``FaultInjector`` to the store's read/prefetch hooks, and
        #: cold-retry rejected clients via the requeue splice.
        self.faults = fault_lib.active(faults)
        self._injector = (fault_lib.FaultInjector(self.faults)
                          if self.faults is not None else None)
        #: clients whose rows the guard rejected, awaiting their cold
        #: retry: spliced into the tail slots of the next selection
        self._retry_queue: list = []
        #: {round -> counter dict} accumulated by the host driver; drained
        #: into run_rounds' metrics
        self._fault_log: Dict[int, Dict[str, int]] = {}
        #: seconds ``_acquire_window`` waits on a prefetch handle before
        #: falling back to a synchronous gather (None = wait forever,
        #: though a DEAD worker still raises immediately and falls back);
        #: default ``fl.prefetch_timeout`` (0 = forever)
        pt = fl.prefetch_timeout if prefetch_timeout is None else prefetch_timeout
        self.prefetch_timeout = float(pt) if pt else None
        #: prefetches abandoned for a synchronous gather over this engine's
        #: life, counted under every fault plan (None included): a healthy
        #: pipeline keeps it at 0
        self.prefetch_fallbacks = 0
        #: D — enrolled population; K — active window per round
        self.num_enrolled = fl.enrolled
        self.window = validate_participation(fl, proto)
        #: static window cluster layout — the protocol's own mesh
        #: assignment at width K (validate_participation proved it exists)
        self._cluster_ids = proto.mesh_cluster_ids(self.window, fl)
        self._num_clusters = (int(self._cluster_ids.max()) + 1
                              if self._cluster_ids.size else 1)
        self._data_clients = int(
            jax.tree.leaves(data_dev["counts"])[0].shape[0])
        local_train = make_local_trainer(net, fl)
        self._vtrain_per = jax.vmap(local_train, in_axes=(0, 0, 0, 0, 0))
        strategy = get_participation(fl.participation_strategy)
        #: jitted [K]-id draw over the FULL enrolled population — the only
        #: O(D) compute of a round, outside the window program
        self.select_fn = jax.jit(
            lambda k: strategy.select(k, self.num_enrolled, self.window, fl))
        donate = (() if jax.default_backend() == "cpu"
                  else self._donate_argnums)
        #: jitted (flat_win, active_ids, k_tr, k_str, k_mix, round_index
        #: [, codec_state]) -> (flat_mixed, mean_loss[, codec_state]) —
        #: every operand is [K, sum(sizes)] or smaller; D never enters
        self.window_fn = jax.jit(self._window_round, donate_argnums=donate)
        #: fault-wired variant (active plans only): extra [K] drop/flag/
        #: mode operands, returns the rejected-row mask alongside the
        #: guarded window
        self.window_fault_fn = (
            jax.jit(self._window_round_faulted, donate_argnums=donate)
            if self.faults is not None else None)
        #: max windows in flight in ``run_rounds``: 1 = serial (the
        #: historical round-by-round loop, bit-for-bit), d >= 2 pipelines
        #: prefetch/compute/retire across up to d rounds
        self.pipeline_depth = self._check_depth(pipeline_depth)
        self.store = None
        self._spec = None

    @staticmethod
    def _check_depth(depth) -> int:
        depth = int(depth)
        if depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {depth}")
        return depth

    #: donation target of ``window_fn``: the gathered window (invar 0) is a
    #: fresh per-round buffer the store never reads again
    _donate_argnums = (0,)

    # -- store lifecycle -----------------------------------------------
    def init_params(self, seed: int = 0):
        return init_paper_net(jax.random.PRNGKey(seed), self.net)

    def init_store(self, params, *, tier: str = "auto", mesh_info=None,
                   store=None):
        """Enroll D clients, every one starting at ``params``: packs the
        global model once and builds (or adopts) the backing store. The
        TreeSpec captured here is the engine's packed layout for every
        subsequent window round."""
        from repro.protocols import store as store_mod
        flat, spec = kernel_ops.pack_tree(
            jax.tree.map(lambda p: p[None], params))
        self._spec = spec
        if store is not None:
            if store.width != flat.shape[-1]:
                raise ValueError(
                    f"store width {store.width} does not match the packed "
                    f"model width {flat.shape[-1]}")
            self.store = store
        else:
            self.store = store_mod.make_store(
                flat[0], self.num_enrolled, tier=tier, mesh_info=mesh_info,
                residual=self._codec_stateful,
                read_retries=self.fl.store_read_retries,
                read_backoff=self.fl.store_read_backoff)
        if self._injector is not None:
            # the store's read/prefetch hooks fire this engine's plan
            self.store.fault_injector = self._injector
        return self.store

    @property
    def _codec_stateful(self) -> bool:
        return self.codec is not None and self.codec.stateful

    # -- the compiled window round -------------------------------------
    def _window_round(self, flat_win, active_ids, k_tr, k_str, k_mix,
                      round_index=0, codec_state=None):
        """One round on the [K, sum(sizes)] active window. ``flat_win``
        rows are the clients' persistent states: training starts from them
        per-row and mixing falls back to them for stragglers — the sampled
        analogue of ``DenseEngine._round_flat``'s broadcast carry, sharing
        its sub_rounds structure and the ``mix_flat`` seam. Client i's
        dataset is data row ``active_ids[i] % data_clients`` (enrollment
        can exceed the dataset's client count; the shard map is cyclic)."""
        fl, K = self.fl, self.window
        sel_data = active_ids % self._data_clients
        cx, cy, cm, counts = _gather_clients(self.data_dev, sel_data)
        smask = straggler_mask(k_str, K, fl.straggler_rate)
        flat_old = flat_win

        def ctx_for(sub_round: int, sync: bool):
            return make_context(
                key=jax.random.fold_in(k_mix, sub_round),
                round_index=round_index, survive=smask, counts=counts,
                cluster_ids=jnp.asarray(self._cluster_ids),
                num_clusters=self._num_clusters, do_global_sync=sync,
                topology=self.topology, active_ids=active_ids,
                num_enrolled=self.num_enrolled)

        def mix(flat_new, ctx, cstate):
            return mix_flat(self.proto, flat_new, flat_old, ctx, cstate,
                            mix_path=self.mix_path, codec=self.codec,
                            use_pallas=self.mix_use_pallas)

        flat_cp, losses = None, jnp.zeros(())
        cstate = codec_state
        sub_rounds = max(1, fl.sync_period)
        for r in range(sub_rounds):
            keys = jax.random.split(jax.random.fold_in(k_tr, r), K)
            if flat_cp is None:
                flat_start = flat_win
            else:
                flat_start, cstate = mix(flat_cp, ctx_for(r, False), cstate)
            start = kernel_ops.unpack_tree(flat_start, self._spec)
            cp, losses = self._vtrain_per(start, cx, cy, cm, keys)
            flat_cp = kernel_ops.pack_tree(cp)[0]

        flat_mixed, cstate = mix(flat_cp, ctx_for(sub_rounds, True), cstate)
        if self._codec_stateful:
            return flat_mixed, jnp.mean(losses), cstate
        return flat_mixed, jnp.mean(losses)

    def _window_round_faulted(self, flat_win, active_ids, k_tr, k_str,
                              k_mix, drop, flag, mode, round_index=0,
                              codec_state=None):
        """``_window_round`` with the fault wire spliced in (a SEPARATE
        traced program — the fault-free ``window_fn`` stays byte-identical
        to the pre-fault build). ``drop``/``flag``/``mode`` are this
        round's per-SLOT vectors: dropped slots leave the survive mask for
        every sub-round; flagged slots' final uploads are poisoned
        (``corrupt_flat``), detected non-finite rows are excluded from the
        mix like stragglers (bytes sanitized first — a masked NaN would
        still poison a dense contraction), and the scatter-back guard
        reverts every rejected row to its pre-round persistent state.
        Returns ``(guarded, mean_loss, rejected [K] bool[, codec_state])``
        — the host driver requeues rejected clients and withholds their
        staleness touch."""
        fl, K = self.fl, self.window
        sel_data = active_ids % self._data_clients
        cx, cy, cm, counts = _gather_clients(self.data_dev, sel_data)
        smask = straggler_mask(k_str, K, fl.straggler_rate) * (1.0 - drop)
        flat_old = flat_win

        def ctx_for(sub_round: int, sync: bool, survive=None):
            return make_context(
                key=jax.random.fold_in(k_mix, sub_round),
                round_index=round_index,
                survive=smask if survive is None else survive,
                counts=counts, cluster_ids=jnp.asarray(self._cluster_ids),
                num_clusters=self._num_clusters, do_global_sync=sync,
                topology=self.topology, active_ids=active_ids,
                num_enrolled=self.num_enrolled, fault_drop=drop)

        def mix(flat_new, ctx, cstate):
            return mix_flat(self.proto, flat_new, flat_old, ctx, cstate,
                            mix_path=self.mix_path, codec=self.codec,
                            use_pallas=self.mix_use_pallas)

        flat_cp, losses = None, jnp.zeros(())
        cstate = codec_state
        sub_rounds = max(1, fl.sync_period)
        for r in range(sub_rounds):
            keys = jax.random.split(jax.random.fold_in(k_tr, r), K)
            if flat_cp is None:
                flat_start = flat_win
            else:
                flat_start, cstate = mix(flat_cp, ctx_for(r, False), cstate)
            start = kernel_ops.unpack_tree(flat_start, self._spec)
            cp, losses = self._vtrain_per(start, cx, cy, cm, keys)
            flat_cp = kernel_ops.pack_tree(cp)[0]

        # receive-side validation: finite check + integrity flag (a
        # bit-flipped row stays finite — unflagged it would contaminate
        # the mix average for every other row); detected rows are
        # excluded from the mix and sanitized before the contraction
        flat_cp = fault_lib.corrupt_flat(flat_cp, flag, mode)
        ok = jnp.all(jnp.isfinite(flat_cp), axis=1) & (flag <= 0)
        flat_cp = jnp.where(ok[:, None], flat_cp, flat_old)
        flat_mixed, cstate = mix(
            flat_cp,
            ctx_for(sub_rounds, True, survive=smask * ok.astype(smask.dtype)),
            cstate)
        guarded, bad = fault_lib.guard_flat(flat_mixed, flat_old, flag)
        if self._codec_stateful:
            # a rejected row's residual must not absorb this round's
            # feedback either — revert it with the row
            cstate = jnp.where(bad[:, None], codec_state, cstate)
            return guarded, jnp.mean(losses), bad, cstate
        return guarded, jnp.mean(losses), bad

    # -- fault-mode host bookkeeping ------------------------------------

    def _log_fault(self, t: int, **kw) -> None:
        rec = self._fault_log.setdefault(int(t), {
            "dropped": 0, "rejected_rows": 0, "retries": 0,
            "prefetch_fallbacks": 0})
        for k, v in kw.items():
            rec[k] += int(v)

    def _splice_retries(self, ids_np: np.ndarray):
        """Cold retry: clients the guard rejected earlier replace the TAIL
        slots of this selection (skipping ids already selected — being
        picked again IS the retry). Returns the patched id vector."""
        if not self._retry_queue:
            return ids_np
        ids_np = np.array(ids_np, copy=True)
        present = {int(c) for c in ids_np}
        take, rest = [], []
        for c in self._retry_queue:
            if int(c) in present:
                continue                     # selected organically — retried
            if len(take) < ids_np.shape[0]:
                take.append(int(c))
                present.add(int(c))
            else:
                rest.append(int(c))
        self._retry_queue = rest
        if take:
            ids_np[-len(take):] = np.asarray(take, ids_np.dtype)
        return ids_np

    def _fault_vectors(self, spec, ids_np: np.ndarray):
        """This round's per-slot ``(drop, flag, mode)`` vectors: the
        ``FaultSpec`` names ENROLLED client ids; ids not in this window
        simply don't fire."""
        K = ids_np.shape[0]
        drop = np.zeros((K,), np.float32)
        flag = np.zeros((K,), np.float32)
        mode = np.zeros((K,), np.int32)
        if spec is not None:
            pos = {int(c): j for j, c in enumerate(ids_np)}
            for c in spec.drop:
                j = pos.get(int(c))
                if j is not None:
                    drop[j] = 1.0
            for c, m in spec.corrupt:
                j = pos.get(int(c))
                if j is not None:
                    flag[j] = 1.0
                    mode[j] = fault_lib.plan.MODE_CODES[m]
        return drop, flag, mode

    def _requeue_rejected(self, ids_np: np.ndarray, bad_np: np.ndarray,
                          drop: np.ndarray, t: int):
        """Post-guard host bookkeeping shared by the serial and pipelined
        drivers: requeue rejected clients for their cold retry, log the
        round's counters, and return the ids whose staleness may advance
        (accepted AND not injected-dropped)."""
        for c in ids_np[bad_np]:
            if int(c) not in self._retry_queue:
                self._retry_queue.append(int(c))
        self._log_fault(t, dropped=int(drop.sum()),
                        rejected_rows=int(bad_np.sum()))
        return ids_np[(~bad_np) & (drop == 0)]

    # -- host driver ----------------------------------------------------
    def round(self, key, round_index: int = 0):
        """One sampled round against the store: select -> gather -> window
        -> scatter/touch. The key splits exactly as ``DenseEngine._round_
        flat`` (k_sel, k_tr, k_str, k_mix), so at ``num_enrolled ==
        num_clients`` and K == P the same key drives the same selection
        and the same round program. Returns the round's mean train loss
        (device scalar)."""
        if self.store is None:
            raise ValueError("SampledEngine.round: call init_store(params) "
                             "first — the engine has no enrolled state")
        with jax.profiler.TraceAnnotation("fl.round", round=int(round_index)):
            if self.faults is not None:
                return self._round_faulted(key, round_index)
            return self._round_serial(key, round_index)

    def _round_serial(self, key, round_index: int):
        k_sel, k_tr, k_str, k_mix = jax.random.split(key, 4)
        with jax.profiler.TraceAnnotation("fl.select"):
            active_ids = self.select_fn(k_sel)
            ids_np = np.asarray(active_ids)
        flat_win = self.store.gather(ids_np)
        if self._codec_stateful:
            res = self.store.gather_residual(ids_np)
            with jax.profiler.TraceAnnotation("fl.window"):
                flat_mixed, loss, res = self.window_fn(
                    flat_win, active_ids, k_tr, k_str, k_mix,
                    jnp.asarray(round_index, jnp.int32), res)
            # the store converts ONCE at its seam (np for the cold tier,
            # zero-copy for device tiers) — no np.asarray here
            self.store.scatter_residual(ids_np, res)
        else:
            with jax.profiler.TraceAnnotation("fl.window"):
                flat_mixed, loss = self.window_fn(
                    flat_win, active_ids, k_tr, k_str, k_mix,
                    jnp.asarray(round_index, jnp.int32))
        self.store.scatter(ids_np, flat_mixed)
        self.store.touch(ids_np, round_index)
        return loss

    def _round_faulted(self, key, round_index: int):
        """The serial round under an active plan: arm the injector, splice
        cold retries into the selection, run the fault-wired window, then
        scatter the GUARDED rows (a rejected row writes back its pre-round
        bytes — the store never absorbs a poisoned row) and touch only the
        accepted ids. Store read retries are metered per round via the
        cumulative counter's delta."""
        inj = self._injector
        inj.begin_round(round_index)
        spec = self.faults.for_round(round_index)
        k_sel, k_tr, k_str, k_mix = jax.random.split(key, 4)
        with jax.profiler.TraceAnnotation("fl.select"):
            ids_np = np.asarray(self.select_fn(k_sel))
        ids_np = self._splice_retries(ids_np)
        active_ids = jnp.asarray(ids_np)
        drop, flag, mode = self._fault_vectors(spec, ids_np)
        r0 = self.store.read_retry_count
        flat_win = self.store.gather(ids_np)
        t_idx = jnp.asarray(round_index, jnp.int32)
        if self._codec_stateful:
            res = self.store.gather_residual(ids_np)
            with jax.profiler.TraceAnnotation("fl.window"):
                flat_out, loss, bad, res = self.window_fault_fn(
                    flat_win, active_ids, k_tr, k_str, k_mix,
                    jnp.asarray(drop), jnp.asarray(flag), jnp.asarray(mode),
                    t_idx, res)
            self.store.scatter_residual(ids_np, res)
        else:
            with jax.profiler.TraceAnnotation("fl.window"):
                flat_out, loss, bad = self.window_fault_fn(
                    flat_win, active_ids, k_tr, k_str, k_mix,
                    jnp.asarray(drop), jnp.asarray(flag), jnp.asarray(mode),
                    t_idx)
        bad_np = np.asarray(bad).astype(bool)
        self.store.scatter(ids_np, flat_out)
        touch_ids = self._requeue_rejected(ids_np, bad_np, drop, round_index)
        self.store.touch(touch_ids, round_index)
        self._log_fault(round_index,
                        retries=self.store.read_retry_count - r0)
        return loss

    # -- the software pipeline (pipeline_depth >= 2) --------------------

    def _issue_round(self, key, t: int):
        """Stage A: select round t's ids and start the store prefetch.
        Selection depends only on the key — never on store contents — so
        it can run arbitrarily far ahead of the scatters. The still-
        computing DEVICE id vector goes straight to ``prefetch``: tiers
        with a fetch thread materialize it there, so the O(D) selection
        (the only population-sized compute of a round) never stalls this
        loop; ``ids_np`` is filled in at acquire time, when the selection
        has long finished."""
        k_sel, k_tr, k_str, k_mix = jax.random.split(
            jax.random.fold_in(key, t), 4)
        with jax.profiler.TraceAnnotation("fl.select"):
            active_ids = self.select_fn(k_sel)
        if self.faults is not None:
            # fault mode: the injector is armed BEFORE the prefetch goes
            # out (round t's store reads are the ones its spec targets —
            # round t-1's acquire already completed, so the previous
            # round's arms cannot be clobbered mid-read), and the retry
            # splice needs concrete ids — the selection materializes here
            # rather than on the fetch thread
            self._injector.begin_round(t)
            spec = self.faults.for_round(t)
            ids_np = self._splice_retries(np.asarray(active_ids))
            active_ids = jnp.asarray(ids_np)
            return {
                "t": t, "active_ids": active_ids, "ids_np": ids_np,
                "keys": (k_tr, k_str, k_mix),
                "fault": self._fault_vectors(spec, ids_np),
                "r0": self.store.read_retry_count,
                "win": self.store.prefetch(active_ids),
                "res": (self.store.prefetch_residual(active_ids)
                        if self._codec_stateful else None),
            }
        return {
            "t": t, "active_ids": active_ids, "ids_np": None,
            "keys": (k_tr, k_str, k_mix),
            "win": self.store.prefetch(active_ids),
            "res": (self.store.prefetch_residual(active_ids)
                    if self._codec_stateful else None),
        }

    @staticmethod
    def _patch_rows(win, ids_np, sources, field):
        """Overlay rows of ``win`` whose ids collide with in-flight rounds:
        ``sources`` are older rounds (round order) whose scatters the
        prefetch behind ``win`` may not have observed — their outputs are
        the rows a serial gather WOULD have returned. Oldest first, so the
        newest writer of an id wins, exactly like serial scatter order.
        The ``.astype(win.dtype)`` mirrors the store's scatter-side cast,
        keeping patched rows bit-identical to a store round-trip."""
        for p in sources:
            src = p[field]
            if src is None:
                continue
            pos = {int(c): j for j, c in enumerate(p["ids_np"])}
            hit_i = [i for i, c in enumerate(ids_np) if int(c) in pos]
            if not hit_i:
                continue
            hit_j = [pos[int(ids_np[i])] for i in hit_i]
            win = win.at[jnp.asarray(np.array(hit_i, np.int64))].set(
                jnp.take(src, jnp.asarray(np.array(hit_j, np.int64)),
                         axis=0).astype(win.dtype))
        return win

    def _acquire_window(self, cur, shadow, pending):
        """Finish stage A for round ``cur``: wait the prefetch, then make
        the window serially-consistent. Two kinds of rounds may own rows
        the prefetch missed: ``pending`` rounds (dispatched, not yet
        scattered) and ``shadow`` rounds (scattered AFTER this prefetch
        was issued — the background fetch may have read pre-scatter
        rows). Both patch from their in-flight outputs; patching a row
        the prefetch DID see post-scatter rewrites it with the same bits,
        so the patch is idempotent and the read race is benign."""
        if cur["ids_np"] is None:
            cur["ids_np"] = np.asarray(cur["active_ids"])
        ids_np = cur["ids_np"]
        sources = shadow + pending
        flat_win = self._patch_rows(
            self._prefetch_rows(cur, "win", self.store.gather), ids_np,
            sources, "out_flat")
        res = None
        if self._codec_stateful:
            res = self._patch_rows(
                self._prefetch_rows(cur, "res", self.store.gather_residual),
                ids_np, sources, "out_res")
        return flat_win, res

    def _prefetch_rows(self, cur, field, sync_gather):
        """Collect one prefetch handle with the engine's timeout; a DEAD
        worker (its exception re-raises here) or a STUCK one (timeout) is
        not fatal — the round falls back to a synchronous gather. A
        permanent store failure (e.g. ``CheckpointCorruptionError``) then
        raises from the synchronous path, so real errors still surface."""
        try:
            return cur[field].result(self.prefetch_timeout)
        except Exception:
            self.prefetch_fallbacks += 1
            if self.faults is not None:
                self._log_fault(cur["t"], prefetch_fallbacks=1)
            return sync_gather(cur["ids_np"])

    def _retire_round(self, p):
        """Stage C: scatter round p's mixed rows (+ residual) back and
        advance staleness. The store seam does the one host conversion;
        ``copy_to_host_async`` was already started at dispatch, so the
        device->host sync here usually finds the bytes waiting."""
        if p["out_res"] is not None:
            self.store.scatter_residual(p["ids_np"], p["out_res"])
        self.store.scatter(p["ids_np"], p["out_flat"])
        # fault mode restricts the staleness touch to accepted ids (the
        # guard already reverted rejected rows, so the scatter is safe)
        touch = p.get("touch_ids")
        self.store.touch(p["ids_np"] if touch is None else touch, p["t"])

    def _dispatch_window(self, cur, flat_win, res, t: int):
        """Stage B: dispatch round t's compiled window (async). Returns
        (out_flat, loss, bad, out_res); ``bad`` is None without a fault
        plan and ``out_res`` None without a stateful codec."""
        k_tr, k_str, k_mix = cur["keys"]
        args = (flat_win, cur["active_ids"], k_tr, k_str, k_mix)
        t_idx = jnp.asarray(t, jnp.int32)
        extra = (res,) if self._codec_stateful else ()
        bad = None
        with jax.profiler.TraceAnnotation("fl.window"):
            if self.faults is not None:
                fxs = tuple(jnp.asarray(v) for v in cur["fault"])
                out = self.window_fault_fn(*args, *fxs, t_idx, *extra)
                bad = out[2]
            else:
                out = self.window_fn(*args, t_idx, *extra)
        return out[0], out[1], bad, (out[-1] if extra else None)

    def _run_rounds_pipelined(self, key, T: int, depth: int):
        """T rounds with up to ``depth`` windows in flight. Per loop
        iteration: acquire round t's prefetched window (patching id
        conflicts), dispatch its compiled window_fn (stage B, async),
        issue round t+1's select+prefetch (stage A), then retire the
        oldest rounds (stage C) until at most depth-1 stay in flight.
        Retires run in round order, so ``last_round`` and the store match
        serial exactly."""
        host_retire = self.store.resident_flat() is None
        pending, shadow, losses = [], [], [None] * T
        nxt = self._issue_round(key, 0) if T > 0 else None
        for t in range(T):
            with jax.profiler.TraceAnnotation("fl.round", round=t):
                cur = nxt
                flat_win, res = self._acquire_window(cur, shadow, pending)
                # every prefetch issued from here on sees the shadow rounds'
                # scatters (they completed before this point) — drop them
                shadow.clear()
                out_flat, loss, bad, out_res = self._dispatch_window(
                    cur, flat_win, res, t)
                if host_retire:
                    # start the device->host copy NOW so stage C's np
                    # conversion doesn't block on the transfer later
                    for buf in (out_flat, out_res):
                        if buf is not None and hasattr(buf,
                                                       "copy_to_host_async"):
                            buf.copy_to_host_async()
                cur.update(out_flat=out_flat, out_res=out_res)
                losses[t] = loss
                pending.append(cur)
                if self.faults is not None:
                    # host-sync the guard verdict BEFORE issuing round t+1
                    # so the requeue splice sees this round's rejections at
                    # every depth — fault mode trades that slice of overlap
                    # for depth-invariant cold-retry semantics
                    bad_np = np.asarray(bad).astype(bool)
                    cur["touch_ids"] = self._requeue_rejected(
                        cur["ids_np"], bad_np, cur["fault"][0], t)
                    self._log_fault(
                        t, retries=self.store.read_retry_count - cur["r0"])
                nxt = self._issue_round(key, t + 1) if t + 1 < T else None
                while len(pending) > depth - 1:
                    p = pending.pop(0)
                    self._retire_round(p)
                    shadow.append(p)
        for p in pending:
            self._retire_round(p)
        return losses

    def run_rounds(self, key, T: int, *, pipeline_depth: Optional[int] = None):
        """Run T sampled rounds against the store (a host loop — the store
        is host-owned state; each round's WINDOW is one compiled program).
        ``pipeline_depth`` (default: the engine's) overlaps select/prefetch
        and retire/scatter with the compiled window at depth >= 2,
        bit-for-bit identical to the depth-1 serial loop. Returns metrics
        with the [T] per-round mean train losses; under an active fault
        plan the dict grows the four per-round counters ``dropped``,
        ``rejected_rows``, ``retries`` and ``prefetch_fallbacks`` ([T]
        int64)."""
        T = int(T)
        with jax.profiler.TraceAnnotation("fl.run_rounds", rounds=T):
            return self._run_rounds(key, T, pipeline_depth)

    def _run_rounds(self, key, T: int, pipeline_depth: Optional[int]):
        if self.store is None:
            raise ValueError("SampledEngine.run_rounds: call "
                             "init_store(params) first")
        depth = self._check_depth(self.pipeline_depth if pipeline_depth
                                  is None else pipeline_depth)
        if self.faults is not None:
            # one run_rounds call == one chaos run: counters and the cold-
            # retry queue start clean
            self._fault_log = {}
            self._retry_queue = []
        if depth == 1:
            losses = [self.round(jax.random.fold_in(key, t), round_index=t)
                      for t in range(T)]
        else:
            losses = self._run_rounds_pipelined(key, T, depth)
        metrics = {"train_loss": np.asarray(jax.device_get(losses))}
        if self.faults is not None:
            for name in ("dropped", "rejected_rows", "retries",
                         "prefetch_fallbacks"):
                metrics[name] = np.asarray(
                    [self._fault_log.get(t, {}).get(name, 0)
                     for t in range(T)], np.int64)
        return metrics

    def global_params(self):
        """Consensus readout: the mean over ALL enrolled rows, unpacked to
        the model pytree. On resident tiers (``resident_flat()`` returns
        the live buffer) this is exactly the dense engine's per-leaf-dtype
        ``mean_packed`` collapse; tiers without a resident buffer fall
        back to the store's ``consensus()`` contract."""
        if self.store is None:
            raise ValueError("SampledEngine.global_params: no store")
        flat = self.store.resident_flat()
        if flat is not None:
            row = kernel_ops.mean_packed(flat, self._spec)
        else:
            row = jnp.asarray(self.store.consensus())
        return kernel_ops.unpack_tree(row, self._spec)


# ---------------------------------------------------------------------------
# Mesh engine — production shard_map path
# ---------------------------------------------------------------------------

class MeshEngine:
    """Drives one protocol's rounds on the production federated state: every
    param leaf carries a leading client axis [D, ...] sharded over the data
    mesh axes; local SGD is a vmap over the client axis (client-diagonal, so
    GSPMD emits zero collectives there) and mixing is the protocol's
    ``psum_mix`` shard_map lowering when ``mesh_info`` is given, else the
    dense [D, D] oracle.

    ``counts`` carries non-uniform per-client data weights |D_i| onto the
    production path (default: uniform).

    ``round_fn(f_params, batches, survive, key, do_global_sync=...)`` is one
    jitted round; ``run_rounds(f_params, key, T, batches)`` scan-compiles
    the whole loop (batch leaves [T, D, steps, ...]) with ``sync_period``
    chunking so ``do_global_sync`` stays a static program structure: global
    sync fires when (t+1) % sync_period == 0, as in the paper.
    """

    def __init__(self, model, fl: FLConfig, num_clients_dev: int,
                 local_steps: int, *, algorithm: str = "", counts=None,
                 remat: bool = True, out_shardings=None, mesh_info=None,
                 mix_use_pallas: Optional[bool] = None, codec=None,
                 mix_path: Optional[str] = None):
        self.proto = get(algorithm or fl.algorithm)
        self.fl = fl
        self.num_clients_dev = num_clients_dev
        self.local_steps = local_steps
        self.mesh_info = mesh_info
        #: backend for the no-mesh dense fallback's fused mixing (see
        #: DenseEngine.mix_use_pallas); ignored when mesh_info is set
        self.mix_use_pallas = mix_use_pallas
        #: mixing lowering for the no-mesh fallback (see
        #: DenseEngine.mix_path; default ``fl.mix_path``). On a real mesh
        #: the protocol's ``psum_mix`` grouped psums already realize the
        #: structured traffic — the [D, D] oracle never runs there.
        self.mix_path = _check_mix_path(mix_path or fl.mix_path)
        #: quantized-exchange wire (``repro.compression`` name or Codec),
        #: defaulting to ``fl.codec``; active form — None/"none" keeps the
        #: round bit-for-bit the uncompressed program. On a real mesh the
        #: codec rides ``RoundContext.codec`` into the protocol's
        #: ``psum_mix`` (quantize/dequantize wrapped around the grouped
        #: psums); stateful codecs additionally thread a per-leaf residual
        #: pytree through ``run_rounds``'s scan carry.
        self.codec = compression.active(
            codec if codec is not None else fl.codec)
        ids = self.proto.mesh_cluster_ids(num_clients_dev, fl)
        self._cluster_ids = ids                      # concrete — mesh groups
        self._num_clusters = int(ids.max()) + 1
        self._counts = (jnp.ones((num_clients_dev,), jnp.float32)
                        if counts is None
                        else jnp.asarray(counts, jnp.float32))

        @jax.named_scope("local_train")
        def local_train(params, batches):
            """-> (params, mean loss, the loss's counters summed over the
            steps; {} for a model without counters)."""
            def step(p, b):
                (loss, aux), grads = jax.value_and_grad(
                    functools.partial(model.loss_fn, remat=remat),
                    has_aux=True)(p, b)
                p = jax.tree.map(lambda w, g: (w - fl.lr * g.astype(jnp.float32)
                                               ).astype(w.dtype), p, grads)
                return p, (loss, aux.get("counters", {}))

            params, (losses, counters) = jax.lax.scan(step, params, batches)
            return params, jnp.mean(losses), jax.tree.map(jnp.sum, counters)

        self._vlocal = (jax.vmap(local_train)
                        if getattr(model, "client_vmap", True)
                        else self._one_by_one(local_train))
        #: the last ``run_rounds`` call's counters, {name: [T]} summed over
        #: the clients (device arrays; {} for a model without counters)
        self.counters = {}

        jit_kwargs = {"static_argnames": ("do_global_sync",)}
        if out_shardings is not None:
            if self._codec_stateful:
                # _round returns (f_out, loss, residual) here — extend the
                # caller's (f_out, loss) shardings with the residual's
                # (client-axis leaves, same layout as f_params)
                if mesh_info is None:
                    raise ValueError(
                        "out_shardings with a stateful codec requires "
                        "mesh_info (the residual sharding is derived from "
                        "its data axes)")
                from jax.sharding import NamedSharding, PartitionSpec as P
                ax = (mesh_info.dp_axes if len(mesh_info.dp_axes) > 1
                      else mesh_info.dp_axes[0])
                state_sh = NamedSharding(mesh_info.mesh, P(ax, None))
                out_shardings = tuple(out_shardings) + (state_sh,)
            jit_kwargs["out_shardings"] = out_shardings
        #: jitted (f_params, batches, survive, key[, do_global_sync,
        #: round_index]) -> (f_params', mean_loss)
        self.round_fn = jax.jit(self._round, **jit_kwargs)
        self._run_jit = jax.jit(self._run)

    def _one_by_one(self, fn):
        """Map ``fn`` over the client axis one client after another, for a
        model that cannot be vmapped: on a mesh each device maps over its
        own clients inside a shard_map, so no operation sees the client
        axis."""
        def mapped(params, batches):
            return jax.lax.map(lambda pb: fn(*pb), (params, batches))
        if self.mesh_info is None:
            return mapped
        from jax.sharding import PartitionSpec as P
        ax = self.mesh_info.dp_axes
        spec = P(ax if len(ax) > 1 else ax[0])
        return jax.shard_map(mapped, mesh=self.mesh_info.mesh,
                             in_specs=(spec, spec), out_specs=spec,
                             check_vma=False)

    def _ctx(self, survive, key, round_index, do_global_sync: bool):
        return make_context(
            key=key, round_index=round_index, survive=survive,
            counts=self._counts, cluster_ids=self._cluster_ids,
            num_clusters=self._num_clusters, do_global_sync=do_global_sync,
            mesh_info=self.mesh_info, codec=self.codec)

    @property
    def _codec_stateful(self) -> bool:
        return self.codec is not None and self.codec.stateful

    def _round(self, f_params, batches, survive, key,
               do_global_sync: bool = True, round_index=0, codec_state=None,
               with_counters: bool = False):
        """One mesh round. Stateless codecs ride ``ctx.codec`` into the
        protocol's ``psum_mix`` (the quantize/dequantize wire around the
        grouped psums). Stateful ones (error feedback) split the residual
        *here* — the engine owns cross-round state — by pre-transmitting
        f_new and handing ``psum_mix`` an already-on-the-wire tree with the
        codec cleared; the return grows a third element (the residual).
        ``with_counters`` appends the round's counters, summed over the
        clients."""
        f_new, losses, counters = self._vlocal(f_params, batches)
        out = self._mix_round(f_params, f_new, losses, survive, key,
                              do_global_sync, round_index, codec_state)
        if with_counters:
            return out + (jax.tree.map(jnp.sum, counters),)
        return out

    def _mix_round(self, f_params, f_new, losses, survive, key,
                   do_global_sync, round_index, codec_state):
        ctx = self._ctx(survive, key, round_index, bool(do_global_sync))
        if self.mesh_info is not None:
            if self._codec_stateful:
                if codec_state is None:
                    codec_state = compression.init_feedback_state(
                        self.codec, f_new)
                f_new, codec_state = compression.feedback_wire_tree(
                    self.codec, f_new, f_params, codec_state, key=ctx.key)
                ctx = ctx.replace(codec=None)
            f_out = self.proto.psum_mix(f_new, f_params, ctx)
            loss = jnp.mean(losses)
            return ((f_out, loss, codec_state) if self._codec_stateful
                    else (f_out, loss))
        # no-mesh fallback: the protocol's structured mixing_spec kernels
        # when the path allows (no [D, D] operator), else the dense oracle
        spec = _resolve_spec(self.proto, ctx, self.mix_path)
        M_new = M_old = None
        if spec is None:
            M_new, M_old = self.proto.mixing_matrix(ctx)
        if self.codec is None:
            f_out = self.proto.apply_mixing(M_new, M_old, f_new, f_params,
                                            spec=spec,
                                            use_pallas=self.mix_use_pallas)
            return f_out, jnp.mean(losses)
        # codec at the pack_tree seam, residual as one [D, sum(sizes)]
        # buffer (auto-initialized inside)
        f_out, codec_state = self.proto.apply_mixing(
            M_new, M_old, f_new, f_params, spec=spec, codec=self.codec,
            codec_state=codec_state, key=jax.random.fold_in(key, 0x636F6465),
            use_pallas=self.mix_use_pallas)
        if self._codec_stateful:
            return f_out, jnp.mean(losses), codec_state
        return f_out, jnp.mean(losses)

    # -- the scan-compiled training loop -------------------------------
    def _run(self, f_params, key, batches, codec_state=None):
        fl, D = self.fl, self.num_clients_dev
        sp = max(1, fl.sync_period)
        T = jax.tree.leaves(batches)[0].shape[0]     # static at trace time
        n_chunks, rem = divmod(T, sp)
        stateful = self._codec_stateful

        def one_round(f_params, key, b, t, sync: bool, cstate):
            key, k_str, k_mix = jax.random.split(key, 3)
            survive = straggler_mask(k_str, D, fl.straggler_rate)
            out = self._round(f_params, b, survive, k_mix,
                              do_global_sync=sync, round_index=t,
                              codec_state=cstate, with_counters=True)
            if stateful:
                f_params, loss, cstate, counts = out
            else:
                f_params, loss, counts = out
            return f_params, key, (loss, counts), cstate

        def body(carry, xs):
            f_params, key, cstate = carry
            chunk, t0 = xs
            out = []
            for i in range(sp):                      # unrolled: sync static
                b_i = jax.tree.map(lambda leaf: leaf[i], chunk)
                f_params, key, lc, cstate = one_round(
                    f_params, key, b_i, t0 + i, i == sp - 1, cstate)
                out.append(lc)
            return (f_params, key, cstate), jax.tree.map(
                lambda *a: jnp.stack(a), *out)

        cstate = codec_state
        if stateful and cstate is None:
            cstate = self.init_codec_state(f_params)
        main = jax.tree.map(
            lambda x: x[:n_chunks * sp].reshape((n_chunks, sp) + x.shape[1:]),
            batches)
        t0s = jnp.arange(n_chunks, dtype=jnp.int32) * sp
        (f_params, key, cstate), per_round = jax.lax.scan(
            body, (f_params, key, cstate), (main, t0s))
        per_round = jax.tree.map(lambda a: a.reshape((n_chunks * sp,)),
                                 per_round)
        # T % sync_period tail rounds: never hit (t+1) % sp == 0 -> no sync
        tail = []
        for i in range(rem):
            b_i = jax.tree.map(lambda leaf: leaf[n_chunks * sp + i], batches)
            f_params, key, lc, cstate = one_round(
                f_params, key, b_i, n_chunks * sp + i, False, cstate)
            tail.append(lc)
        if tail:
            per_round = jax.tree.map(
                lambda a, *t: jnp.concatenate([a, jnp.stack(t)]),
                per_round, *tail)
        losses, counters = per_round
        if stateful:
            return f_params, losses, cstate, counters
        return f_params, losses, counters

    def init_codec_state(self, f_params):
        """Zero error-feedback residual for stateful codecs (``None``
        otherwise): per-leaf [D, size] f32 on the mesh path, one packed
        [D, sum(sizes)] buffer on the dense fallback."""
        if not self._codec_stateful:
            return None
        if self.mesh_info is not None:
            return compression.init_feedback_state(self.codec, f_params)
        total = sum(int(leaf.size) // self.num_clients_dev
                    for leaf in jax.tree.leaves(f_params))
        return jnp.zeros((self.num_clients_dev, total), jnp.float32)

    def run_rounds(self, f_params, key, T: int, batches, codec_state=None):
        """Run T rounds as one compiled scan. ``batches`` leaves are
        [T, D, local_steps, ...]; returns (f_params, losses[T]) with the
        loss buffer on device (no per-round host syncs).

        With a *stateful* codec (error feedback) the return grows a third
        element — the final residual — and ``codec_state`` seeds the scan
        (zeros when None). The rounds' counters ({name: [T]}, summed over
        the clients) are left in ``self.counters``. Drivers that stage T in
        chunks (several run_rounds calls per training run, e.g.
        ``launch.train``) MUST thread it through, or every chunk boundary
        silently drops the accumulated feedback mass."""
        T = int(T)
        got = jax.tree.leaves(batches)[0].shape[0]
        if got != T:
            raise ValueError(f"batches carry {got} rounds, expected T={T}")
        with jax.profiler.TraceAnnotation("fl.run_rounds", rounds=T):
            if self._codec_stateful:
                *out, self.counters = self._run_jit(f_params, key, batches,
                                                    codec_state)
            else:
                *out, self.counters = self._run_jit(f_params, key, batches)
        return tuple(out)
