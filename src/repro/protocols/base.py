"""The pluggable federated-learning Protocol interface + registry.

The paper's contribution is a *family* of decentralization strategies
(FedAvg -> FedP2P -> topology-aware FedP2P -> gossip -> async gossip); this
module makes each strategy a single object that carries

  * its client-selection / cluster-formation rule (``select_participants`` /
    ``partition``),
  * its aggregation semantics as a dense [D, D] client-mixing matrix
    (``mixing_matrix`` — the simulator / oracle path),
  * its production TPU lowering as a hierarchical grouped-psum shard_map
    program (``psum_mix`` — the mesh path),
  * and its §3.2 analytic communication-cost model (``comm_time``).

Every per-round method consumes a single ``RoundContext`` record
(``protocols.context``) carrying the round's PRNG key, straggler mask,
per-client data weights, cluster assignment, and the static
topology/mesh metadata:

    ctx = make_context(key=k, survive=s, counts=c, cluster_ids=ids,
                       num_clusters=L, do_global_sync=True)
    M_new, M_old = proto.mixing_matrix(ctx)
    f_out = proto.psum_mix(f_new, f_old, ctx)          # ctx.mesh_info set
    seconds = proto.comm_time(p, P, ctx=ctx)           # ctx.topology read

The engines in ``protocols.engine`` (``DenseEngine`` for the simulator /
oracle path, ``MeshEngine`` for the production shard_map path) build the
context each round and drive any registered protocol through it — adding an
algorithm is one new file plus one ``register`` call; nothing in the engine
layers changes. Because the context carries a per-round key, *stochastic*
protocols (fresh random matchings every round — see ``async_gossip``) work
on both paths, which the old keyless positional API could not express.

Mixing-matrix convention (shared by both lowerings):

    f_out = M_new @ f_new + M_old @ f_old

where ``f_new`` are the post-local-training client models, ``f_old`` the
pre-round models, and every row of ``M_new + M_old`` sums to 1 (each output
model is a convex combination — dropped updates fall back to old params,
never to zeros).
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FLConfig
from repro.core.comm_model import CommParams
from repro.core.partition import sample_participants
from repro.core.topology import Topology
from repro.kernels import ops as kernel_ops
from repro.protocols.context import (  # noqa: F401
    RoundContext, concrete_cluster_ids, make_context)


class Protocol:
    """Abstract decentralization strategy. Subclass + ``register`` to add one.

    Implementations must be stateless (a single instance is shared by every
    simulator / mesh program), and every array-valued method must be
    jit-traceable.
    """

    #: registry key, e.g. "fedp2p"
    name: str = ""
    #: True -> ``partition``/``comm_time`` want a ``core.topology.Topology``
    needs_topology: bool = False

    # ------------------------------------------------------------------
    # participant selection / cluster formation
    # ------------------------------------------------------------------
    def num_participants(self, fl: FLConfig) -> int:
        """P — how many clients one round of this protocol trains."""
        return fl.participation

    def num_clusters(self, fl: FLConfig) -> int:
        """L — static cluster count backing ``partition``'s cluster_ids."""
        return 1

    def select_participants(self, key, fl: FLConfig) -> jnp.ndarray:
        """[P] distinct client indices sampled for this round, via the
        first-class participation strategy named by
        ``fl.participation_strategy`` (the ``uniform`` default is
        bit-for-bit the historical ``sample_participants`` draw)."""
        return get_participation(fl.participation_strategy).select(
            key, fl.num_clients, self.num_participants(fl), fl)

    def partition(self, key, fl: FLConfig,
                  topology: Optional[Topology] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(selected [P], cluster_ids [P] in [0, num_clusters(fl)))."""
        sel = self.select_participants(key, fl)
        return sel, jnp.zeros((self.num_participants(fl),), jnp.int32)

    def mesh_cluster_ids(self, num_clients_dev: int, fl: FLConfig) -> np.ndarray:
        """Static [D] cluster assignment for the production mesh, where the
        client axis is laid out over the data mesh axes. Contiguous by
        default so cluster traffic stays on neighboring devices."""
        return np.zeros((num_clients_dev,), np.int32)

    # ------------------------------------------------------------------
    # aggregation semantics — dense oracle form
    # ------------------------------------------------------------------
    def mixing_matrix(self, ctx: RoundContext
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(M_new, M_old), each [D, D]: f_out = M_new @ f_new + M_old @ f_old.

        Reads ``ctx.survive`` ([D] 0/1 straggler mask), ``ctx.counts``
        ([D] per-client data weights |D_i|), ``ctx.cluster_ids`` ([D]),
        ``ctx.num_clusters`` (static L), ``ctx.do_global_sync``, and — for
        stochastic protocols — ``ctx.key``.
        """
        raise NotImplementedError

    def mixing_spec(self, ctx: RoundContext):
        """The structured form of ``mixing_matrix`` — a ``SegmentSpec`` /
        ``MatchingSpec`` pytree (``protocols.spec``) when this protocol's
        operator has O(D) structure, else ``None`` (dense-only protocols).

        Contract: ``mixing_spec(ctx).to_dense()`` must reproduce
        ``mixing_matrix(ctx)`` exactly (pinned per protocol by
        ``tests/test_mixing_spec.py``), and the structured kernels behind
        ``apply_mixing(spec=...)`` must match the dense path round-for-
        round. Engines with ``mix_path='auto'`` take this fast path
        whenever it exists — O(D·P) per round instead of O(D²·P)."""
        return None

    # ------------------------------------------------------------------
    # aggregation semantics — hierarchical mesh lowering
    # ------------------------------------------------------------------
    def psum_mix(self, f_new, f_old, ctx: RoundContext):
        """shard_map realization of ``mixing_matrix`` on the production mesh
        (``ctx.mesh_info``): one client per data-axis slice, O(leaf) memory
        per device (vs the O(D·leaf) gather the dense [D, D] contraction
        degenerates to under GSPMD). ``ctx.cluster_ids`` must be concrete
        (numpy) here — mesh lowerings build static ``axis_index_groups``
        from it. Must agree numerically with the dense form, including under
        non-uniform ``ctx.counts``.
        """
        raise NotImplementedError

    # ------------------------------------------------------------------
    # §3.2 analytic communication model
    # ------------------------------------------------------------------
    def comm_time(self, p: CommParams, P: int, *, L: Optional[float] = None,
                  ctx: Optional[RoundContext] = None) -> float:
        """Wall-clock seconds of one round's communication for P sampled
        devices (the paper's H(·) functions). Topology-aware protocols read
        ``ctx.topology``."""
        raise NotImplementedError

    def wire_model(self, D: int, L: int, *, do_global_sync: bool = True
                   ) -> Optional[Tuple[Tuple[int, int, float], ...]]:
        """The declared §3.2 wire structure of one mesh round: a tuple of
        ``(group_size, num_groups, model_copies)`` ring-allreduce terms.
        One round moves ``sum(num_groups * copies *
        ring_wire_bytes(p.wire_bytes, group_size))`` bytes — and the
        ``wire-model-parity`` analysis rule requires the STATIC byte count
        of the traced ``psum_mix`` program (sized from psum operands and
        ``axis_index_groups``) to equal exactly that, for every codec.

        ``model_copies`` counts full-model allreduces in the term: our
        lowerings move the weighted new models AND the old-params straggler
        fallback (two copies) — a deliberate simulator-fidelity choice the
        model must price rather than hide.

        Returns ``None`` when the protocol declares no wire structure
        (the parity rule then skips it)."""
        return None

    # ------------------------------------------------------------------
    # shared helpers
    # ------------------------------------------------------------------
    @staticmethod
    def apply_mixing(M_new: jnp.ndarray, M_old: jnp.ndarray, f_new, f_old, *,
                     spec=None, codec=None, codec_state=None, key=None,
                     use_pallas: Optional[bool] = None,
                     interpret: Optional[bool] = None):
        """Apply one round of mixing over [D, ...] pytrees as ONE fused
        flat pass: both trees are packed once into [D, sum(sizes)] buffers,
        the flat operator runs, and the result is unpacked back to the leaf
        shapes/dtypes.

        The flat operator is either the dense contraction
        ``M_new @ X_new + M_old @ X_old`` (``kernels.ops.fed_mix`` —
        Pallas on TPU, interpret under ``use_pallas=True`` on CPU, jnp
        oracle otherwise, f32 accumulation) or — when ``spec`` (a
        ``protocols.spec`` MixingSpec from ``mixing_spec(ctx)``) is given —
        the structured-sparse fast path (``kernels/fed_mix_sparse``):
        O(D·P) segment-reduce / permutation-gather kernels that never
        materialize a [D, D] operator (``M_new``/``M_old`` may be ``None``
        then).

        ``codec`` (a ``repro.compression`` name or Codec) puts the round
        DELTA — ``f_new - f_old``, what the clients upload against the
        round-start state the receivers hold — through the lossy wire at
        the packing seam; on the dense path the int8 codec runs the fused
        ``fed_mix_q`` kernel which dequantizes wire tiles inline in the
        MXU loop. With a codec the call returns ``(tree,
        new_codec_state)`` (error-feedback residual for stateful codecs,
        pass-through otherwise); ``key`` seeds stochastic rounding."""
        if spec is not None:
            from repro.protocols.spec import apply_spec_tree
            return apply_spec_tree(spec, f_new, f_old, codec=codec,
                                   codec_state=codec_state, key=key,
                                   use_pallas=use_pallas,
                                   interpret=interpret)
        return kernel_ops.fed_mix_tree(M_new, M_old, f_new, f_old,
                                       codec=codec, codec_state=codec_state,
                                       key=key, use_pallas=use_pallas,
                                       interpret=interpret)

    @staticmethod
    def _shard_mix(local_fn, f_new, f_old, ctx: RoundContext, *extras):
        """Run ``local_fn(x_new, x_old, s, c, *extras) -> x_out`` under
        shard_map with every leaf sharded along the data axes (the federated
        client axis). ``s``/``c`` are this device's survive/count slices;
        ``extras`` are replicated scalars (e.g. a matching index drawn from
        ``ctx.key``).

        When ``ctx.codec`` is set, every f_new leaf is first replaced by
        what the receivers reconstruct after the wire: ``f_old +
        roundtrip(f_new - f_old)`` (clients upload compressed round
        *deltas* against the round-start state, per-client rows, per-leaf
        chunking) — the quantized-exchange wire wrapped around the grouped
        psums. All wrap ops are client-diagonal, so GSPMD emits zero extra
        collectives; f_old (the receivers' local state) stays exact, which
        is also why stragglers fall back to *unquantized* old params."""
        from jax.sharding import PartitionSpec as P
        if ctx.codec is not None:
            from repro import compression
            f_new = compression.wire_tree(ctx.codec, f_new, f_old,
                                          key=ctx.key)
        mesh_info = ctx.mesh_info
        names = mesh_info.dp_axes
        axes = names if len(names) > 1 else names[0]
        spec = jax.tree.map(lambda _: P(axes), f_new)
        sspec = P(axes)
        fn = jax.shard_map(local_fn, mesh=mesh_info.mesh,
                           in_specs=(spec, spec, sspec, sspec)
                                    + (P(),) * len(extras),
                           out_specs=spec, check_vma=False)
        return fn(f_new, f_old, ctx.survive, ctx.counts, *extras)

    @staticmethod
    def _groups_from_ids(cluster_ids):
        """axis_index_groups (one group per cluster) from a static [D]
        assignment. Raises on traced ids — mesh lowerings need a concrete
        cluster layout."""
        ids = concrete_cluster_ids(
            cluster_ids,
            hint="psum_mix axis_index_groups need a CONCRETE [D] cluster "
                 "assignment; got a traced cluster_ids. Mesh engines must "
                 "close over the static assignment (numpy array) rather "
                 "than thread it through jit.")
        L = int(ids.max()) + 1 if ids.size else 1
        return [np.nonzero(ids == c)[0].tolist() for c in range(L)]

    @staticmethod
    def static_num_clients(ctx: RoundContext) -> int:
        """D as a static int, from the concrete mesh cluster assignment."""
        ids = concrete_cluster_ids(
            ctx.cluster_ids,
            hint="static_num_clients needs a concrete cluster_ids array; "
                 "got a traced value (mesh contexts close over the static "
                 "assignment).")
        return int(ids.shape[0])


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Protocol] = {}


def register(protocol: Protocol) -> Protocol:
    """Register a Protocol instance under ``protocol.name``."""
    if not protocol.name:
        raise ValueError("protocol must define a non-empty .name")
    if protocol.name in _REGISTRY:
        raise ValueError(f"protocol {protocol.name!r} is already registered")
    _REGISTRY[protocol.name] = protocol
    return protocol


def unregister(name: str) -> None:
    """Remove a registered protocol (plugin teardown / tests)."""
    _REGISTRY.pop(name, None)


def names() -> Tuple[str, ...]:
    """Registered protocol names, in registration order."""
    return tuple(_REGISTRY)


def get(name: str) -> Protocol:
    """Look up a registered protocol; unknown names raise (never a silent
    FedAvg fallback)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown protocol {name!r}; registered protocols: "
            f"{', '.join(names())}") from None


# ---------------------------------------------------------------------------
# Participation strategies — how the K-sized active set is drawn
# ---------------------------------------------------------------------------

class ParticipationStrategy:
    """First-class client-selection rule: ``select(key, D, K, fl)`` returns
    [K] distinct indices into the D-client population. Strategies are
    stateless and jit-traceable, mirroring the Protocol contract; register
    one instance per rule (``register_participation``)."""

    #: registry key, e.g. "uniform"
    name: str = ""

    def select(self, key, num_clients: int, num_participants: int,
               fl: FLConfig) -> jnp.ndarray:
        raise NotImplementedError


class UniformParticipation(ParticipationStrategy):
    """The paper's uniform-without-replacement sampling — bit-for-bit the
    historical ``core.partition.sample_participants`` draw (same key, same
    permutation), so making selection pluggable changes no existing
    program."""

    name = "uniform"

    def select(self, key, num_clients: int, num_participants: int,
               fl: FLConfig) -> jnp.ndarray:
        return sample_participants(key, num_clients, num_participants)


class ParetoParticipation(ParticipationStrategy):
    """Participation-rate-capped biased selection (SNIPPETS.md snippet 1):
    real cross-device fleets see heavy-tailed client capability, and
    selecting for resource-rich clients under an availability cap improves
    round efficiency without starving the tail.

    Each enrolled client carries a STATIC Pareto(alpha)-distributed
    resource score (drawn once from a fixed fold of client identity, so
    scores are stable across rounds and across processes); each round an
    independent Bernoulli(``fl.participation_rate``) availability mask is
    drawn, and the K winners are a weighted-without-replacement sample
    (Gumbel top-K over log-scores) among available clients. Unavailable
    clients rank strictly below every available one, so they only fill
    slots a too-small available pool leaves empty — the draw always
    returns K distinct indices."""

    name = "pareto"
    #: Pareto shape: alpha = 3 keeps a heavy but finite-variance tail
    alpha: float = 3.0

    def select(self, key, num_clients: int, num_participants: int,
               fl: FLConfig) -> jnp.ndarray:
        k_avail, k_pick = jax.random.split(key)
        # static per-client resource scores via inverse-CDF from a fixed
        # enrollment key — NOT the round key, so capability is a property
        # of the client, not of the round
        u = jax.random.uniform(jax.random.PRNGKey(0x5C0BE5),
                               (num_clients,), minval=1e-6, maxval=1.0)
        log_score = -(1.0 / self.alpha) * jnp.log(u)   # log Pareto(alpha)
        avail = jax.random.bernoulli(k_avail, fl.participation_rate,
                                     (num_clients,))
        g = log_score + jax.random.gumbel(k_pick, (num_clients,))
        g = jnp.where(avail, g, g - 1e9)   # unavailable: strictly last
        return jax.lax.top_k(g, num_participants)[1].astype(jnp.int32)


_PARTICIPATION: Dict[str, ParticipationStrategy] = {}


def register_participation(strategy: ParticipationStrategy
                           ) -> ParticipationStrategy:
    """Register a ParticipationStrategy instance under ``strategy.name``."""
    if not strategy.name:
        raise ValueError("participation strategy must define a non-empty "
                         ".name")
    if strategy.name in _PARTICIPATION:
        raise ValueError(f"participation strategy {strategy.name!r} is "
                         "already registered")
    _PARTICIPATION[strategy.name] = strategy
    return strategy


def participation_names() -> Tuple[str, ...]:
    """Registered participation-strategy names, in registration order."""
    return tuple(_PARTICIPATION)


def get_participation(name: str) -> ParticipationStrategy:
    """Look up a participation strategy; unknown names raise (never a
    silent uniform fallback)."""
    try:
        return _PARTICIPATION[name]
    except KeyError:
        raise ValueError(
            f"unknown participation strategy {name!r}; registered "
            f"strategies: {', '.join(participation_names())}") from None


register_participation(UniformParticipation())
register_participation(ParetoParticipation())


def active_window_size(fl: FLConfig, proto: Protocol) -> int:
    """K — clients per sampled round: the explicit
    ``fl.participants_per_round`` knob, else the protocol's own count."""
    return fl.participants_per_round or proto.num_participants(fl)


def validate_participation(fl: FLConfig, proto: Protocol) -> int:
    """Validate the (enrolled D, active K) pair against ``proto``'s
    structural needs and return K. Raises ``ValueError`` with the failing
    numbers spelled out (the ``pack_tree`` error-message precedent):
    K <= D, K >= the protocol's cluster count, and — for protocols whose
    mesh layout carves the window into L contiguous clusters — L | K."""
    D = fl.enrolled
    K = active_window_size(fl, proto)
    if K > D:
        raise ValueError(
            f"sampled participation: K={K} active clients per round exceed "
            f"the D={D} enrolled population (protocol {proto.name!r}); "
            "need K <= D")
    # the window's cluster layout is the protocol's own static assignment
    # at width K; protocols that carve L equal contiguous clusters
    # (fedp2p family) assert L | K there — surface that as a clear error
    # (the gossip family's per-client "clusters" scale with any K)
    try:
        proto.mesh_cluster_ids(K, fl)
    except AssertionError:
        L = fl.num_clusters
        need = "K >= L (and L | K)" if K < L else "L | K"
        raise ValueError(
            f"sampled participation: protocol {proto.name!r} carves its "
            f"active window into L={L} equal contiguous clusters, which a "
            f"K={K} window cannot realize; need {need}") from None
    return K


def resolve(name: str, topology_aware: bool = False) -> Protocol:
    """Map an ``FLConfig`` (algorithm, topology_aware) pair to a protocol:
    ``topology_aware=True`` upgrades ``name`` to ``name + '_topo'`` when such
    a variant is registered. When it is NOT, and the base protocol is not
    itself topology-aware, the flag would silently do nothing — we warn so
    ``gossip`` + ``topology_aware=True`` is never a silent no-op."""
    if topology_aware:
        if f"{name}_topo" in _REGISTRY:
            return get(f"{name}_topo")
        proto = get(name)
        if not proto.needs_topology:
            warnings.warn(
                f"topology_aware=True has no effect for protocol {name!r}: "
                f"no {name + '_topo'!r} variant is registered and {name!r} "
                f"is not topology-aware itself",
                UserWarning, stacklevel=2)
        return proto
    return get(name)
