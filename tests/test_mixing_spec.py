"""Pins for the structured-sparse mixing fast path (PR 5).

* MixingSpec -> dense reconstruction: for EVERY registered protocol and
  random RoundContexts, ``mixing_spec(ctx).to_dense()`` equals
  ``mixing_matrix(ctx)`` EXACTLY (assert_array_equal — the reconstruction
  is elementwise/dyadic, so bit-for-bit is achievable and required);
* the sparse kernel path matches the dense oracle path round-for-round on
  the flat buffers and through full ``DenseEngine.run_rounds`` training
  runs (tight f32 tolerance — summation *order* differs between a
  segment-sum and a dense dot, so bitwise equality is not defined here —
  loose on bf16), including with ``codec="int8"`` and topk error feedback
  threaded through the packed scan carry;
* ``mix_path`` semantics: "dense" never calls ``mixing_spec``, "sparse"
  raises for spec-less protocols, unknown values raise;
* the D=4096 guarantee: a sparse ``DenseEngine`` round jaxpr materializes
  NO [D, D] array (and the dense path does — the inspection is not
  vacuous);
* the packed-state regressions: ``pack_tree`` runs sub_rounds+1 times per
  round (the round-start state is packed once, not once per sub-round
  mix) and the client data gather runs once per round (not once per
  sub-round).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import protocols
from repro.config import FLConfig
from repro.configs.paper_models import LOGREG_SYN
from repro.core.simulator import Simulator
from repro.data.federated import pack_clients
from repro.data.synthetic import syncov
from repro.kernels import ops, ref
from repro.kernels.fed_mix_sparse import fed_mix_matching, fed_mix_segment
from repro.protocols import (
    MatchingSpec, SegmentSpec, apply_spec_flat, make_context,
)
from repro.protocols.engine import DenseEngine
from repro.protocols.spec import jaxpr_materializes_shape


def _random_ctx(proto, D, seed, sync, key=None):
    rng = np.random.default_rng(seed)
    L = max(1, D // 2)
    cids = rng.integers(0, L, D).astype(np.int32)
    return make_context(
        key=jax.random.PRNGKey(seed) if key is None else key,
        survive=jnp.asarray((rng.random(D) > 0.35).astype(np.float32)),
        counts=jnp.asarray(rng.uniform(0.5, 5.0, D).astype(np.float32)),
        cluster_ids=jnp.asarray(cids), num_clusters=L,
        do_global_sync=sync)


# ---------------------------------------------------------------------------
# spec -> dense reconstruction is EXACT for every protocol
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(protocols.names()))
@pytest.mark.parametrize("sync", [True, False])
@pytest.mark.parametrize("D", [5, 8, 16])
def test_spec_to_dense_equals_mixing_matrix_exactly(name, sync, D):
    proto = protocols.get(name)
    ctx = _random_ctx(proto, D, seed=D * 7 + sync, sync=sync)
    spec = proto.mixing_spec(ctx)
    assert spec is not None, f"{name} should provide a MixingSpec"
    S_new, S_old = spec.to_dense()
    M_new, M_old = proto.mixing_matrix(ctx)
    np.testing.assert_array_equal(np.asarray(S_new), np.asarray(M_new))
    np.testing.assert_array_equal(np.asarray(S_old), np.asarray(M_old))


@pytest.mark.parametrize("name", list(protocols.names()))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_flat_path_matches_dense_oracle(name, dtype):
    proto = protocols.get(name)
    D, P = 12, 300
    rng = np.random.default_rng(3)
    for sync in (True, False):
        ctx = _random_ctx(proto, D, seed=11 + sync, sync=sync)
        xn = jnp.asarray(rng.normal(size=(D, P)).astype(np.float32)
                         ).astype(dtype)
        xo = jnp.asarray(rng.normal(size=(D, P)).astype(np.float32)
                         ).astype(dtype)
        M_new, M_old = proto.mixing_matrix(ctx)
        dense = ref.fed_mix_ref(M_new, M_old, xn, xo)
        sparse = apply_spec_flat(proto.mixing_spec(ctx), xn, xo)
        assert sparse.dtype == dense.dtype
        tol = 2e-6 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(sparse, np.float32),
                                   np.asarray(dense, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)
        # the Pallas kernels (interpret mode) agree too
        sparse_k = apply_spec_flat(proto.mixing_spec(ctx), xn, xo,
                                   use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(sparse_k, np.float32),
                                   np.asarray(dense, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("d,p,block", [
    (12, 300, {}),                                  # one ragged param tile
    (20, 1100, dict(block_r=8, block_k=8, block_d=512)),   # padded rows,
    (33, 1024, dict(block_r=16, block_k=32, block_d=256)),  # many tiles
])
def test_sparse_kernels_match_oracles_on_unaligned_shapes(d, p, block):
    """Rows padded only past one block, the last param tile ragged: the
    interpret-mode kernels still equal their jnp oracles."""
    rng = np.random.default_rng(d + p)
    xn = jnp.asarray(rng.normal(size=(d, p)).astype(np.float32))
    xo = jnp.asarray(rng.normal(size=(d, p)).astype(np.float32))
    cids = jnp.asarray(rng.integers(0, 3, d).astype(np.int32))
    w_new = jnp.asarray(rng.uniform(0, 1, d).astype(np.float32))
    w_old = jnp.asarray(rng.uniform(0, 1, d).astype(np.float32))
    got = fed_mix_segment(cids, w_new, w_old, xn, xo, num_segments=3,
                          interpret=True, **block)
    want = ref.fed_mix_segment_ref(cids, w_new, w_old, xn, xo,
                                   num_segments=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    perms = jnp.asarray(np.stack([rng.permutation(d) for _ in range(2)])
                        .astype(np.int32))
    survive = jnp.asarray((rng.random(d) > 0.3).astype(np.float32))
    block.pop("block_k", None)
    got = fed_mix_matching(perms, survive, xn, xo, interpret=True, **block)
    want = ref.fed_mix_matching_ref(perms, survive, xn, xo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# engine: sparse path == dense path round-for-round (incl. codecs)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sim_data():
    xs, ys = syncov(num_clients=24, seed=0)
    data = pack_clients(xs, ys, 10, seed=0)
    fl = FLConfig(num_clients=24, num_clusters=3, devices_per_cluster=2,
                  participation=6, local_epochs=1, batch_size=10, lr=0.05,
                  straggler_rate=0.3, sync_period=2)
    sim = Simulator(LOGREG_SYN, data, fl)
    return sim, fl


def _engine(sim, fl, algo, mix_path, codec=None):
    return DenseEngine(LOGREG_SYN, sim.data_dev, fl, protocols.get(algo),
                       codec=codec, mix_path=mix_path)


@pytest.mark.parametrize("algo", ["fedavg", "fedp2p", "gossip",
                                  "gossip_async"])
def test_engine_sparse_matches_dense_rounds(sim_data, algo):
    sim, fl = sim_data
    params = sim.init_params(0)
    key = jax.random.PRNGKey(1)
    T = 3
    p_d, m_d = _engine(sim, fl, algo, "dense").run_rounds(params, key, T)
    p_s, m_s = _engine(sim, fl, algo, "sparse").run_rounds(params, key, T)
    np.testing.assert_allclose(np.asarray(m_s["train_loss"]),
                               np.asarray(m_d["train_loss"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_s["acc"]),
                               np.asarray(m_d["acc"]), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("codec", ["int8", "topk"])
def test_engine_sparse_matches_dense_with_codec(sim_data, codec):
    """The quantized-exchange seam composes with the sparse path: the same
    wire record (same key-seeded stochastic rounding, same error-feedback
    residual through the packed scan carry) feeds both mixing lowerings.
    int8 tolerance is wider: the dense path contracts the int8 record via
    the fused fed_mix_q algebra while the sparse path decodes first."""
    sim, fl = sim_data
    params = sim.init_params(0)
    key = jax.random.PRNGKey(2)
    T = 3
    p_d, m_d = _engine(sim, fl, "fedp2p", "dense",
                       codec=codec).run_rounds(params, key, T)
    p_s, m_s = _engine(sim, fl, "fedp2p", "sparse",
                       codec=codec).run_rounds(params, key, T)
    np.testing.assert_allclose(np.asarray(m_s["train_loss"]),
                               np.asarray(m_d["train_loss"]),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_engine_topk_feedback_rides_packed_carry(sim_data):
    """Stateful codec on the sparse path: round_fn returns the
    [P, sum(sizes)] residual and threading it changes the next round
    (the feedback mass is really carried, not dropped)."""
    sim, fl = sim_data
    eng = _engine(sim, fl, "fedp2p", "sparse", codec="topk")
    params = sim.init_params(0)
    P = protocols.get("fedp2p").num_participants(fl)
    total = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    p1, _, res = eng.round_fn(params, jax.random.PRNGKey(3))
    assert res.shape == (P, total)
    assert float(jnp.sum(jnp.abs(res))) > 0.0
    # threading the residual vs dropping it diverges on the next round
    p2_threaded, _, _ = eng.round_fn(p1, jax.random.PRNGKey(4), 1, res)
    p2_dropped, _, _ = eng.round_fn(p1, jax.random.PRNGKey(4), 1)
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(p2_threaded),
                             jax.tree.leaves(p2_dropped))]
    assert max(diffs) > 0.0


# ---------------------------------------------------------------------------
# mix_path semantics
# ---------------------------------------------------------------------------

class _DenseOnly(protocols.Protocol):
    name = "_dense_only_test"

    def mixing_matrix(self, ctx):
        D = ctx.survive.shape[0]
        return (jnp.full((D, D), 1.0 / D, jnp.float32),
                jnp.zeros((D, D), jnp.float32))


def test_mix_path_sparse_raises_for_specless_protocol(sim_data):
    sim, fl = sim_data
    eng = DenseEngine(LOGREG_SYN, sim.data_dev, fl, _DenseOnly(),
                      mix_path="sparse")
    with pytest.raises(ValueError, match="provides no mixing_spec"):
        eng.round_fn(sim.init_params(0), jax.random.PRNGKey(0))


def test_mix_path_auto_falls_back_to_dense_for_specless(sim_data):
    """'auto' is sparse only WHERE A SPEC EXISTS — a spec-less protocol
    runs the dense oracle, identically to mix_path='dense'."""
    sim, fl = sim_data
    params = sim.init_params(0)
    key = jax.random.PRNGKey(5)
    eng_a = DenseEngine(LOGREG_SYN, sim.data_dev, fl, _DenseOnly(),
                        mix_path="auto")
    eng_d = DenseEngine(LOGREG_SYN, sim.data_dev, fl, _DenseOnly(),
                        mix_path="dense")
    pa, la = eng_a.round_fn(params, key)
    pd, ld = eng_d.round_fn(params, key)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(ld))
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pd)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mix_path_unknown_raises(sim_data):
    sim, fl = sim_data
    with pytest.raises(ValueError, match="unknown mix_path"):
        DenseEngine(LOGREG_SYN, sim.data_dev, fl, protocols.get("fedavg"),
                    mix_path="blocked")


# ---------------------------------------------------------------------------
# the D=4096 guarantee: no [D, D] array anywhere in a sparse round
# ---------------------------------------------------------------------------

def _big_engine(D, mix_path, algo="fedp2p"):
    fl = FLConfig(num_clients=D, num_clusters=8, devices_per_cluster=D // 8,
                  participation=D, local_epochs=1, batch_size=4, lr=0.05,
                  straggler_rate=0.1)
    z = jnp.zeros
    data_dev = {"x": z((D, 4, LOGREG_SYN.input_dim)), "y": z((D, 4),
                jnp.int32), "mask": z((D, 4)), "counts": jnp.ones((D,)),
                "test_x": z((D, 2, LOGREG_SYN.input_dim)),
                "test_y": z((D, 2), jnp.int32), "test_mask": z((D, 2))}
    return DenseEngine(LOGREG_SYN, data_dev, fl, protocols.get(algo),
                       mix_path=mix_path)


@pytest.mark.parametrize("algo", ["fedp2p", "gossip"])
def test_sparse_round_materializes_no_dense_matrix_at_4096(algo):
    D = 4096
    eng = _big_engine(D, "sparse", algo)
    params = eng.init_params(0)
    jaxpr = jax.make_jaxpr(eng._round)(params, jax.random.PRNGKey(0))
    assert not jaxpr_materializes_shape(jaxpr, (D, D)), \
        f"sparse {algo} round materializes a [{D}, {D}] array"


def test_sparse_run_rounds_completes_at_4096():
    """The point of the fast path: a 4096-client DenseEngine.run_rounds
    actually executes (seconds on CPU — the dense path's two 64 MiB
    matrices and 137 GFLOP contraction per mix are gone)."""
    eng = _big_engine(4096, "sparse", "fedp2p")
    _, metrics = eng.run_rounds(eng.init_params(0), jax.random.PRNGKey(0), 1)
    assert np.isfinite(float(metrics["train_loss"][0]))


def test_gossip_async_odd_d_perm_stack_not_flagged():
    """At odd D the round-robin schedule has R == D matchings, so the
    [R, D] int32 partner stack is (D, D)-shaped — the float-only probe
    must not mistake the O(D) index structure for a dense operator."""
    D = 255
    fl = FLConfig(num_clients=D, participation=D, local_epochs=1,
                  batch_size=4, lr=0.05)
    z = jnp.zeros
    data_dev = {"x": z((D, 4, LOGREG_SYN.input_dim)), "y": z((D, 4),
                jnp.int32), "mask": z((D, 4)), "counts": jnp.ones((D,)),
                "test_x": z((D, 2, LOGREG_SYN.input_dim)),
                "test_y": z((D, 2), jnp.int32), "test_mask": z((D, 2))}
    eng = DenseEngine(LOGREG_SYN, data_dev, fl,
                      protocols.get("gossip_async"), mix_path="sparse")
    jaxpr = jax.make_jaxpr(eng._round)(eng.init_params(0),
                                       jax.random.PRNGKey(0))
    assert not jaxpr_materializes_shape(jaxpr, (D, D))
    # the int32 stack IS there — only the float filter clears it
    assert jaxpr_materializes_shape(jaxpr, (D, D), floating_only=False)


def test_dense_round_does_materialize_dense_matrix():
    """The jaxpr inspection is not vacuous: the dense path at the same D
    really contains the [D, D] operator the sparse path eliminates."""
    D = 256
    eng = _big_engine(D, "dense")
    params = eng.init_params(0)
    jaxpr = jax.make_jaxpr(eng._round)(params, jax.random.PRNGKey(0))
    assert jaxpr_materializes_shape(jaxpr, (D, D))


# ---------------------------------------------------------------------------
# packed-state regressions: pack once per round, gather once per round
# ---------------------------------------------------------------------------

def _counting(monkeypatch, fn_name="pack_tree"):
    calls = {"n": 0}
    orig = getattr(ops, fn_name)

    def counted(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(ops, fn_name, counted)
    return calls


def test_round_packs_round_start_state_once(sim_data, monkeypatch):
    """sync_period=S traces exactly S+1 pack_tree calls per round: one for
    the global carry (the round-start state is a broadcast of it — packed
    once per round_fn call, with ONE TreeSpec) plus one per sub-round for
    the freshly-trained client models. The pre-packed-state engine packed
    f_old anew inside every one of the S mixing applications (2S total)."""
    sim, fl = sim_data                   # sync_period == 2
    calls = _counting(monkeypatch)
    eng = _engine(sim, fl, "fedp2p", "sparse")
    jax.make_jaxpr(eng._round)(sim.init_params(0), jax.random.PRNGKey(0))
    assert calls["n"] == fl.sync_period + 1


def test_run_rounds_packs_global_model_once(sim_data, monkeypatch):
    """A whole T-round run_rounds program packs the global model ONCE (the
    scan body re-packs only the per-sub-round training outputs)."""
    sim, fl = sim_data
    calls = _counting(monkeypatch)
    eng = _engine(sim, fl, "fedavg", "sparse")
    eng.run_rounds(sim.init_params(0), jax.random.PRNGKey(0), 3)
    # 1 global pack + sync_period packs inside the (once-traced) scan body
    assert calls["n"] == 1 + fl.sync_period


def _count_data_gathers(jaxpr, data_shape):
    """# of gather eqns (recursively) whose operand is the full client
    data array — the per-round client-batch gather."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subs(eqn):
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for u in vs:
                if isinstance(u, ClosedJaxpr):
                    yield u.jaxpr
                elif isinstance(u, Jaxpr):
                    yield u

    def walk(j):
        n = 0
        for eqn in j.eqns:
            if eqn.primitive.name == "gather" and \
                    tuple(eqn.invars[0].aval.shape) == data_shape:
                n += 1
            n += sum(walk(s) for s in subs(eqn))
        return n

    return walk(jaxpr.jaxpr)


def test_client_batches_gathered_once_per_round(sim_data):
    """The round's client selection is fixed across sub-rounds, so the full
    [num_clients, ...] batch arrays are gathered exactly once per round —
    the gather count must NOT scale with sync_period."""
    sim, fl = sim_data
    import dataclasses
    counts = {}
    for sp in (1, 3):
        eng = DenseEngine(LOGREG_SYN, sim.data_dev,
                          dataclasses.replace(fl, sync_period=sp),
                          protocols.get("fedp2p"))
        jaxpr = jax.make_jaxpr(eng._round)(sim.init_params(0),
                                           jax.random.PRNGKey(0))
        counts[sp] = _count_data_gathers(
            jaxpr, tuple(sim.data_dev["x"].shape))
    assert counts[1] == counts[3] == 1


# ---------------------------------------------------------------------------
# closed-form perm stack / packed-mean helpers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D", [1, 2, 3, 8, 9, 17, 64])
def test_matching_perm_stack_matches_tuple_form(D):
    """The vectorized circle-method perm stack equals the (expensive)
    tuple-structured round_robin_matchings form exactly, even/odd D."""
    from repro.protocols.async_gossip import (
        matching_perm_stack, round_robin_matchings,
    )
    from repro.protocols.gossip import perm_of_groups
    got = matching_perm_stack(D)
    want = np.stack([perm_of_groups(D, [list(g) for g in groups])
                     for groups in round_robin_matchings(D)])
    np.testing.assert_array_equal(got, want)
    # every row is an involution (a valid pairing)
    rows = np.arange(got.shape[0])[:, None]
    np.testing.assert_array_equal(got[rows, got],
                                  np.broadcast_to(np.arange(D), got.shape))


def test_mean_packed_respects_leaf_dtypes():
    """The packed consensus collapse reduces each leaf in ITS dtype —
    identical to tree.map(mean, unpack(...)) even for mixed f32/bf16."""
    rng = np.random.default_rng(0)
    tree = {"a": jnp.asarray(rng.normal(size=(6, 11)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(6, 7)).astype(np.float32)
                             ).astype(jnp.bfloat16)}
    flat, spec = ops.pack_tree(tree)
    got = ops.unpack_tree(ops.mean_packed(flat, spec), spec)
    want = jax.tree.map(lambda x: jnp.mean(x, axis=0),
                        ops.unpack_tree(flat, spec))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# hypothesis: random contexts keep the reconstruction exact (skip w/o dev
# deps)
# ---------------------------------------------------------------------------

try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings

    _SETTINGS = settings(
        deadline=None, max_examples=20,
        suppress_health_check=[hypothesis.HealthCheck.too_slow])
    HAVE_HYPOTHESIS = True
except ImportError:                       # degrade, don't die, without dev deps
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @_SETTINGS
    @given(st.sampled_from(list(protocols.names())), st.integers(1, 24),
           st.booleans(), st.integers(0, 2 ** 31 - 1))
    def test_spec_reconstruction_property(name, D, sync, seed):
        proto = protocols.get(name)
        rng = np.random.default_rng(seed)
        L = int(rng.integers(1, D + 1))
        ctx = make_context(
            key=jax.random.PRNGKey(seed),
            survive=jnp.asarray((rng.random(D) > rng.random())
                                .astype(np.float32)),
            counts=jnp.asarray(rng.uniform(0.1, 9.0, D).astype(np.float32)),
            cluster_ids=jnp.asarray(rng.integers(0, L, D).astype(np.int32)),
            num_clusters=L, do_global_sync=sync)
        spec = proto.mixing_spec(ctx)
        assert isinstance(spec, (SegmentSpec, MatchingSpec))
        S_new, S_old = spec.to_dense()
        M_new, M_old = proto.mixing_matrix(ctx)
        np.testing.assert_array_equal(np.asarray(S_new), np.asarray(M_new))
        np.testing.assert_array_equal(np.asarray(S_old), np.asarray(M_old))
        # flat paths agree on the same context
        xn = jnp.asarray(rng.normal(size=(D, 17)).astype(np.float32))
        xo = jnp.asarray(rng.normal(size=(D, 17)).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(apply_spec_flat(spec, xn, xo)),
            np.asarray(ref.fed_mix_ref(M_new, M_old, xn, xo)),
            rtol=2e-6, atol=2e-6)
