"""Nemotron-H (the layer-pattern backbone, the grouped SSD and the dropless
held-slice MoE) against the float32 reference ``bench/models/nemotron_h.py``
at a tiny cut on the CPU: d_model 64, two SSM groups of two heads, 16
routed experts of which 8 are held, top 3, a shared expert twice a routed
expert's width, blocks MEMEM*E, vocabulary 256, sequence 64 in chunks of
16, on seeded weights."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "bench", "tests")]

from nemotron_tiny import CONFIG, tiny_config  # noqa: E402

from bench.common import load_json  # noqa: E402
from bench.drivers.mesh_rounds import model_config  # noqa: E402
from bench.models import nemotron_h  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.model import build_model  # noqa: E402

#: loss: float32 sums taken in another order (chunked CE, blocked SSD and
#: attention) agree to a few units in the 7th digit
LOSS_RTOL = 1e-5
#: a leaf's gradient: the same rounding, accumulated through seven blocks
#: and the backward pass, relative to the leaf's gradient norm
GRAD_RTOL = 1e-4


def _setup(held: int = 8, seed: int = 0):
    cfg = tiny_config(load_json(CONFIG), num_experts=held)
    params = nemotron_h.init(jax.random.PRNGKey(seed), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    tok = jax.random.randint(k1, (2, 64), 0, cfg["vocab_size"])
    lab = jax.random.randint(k2, (2, 64), 0, cfg["vocab_size"])
    return cfg, params, tok, lab


def _program(cfg):
    model = build_model(model_config(cfg))

    def loss(p, tok, lab):
        return model.loss_fn(p, {"tokens": tok, "labels": lab})[0]
    return model, loss


def _ref_loss(cfg):
    def loss(p, tok, lab):
        return nemotron_h.loss(p, tok, lab, cfg)
    return loss


def _gaps(a: dict, b: dict) -> dict:
    """Per leaf, |a - b| over |b| (norms)."""
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return {i: float(jnp.linalg.norm((x - y).astype(jnp.float32))
                     / max(float(jnp.linalg.norm(y.astype(jnp.float32))), 1e-30))
            for i, (x, y) in enumerate(zip(fa, fb))
            if float(jnp.linalg.norm(y.astype(jnp.float32))) > 0}


def test_program_builds_the_reference_tree():
    cfg, params, _, _ = _setup()
    model, _ = _program(cfg)
    prog = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(prog), jax.tree.leaves(params)))


def test_loss_and_gradients_match_reference():
    cfg, params, tok, lab = _setup()
    _, prog = _program(cfg)
    ref = _ref_loss(cfg)
    lp, gp = jax.jit(jax.value_and_grad(prog))(params, tok, lab)
    lr, gr = jax.jit(jax.value_and_grad(ref))(params, tok, lab)
    assert abs(float(lp) - float(lr)) <= LOSS_RTOL * abs(float(lr))
    gaps = _gaps(gp, gr)
    assert max(gaps.values()) <= GRAD_RTOL, gaps
    # the selection-only bias gets no gradient in either
    for g in (gp, gr):
        assert all(float(jnp.abs(run["moe"]["router_bias"]).max()) == 0
                   for run in g["blocks"] if "moe" in run)


def test_bf16_reference_in_the_programs_place_fails():
    cfg, params, tok, lab = _setup()
    ref = _ref_loss(cfg)
    lr, gr = jax.jit(jax.value_and_grad(ref))(params, tok, lab)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    lb, gb = jax.jit(jax.value_and_grad(ref))(half, tok, lab)
    loss_bad = abs(float(lb) - float(lr)) > LOSS_RTOL * abs(float(lr))
    grad_bad = max(_gaps(jax.tree.map(lambda a: a.astype(jnp.float32), gb),
                         gr).values()) > GRAD_RTOL
    assert loss_bad or grad_bad
    assert grad_bad


def _moe_inputs(held: int):
    cfg, params, _, _ = _setup(held=held, seed=7)
    mcfg = model_config(cfg)
    lp = params["blocks"][1]["moe"]                       # the first E block
    lp = jax.tree.map(lambda a: a[0], lp)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 64, mcfg.d_model))
    return cfg, mcfg, lp, x


def test_expert_shares_add_up_to_the_whole_layer():
    """Two chips' shares of the 16 experts, [0, 8) and [8, 16), with the
    shared expert counted once, give the uncut layer's output; so does the
    reference's."""
    cfg, mcfg, lp, x = _moe_inputs(held=16)
    whole, c = moe_mod.moe_dropless(lp, x, mcfg)
    parts, assigned = 0, 0
    for s in range(2):
        share = dict(lp, w_in=lp["w_in"][8 * s:8 * s + 8],
                     w_out=lp["w_out"][8 * s:8 * s + 8])
        y, cs = moe_mod.moe_dropless(share, x, mcfg, offset=8 * s,
                                     shared=(s == 0))
        parts = parts + y
        assigned += int(cs["moe_assignments"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # every token's k assignments land on one of the two shares
    assert assigned == int(c["moe_assignments"]) == x.shape[0] * x.shape[1] * 3
    m = nemotron_h.dims(cfg)
    ref_parts = sum(
        nemotron_h.moe(cfg, m, x, dict(lp, w_in=lp["w_in"][8 * s:8 * s + 8],
                                       w_out=lp["w_out"][8 * s:8 * s + 8]),
                       first=8 * s, shared=(s == 0)) for s in range(2))
    np.testing.assert_allclose(np.asarray(ref_parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_bias_selects_but_does_not_weight():
    cfg, mcfg, lp, x = _moe_inputs(held=8)
    xf = x.reshape(-1, mcfg.d_model)
    w0, i0, _ = moe_mod.route(lp["router"], xf, mcfg, jnp.zeros((16,)))
    bias = jnp.zeros((16,)).at[jnp.array([2, 9, 13])].set(0.5)
    w1, i1, _ = moe_mod.route(lp["router"], xf, mcfg, bias)
    assert bool(jnp.any(jnp.sort(i0, -1) != jnp.sort(i1, -1)))
    scores = jax.nn.sigmoid(jnp.dot(xf, lp["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    plain = jnp.take_along_axis(scores, i1, -1)
    np.testing.assert_allclose(np.asarray(w1),
                               np.asarray(2.5 * plain / plain.sum(-1, keepdims=True)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 2.5, rtol=1e-6)


def test_nothing_dropped_when_every_token_picks_one_expert():
    """A bias that sends every token to held expert 3 (the 3 * 128
    assignments of a capacity-bound layer would overflow it): every
    assignment is computed, and the output is the reference's."""
    cfg, mcfg, lp, x = _moe_inputs(held=8)
    lp = dict(lp, router_bias=jnp.zeros((16,)).at[3].set(100.0))
    y, c = moe_mod.moe_dropless(lp, x, mcfg)
    gates, idx = nemotron_h.route(cfg, nemotron_h.dims(cfg),
                                  x.reshape(-1, mcfg.d_model), lp["router"],
                                  lp["router_bias"])
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
    assert int(c["moe_dropped"]) == 0
    assert int(c["moe_assignments"]) == int(jnp.sum(idx < 8))
    ref = nemotron_h.moe(cfg, nemotron_h.dims(cfg), x, lp)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("sorted_group,sizes,computed", [
    ([0, 0, 1, 1, 2], [2, 2], 4),     # the layer's own layout: all computed
    ([0, 0, 1, 1, 2], [1, 1], 1),     # sizes capped at a capacity of one
    ([1, 1, 0, 0, 2], [2, 2], 0),     # rows out of expert order
])
def test_dropped_counter_sees_rows_outside_their_group(sorted_group, sizes,
                                                       computed):
    """``moe_dropped`` is the held rows less those ``computed_rows`` finds
    in their own expert's ragged group (group 2 = an absent expert): a
    capacity cap or a wrong sort shows as dropped rows."""
    got = moe_mod.computed_rows(jnp.array(sorted_group, jnp.int32),
                                jnp.array(sizes, jnp.int32))
    assert int(got) == computed


def test_single_group_ssd_is_unchanged():
    """mamba2-130m's reduced model (one B/C group) reads what it read before
    the grouped SSD was added: its loss and one SSD mixer's output, to
    float32 rounding."""
    from repro.configs import get_config
    from repro.models import ssm as ssm_mod
    cfg = get_config("mamba2-130m").reduced()
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(3))
    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, cfg.vocab_size)
    loss, _ = m.loss_fn(p, {"tokens": tok, "labels": tok})
    lp = jax.tree.map(lambda a: a[0], p["layers"]["ssm"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.d_model))
    y, _ = ssm_mod.ssm_mixer(lp, x, ssm_mod.ssm_dims(cfg))
    got = [float(loss), float(jnp.sum(y)), float(jnp.sum(jnp.abs(y))),
           float(y[0, 5, 7]), float(y[1, 63, 3])]
    before = [6.217450141906738, 663.789794921875, 25838.12109375,
              -0.4093208611011505, -0.5655527710914612]
    np.testing.assert_allclose(got, before, rtol=1e-6)


def test_grouped_ssd_with_one_group_equals_shared_path():
    from repro.models.ssm import ssd_chunked
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(k[0], (2, 32, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, 32, 4)))
    A = -jnp.exp(jax.random.normal(k[2], (4,)))
    B = jax.random.normal(k[3], (2, 32, 16))
    C = jax.random.normal(k[4], (2, 32, 16))
    y1, s1 = ssd_chunked(x, dt, A, B, C, 8)
    y2, s2 = ssd_chunked(x, dt, A, B[:, :, None], C[:, :, None], 8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-5)


def test_decode_matches_the_full_forward():
    """Prefill half the sequence, then decode token by token through the
    Mamba-2 states and the attention cache: the logits of the full
    forward pass."""
    from repro.models import transformer
    cfg, params, tok, _ = _setup()
    mcfg = model_config(cfg)
    model = build_model(mcfg)
    full, _, _ = transformer.forward(params, mcfg, tokens=tok)
    cache = model.make_cache(2, 64)
    last, cache = jax.jit(model.prefill)(params, {"tokens": tok[:, :32]},
                                         cache)
    decode = jax.jit(model.decode)
    outs = [last[:, -1]]
    for t in range(32, 63):
        logits, cache = decode(params, cache, {"token": tok[:, t:t + 1]})
        outs.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full[:, 31:63]), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("held", [8, 16])
def test_engine_counters_and_one_by_one_clients(held):
    """MeshEngine without a mesh trains a non-vmappable model's clients one
    by one and passes the summed counters out of ``run_rounds``."""
    from repro.config import FLConfig
    from repro.protocols.engine import MeshEngine
    cfg, params, tok, lab = _setup(held=held)
    model = build_model(model_config(cfg))
    assert not model.client_vmap
    D, steps, T = 2, 2, 2
    eng = MeshEngine(model, FLConfig(num_clusters=1, lr=0.01), D, steps,
                     algorithm="fedavg")
    f = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (D,) + a.shape),
                     params)
    batches = {"tokens": jnp.broadcast_to(tok[:1], (T, D, steps, 1, 64)),
               "labels": jnp.broadcast_to(lab[:1], (T, D, steps, 1, 64))}
    f2, losses = eng.run_rounds(f, jax.random.PRNGKey(0), T, batches)
    assert losses.shape == (T,) and bool(jnp.all(jnp.isfinite(losses)))
    c = eng.counters
    assert c["moe_dropped"].shape == (T,) and int(c["moe_dropped"].sum()) == 0
    # each round: D clients x steps x 3 E blocks x 64 tokens x top 3, the
    # share that lands on the held experts
    assert 0 < int(c["moe_assignments"][0]) <= D * steps * 3 * 64 * 3
    if held == 16:
        assert int(c["moe_assignments"][0]) == D * steps * 3 * 64 * 3
