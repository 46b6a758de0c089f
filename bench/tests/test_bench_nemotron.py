"""The Nemotron-H cell's own files: the configuration's parameter count
and its agreement with the program's tree and the registry, the exact
operation and byte counts at the published widths, the driver cut tiny on
four CPU devices, and the two MoE readers on a small recorded trace
(``data/moe_trace.pbtxt``; window [0, 220] us, two chips, 2 calls of 2
rounds)."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from nemotron_tiny import CELL, CONFIG, ROOT  # noqa: E402

import jax  # noqa: E402

from bench import check, peaks, spans, trace  # noqa: E402
from bench.common import load_json, manifest  # noqa: E402
from bench.drivers.mesh_rounds import model_config  # noqa: E402
from bench.metrics import load  # noqa: E402
from bench.models import nemotron_h  # noqa: E402

CFG = load_json(CONFIG)
SEED = 2**33 + 29


def _count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_parameters_match_program_and_registry():
    from repro.configs import get_config
    from repro.models.model import build_model
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == CFG["name"])
    assert entry["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    mcfg = model_config(CFG)
    assert mcfg == dataclasses.replace(
        get_config(CFG["name"]),
        **{k: CFG[k] for k in entry["reduced"]})
    # the published widths, router width and top-k are kept
    assert (mcfg.d_model, mcfg.router_experts, mcfg.num_experts_per_tok,
            mcfg.moe_d_ff, mcfg.shared_expert_d_ff) == (2688, 128, 6, 1856, 3712)
    assert CFG["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128,
                                "vocab_size": 131072, "parameters": 31577940288}
    prog = jax.eval_shape(build_model(mcfg).init, jax.random.PRNGKey(0))
    mine = jax.eval_shape(lambda k: nemotron_h.init(k, CFG),
                          jax.random.PRNGKey(0))
    assert check.leaf_names(prog) == check.leaf_names(mine)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(prog), jax.tree.leaves(mine)))
    d, V = 2688, 16384
    mamba = d * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * d + d
    moe = d * 128 + 128 + 16 * 2 * d * 1856 + 2 * d * 3712 + d
    attn = d * 4096 + 2 * d * 256 + 4096 * d + d
    assert (mamba, moe, attn) == (38_744_896, 179_948_288, 23_399_040)
    assert 2 * V * d == 88_080_384
    assert _count(mine) == 2 * V * d + 3 * mamba + 3 * moe + attn + d \
        == CFG["parameters"] == 767_561_664
    assert CFG["num_experts"] * CFG["deployment"]["chips_per_layer"] == 128
    assert V * CFG["deployment"]["chips_per_layer"] == 131072


def test_flops_and_expert_bytes_at_published_widths():
    d, ff, S, q = 2688, 1856, 2048, 128
    h, p, n, g = 64, 64, 128, 8
    mamba = d * 10304 + 4096 * d + q * g * n + q * h * p + 2 * h * p * n
    moe = d * 128 + 2 * d * 3712 + 6 * 16 * 2 * d * ff // 128
    attn = d * (32 + 4) * 128 + 4096 * d
    fwd = 2 * (3 * mamba + 3 * moe + attn + 16384 * d) + 2 * 32 * 128 * (S + 1)
    assert nemotron_h.forward_flops_per_token(CFG, S) == fwd
    assert nemotron_h.train_flops_per_token(CFG, S) == 3 * fwd == 1_682_472_960
    assert nemotron_h.expert_flops_per_assignment(CFG) == 12 * d * ff
    assert nemotron_h.expert_weight_bytes(CFG) == 3 * 4 * 16 * 2 * d * ff
    assert nemotron_h.expert_activation_bytes_per_assignment(CFG) == 20 * d
    # 384 assignments a held expert a step: the expert matmuls' FLOP per
    # byte of weights sits under the v5e ridge (240): HBM bounds them
    per_byte = (384 * 16 * 12 * d * ff) / nemotron_h.expert_weight_bytes(CFG)
    assert per_byte == 192
    assert per_byte < 197e12 / 819e9


def test_driver_on_four_devices():
    """The driver cut tiny over four virtual CPU devices, in a process of
    its own: the readings agree with the reference within the cell's
    limits, both planted faults fail them, and no assignment is dropped."""
    code = textwrap.dedent(f"""
        import sys, json
        sys.path.insert(0, {HERE!r})
        from nemotron_tiny import tiny_cell
        import jax
        from bench import check, drivers
        cell = tiny_cell()
        d = drivers.load(cell["cell"]["driver"])(
            cell["config"], cell["traffic"], cell["cell"], {SEED},
            jax.devices())
        d.setup()
        shards = {{s.device for leaf in jax.tree.leaves(d.f)
                   for s in leaf.addressable_shards}}
        prog = d.readings(2)
        d.free()
        ref = d.reference(2)
        print(json.dumps({{
            "n_dev": len(shards), "calls": d.calls,
            "assigned": [int(c["moe_assignments"].sum()) for c in d.counts],
            "dropped": [int(c["moe_dropped"].sum()) for c in d.counts],
            "ok": check.numbers(prog, ref),
            "half_batch": check.numbers(d.reference(2, fault="half_batch"), ref),
            "no_exchange": check.numbers(d.reference(2, fault="no_exchange"), ref),
            "limits": cell["cell"]["limits"],
            "flops": d.required_flops_per_call(),
            "work": d.expert_work(1)}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["n_dev"] == 4 and out["calls"] == 2
    assert all(a > 0 for a in out["assigned"])
    assert check.verdict(out["ok"], out["limits"])[0], out["ok"]
    assert out["ok"]["loss_gap"] < 1e-5 and out["ok"]["update_gap"] < 1e-3
    for fault in ("half_batch", "no_exchange"):
        assert not check.verdict(out[fault], out["limits"])[0], fault
    assert out["dropped"] == [0, 0]
    assert out["flops"] > 0
    # the last call by hand: 2 rounds x 4 clients x 2 local steps x 3 E
    # blocks (MEMEM*E), each moving the 8 held experts' f32 weights three
    # times (3 x 4 B x 8 x 2 x 64 x 32); per assignment 12 x 64 x 32 FLOPs
    # and 5 f32 rows of 64; all of it over the 4 chips
    a = out["assigned"][-1]
    weights = 2 * 4 * 2 * 3 * (3 * 4 * 8 * 2 * 64 * 32)
    assert out["work"] == [a * 12 * 64 * 32 / 4,
                           (weights + a * 5 * 4 * 64) / 4]


def test_refuses_a_program_without_the_pattern_blocks():
    from bench.drivers.mesh_hybrid_rounds import program_matches_reference
    program_matches_reference(CFG)
    with pytest.raises(SystemExit):
        program_matches_reference(dict(CFG, layer_pattern="M" * 52))


# ---------------------------------------------------------------------------
# the readers, on a recorded trace
# ---------------------------------------------------------------------------

DATA = os.path.join(HERE, "data", "moe_trace.pbtxt")


def _ctx(path, driver):
    from jax.profiler import ProfileData
    with open(path) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    tr = trace.from_profile(ProfileData.from_serialized_xspace(raw))
    lo, hi = trace.window(tr)
    return {"trace": tr, "spans": spans.from_xspace(raw), "lo": lo, "hi": hi,
            "window_s": (hi - lo) / 1e9, "calls": 2, "rounds": 4,
            "driver": driver, "peaks": peaks.peaks("TPU v5 lite"),
            "chips": 2}


class Stub:
    rounds_per_call = 2

    def __init__(self, flops_us, bytes_us):
        self.need = (flops_us * 1e-6 * 197e12, bytes_us * 1e-6 * 819e9)

    def expert_work(self, calls):
        assert calls == 2
        return self.need


@pytest.mark.parametrize("flops_us,bytes_us,share", [
    (10.2, 25.5, 50.0),           # bound by HBM: 25.5 of 51 us
    (30.6, 5.1, 60.0),            # bound by FLOPs: 30.6 of 51 us
])
def test_moe_readers(flops_us, bytes_us, share):
    ctx = _ctx(DATA, Stub(flops_us, bytes_us))
    # (102 + 80) / 2 us over 4 rounds, in ms: the scope-less ragged dot
    # counts in moe
    assert load("moe_ms_per_round")(ctx) == pytest.approx(91e-3 / 4)
    # least time over (62 + 40) / 2 us in moe_experts and the ragged dot
    assert load("moe_expert_roofline")(ctx) == pytest.approx(share)


def test_moe_readers_find_nothing_elsewhere():
    """A trace without the scopes (a program that has no MoE blocks, or a
    driver without ``expert_work``) reads None and raises nothing."""
    other = os.path.join(HERE, "data", "span_trace.pbtxt")
    ctx = _ctx(other, Stub(1.0, 1.0))
    assert load("moe_ms_per_round")(ctx) is None
    assert load("moe_expert_roofline")(ctx) is None
    ctx = _ctx(DATA, object())
    assert load("moe_expert_roofline")(ctx) is None


def test_cell_reuses_the_mesh4_traffic():
    entry = next(w for w in manifest()["workloads"] if w["name"] == CELL)
    assert entry["traffic"] == "fedp2p-mesh4" and entry["chips"] == 4
    cell = load_json(os.path.join(ROOT, "bench", "workloads", f"{CELL}.json"))
    assert cell["driver"] == "mesh_hybrid_rounds"
    assert (cell["rounds_per_call"], cell["check_steps"]) == (4, 2)
