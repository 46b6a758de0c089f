"""repro.analysis: the shared jaxpr walker, the rule pack, and the CLI.

Three layers of pins:

* the two legacy traversals (``spec.jaxpr_materializes_shape``,
  ``roofline.jaxpr_cost``) are now shims on ``analysis.walker`` — parity
  tests keep them BIT-identical to the pre-refactor implementations,
* each built-in rule fires on a deliberately-broken program and stays
  silent on the real engines' programs (the clean-on-main gate),
* the CLI audits a real (dense + mesh) slice end to end in a subprocess
  and exits nonzero exactly when an ERROR finding exists.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import base as rule_base
from repro.analysis import programs as aprog
from repro.analysis import report
from repro.analysis.findings import ERROR, WARNING, Finding
from repro.analysis.rules.collective_census import census
from repro.analysis.walker import iter_eqns, materializes_shape

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def dense_suite():
    """One traced dense program set reused by the parity + clean tests."""
    from repro import protocols
    progs = []
    for name in protocols.names():
        progs.extend(aprog.dense_programs(name, codec="none"))
    progs.extend(aprog.dense_programs("fedavg", codec="int8"))
    return progs


# ---------------------------------------------------------------------------
# shim parity: the walker reproduces the legacy traversals bit-for-bit
# ---------------------------------------------------------------------------

def _legacy_jaxpr_cost(jaxpr):
    """The pre-walker roofline traversal, verbatim — the parity oracle."""
    from repro.launch.roofline import (_BYTES_OPS, _aval_bytes, _conv_flops,
                                       _dot_flops)
    flops = 0.0
    byts = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            flops += _dot_flops(eqn)
            byts += sum(_aval_bytes(v.aval) for v in eqn.invars)
            byts += _aval_bytes(eqn.outvars[0].aval)
        elif prim == "conv_general_dilated":
            flops += _conv_flops(eqn)
            byts += sum(_aval_bytes(v.aval) for v in eqn.invars)
            byts += _aval_bytes(eqn.outvars[0].aval)
        elif prim in _BYTES_OPS:
            byts += _aval_bytes(eqn.outvars[0].aval)
            byts += _aval_bytes(eqn.invars[0].aval) if prim == "concatenate" \
                else 0.0
        elif prim == "scan":
            f, b = _legacy_jaxpr_cost(eqn.params["jaxpr"].jaxpr)
            n = eqn.params["length"]
            flops += n * f
            byts += n * b
        elif prim == "shard_map":
            sub = eqn.params["jaxpr"]
            f, b = _legacy_jaxpr_cost(sub.jaxpr if hasattr(sub, "jaxpr")
                                      else sub)
            n = int(eqn.params["mesh"].size)
            flops += n * f
            byts += n * b
        elif prim == "while":
            f, b = _legacy_jaxpr_cost(eqn.params["body_jaxpr"].jaxpr)
            flops += f
            byts += b
        elif prim == "cond":
            costs = [_legacy_jaxpr_cost(br.jaxpr)
                     for br in eqn.params["branches"]]
            flops += max(c[0] for c in costs)
            byts += max(c[1] for c in costs)
        else:
            sub = None
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    sub = eqn.params[key]
                    break
            if sub is not None:
                sj = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                f, b = _legacy_jaxpr_cost(sj)
                flops += f
                byts += b
    return flops, byts


def test_jaxpr_cost_bit_identical_to_legacy(dense_suite):
    """Float addition is non-associative: the fold must replay the legacy
    accumulation order exactly, not just land within an epsilon."""
    from repro.launch.roofline import jaxpr_cost
    assert dense_suite
    for p in dense_suite:
        new = jaxpr_cost(p.jaxpr.jaxpr)
        old = _legacy_jaxpr_cost(p.jaxpr.jaxpr)
        assert new == old, p.name            # exact, not approx


def test_materializes_shape_matches_legacy_semantics():
    """The shim probe: float (D, D) trips it, int (D, D) only without the
    float filter, and sub-jaxprs (scan body) are reached."""
    D = 6

    def f(x):
        dense = jnp.ones((D, D), jnp.float32) @ x         # float [D, D]
        idx = jnp.zeros((D, D), jnp.int32)                # int [D, D]
        return dense.sum() + idx.sum()

    j = jax.make_jaxpr(f)(jnp.ones((D,)))
    assert materializes_shape(j, (D, D))
    assert materializes_shape(j, (D, D), floating_only=False)

    def g(x):                                             # int-only program
        idx = jnp.zeros((D, D), jnp.int32)
        return x.sum() + idx.sum()

    j = jax.make_jaxpr(g)(jnp.ones((D,)))
    assert not materializes_shape(j, (D, D))              # float filter
    assert materializes_shape(j, (D, D), floating_only=False)

    def h(x):                                             # inside a scan body
        def body(c, _):
            return c + (jnp.ones((D, D)) @ c), None
        c, _ = jax.lax.scan(body, x, None, length=3)
        return c

    j = jax.make_jaxpr(h)(jnp.ones((D,)))
    assert materializes_shape(j, (D, D))

    from repro.protocols.spec import jaxpr_materializes_shape
    assert jaxpr_materializes_shape(j, (D, D))            # shim agrees


def test_walker_nested_scan_cond_pjit():
    """Traversal edge cases: multiplicities compose through nesting, cond
    branches are alternatives (max), and pjit bodies are reached with the
    right path labels."""
    D = 4

    def inner(x):
        return x @ jnp.ones((D, D))                       # 2*D*D*D flops

    def f(x):
        def body(c, _):
            c = jax.lax.cond(c.sum() > 0,
                             lambda v: jax.jit(inner)(v),  # pjit in branch
                             lambda v: v + 1.0, c)
            return c, None
        c, _ = jax.lax.scan(body, x, None, length=5)
        return c

    j = jax.make_jaxpr(f)(jnp.ones((D, D)))

    from repro.launch.roofline import jaxpr_cost
    flops, _ = jaxpr_cost(j.jaxpr)
    assert flops == 5 * (2.0 * D * D * D)                 # length x max-branch

    paths = {s.pretty_path for s in iter_eqns(j)}
    assert any("scan.body" in p and "cond.branch" in p for p in paths)
    assert any("jit.call" in p and p.endswith("dot_general") for p in paths)

    # loop membership survives nesting: the dot sits inside the scan body
    dots = [s for s in iter_eqns(j) if s.eqn.primitive.name == "dot_general"]
    assert dots and all(s.in_loop and s.mult == 5.0 for s in dots)


def test_census_loop_weighting_single_device():
    """census() scales collectives by trip count (1-device mesh so the
    psum traces in-process)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P

    def mix(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                             in_specs=P("data"), out_specs=P(None),
                             check_vma=False)(x)

    def run(x):
        def body(c, _):
            return c + mix(c)[0], None
        return jax.lax.scan(body, x, None, length=3)[0]

    assert census(jax.make_jaxpr(mix)(jnp.ones((1, 2)))) == {"psum": 1.0}
    assert census(jax.make_jaxpr(run)(jnp.ones((1, 2)))) == {"psum": 3.0}


# ---------------------------------------------------------------------------
# rules: broken programs fire, real programs stay clean
# ---------------------------------------------------------------------------

def _findings_for(rule_id, program):
    rule = rule_base.get(rule_id)
    assert rule.applies(program)
    return rule.check(program)


def test_no_dense_mixing_flags_forced_dense_lowering():
    """Forcing mix_path=dense while asserting the sparse-path invariant is
    the exact regression the rule exists for: ERROR findings at the [P, P]
    sites."""
    [prog] = aprog.dense_programs("gossip", mix_path="dense",
                                  kinds=("round",))
    assert prog.mix_path == "dense" and not prog.meta["sparse_path"]
    broken = dataclasses.replace(
        prog, meta=dict(prog.meta, sparse_path=True))
    findings = _findings_for("no-dense-mixing", broken)
    assert findings and all(f.severity == ERROR for f in findings)
    assert "8, 8" in findings[0].message or "(8, 8)" in findings[0].message

    # the honest dense program doesn't claim sparseness -> rule inapplicable
    assert not rule_base.get("no-dense-mixing").applies(prog)


def test_collective_census_mismatch_is_error():
    """A program whose wire traffic diverges from its mixing-structure
    budget — here an extra psum against an empty budget — is an ERROR."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P

    def leaky(x):                      # one psum the budget doesn't allow
        return jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                             in_specs=P("data"), out_specs=P(None),
                             check_vma=False)(x)

    j = jax.make_jaxpr(leaky)(jnp.ones((1, 2)))
    prog = aprog.Program(name="fixture/leaky", jaxpr=j, engine="mesh",
                         protocol="fedavg", mix_path="psum", codec="none",
                         kind="round",
                         meta={"census_budget": {}, "rounds": 1})
    findings = _findings_for("collective-census", prog)
    assert len(findings) == 1 and findings[0].severity == ERROR
    assert "psum=1" in findings[0].message

    # and exact agreement is clean
    ok = aprog.Program(name="fixture/ok", jaxpr=j, engine="mesh",
                       protocol="fedavg", mix_path="psum", codec="none",
                       kind="round",
                       meta={"census_budget": {"psum": 1.0}, "rounds": 1})
    assert _findings_for("collective-census", ok) == []


def test_scan_carry_repack_warning_and_1d_exemption():
    def repack(x):                     # 2-D carry rebuilt by concatenate
        def body(c, _):
            return jnp.concatenate([c[1:], c[:1]], axis=0), None
        return jax.lax.scan(body, x, None, length=4)[0]

    j = jax.make_jaxpr(repack)(jnp.ones((3, 2)))
    prog = aprog.Program(name="fixture/repack", jaxpr=j, engine="dense",
                         protocol="fedavg", mix_path="sparse", codec="none",
                         kind="run", meta={})
    findings = _findings_for("scan-carry-stability", prog)
    assert [f.severity for f in findings] == [WARNING]
    assert "concatenate" in findings[0].message

    def repack_1d(x):                  # mean_packed-style 1-D rebuild: OK
        def body(c, _):
            return jnp.concatenate([c[1:], c[:1]], axis=0), None
        return jax.lax.scan(body, x, None, length=4)[0]

    j = jax.make_jaxpr(repack_1d)(jnp.ones((6,)))
    prog = dataclasses.replace(prog, jaxpr=j, name="fixture/repack1d")
    assert _findings_for("scan-carry-stability", prog) == []


def test_no_host_transfer_callback_severity_by_loop():
    def looped(x):
        def body(c, _):
            jax.debug.callback(lambda v: None, c)
            return c + 1.0, None
        return jax.lax.scan(body, x, None, length=2)[0]

    j = jax.make_jaxpr(looped)(jnp.ones((2,)))
    prog = aprog.Program(name="fixture/cb-loop", jaxpr=j, engine="dense",
                         protocol="fedavg", mix_path="sparse", codec="none",
                         kind="run", meta={})
    findings = _findings_for("no-host-transfer", prog)
    assert [f.severity for f in findings] == [ERROR]
    assert "loop" in findings[0].message

    def once(x):                       # outside any loop: stalls, WARNING
        jax.debug.callback(lambda v: None, x)
        return x + 1.0

    j = jax.make_jaxpr(once)(jnp.ones((2,)))
    prog = dataclasses.replace(prog, jaxpr=j, name="fixture/cb-once")
    findings = _findings_for("no-host-transfer", prog)
    assert [f.severity for f in findings] == [WARNING]


def test_donation_integrity_dead_and_aliased_args():
    def dead(x, y):                    # x never consumed
        return y * 2.0

    j = jax.make_jaxpr(dead)(jnp.ones((4,)), jnp.ones((4,)))
    prog = aprog.Program(name="fixture/dead", jaxpr=j, engine="dense",
                         protocol="fedavg", mix_path="sparse", codec="none",
                         kind="run", meta={"donate_intent": (0,)})
    findings = _findings_for("donation-integrity", prog)
    assert [f.severity for f in findings] == [ERROR]
    assert "dead" in findings[0].message

    def aliased(x, y):                 # x passes straight through
        return x, y * 2.0

    j = jax.make_jaxpr(aliased)(jnp.ones((4,)), jnp.ones((4,)))
    prog = dataclasses.replace(prog, jaxpr=j, name="fixture/aliased")
    findings = _findings_for("donation-integrity", prog)
    assert [f.severity for f in findings] == [WARNING]
    assert "aliased away" in findings[0].message


def test_dense_suite_clean_on_main(dense_suite):
    """The real engines' programs carry zero ERROR findings — the CI gate's
    dense half, in-process."""
    findings = rule_base.run_rules(dense_suite)
    errors = [f for f in findings if f.severity == ERROR]
    assert errors == [], "\n".join(f"{f.rule}::{f.program}: {f.message}"
                                   for f in errors)
    # run programs exercise the donation contract (intent present + clean)
    runs = [p for p in dense_suite if p.kind == "run"]
    assert runs and all(p.meta.get("donate_intent") == (0,) for p in runs)


# ---------------------------------------------------------------------------
# registry + report plumbing
# ---------------------------------------------------------------------------

def test_rule_registry_lists_builtins_and_rejects_duplicates():
    names = rule_base.names()
    for rid in ("no-dense-mixing", "collective-census",
                "scan-carry-stability", "no-host-transfer",
                "donation-integrity"):
        assert rid in names
    with pytest.raises(ValueError, match="duplicate"):
        rule_base.register(rule_base.get("no-dense-mixing"))
    with pytest.raises(KeyError, match="unknown rule"):
        rule_base.get("no-such-rule")


def test_report_json_and_exit_semantics(tmp_path):
    j = jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones((2,)))
    prog = aprog.Program(name="fixture/min", jaxpr=j, engine="dense",
                         protocol="fedavg", mix_path="sparse", codec="none",
                         kind="round", meta={})
    bad = Finding(rule="r", severity=ERROR, program=prog.name,
                  where="", message="boom")
    doc = report.write_json(str(tmp_path / "A.json"), [prog], [bad],
                            rule_base.all_rules())
    on_disk = json.loads((tmp_path / "A.json").read_text())
    assert on_disk["num_errors"] == doc["num_errors"] == 1
    assert not on_disk["ok"]
    table = report.render_table([prog], [bad])
    assert "fixture/min" in table and "boom" in table

    clean = report.to_json([prog], [], rule_base.all_rules())
    assert clean["ok"] and clean["num_errors"] == 0


def test_cli_main_inprocess_gates_on_errors(tmp_path):
    """main() returns 0 on a clean dense slice and 1 when a rule errors
    (an always-fail rule injected through the registry)."""
    from repro.analysis.__main__ import main

    out = tmp_path / "ANALYSIS.json"
    rc = main(["--engine", "dense", "--protocol", "fedavg",
               "--codec", "none", "--rounds", "2", "--out", str(out),
               "--baseline", "", "--diff-out", ""])
    assert rc == 0
    doc = json.loads(out.read_text())
    # default --mix-path both: dense AND sparse lowerings, round + run
    # each, plus the fault-wired run per lowering (codec "none" only)
    assert doc["ok"] and len(doc["programs"]) == 6
    assert len(doc["contracts"]) == 6

    class AlwaysBad(rule_base.Rule):
        id = "always-bad"
        doc = "test fixture"

        def check(self, program):
            return [self.finding(ERROR, program, "", "injected")]

    rule_base.register(AlwaysBad())
    try:
        rc = main(["--engine", "dense", "--protocol", "fedavg",
                   "--codec", "none", "--rounds", "2",
                   "--rule", "always-bad", "--out", "",
                   "--baseline", "", "--diff-out", ""])
        assert rc == 1
    finally:
        rule_base.unregister("always-bad")

    assert main(["--list-rules"]) == 0


def test_cli_subprocess_mesh_and_dense_clean(tmp_path):
    """End to end as CI runs it: the CLI forces 8 host devices itself, so
    the mesh suite (and its psum_mix-derived census budgets) only works in
    a subprocess."""
    out = tmp_path / "ANALYSIS.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--protocol", "fedavg",
         "--engine", "both", "--codec", "none", "--rounds", "2",
         "--out", str(out), "--baseline", "", "--diff-out", ""],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["ok"] and not doc["findings"]
    names = {p["name"] for p in doc["programs"]}
    assert "dense/fedavg/sparse/none/round" in names
    assert "mesh/fedavg/psum/none/round" in names
    # the mesh round's census was measured and equals its budget
    mesh_round = next(p for p in doc["programs"]
                      if p["name"] == "mesh/fedavg/psum/none/round")
    assert mesh_round["census"].get("psum", 0) > 0
    assert mesh_round["census"] == mesh_round["census_budget"]
    # run2 = 2 x the round budget, via the loop-weighted census
    mesh_run = next(p for p in doc["programs"]
                    if p["name"] == "mesh/fedavg/psum/none/run2")
    assert mesh_run["census"] == {k: 2 * v
                                  for k, v in mesh_round["census"].items()}


def test_mesh_programs_inprocess_raises_clear_error():
    if len(jax.devices()) >= aprog.MESH_D:
        pytest.skip("enough devices to trace the mesh suite in-process")
    with pytest.raises(RuntimeError, match="forces host devices"):
        aprog.mesh_programs("fedavg")
