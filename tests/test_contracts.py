"""Program contracts (`repro.analysis.contracts`): the liveness pass, the
wire accounting, the snapshot differ, and the two contract-backed rules.

The load-bearing pins:

* injected regressions ARE caught with the right diff rule id — an extra
  collective flips ``contract-diff.census``, a large reintroduced buffer
  flips ``contract-diff.peak-live-bytes``, a single extra wire byte flips
  ``contract-diff.wire`` (the exact gate), a missing baseline entry flips
  ``contract-diff.coverage`` — so the CI diff gate demonstrably fails on
  the regressions it exists for,
* the ``peak-live-bytes`` rule fires on a [D, D] temporary at LARGE D
  (where the O(D·n) budget bites) and stays silent on O(D) programs,
* the liveness estimator is deterministic, lower-bounded by the
  program's inputs, and monotone under appending a big temporary —
  across nested scan/cond/while programs (randomized versions live in
  test_contracts_properties.py, which needs the hypothesis dev dep),
* `wire-model-parity` errors when a protocol's declared wire structure
  disagrees with the traced program, and the checked-in baseline is
  diff-clean against freshly built contracts (the repo's own gate,
  in-process for the dense half).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import contracts as C
from repro.analysis import programs as aprog
from repro.analysis import base as rule_base
from repro.analysis.findings import ERROR
from repro.core.comm_model import ring_wire_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sds_args(closed):
    return [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
            for v in closed.jaxpr.invars]


def _rewrap(prog, extra_fn, suffix):
    """Re-trace ``prog`` with ``extra_fn(args) -> scalar`` folded into an
    extra output — the 'someone edited the engine' regression fixture."""
    closed = prog.jaxpr

    def wrapped(*args):
        out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)
        return out, extra_fn(args)

    j = jax.make_jaxpr(wrapped)(*_sds_args(closed))
    return dataclasses.replace(prog, jaxpr=j, name=prog.name + suffix)


@pytest.fixture(scope="module")
def sparse_round():
    [p] = aprog.dense_programs("fedavg", mix_path="sparse", kinds=("round",))
    return p


# ---------------------------------------------------------------------------
# the snapshot differ catches injected regressions, with the right rule id
# ---------------------------------------------------------------------------

def _diff_rules(current_prog, baseline_prog):
    cur = {baseline_prog.name: C.build_contract(
        dataclasses.replace(current_prog, name=baseline_prog.name))}
    base = {baseline_prog.name: C.build_contract(baseline_prog)}
    findings, rows = C.diff_contracts(cur, base)
    return findings, rows


def test_differ_flags_added_collective(sparse_round):
    """An extra psum smuggled into the round (here via a 1-device mesh so
    it traces in-process) must flip the collective-census diff gate."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P

    def extra_psum(args):
        leak = jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                             in_specs=P("data"), out_specs=P(None),
                             check_vma=False)(jnp.ones((1, 2)))
        return leak.sum()

    broken = _rewrap(sparse_round, extra_psum, "+psum")
    findings, rows = _diff_rules(broken, sparse_round)
    assert any(f.rule == "contract-diff.census" and f.severity == ERROR
               for f in findings), findings
    assert any(r["field"] == "census" and r["gate"] == "ERROR" for r in rows)


def test_differ_flags_reintroduced_big_buffer(sparse_round):
    """A re-materialized large operator (the [D, D]-at-scale failure mode)
    moves peak_live_bytes past the 10% gate."""
    N = 600    # 600x600 f32 = 1.44 MB >> 10% of the toy round's peak

    def big_temp(args):
        return (jnp.zeros((N, N), jnp.float32) + 1.0).mean()

    broken = _rewrap(sparse_round, big_temp, "+dd")
    findings, _ = _diff_rules(broken, sparse_round)
    assert any(f.rule == "contract-diff.peak-live-bytes"
               and f.severity == ERROR for f in findings), findings


def test_differ_wire_gate_is_exact_and_coverage_errors(sparse_round):
    base = {"p": C.build_contract(sparse_round)}
    cur = {"p": dict(base["p"],
                     wire_payload_bytes=base["p"]["wire_payload_bytes"] + 1.0)}
    findings, _ = C.diff_contracts(cur, base)
    assert [f.rule for f in findings if f.severity == ERROR] \
        == ["contract-diff.wire"]

    # program with no baseline entry -> coverage ERROR telling you the fix
    findings, _ = C.diff_contracts({"new/prog": base["p"]}, {})
    assert [f.rule for f in findings] == ["contract-diff.coverage"]
    assert "--update-baseline" in findings[0].message

    # baseline-only programs (a filtered run) are skipped silently
    findings, rows = C.diff_contracts({}, base)
    assert findings == [] and rows == []


def test_differ_flags_changed_scan_carry(sparse_round):
    base = {"p": C.build_contract(sparse_round)}
    carries = json.loads(json.dumps(base["p"]["scan_carries"]))  # deep copy
    if not carries:
        pytest.skip("round program has no scan")
    carries[0]["carry"] = list(carries[0]["carry"]) + ["f32[9,9]"]
    findings, _ = C.diff_contracts({"p": dict(base["p"],
                                              scan_carries=carries)}, base)
    assert [f.rule for f in findings] == ["contract-diff.scan-carry"]


def test_diff_table_renders_markdown(sparse_round):
    base = {"p": C.build_contract(sparse_round)}
    cur = {"p": dict(base["p"], flops=base["p"]["flops"] * 2.0)}
    findings, rows = C.diff_contracts(cur, base)
    table = C.render_diff_table(rows, compared=1, baseline_path="b.json")
    assert "| p | flops |" in table and "ERROR" in table
    clean = C.render_diff_table([], compared=1, baseline_path="b.json")
    assert "No contract regressions" in clean


# ---------------------------------------------------------------------------
# peak-live-bytes: the budget bites at scale
# ---------------------------------------------------------------------------

def _synthetic_program(fn, args, *, name):
    return aprog.Program(name=name, jaxpr=jax.make_jaxpr(fn)(*args),
                         engine="dense", protocol="fedavg",
                         mix_path="sparse", codec="none", kind="round",
                         meta={"num_peers": 2048, "sparse_path": True,
                               "rounds": 1})


def test_peak_rule_fires_on_DxD_at_scale():
    """At D=2048, n=4 the O(D·n) state is ~32 KiB; a [D, D] one-hot mixing
    operator is 16 MiB. no-dense-mixing would need the shape; the budget
    rule needs only the bytes."""
    D = 2048
    x = jax.ShapeDtypeStruct((D, 4), jnp.float32)
    ids = jax.ShapeDtypeStruct((D,), jnp.int32)

    def densified(x, ids):                      # the regression
        M = jax.nn.one_hot(ids, D, dtype=jnp.float32)     # [D, D]
        return M @ x

    bad = _synthetic_program(densified, (x, ids), name="fixture/dd")
    findings = rule_base.get("peak-live-bytes").check(bad)
    assert [f.severity for f in findings] == [ERROR]
    assert "[D, D]" in findings[0].message

    def linear(x, ids):                         # the O(D·n) path
        seg = jax.ops.segment_sum(x, ids, num_segments=8)   # [8, 4]
        return x + seg[ids % 8]

    ok = _synthetic_program(linear, (x, ids), name="fixture/lin")
    assert rule_base.get("peak-live-bytes").check(ok) == []


def test_dense_and_mesh_suite_peaks_within_budget(sparse_round):
    """The real programs pass their own budget (the clean-on-main gate for
    the new rule, dense half in-process)."""
    rule = rule_base.get("peak-live-bytes")
    assert rule.applies(sparse_round)
    assert rule.check(sparse_round) == []


# ---------------------------------------------------------------------------
# wire-model-parity: declared structure vs traced program
# ---------------------------------------------------------------------------

def test_wire_parity_errors_on_false_declaration(sparse_round):
    """A protocol declaring wire traffic its program does not perform (or
    vice versa) is exactly what the rule must catch — the dense engine
    moves zero bytes, so declare one fedavg ring and watch it fire."""
    lying = dataclasses.replace(
        sparse_round, meta=dict(sparse_round.meta,
                                wire_model=((8, 1, 2.0),)))
    findings = rule_base.get("wire-model-parity").check(lying)
    assert [f.severity for f in findings] == [ERROR]
    assert "disagree" in findings[0].message

    assert rule_base.get("wire-model-parity").check(sparse_round) == []


def test_analytic_wire_bytes_closed_forms():
    """Hand-derived §3.2 byte counts per protocol at D=8, L=2: fedavg
    4(D-1)M, fedp2p sync 2(2(q-1)L + 2(D-1))M = 52M at q=4, gossip 2DM,
    async gossip DM."""
    from repro import protocols
    M = 144.0
    D, L = 8, 2
    cases = {"fedavg": 4 * (D - 1) * M,                       # 28 M
             "fedp2p": (4 * (4 - 1) * L + 4 * (D - 1)) * M,   # 52 M
             "fedp2p_topo": (4 * (4 - 1) * L + 4 * (D - 1)) * M,
             "gossip": 2 * D * M,
             "gossip_async": D * M}
    for name, want in cases.items():
        entries = protocols.get(name).wire_model(D, L, do_global_sync=True)
        got = C.analytic_wire_bytes(entries, M, None)
        assert got == want, (name, got, want)
        # int8 scales exactly by bits/32 on the analytic side
        scaled = C.analytic_wire_bytes(entries, M, "int8")
        assert scaled == pytest.approx(want * C.codec_bits("int8") / 32.0)


def test_ring_wire_bytes_matches_allreduce_time():
    from repro.core.comm_model import allreduce_time
    for n in (1, 2, 4, 7):
        M, bw = 1234.5, 7.5
        assert ring_wire_bytes(M, n) == pytest.approx(
            n * bw * allreduce_time(M, n, bw))


def test_collective_wire_sizes_groups_and_codecs():
    """Static accounting on a hand-built grouped psum: one [1, 6] f32
    payload over a 1-device group moves 0; the census still sees it; and
    the codec scales payload but not overhead."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P

    def f(x):
        def local(v):
            scalar = jax.lax.psum(jnp.ones(()), "data")     # overhead
            return jax.lax.psum(v * scalar, "data")         # payload
        return jax.shard_map(local, mesh=mesh, in_specs=P("data"),
                             out_specs=P(None), check_vma=False)(x)

    j = jax.make_jaxpr(f)(jnp.ones((1, 6)))
    wire = C.collective_wire(j, bits_per_param=32.0)
    # 1-device groups: ring moves 2(g-1)b = 0 bytes — parity with the
    # cost model's n=1 allreduce_time == 0
    assert wire == {"payload_bytes": 0.0, "overhead_bytes": 0.0}


# ---------------------------------------------------------------------------
# liveness estimator properties (nested scan/cond/while)
# ---------------------------------------------------------------------------

def build_nested_program(ops, n):
    """A nested jaxpr builder driven by an op list: each op wraps the
    running function in a scan body, a cond branch, a while-loop body, or
    an elementwise stage. Shared with test_contracts_properties.py, where
    hypothesis drives the op list."""
    def fn(x):
        return x * 2.0 + 1.0

    for op, k in ops:
        prev = fn
        if op == "scan":
            def fn(x, _p=prev, _k=k):
                def body(c, _):
                    return _p(c), None
                return jax.lax.scan(body, x, None, length=_k)[0]
        elif op == "cond":
            def fn(x, _p=prev):
                return jax.lax.cond(x.sum() > 0, _p, lambda v: v - 1.0, x)
        elif op == "while":
            def fn(x, _p=prev, _k=k):
                def cond(c):
                    return c[0] < _k
                def body(c):
                    return c[0] + 1, _p(c[1])
                return jax.lax.while_loop(cond, body, (0, x))[1]
        else:
            def fn(x, _p=prev):
                return _p(x) + x.mean()
    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((n, 3), jnp.float32))


NESTINGS = [
    [],
    [("scan", 3)],
    [("while", 2)],
    [("cond", 1)],
    [("scan", 2), ("cond", 1)],
    [("cond", 1), ("while", 3), ("ew", 1)],
    [("while", 2), ("scan", 4), ("scan", 2)],
    [("scan", 3), ("while", 2), ("cond", 1), ("ew", 1)],
]


@pytest.mark.parametrize("ops", NESTINGS, ids=lambda o: "-".join(
    f"{op}{k}" for op, k in o) or "flat")
def test_peak_liveness_bounds_and_determinism(ops):
    j = build_nested_program(ops, n=5)
    peak = C.peak_live_bytes(j)
    assert peak == C.peak_live_bytes(j)          # deterministic
    assert peak >= C.input_bytes(j) > 0          # inputs are live at entry


@pytest.mark.parametrize("ops", NESTINGS, ids=lambda o: "-".join(
    f"{op}{k}" for op, k in o) or "flat")
def test_peak_liveness_monotone_under_big_temp(ops):
    """Appending a [big, big] temporary raises the estimate by at least the
    temporary's size — the property the [D, D] gate rests on."""
    big, n = 100, 5
    j = build_nested_program(ops, n)
    peak = C.peak_live_bytes(j)

    def with_temp(x):
        t = jnp.zeros((big, big), jnp.float32) + x.mean()
        return jax.core.eval_jaxpr(j.jaxpr, j.consts, x), t.sum()

    j2 = jax.make_jaxpr(with_temp)(
        jax.ShapeDtypeStruct((n, 3), jnp.float32))
    peak2 = C.peak_live_bytes(j2)
    assert peak2 >= peak
    assert peak2 >= big * big * 4


def test_peak_liveness_scan_body_counts_once():
    """Memory, unlike time, does not scale with trip count: the same body
    scanned 2x and 50x peaks identically (xs/ys stacks aside — this body
    carries only)."""
    peaks = [C.peak_live_bytes(build_nested_program([("scan", k)], n=6))
             for k in (2, 50)]
    assert peaks[0] == peaks[1] > 0


# ---------------------------------------------------------------------------
# baseline: the checked-in snapshot is live and diff-clean
# ---------------------------------------------------------------------------

def test_checked_in_baseline_covers_full_matrix():
    path = os.path.join(REPO, "contracts", "baseline.json")
    contracts = C.load_baseline(path)
    protos = {"fedavg", "fedp2p", "fedp2p_topo", "gossip", "gossip_async"}
    for proto in protos:
        for codec in ("none", "int8"):
            for mp in ("dense", "sparse"):
                assert f"dense/{proto}/{mp}/{codec}/round" in contracts
                assert f"sampled/{proto}/{mp}/{codec}/round" in contracts
            assert f"mesh/{proto}/psum/{codec}/round" in contracts
        # the fault-wired programs ride the baseline too (codec "none"
        # only), keeping the DISABLED path's entries byte-identical
        for mp in ("dense", "sparse"):
            assert f"dense/{proto}/{mp}/none/faulty-run3" in contracts
            assert f"sampled/{proto}/{mp}/none/faulty-round" in contracts
    for kind in ("gather", "scatter"):
        assert f"store/memory/dev/none/{kind}" in contracts
    assert len(contracts) == 102
    # every mesh contract's static payload equals its analytic pricing —
    # the parity acceptance criterion, re-checked from the artifact
    for name, c in contracts.items():
        if c["wire_model_bytes"] is not None:
            assert c["wire_payload_bytes"] == pytest.approx(
                c["wire_model_bytes"], rel=C.EXACT_RTOL), name


def test_dense_contracts_diff_clean_against_checked_in_baseline():
    """Freshly built dense contracts match the committed snapshot — the
    regression gate, in-process (CI's subprocess run covers the mesh)."""
    baseline = C.load_baseline(
        os.path.join(REPO, "contracts", "baseline.json"))
    progs = []
    for mp in ("dense", "sparse"):
        progs.extend(aprog.dense_programs("fedavg", codec="none",
                                          mix_path=mp))
    findings, rows = C.diff_contracts(C.build_contracts(progs), baseline)
    assert [f for f in findings if f.severity == ERROR] == [], rows


def test_cli_update_baseline_roundtrip(tmp_path):
    """--update-baseline writes a loadable snapshot that immediately diffs
    clean against itself, and a doctored baseline fails the gate."""
    from repro.analysis.__main__ import main
    path = tmp_path / "baseline.json"
    args = ["--engine", "dense", "--protocol", "gossip", "--codec", "none",
            "--rounds", "2", "--out", "", "--diff-out", "",
            "--baseline", str(path)]
    assert main(args + ["--update-baseline"]) == 0
    assert main(args) == 0                       # self-diff is clean

    doc = json.loads(path.read_text())
    name = next(iter(doc["contracts"]))
    doc["contracts"][name]["census"] = {"psum": 999.0}
    path.write_text(json.dumps(doc))
    assert main(args) == 1                       # doctored baseline -> gate


def test_cli_subprocess_full_matrix_matches_baseline(tmp_path):
    """End to end as CI runs it: all three engines, both codecs, mix-path
    both, diffed against the checked-in baseline — exit 0, no
    regressions."""
    out = tmp_path / "ANALYSIS.json"
    diff = tmp_path / "CONTRACTS_DIFF.md"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--out", str(out),
         "--diff-out", str(diff)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["ok"] and len(doc["contracts"]) == 102
    assert doc["contract_diff"]["ok"]
    assert doc["contract_diff"]["compared"] == 102
    assert "No contract regressions" in diff.read_text()
