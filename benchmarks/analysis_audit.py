"""Static-analysis audit as a benchmark module: row-ifies ANALYSIS.json.

Runs the ``repro.analysis`` CLI in a subprocess — it must force an 8-way
host platform through XLA_FLAGS *before* jax is imported, which a parent
process that already imported jax cannot do — and emits the audit summary
as rows so ``BENCH_*.json`` tracks the audited-program surface over PRs.
The quick pass audits the dense engine only; ``--full`` audits both
engines across the default codec set, same as the gating CI step.

ERROR findings (rule violations or contract-diff regressions) RAISE after
row-ification, so ``benchmarks/run.py --only analysis`` exits nonzero
exactly when the CI gate would — local runs and CI agree.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(quick: bool = True):
    tmp = tempfile.mkdtemp(prefix="repro-analysis-")
    out = os.path.join(tmp, "ANALYSIS.json")
    # keep the default --rounds so program names line up with the
    # checked-in contracts baseline (names embed the trip count)
    cmd = [sys.executable, "-m", "repro.analysis", "--out", out,
           "--diff-out", os.path.join(tmp, "CONTRACTS_DIFF.md")]
    if quick:
        # dense + sampled: the sampled suite is trace-only (the 10^6-client
        # store never allocates) so it is cheap enough for the quick pass,
        # and its state-residency verdict is a row we want tracked per PR
        cmd += ["--engine", "dense,sampled", "--codec", "none"]
    # the audit traces on host devices: the child must never claim an
    # accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_REPO, "src"), env.get("PYTHONPATH"))
        if p)
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=_REPO)
    if not os.path.exists(out):
        raise RuntimeError(
            f"analysis CLI produced no report (exit {proc.returncode}): "
            f"{proc.stderr[-500:]}")
    with open(out) as fh:
        doc = json.load(fh)
    sev = {}
    for f in doc["findings"]:
        sev[f["severity"]] = sev.get(f["severity"], 0) + 1
    diff = doc.get("contract_diff") or {}
    rows = [
        ("analysis/programs", float(len(doc["programs"])), ""),
        ("analysis/rules", float(len(doc["rules"])), ""),
        ("analysis/errors", float(doc["num_errors"]), ""),
        ("analysis/warnings", float(sev.get("WARNING", 0)), ""),
        ("analysis/contracts_compared", float(diff.get("compared", 0)), ""),
        ("analysis/contract_regressions",
         float(sum(1 for r in diff.get("rows", ())
                   if r.get("gate") == "ERROR")), ""),
        ("analysis/ok", float(doc["ok"] and proc.returncode == 0),
         f"exit={proc.returncode}"),
    ]
    # state-residency row-ification: the sampled-window programs' peak live
    # bytes must track the K-row window, never the D=10^6 enrollment
    sampled = [p for p in doc["programs"]
               if p["name"].startswith("sampled/")]
    if sampled:
        peaks = [p["peak_live_bytes"] or 0 for p in sampled]
        sr_errs = sum(1 for f in doc["findings"]
                      if f["rule"] == "state-residency"
                      and f["severity"] == "ERROR")
        rows += [
            ("analysis/sampled_programs", float(len(sampled)), ""),
            ("analysis/sampled_peak_live_mib",
             max(peaks) / 2 ** 20,
             "max over sampled-window programs; window-sized, D-free"),
            ("analysis/state_residency_errors", float(sr_errs),
             "population-shaped avals or window-budget breaches"),
        ]
    if doc["num_errors"] or proc.returncode != 0:
        errs = [f"{f['rule']} :: {f['program']}: {f['message']}"
                for f in doc["findings"] if f["severity"] == "ERROR"]
        raise RuntimeError(
            f"analysis audit failed (exit {proc.returncode}, "
            f"{doc['num_errors']} error finding(s)):\n  "
            + "\n  ".join(errs[:5] or [proc.stderr[-500:]]))
    return rows
