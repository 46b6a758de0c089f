import os
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ^ MUST precede any jax-importing import (dryrun.py pattern): mesh-engine
#   programs trace shard_map bodies against an 8-way host data mesh.

"""Audit every registered protocol's compiled programs on both engines.

  PYTHONPATH=src python -m repro.analysis --protocol all --engine both \
      --mix-path both --codec none,int8

Traces one-round and T-round programs for each (protocol, codec) on the
requested engines, runs every registered rule, derives each program's
static CONTRACT (collective census, wire bytes, flops, peak live bytes,
scan-carry layout — ``repro.analysis.contracts``), diffs the contracts
against the checked-in ``contracts/baseline.json`` snapshot, prints the
findings table, writes ANALYSIS.json + CONTRACTS_DIFF.md, and exits
nonzero on ERROR findings — the CI gate. ``--update-baseline``
regenerates the snapshot after an intentional change; ``--list-rules``
and ``--rule ID`` inspect / run individual rules.
"""
import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static jaxpr auditor for the engines' performance "
                    "invariants: rule checks + contract snapshot diffing")
    ap.add_argument("--protocol", default="all", metavar="NAME[,NAME...]",
                    help="registered protocol name(s), or 'all'")
    ap.add_argument("--engine", default="all",
                    metavar="{dense,mesh,sampled,both,all}[,...]",
                    help="engine suite(s) to trace, comma-separable; 'all' "
                         "(default) covers dense + mesh + sampled — the "
                         "baseline's coverage ratchet ('both' = the "
                         "pre-sampled dense + mesh pair)")
    ap.add_argument("--mix-path", dest="mix_path", default="both",
                    choices=("dense", "sparse", "auto", "both"),
                    help="dense-engine mixing lowering to trace; 'both' "
                         "(default) traces dense AND sparse — the "
                         "baseline's full coverage (the mesh engine always "
                         "lowers grouped psums)")
    ap.add_argument("--codec", default="none,int8", metavar="NAME[,NAME...]",
                    help="repro.compression codec(s) to lower into the "
                         "programs")
    ap.add_argument("--rounds", type=int, default=3, metavar="T",
                    help="trip count of the T-round run_rounds programs")
    ap.add_argument("--rules", default=None, metavar="ID[,ID...]",
                    help="run only these rules (default: all registered)")
    ap.add_argument("--rule", action="append", default=None, metavar="ID",
                    help="run a single rule by id (repeatable; see "
                         "--list-rules for ids)")
    ap.add_argument("--out", default="ANALYSIS.json",
                    help="JSON artifact path ('' to skip writing)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="contracts baseline to diff against (default: "
                         "<repo>/contracts/baseline.json; '' disables the "
                         "diff)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from this run's contracts "
                         "instead of diffing (commit the result)")
    ap.add_argument("--diff-out", dest="diff_out", default="CONTRACTS_DIFF.md",
                    metavar="PATH",
                    help="markdown contract-diff table artifact ('' to "
                         "skip writing)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print every registered rule's id + doc and exit")
    args = ap.parse_args(argv)

    from repro import protocols
    from repro.analysis import base, contracts as contracts_mod, programs, \
        report

    if args.list_rules:
        for rule in base.all_rules():
            print(f"{rule.id:24s} {rule.doc}")
        return 0

    names = (list(protocols.names()) if args.protocol == "all"
             else [protocols.get(n.strip()).name
                   for n in args.protocol.split(",")])
    _engine_sets = {"dense": ("dense",), "mesh": ("mesh",),
                    "sampled": ("sampled",), "both": ("dense", "mesh"),
                    "all": ("dense", "mesh", "sampled")}
    engines = []
    for tok in (t.strip() for t in args.engine.split(",") if t.strip()):
        if tok not in _engine_sets:
            ap.error(f"unknown engine {tok!r} (choose from "
                     f"{', '.join(sorted(_engine_sets))})")
        engines += [e for e in _engine_sets[tok] if e not in engines]
    engines = tuple(engines)
    codecs = tuple(c.strip() for c in args.codec.split(",") if c.strip())
    rule_ids = ([r.strip() for r in args.rules.split(",")]
                if args.rules else []) + (args.rule or [])
    rules = base.all_rules() if not rule_ids else [base.get(r)
                                                   for r in rule_ids]

    progs = programs.build_suite(names, engines=engines,
                                 mix_path=args.mix_path, codecs=codecs,
                                 rounds=args.rounds)
    findings = base.run_rules(progs, rules)

    contracts = contracts_mod.build_contracts(progs)
    baseline_path = (contracts_mod.default_baseline_path()
                     if args.baseline is None else args.baseline)
    diff_doc = None
    if args.update_baseline:
        contracts_mod.write_baseline(baseline_path, contracts)
        print(f"wrote baseline {baseline_path} "
              f"({len(contracts)} contracts)")
    elif baseline_path and os.path.exists(baseline_path):
        baseline = contracts_mod.load_baseline(baseline_path)
        diff_findings, diff_rows = contracts_mod.diff_contracts(
            contracts, baseline)
        findings = findings + diff_findings
        table = contracts_mod.render_diff_table(
            diff_rows, compared=len(contracts), baseline_path=baseline_path)
        diff_doc = {"baseline": baseline_path, "compared": len(contracts),
                    "rows": diff_rows,
                    "ok": not any(r["gate"] == "ERROR" for r in diff_rows)}
        if args.diff_out:
            with open(args.diff_out, "w") as fh:
                fh.write(table)
            print(f"wrote {args.diff_out}")
    elif baseline_path:
        print(f"no baseline at {baseline_path}; skipping contract diff "
              "(generate one with --update-baseline)")

    print(report.render_table(progs, findings))
    if args.out:
        doc = report.write_json(args.out, progs, findings, rules,
                                contracts=contracts, contract_diff=diff_doc)
        print(f"wrote {args.out}")
    else:
        doc = report.to_json(progs, findings, rules, contracts=contracts,
                             contract_diff=diff_doc)
    n_err = doc["num_errors"]
    print(f"{len(progs)} programs, {len(rules)} rules, "
          f"{len(findings)} findings, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
