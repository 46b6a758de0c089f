"""Program contracts: static cost/memory/wire certification per program.

A *contract* is the machine-derived performance signature of one traced
program (``analysis/programs.Program``): what collectives it runs, how many
bytes they move, how many FLOPs the round folds to, how much memory is live
at the worst point, and what its scan carries look like. Contracts are pure
jaxpr analysis — nothing executes — so they are deterministic on one CPU
and can be checked into the repo (``contracts/baseline.json``) and diffed
on every CI run: an unexplained new collective, wire-byte growth, a FLOP or
peak-live-bytes jump past 10%, or a changed scan-carry layout fails the
gate before any benchmark has to run.

Wire accounting (the static side of the ``wire-model-parity`` rule):

* Every ``psum``/``pmax``/``pmin`` inside a ``shard_map`` is a ring
  allreduce over its group: a group of size ``g`` with per-device payload
  ``b`` moves ``2 (g - 1) b`` bytes across its links in total
  (reduce-scatter + all-gather phases — ``core.comm_model.ring_wire_bytes``,
  the byte content of the paper's §3.2 ``allreduce_time`` footnote).
  Groups come from ``axis_index_groups`` (one collective per listed group)
  or span the full named axis; mesh axes the collective does NOT reduce
  over replicate it (one instance per unreduced index combination).
* Float operands with more than one element are *payload* — model traffic.
  They are priced logically at ``num_params * bits_per_param / 8``: the
  quantized-exchange codecs wrap the wire client-side (the traced psum
  still reduces f32), so what crosses the physical link is the codec'd
  representation, exactly how ``CommParams.wire_bytes`` prices it. This
  symmetry is what lets ``wire-model-parity`` demand exact equality for
  ``none`` and ``int8`` alike.
* Scalar (and integer) operands are *overhead* — control traffic (survivor
  counts, group sizes) the §3.2 model ignores; they are reported in the
  contract and pinned by the snapshot differ, not by the parity rule.
* ``scan`` bodies scale by trip count; ``cond``/``switch`` branches are
  alternatives (componentwise max — at most one matching executes per
  round); ``shard_map`` bodies are NOT multiplied by mesh size (the body
  runs on every device, but one psum is still one collective).

Peak live bytes (the static side of the ``peak-live-bytes`` rule): a
last-use liveness sweep over the equations. Inputs and constants are live
from entry; an equation's outputs join the live set (plus any *extra*
memory its sub-jaxprs need beyond their own inputs — alternatives max;
loop bodies count ONCE: memory, unlike time, does not scale with trip
count), and every value dies right after its last use. The result is an
estimate — XLA fusion can only shrink it — but it is deterministic and
moves when someone rematerializes a ``[D, D]`` operator, which is what the
budget gates.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jax.extend.core import Literal, Var  # noqa: F401

from repro.analysis.findings import ERROR, INFO, Finding
from repro.analysis.walker import _open, sub_jaxprs
from repro.core.comm_model import ring_wire_bytes

#: collectives the wire pass prices with the ring-allreduce convention
_RING_PRIMS = frozenset({"psum", "pmax", "pmin"})
#: collectives priced at one payload traversal per group member
_GATHER_PRIMS = frozenset({"all_gather", "all_gather_invariant",
                           "all_to_all", "ppermute", "pgather",
                           "pbroadcast", "reduce_scatter"})

BASELINE_VERSION = 1

#: relative tolerance for "exact" byte/flop equality (float-sum ordering)
EXACT_RTOL = 1e-9
#: snapshot-diff threshold for the estimator fields (flops, peak bytes)
DIFF_RTOL = 0.10


def _aval_bytes(aval) -> float:
    try:
        return float(aval.size) * float(aval.dtype.itemsize)
    except Exception:  # noqa: BLE001 — abstract tokens etc.
        return 0.0


def _is_payload(aval) -> bool:
    """Model traffic: a float array with more than one element. Scalars
    (survivor counts, group sizes) and integer structures are control
    overhead the §3.2 model does not price."""
    import jax.numpy as jnp
    dtype = getattr(aval, "dtype", None)
    size = getattr(aval, "size", 0)
    return (dtype is not None and jnp.issubdtype(dtype, jnp.floating)
            and size > 1)


# ---------------------------------------------------------------------------
# static collective wire bytes
# ---------------------------------------------------------------------------

def _collective_groups(eqn, axis_env: Dict[str, int]
                       ) -> Optional[Tuple[List[int], float]]:
    """(group sizes, replication factor) of one collective equation under
    the enclosing shard_map's axis environment, or None when the equation
    carries no bound mesh axis (not a cross-device collective)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    named = [a for a in axes if a in axis_env]
    if not named:
        return None
    axis_total = 1
    for a in named:
        axis_total *= axis_env[a]
    rep = 1.0
    for a, n in axis_env.items():
        if a not in named:
            rep *= float(n)
    groups = eqn.params.get("axis_index_groups")
    if groups is None:
        return [axis_total], rep
    return [len(g) for g in groups], rep


def _eqn_wire(eqn, axis_env: Dict[str, int], bits_per_param: float
              ) -> Tuple[float, float]:
    """(payload bytes, overhead bytes) one execution of ``eqn`` moves."""
    prim = eqn.primitive.name
    if prim not in _RING_PRIMS and prim not in _GATHER_PRIMS:
        return 0.0, 0.0
    got = _collective_groups(eqn, axis_env)
    if got is None:
        return 0.0, 0.0
    sizes, rep = got
    payload = overhead = 0.0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None:
            continue
        if _is_payload(aval):
            b = float(aval.size) * bits_per_param / 8.0
            is_payload = True
        else:
            b = _aval_bytes(aval)
            is_payload = False
        if prim in _RING_PRIMS:
            moved = sum(ring_wire_bytes(b, g) for g in sizes)
        else:
            # gather-family convention: every device in the group
            # traverses one payload per partner
            moved = sum(float(g - 1) * b for g in sizes)
        moved *= rep
        if is_payload:
            payload += moved
        else:
            overhead += moved
    return payload, overhead


def collective_wire(jaxpr, *, bits_per_param: float = 32.0
                    ) -> Dict[str, float]:
    """Total bytes the program's collectives put on mesh links, split into
    model payload (codec-priced) and control overhead (raw).

    Loop semantics: scan bodies x trip count; cond/switch branches are
    alternatives (componentwise max); shard_map bodies x 1 (one psum is one
    collective, whatever the mesh size) while their mesh binds the axis
    environment the group sizes are resolved against; uncounted sub-jaxprs
    (a while condition) move nothing.
    """
    def walk(j, axis_env) -> Tuple[float, float]:
        payload = overhead = 0.0
        for eqn in j.eqns:
            prim = eqn.primitive.name
            p, o = _eqn_wire(eqn, axis_env, bits_per_param)
            payload += p
            overhead += o
            sub_env = axis_env
            if prim == "shard_map":
                mesh = eqn.params.get("mesh")
                if mesh is not None:
                    sub_env = dict(axis_env)
                    sub_env.update({str(k): int(v)
                                    for k, v in dict(mesh.shape).items()})
            alt: Optional[Tuple[float, float]] = None
            for sub in sub_jaxprs(eqn):
                if not sub.counted:
                    continue
                mult = sub.mult if prim != "shard_map" else 1.0
                sp, so = walk(sub.jaxpr, sub_env)
                sp, so = sp * mult, so * mult
                if sub.alternative:
                    alt = ((sp, so) if alt is None
                           else (max(alt[0], sp), max(alt[1], so)))
                else:
                    payload += sp
                    overhead += so
            if alt is not None:
                payload += alt[0]
                overhead += alt[1]
        return payload, overhead

    payload, overhead = walk(_open(jaxpr), {})
    return {"payload_bytes": payload, "overhead_bytes": overhead}


def analytic_wire_bytes(entries: Sequence[Tuple[int, int, float]],
                        model_bytes: float, codec: Optional[str]) -> float:
    """Price a protocol's declared wire structure (``Protocol.wire_model``
    entries, ``(group_size, num_groups, model_copies)``) through the §3.2
    cost model: each entry moves ``num_groups * copies`` codec-adjusted
    models around rings of ``group_size`` devices. This is the analytic
    side of ``wire-model-parity``; bandwidths cancel (bytes, not time)."""
    from repro.core.comm_model import CommParams
    p = CommParams(model_bytes=float(model_bytes), server_bw=1.0,
                   device_bw=1.0)
    if codec not in (None, "none"):
        p = p.with_codec(codec)
    total = 0.0
    for group_size, num_groups, copies in entries or ():
        total += (float(num_groups) * float(copies)
                  * ring_wire_bytes(p.wire_bytes, int(group_size)))
    return total


def codec_bits(codec: Optional[str]) -> float:
    """Codec-adjusted wire width in bits/param (32.0 for ``none``)."""
    if codec in (None, "none"):
        return 32.0
    from repro.compression import as_codec
    return float(as_codec(codec).bits_per_param())


# ---------------------------------------------------------------------------
# peak live bytes (liveness sweep)
# ---------------------------------------------------------------------------

def input_bytes(jaxpr) -> float:
    """Bytes of the program's inputs: invars + constvars (closed-over
    data/weights), deduplicated — the O(D·n) state the peak budget is a
    constant factor of."""
    j = _open(jaxpr)
    seen, total = set(), 0.0
    for v in list(j.constvars) + list(j.invars):
        if id(v) not in seen:
            seen.add(id(v))
            total += _aval_bytes(v.aval)
    return total


def peak_live_bytes(jaxpr) -> float:
    """Estimated peak live bytes of ONE execution of the program.

    Last-use liveness over the equations in program order: inputs and
    constants are live from entry until their last use; an equation
    allocates its outputs plus whatever *extra* memory its sub-jaxprs need
    beyond their own inputs (the outer operands already hold those).
    Sub-jaxpr extras combine by max — bodies and branches run sequentially
    and loop-body memory, unlike loop-body time, does not scale with trip
    count. Values die immediately after their last use; jaxpr outputs live
    to the end. Fusion can only shrink the estimate; a rematerialized
    [D, D] operator grows it by ~D² — which is what the budget catches.
    """
    return _peak(_open(jaxpr))


def _peak(j) -> float:
    eqns = list(j.eqns)
    last_use: Dict[int, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if isinstance(v, Var):
                last_use[id(v)] = i
    for v in j.outvars:
        if isinstance(v, Var):
            last_use[id(v)] = len(eqns)

    # frees[i] = bytes that die right after equation i (-1: dead on entry)
    frees: Dict[int, float] = {}
    cur = 0.0
    seen = set()
    for v in list(j.constvars) + list(j.invars):
        if id(v) in seen:
            continue
        seen.add(id(v))
        b = _aval_bytes(v.aval)
        cur += b
        die = last_use.get(id(v), -1)
        frees[die] = frees.get(die, 0.0) + b
    peak = cur
    cur -= frees.pop(-1, 0.0)

    for i, eqn in enumerate(eqns):
        extra = 0.0
        for sub in sub_jaxprs(eqn):
            extra = max(extra,
                        max(0.0, _peak(sub.jaxpr) - input_bytes(sub.jaxpr)))
        out_bytes = 0.0
        for v in eqn.outvars:
            b = _aval_bytes(v.aval)
            out_bytes += b
            die = last_use.get(id(v), i)   # unused output dies here
            frees[die] = frees.get(die, 0.0) + b
        cur += out_bytes
        peak = max(peak, cur + extra)
        cur -= frees.pop(i, 0.0)
    return peak


# ---------------------------------------------------------------------------
# scan-carry layout signature
# ---------------------------------------------------------------------------

def scan_carry_signature(jaxpr) -> List[Dict[str, Any]]:
    """One record per ``lax.scan`` in the program: where it sits, its trip
    count, and the carry slot layout (short aval strings). A changed carry
    — an unpacked pytree, a widened dtype — changes per-round memory
    traffic, so the differ pins it exactly."""
    from repro.analysis.walker import iter_eqns
    out = []
    for site in iter_eqns(jaxpr):
        if site.eqn.primitive.name != "scan":
            continue
        params = site.eqn.params
        body = _open(params["jaxpr"])
        nc, nk = params["num_consts"], params["num_carry"]
        carry = [str(v.aval.str_short()) for v in body.invars[nc:nc + nk]]
        out.append({"path": site.pretty_path,
                    "length": int(params["length"]), "carry": carry})
    return out


# ---------------------------------------------------------------------------
# the contract
# ---------------------------------------------------------------------------

def build_contract(program) -> Dict[str, Any]:
    """Derive one program's full static contract (pure jaxpr analysis)."""
    from repro.analysis.rules.collective_census import census
    from repro.launch.roofline import jaxpr_cost

    bits = codec_bits(program.codec)
    wire = collective_wire(program.jaxpr, bits_per_param=bits)
    flops, hbm = jaxpr_cost(program.jaxpr.jaxpr)
    rounds = float(program.meta.get("rounds", 1))
    entries = program.meta.get("wire_model")
    model_bytes = program.meta.get("model_bytes", 0.0)
    analytic = (None if entries is None else
                rounds * analytic_wire_bytes(entries, model_bytes,
                                             program.codec))
    return {
        "engine": program.engine, "protocol": program.protocol,
        "mix_path": program.mix_path, "codec": program.codec,
        "kind": program.kind, "rounds": int(rounds),
        "census": {k: v for k, v in census(program.jaxpr).items() if v},
        "wire_payload_bytes": wire["payload_bytes"],
        "wire_overhead_bytes": wire["overhead_bytes"],
        "wire_model_bytes": analytic,
        "model_bytes": float(model_bytes),
        "flops": flops,
        "hbm_bytes": hbm,
        "peak_live_bytes": peak_live_bytes(program.jaxpr),
        "input_bytes": input_bytes(program.jaxpr),
        "scan_carries": scan_carry_signature(program.jaxpr),
    }


def build_contracts(programs: Sequence) -> Dict[str, Dict[str, Any]]:
    return {p.name: build_contract(p) for p in programs}


# ---------------------------------------------------------------------------
# baseline store
# ---------------------------------------------------------------------------

def default_baseline_path() -> str:
    """<repo root>/contracts/baseline.json under the src/ layout."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(root, "contracts", "baseline.json")


def write_baseline(path: str, contracts: Dict[str, Dict[str, Any]]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    doc = {"version": BASELINE_VERSION, "contracts": contracts}
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")


def load_baseline(path: str) -> Dict[str, Dict[str, Any]]:
    with open(path) as fh:
        doc = json.load(fh)
    if doc.get("version") != BASELINE_VERSION:
        raise ValueError(f"baseline {path!r} has version "
                         f"{doc.get('version')!r}; expected "
                         f"{BASELINE_VERSION} (regenerate with "
                         f"--update-baseline)")
    return doc["contracts"]


# ---------------------------------------------------------------------------
# the snapshot differ
# ---------------------------------------------------------------------------

#: (contract field, diff rule id, relative threshold: None = exact)
_GATES = (
    ("census", "contract-diff.census", None),
    ("wire_payload_bytes", "contract-diff.wire", EXACT_RTOL),
    ("wire_overhead_bytes", "contract-diff.wire", EXACT_RTOL),
    ("scan_carries", "contract-diff.scan-carry", None),
    ("flops", "contract-diff.flops", DIFF_RTOL),
    ("peak_live_bytes", "contract-diff.peak-live-bytes", DIFF_RTOL),
)
#: fields shown in the diff table but never gated (estimators / reference)
_REPORT_ONLY = ("hbm_bytes", "wire_model_bytes", "input_bytes")


def _rel_delta(old, new) -> float:
    denom = max(abs(float(old)), 1e-12)
    return abs(float(new) - float(old)) / denom


def _fmt_val(v) -> str:
    if isinstance(v, float):
        return f"{v:g}"
    if isinstance(v, dict):
        return ",".join(f"{k}:{v[k]:g}" for k in sorted(v)) or "-"
    if isinstance(v, list):
        return f"{len(v)} scan(s)" if v else "-"
    return str(v)


def diff_contracts(current: Dict[str, Dict], baseline: Dict[str, Dict]
                   ) -> Tuple[List[Finding], List[Dict[str, Any]]]:
    """Compare this run's contracts against the checked-in baseline.

    Returns (findings, table rows). ERROR findings (which fail CI): a
    program missing from the baseline (``contract-diff.coverage`` —
    regenerate with ``--update-baseline``), any exact-field change
    (collective census, wire bytes, scan-carry layout), and estimator
    drift past 10% (flops, peak live bytes). Baseline programs absent
    from a *filtered* run are skipped — partial runs stay diffable.
    """
    findings: List[Finding] = []
    rows: List[Dict[str, Any]] = []

    def finding(rule, severity, name, message):
        findings.append(Finding(rule=rule, severity=severity, program=name,
                                where="", message=message))

    for name in sorted(current):
        cur = current[name]
        base = baseline.get(name)
        if base is None:
            finding("contract-diff.coverage", ERROR, name,
                    "program has no baseline contract; regenerate with "
                    "`python -m repro.analysis --update-baseline`")
            rows.append({"program": name, "field": "(coverage)",
                         "baseline": "missing", "current": "present",
                         "delta": "-", "gate": "ERROR"})
            continue
        for field, rule, rtol in _GATES:
            old, new = base.get(field), cur.get(field)
            if isinstance(old, (int, float)) and isinstance(new, (int, float)):
                changed = _rel_delta(old, new) > (rtol or 0.0)
                delta = f"{_rel_delta(old, new):+.1%}"
            else:
                changed = old != new
                delta = "-"
            if not changed:
                continue
            gate = "ERROR"
            finding(rule, ERROR, name,
                    f"{field} regressed vs baseline: "
                    f"{_fmt_val(old)} -> {_fmt_val(new)}"
                    + (f" ({delta}, threshold {rtol:.0%})"
                       if rtol not in (None, EXACT_RTOL) else ""))
            rows.append({"program": name, "field": field,
                         "baseline": _fmt_val(old), "current": _fmt_val(new),
                         "delta": delta, "gate": gate})
        for field in _REPORT_ONLY:
            old, new = base.get(field), cur.get(field)
            if (isinstance(old, (int, float)) and isinstance(new, (int, float))
                    and _rel_delta(old, new) > DIFF_RTOL):
                finding("contract-diff." + field.replace("_", "-"), INFO,
                        name, f"{field} moved (not gated): "
                              f"{_fmt_val(old)} -> {_fmt_val(new)}")
                rows.append({"program": name, "field": field,
                             "baseline": _fmt_val(old),
                             "current": _fmt_val(new),
                             "delta": f"{_rel_delta(old, new):+.1%}",
                             "gate": "info"})
    return findings, rows


def render_diff_table(rows: List[Dict[str, Any]], *, compared: int,
                      baseline_path: str) -> str:
    """Markdown diff table for the PR artifact / CI step summary."""
    lines = ["# Contract diff", "",
             f"Compared **{compared}** program contract(s) against "
             f"`{os.path.basename(baseline_path)}`."]
    if not rows:
        lines.append("")
        lines.append("No contract regressions.")
        return "\n".join(lines) + "\n"
    lines += ["", "| program | field | baseline | current | delta | gate |",
              "|---|---|---|---|---|---|"]
    for r in rows:
        lines.append("| {program} | {field} | {baseline} | {current} | "
                     "{delta} | {gate} |".format(**r))
    n_err = sum(1 for r in rows if r["gate"] == "ERROR")
    lines += ["", f"**{n_err} gated regression(s)**, "
                  f"{len(rows) - n_err} informational."]
    return "\n".join(lines) + "\n"
