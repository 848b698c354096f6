"""Stage (b''): jaxpr dataflow verifier — graftverify (ISSUE 12).

The collective-inventory audit (``jaxpr_audit.py``) pins *totals*: how
many of which collective over which axes.  Totals cannot see the bug
class the ROADMAP's adaptive-schedule work will create: a traced
per-epoch mode vector routed through ``lax.switch`` whose branches
carry *divergent collective sequences* is a silent SPMD deadlock the
moment two devices disagree on the branch.  This stage walks each
registered entry point's jaxpr as a *program* and checks dataflow:

* **Branch uniformity** — every ``cond``/``switch`` sub-jaxpr is
  descended and the ordered collective sequence (primitive, axis
  tuple, position) compared across branches.  Divergence inside an
  axis scope (a ``shard_map``/``pmap`` body) whose predicate is not
  provably axis-invariant (vma metadata) is a hard finding
  (``branch-divergent-collective``); divergence outside any axis
  scope — e.g. the trainer superstep's mode switch, which dispatches
  on a replicated scalar — is legal but its per-branch sequences are
  PINNED, so drift fails loudly (``collective-order-drift``).
* **Ordered-sequence pins** — ``scan``/``while`` bodies pin the exact
  collective order, not just counts: a hoisted or reordered collective
  changes the pinned sequence even when the totals stay flat.
* **Suppression-claim verification** — the reasons on
  ``raw-collective-in-shard-map`` suppressions are parsed into the
  claim taxonomy (``claims.py``) and each claim is checked against the
  traced program: an ``exit``/``statistic`` claim requires the
  collective's result to flow to a region output; a ``vma-cast`` claim
  requires the line to trace as a bookkeeping cast, not traffic; a
  claimed axis that names a real traced mesh axis must match the
  collective's axes.  A contradicted claim fails lint naming the site
  and the invariant; an unparseable or untraceable claim is *reported*
  (stderr + the pinned claim inventory), never silently passed.
* **vma discipline** — varying/invariant axis sets are tracked through
  axis-scope bodies (when the running jax records ``aval.vma``); an
  eqn mixing axis-varying data with an axis-invariant *captured*
  operand that no ``pvary``/``pcast`` touched is the
  pcast-before-local-cotangent hazard (CLAUDE.md; training/pp.py
  head_fn) and is flagged (``vma-discipline``).  A static donation
  check additionally requires every state leaf of the audited trainer
  entry points to alias an output under ``donate_argnums=(0,)``
  (``donation-alias`` — the tests/test_trainer.py guard, generalized).

Everything pins under ``dataflow:<entry>`` keys (plus the global
``suppression_claims`` inventory) in ``audit_expected.json`` through
the same ``--audit-write`` lifecycle as the collective pins; entries
whose fixtures need jax APIs this environment lacks record
``status="skip"`` and a placeholder pin.  The analysis itself is
duck-typed over jaxpr objects (``.eqns``/``.primitive``/``.params``/
``.invars``) so it is unit-testable against hand-built fakes, and this
module imports jax only inside the tracing path — importing it is
bare-run safe.
"""

from __future__ import annotations

import dataclasses
import json
import os
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

from tools.graftlint.core import REPO_ROOT, Finding, Rule, register
from tools.graftlint.jaxpr_audit import (
    ENTRY_POINTS,
    EXPECTED_PATH,
    _axes_of,
    _live_provenance,
    load_expected,
    normalize_primitive,
)
from tools.graftlint import claims as claims_mod

#: vma bookkeeping casts (mirrors jaxpr_audit._EXCLUDED_PREFIXES — kept
#: in lockstep by tests/test_jaxpr_verify.py).
_CAST_PREFIXES = ("pvary", "pcast", "pbroadcast")


# --------------------------------------------------------------------- #
# Rule registrations (stage-level: findings come from verify(), not     #
# per-file AST checks, so check() is a no-op like the wire rules).      #
# --------------------------------------------------------------------- #
@register
class BranchDivergentCollective(Rule):
    """cond/switch branches inside an axis scope must carry identical
    ordered collective sequences unless the predicate is provably
    axis-invariant."""

    name = "branch-divergent-collective"
    stage = "dataflow"

    def check(self, ctx) -> List[Finding]:
        return []


@register
class CollectiveOrderDrift(Rule):
    """Per-branch and per-loop-body ordered collective sequences must
    match their dataflow pin in audit_expected.json."""

    name = "collective-order-drift"
    stage = "dataflow"

    def check(self, ctx) -> List[Finding]:
        return []


@register
class SuppressionClaim(Rule):
    """raw-collective suppression reasons must parse into the claim
    taxonomy and must not contradict the traced program."""

    name = "suppression-claim"
    stage = "dataflow"

    def check(self, ctx) -> List[Finding]:
        return []


@register
class DonationAlias(Rule):
    """Every state leaf of an audited trainer entry point must alias an
    output under donate_argnums=(0,)."""

    name = "donation-alias"
    stage = "dataflow"

    def check(self, ctx) -> List[Finding]:
        return []


@register
class VmaDiscipline(Rule):
    """Axis-invariant captures meeting axis-varying data without a
    pvary/pcast are the local-cotangent hazard (training/pp.py)."""

    name = "vma-discipline"
    stage = "dataflow"

    def check(self, ctx) -> List[Finding]:
        return []


# --------------------------------------------------------------------- #
# Duck-typed jaxpr dataflow analysis                                    #
# --------------------------------------------------------------------- #
def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _vma_of(v):
    """The varying-axis set recorded on a var's aval, or None when the
    running jax records no vma metadata (0.4.x)."""
    aval = getattr(v, "aval", None)
    if aval is None:
        return None
    vma = getattr(aval, "vma", None)
    if vma is None:
        vma = getattr(aval, "varying_manual_axes", None)
    return vma


def _sub(x):
    """The walkable jaxpr inside a ClosedJaxpr/Jaxpr-like object."""
    inner = getattr(x, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(x, "eqns"):
        return x
    return None


def _sub_jaxprs(params: dict) -> List[object]:
    """Ordered sub-jaxprs found in an eqn's params (the
    collect_collectives descent, minus the explicitly handled
    cond/scan/while keys)."""
    out = []
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else [val]
        for v in vals:
            sub = _sub(v)
            if sub is not None:
                out.append(sub)
    return out


def _axes_introduced(eqn) -> frozenset:
    """Mesh axes an eqn's sub-jaxpr executes under (pmap/shard_map)."""
    name = eqn.primitive.name
    params = eqn.params
    axes = set()
    if name == "xla_pmap" or name.startswith("pmap"):
        a = params.get("axis_name")
        if isinstance(a, str):
            axes.add(a)
        elif isinstance(a, (tuple, list)):
            axes.update(x for x in a if isinstance(x, str))
    elif name == "shard_map":
        mesh = params.get("mesh")
        names = getattr(mesh, "axis_names", None)
        if names:
            axes.update(str(a) for a in names)
        for key in ("axis_names", "manual_axes"):
            v = params.get(key)
            if isinstance(v, (tuple, list, set, frozenset)):
                axes.update(str(a) for a in v)
    return frozenset(axes)


def _source_site(eqn, repo_root: str) -> Optional[Tuple[str, int]]:
    """(repo-relative file, line) of an eqn's user frame, or None."""
    si = getattr(eqn, "source_info", None)
    if si is None:
        return None
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(si)
    except Exception:
        return None
    if frame is None:
        return None
    fn = getattr(frame, "file_name", None)
    ln = getattr(frame, "start_line", None)
    if not fn or not ln:
        return None
    try:
        rel = os.path.relpath(fn, repo_root)
    except ValueError:
        return None
    if rel.startswith(".."):
        return None
    return rel.replace(os.sep, "/"), int(ln)


def _reaches_outputs(j, eqn0) -> bool:
    """Forward taint: does any of eqn0's results flow (transitively,
    conservatively through sub-jaxpr-carrying eqns) to a region
    output?  Jaxprs are topologically ordered, so one pass suffices."""
    tainted = {id(v) for v in eqn0.outvars}
    seen = False
    for eqn in getattr(j, "eqns", ()):
        if eqn is eqn0:
            seen = True
            continue
        if not seen:
            continue
        if any(
            id(v) in tainted for v in eqn.invars if not _is_literal(v)
        ):
            tainted.update(id(v) for v in eqn.outvars)
    return any(
        id(v) in tainted
        for v in getattr(j, "outvars", ())
        if not _is_literal(v)
    )


@dataclasses.dataclass
class BranchSite:
    path: str  # e.g. "scan[0]/cond[0]"
    uniform: bool
    sequences: List[List[str]]
    axis_scope: Tuple[str, ...]
    #: True (provably invariant over the scope) / False (provably
    #: varying) / None (no vma metadata on this jax)
    pred_invariant: Optional[bool]
    source: Optional[Tuple[str, int]]


@dataclasses.dataclass
class LoopSite:
    path: str
    kind: str  # "scan" | "while"
    sequence: List[str]
    source: Optional[Tuple[str, int]]


@dataclasses.dataclass
class CollectiveSite:
    op: str
    axes: Tuple[str, ...]
    region_path: str
    scope: Tuple[str, ...]
    reaches_output: bool
    source: Optional[Tuple[str, int]]


class Analysis:
    """Everything the verifier extracts from one traced entry point."""

    def __init__(self):
        self.branches: Dict[str, BranchSite] = {}
        self.loops: Dict[str, LoopSite] = {}
        self.collectives: List[CollectiveSite] = []
        self.cast_lines: set = set()  # {(relpath, line)}
        self.axes_seen: set = set()
        self.vma_hazards: List[dict] = []
        self.saw_vma = False


def _pred_invariant(
    eqn, scope: frozenset, vma_tracked: bool
) -> Optional[bool]:
    if not scope:
        return True
    invars = getattr(eqn, "invars", ())
    if not invars:
        return None
    pred = invars[0]
    if _is_literal(pred):
        return True
    vma = _vma_of(pred) if vma_tracked else None
    if vma is None:
        return None
    return not (set(vma) & set(scope))


def _token(op: str, axes: Tuple[str, ...]) -> str:
    return f"{op}|{','.join(axes)}"


def analyze_jaxpr(jaxpr, repo_root: str = REPO_ROOT) -> Analysis:
    """Walk a (Closed)Jaxpr and extract branch/loop/collective/vma
    dataflow facts.  Pure over duck-typed jaxpr objects."""
    an = Analysis()
    root = _sub(jaxpr)
    if root is None:
        raise TypeError("object has no walkable jaxpr (.eqns)")
    _walk(root, "", frozenset(), an, repo_root)
    return an


def _tracks_vma(eqn) -> bool:
    """False for a ``shard_map`` traced with ``check_vma=False`` (what
    ``jax.pmap`` traces to on the installed jax, 0.9): every aval inside
    carries an empty ``vma`` that records nothing, so an empty set there
    is "not tracked", never "provably invariant"."""
    params = eqn.params
    return params.get("check_vma", params.get("check_rep")) is not False


def _walk(j, path, scope, an, repo_root, vma_tracked=True) -> List[str]:
    seq: List[str] = []
    counters: Counter = Counter()
    local_collectives = []

    def label(name):
        i = counters[name]
        counters[name] += 1
        base = f"{name}[{i}]"
        return f"{path}/{base}" if path else base

    for eqn in getattr(j, "eqns", ()):
        name = eqn.primitive.name
        op = normalize_primitive(name)
        if op is not None:
            axes = _axes_of(eqn.params)
            seq.append(_token(op, axes))
            an.axes_seen.update(axes)
            local_collectives.append((eqn, op, axes))
            continue
        if any(name.startswith(p) for p in _CAST_PREFIXES):
            src = _source_site(eqn, repo_root)
            if src is not None:
                an.cast_lines.add(src)
            continue
        if name == "cond":
            lab = label("cond")
            branch_seqs = []
            for k, br in enumerate(eqn.params.get("branches", ())):
                sub = _sub(br)
                branch_seqs.append(
                    _walk(sub, f"{lab}.b{k}", scope, an, repo_root,
                          vma_tracked)
                    if sub is not None
                    else []
                )
            uniform = all(s == branch_seqs[0] for s in branch_seqs[1:])
            an.branches[lab] = BranchSite(
                path=lab,
                uniform=uniform,
                sequences=branch_seqs,
                axis_scope=tuple(sorted(scope)),
                pred_invariant=_pred_invariant(eqn, scope, vma_tracked),
                source=_source_site(eqn, repo_root),
            )
            if branch_seqs and uniform:
                seq.extend(branch_seqs[0])
            elif branch_seqs:
                seq.append(f"?divergent@{lab}")
            continue
        if name == "scan":
            lab = label("scan")
            sub = _sub(eqn.params.get("jaxpr"))
            body = (
                _walk(sub, lab, scope, an, repo_root, vma_tracked)
                if sub is not None
                else []
            )
            an.loops[lab] = LoopSite(
                lab, "scan", body, _source_site(eqn, repo_root)
            )
            seq.extend(body)
            continue
        if name == "while":
            lab = label("while")
            csub = _sub(eqn.params.get("cond_jaxpr"))
            bsub = _sub(eqn.params.get("body_jaxpr"))
            cseq = (
                _walk(csub, f"{lab}.cond", scope, an, repo_root, vma_tracked)
                if csub is not None
                else []
            )
            bseq = (
                _walk(bsub, f"{lab}.body", scope, an, repo_root, vma_tracked)
                if bsub is not None
                else []
            )
            an.loops[lab] = LoopSite(
                lab, "while", cseq + bseq, _source_site(eqn, repo_root)
            )
            seq.extend(cseq + bseq)
            continue
        subs = _sub_jaxprs(eqn.params)
        if subs:
            sub_scope = scope | _axes_introduced(eqn)
            an.axes_seen.update(sub_scope)
            sub_tracked = vma_tracked and _tracks_vma(eqn)
            lab = label(name)
            for i, sub in enumerate(subs):
                sublab = lab if len(subs) == 1 else f"{lab}.{i}"
                seq.extend(_walk(sub, sublab, sub_scope, an, repo_root,
                                 sub_tracked))

    for eqn, op, axes in local_collectives:
        an.collectives.append(
            CollectiveSite(
                op=op,
                axes=axes,
                region_path=path,
                scope=tuple(sorted(scope)),
                reaches_output=_reaches_outputs(j, eqn),
                source=_source_site(eqn, repo_root),
            )
        )
    if vma_tracked:
        _vma_pass(j, path, scope, an, repo_root)
    return seq


def _vma_pass(j, path, scope, an, repo_root) -> None:
    """Flag axis-invariant region-input captures meeting axis-varying
    operands in a plain eqn (no cast, no collective, no sub-jaxpr):
    transposing such an eqn psums the capture's cotangent over the
    axis — the pcast-before-local-cotangent hazard."""
    if not scope:
        return
    region_inputs = {id(v) for v in getattr(j, "invars", ())}
    region_inputs |= {id(v) for v in getattr(j, "constvars", ())}
    for eqn in getattr(j, "eqns", ()):
        name = eqn.primitive.name
        if normalize_primitive(name) is not None:
            continue
        if any(name.startswith(p) for p in _CAST_PREFIXES):
            continue
        if name in ("cond", "scan", "while") or _sub_jaxprs(eqn.params):
            continue
        known = []
        for v in getattr(eqn, "invars", ()):
            if _is_literal(v):
                continue
            vma = _vma_of(v)
            if vma is not None:
                known.append((v, vma))
        if not known:
            continue
        an.saw_vma = True
        for ax in scope:
            varying = [v for v, vma in known if ax in vma]
            invariant_caps = [
                v
                for v, vma in known
                if ax not in vma and id(v) in region_inputs
            ]
            if varying and invariant_caps:
                an.vma_hazards.append(
                    {
                        "path": path,
                        "axis": ax,
                        "primitive": name,
                        "source": _source_site(eqn, repo_root),
                    }
                )


# --------------------------------------------------------------------- #
# Policy: hard findings from one entry's analysis                       #
# --------------------------------------------------------------------- #
def entry_findings(name: str, an: Analysis) -> List[Finding]:
    """branch-divergent-collective + vma-discipline findings for one
    traced entry point (pin-independent: these are hazards, not
    drifts)."""
    out: List[Finding] = []
    for lab in sorted(an.branches):
        b = an.branches[lab]
        if b.uniform:
            continue
        if b.axis_scope and b.pred_invariant is not True:
            ref = b.sequences[0] if b.sequences else []
            k = next(
                (i for i, s in enumerate(b.sequences) if s != ref), 0
            )
            axes = sorted(
                {
                    tok.split("|", 1)[1]
                    for s in b.sequences
                    for tok in s
                    if "|" in tok and tok.split("|", 1)[1]
                }
            )
            pth, ln = b.source or (f"<{name}>", 1)
            out.append(
                Finding(
                    "branch-divergent-collective",
                    pth,
                    ln,
                    f"entry {name}: {b.path}: branch collective "
                    f"sequences diverge (branch 0 runs "
                    f"{ref or 'no collectives'}, branch {k} runs "
                    f"{b.sequences[k] or 'no collectives'}; axes "
                    f"{axes or ['-']}) inside axis scope "
                    f"{list(b.axis_scope)} with a predicate not "
                    "provably axis-invariant — devices taking "
                    "different branches deadlock the collective "
                    "rendezvous; make the sequences identical or make "
                    "the predicate vma-invariant over the scope",
                )
            )
    for hz in an.vma_hazards:
        pth, ln = hz["source"] or ("<traced>", 1)
        out.append(
            Finding(
                "vma-discipline",
                pth,
                ln,
                f"entry {name}: region {hz['path'] or '<top>'}: "
                f"'{hz['primitive']}' mixes data varying over axis "
                f"'{hz['axis']}' with an axis-invariant captured "
                "operand and no pvary/pcast dominates the capture — "
                "differentiating this inserts a psum over "
                f"'{hz['axis']}' into the capture's cotangent "
                "(CLAUDE.md vma rule; see training/pp.py head_seed): "
                'cast with pcast(..., to="varying") first',
            )
        )
    return out


# --------------------------------------------------------------------- #
# Suppression-claim checking                                            #
# --------------------------------------------------------------------- #
#: traced source lines may sit a couple of lines below the suppression
#: target (multi-line calls); match within this window.
_SITE_TOLERANCE = 3


def check_claims(
    records: Sequence["claims_mod.SuppressionRecord"],
    sites_by_file: Dict[str, List[Tuple[int, CollectiveSite]]],
    cast_lines: set,
    known_axes: set,
) -> Tuple[List[Finding], dict]:
    """Check every raw-collective claim against the traced sites.

    Returns (findings, summary) where findings are contradictions
    (``suppression-claim``) and summary counts verified / contradicted
    / untraceable / unparseable with human-readable details for the
    reported-never-passed categories."""
    findings: List[Finding] = []
    summary = {
        "verified": 0,
        "contradicted": 0,
        "untraceable": 0,
        "unparseable": 0,
        "details": [],
    }
    stripped_known = {a.rstrip("s") for a in known_axes}
    for r in records:
        if r.claim is None:
            summary["unparseable"] += 1
            summary["details"].append(
                f"{r.site}: reason {r.reason!r} does not parse into the "
                "claim taxonomy (exit | vma-cast | statistic) — "
                "docs/static_analysis.md §Stage 5"
            )
            continue
        near = [
            c
            for ln, c in sites_by_file.get(r.path, [])
            if abs(ln - r.line) <= _SITE_TOLERANCE
        ]
        kind = r.claim.kind
        if kind == "vma-cast":
            if near:
                ops = sorted({c.op for c in near})
                findings.append(
                    Finding(
                        "suppression-claim",
                        r.path,
                        r.line,
                        "claim contradicts the traced program: the "
                        "reason claims a vma bookkeeping cast "
                        "(metadata, no traffic) but the line traces as "
                        f"{', '.join(ops)} — a real collective; fix "
                        "the reason or the program",
                    )
                )
                summary["contradicted"] += 1
            elif any(
                p == r.path and abs(ln - r.line) <= _SITE_TOLERANCE
                for p, ln in cast_lines
            ):
                summary["verified"] += 1
            else:
                summary["untraceable"] += 1
                summary["details"].append(
                    f"{r.site}: vma-cast claim — no audited entry "
                    "traces this line on this environment"
                )
            continue
        if not near:
            summary["untraceable"] += 1
            summary["details"].append(
                f"{r.site}: {kind} claim — no audited entry traces "
                "this line on this environment"
            )
            continue
        contradictions = []
        for c in near:
            if r.claim.axis is not None:
                claimed = r.claim.axis.rstrip("s")
                actual = {a.rstrip("s") for a in c.axes}
                if claimed in stripped_known and claimed not in actual:
                    contradictions.append(
                        f"the reason claims the collective runs over "
                        f"axis '{r.claim.axis}' but the traced "
                        f"{c.op} runs over {list(c.axes)} (region "
                        f"{c.region_path or '<top>'})"
                    )
                    continue
            if not c.reaches_output:
                contradictions.append(
                    f"a {kind} claim requires the {c.op} result to "
                    "flow to a region output (the invariant the "
                    "suppression names), but it is dead past region "
                    f"{c.region_path or '<top>'}"
                )
        if contradictions:
            findings.append(
                Finding(
                    "suppression-claim",
                    r.path,
                    r.line,
                    "claim contradicts the traced program: "
                    + "; ".join(contradictions),
                )
            )
            summary["contradicted"] += 1
        else:
            summary["verified"] += 1
    return findings, summary


def _claims_pin(records) -> Dict[str, dict]:
    """The portable (source-only) claim inventory pinned in
    audit_expected.json: site -> parsed kind/axis."""
    out: Dict[str, dict] = {}
    for r in records:
        if r.claim is None:
            out[r.site] = {"kind": "unparseable"}
        elif r.claim.axis:
            out[r.site] = {"kind": r.claim.kind, "axis": r.claim.axis}
        else:
            out[r.site] = {"kind": r.claim.kind}
    return out


# --------------------------------------------------------------------- #
# Pin lifecycle (mirrors jaxpr_audit.audit)                             #
# --------------------------------------------------------------------- #
def _observed(an: Analysis) -> dict:
    return {
        "branches": {
            p: {
                "uniform": b.uniform,
                "sequences": [list(s) for s in b.sequences],
            }
            for p, b in sorted(an.branches.items())
        },
        "loops": {
            p: {"kind": l.kind, "sequence": list(l.sequence)}
            for p, l in sorted(an.loops.items())
        },
    }


_PIN_KEYS = ("branches", "loops", "donation")


def verify(
    names: Optional[List[str]] = None,
    write: bool = False,
    expected_path: str = EXPECTED_PATH,
    repo_root: str = REPO_ROOT,
) -> Tuple[Dict[str, dict], List[Finding], dict]:
    """Run the dataflow stage over the registered entry points.

    Returns (results, findings, claim_summary): ``results`` carries a
    per-entry status (``ok``/``mismatch``/``skip``/``error``/
    ``unpinned`` — the jaxpr_audit vocabulary) plus the
    ``suppression_claims`` pin status; ``findings`` are the hard
    dataflow findings (divergent branches, vma hazards, donation
    holes, claim contradictions, pin drifts as statuses).  With
    ``write=True`` the observed structure is recorded under
    ``dataflow:<entry>`` keys exactly like ``--audit-write`` records
    collective inventories; skipped entries get placeholder pins so
    every registered entry point is represented."""
    expected = (
        load_expected(expected_path)
        if os.path.exists(expected_path)
        else {}
    )
    results: Dict[str, dict] = {}
    findings: List[Finding] = []
    analyses: Dict[str, Analysis] = {}
    todo = names or sorted(ENTRY_POINTS)
    for name in todo:
        ep = ENTRY_POINTS[name]
        key = f"dataflow:{name}"
        if ep.trace_build is None:
            results[name] = {
                "status": "skip",
                "detail": "no jaxpr surface (GSPMD/HLO entry: the "
                "partitioner inserts the collectives after tracing)",
            }
            if write:
                expected[key] = {
                    "kind": "dataflow",
                    "surface": "hlo",
                    "verified": True,
                    "provenance": "no jaxpr dataflow surface; the "
                    "entry is covered by its HLO collective inventory "
                    "pin",
                }
            continue
        missing = ep.missing_features()
        if missing:
            results[name] = {
                "status": "skip",
                "detail": "environment lacks jax feature(s): "
                + ", ".join(missing),
            }
            if write and not any(
                k in expected.get(key, {}) for k in _PIN_KEYS
            ):
                expected[key] = {
                    "kind": "dataflow",
                    "verified": False,
                    "provenance": "placeholder: environment lacks "
                    + ", ".join(missing)
                    + " — repin with --audit-write on a jax exposing "
                    "them",
                }
            continue
        try:
            jx = ep.trace_build()
            an = analyze_jaxpr(jx, repo_root=repo_root)
        except Exception as exc:
            results[name] = {
                "status": "error",
                "detail": f"{type(exc).__name__}: {exc}",
            }
            continue
        analyses[name] = an
        efindings = entry_findings(name, an)
        findings.extend(efindings)
        observed = _observed(an)
        if ep.donate_build is not None:
            try:
                text, leaves = ep.donate_build()
            except Exception as exc:
                results[name] = {
                    "status": "error",
                    "detail": "donation check failed: "
                    f"{type(exc).__name__}: {exc}",
                }
                continue
            aliased = text.count("tf.aliasing_output")
            observed["donation"] = {"leaves": leaves, "aliased": aliased}
            if aliased < leaves:
                findings.append(
                    Finding(
                        "donation-alias",
                        f"<{name}>",
                        1,
                        f"entry {name}: only {aliased} of {leaves} "
                        "state leaves alias an output under "
                        "donate_argnums=(0,) — an unaliased leaf "
                        "doubles its buffer's footprint every "
                        "superstep (tests/test_trainer.py donation "
                        "guard, as lint)",
                    )
                )
        exp_entry = expected.get(key, {})
        has_pin = any(k in exp_entry for k in _PIN_KEYS)
        if write or not has_pin:
            expected[key] = {
                "kind": "dataflow",
                **observed,
                "verified": True,
                "provenance": _live_provenance(),
            }
            results[name] = {
                "status": "ok" if write else "unpinned",
                "observed": observed,
            }
        else:
            pinned = {
                k: exp_entry[k] for k in _PIN_KEYS if k in exp_entry
            }
            obs_cmp = {k: observed.get(k) for k in pinned}
            if pinned == obs_cmp:
                results[name] = {"status": "ok", "observed": observed}
            else:
                drift = {
                    k: {"expected": pinned[k], "observed": obs_cmp[k]}
                    for k in pinned
                    if pinned[k] != obs_cmp[k]
                }
                results[name] = {
                    "status": "mismatch",
                    "observed": observed,
                    "expected": pinned,
                    "detail": (
                        f"dataflow drift in {name}: "
                        f"{json.dumps(drift, sort_keys=True)} — an "
                        "intentional change is repinned with 'python "
                        "-m tools.graftlint --audit --audit-write'"
                    ),
                }
        if efindings:
            results[name]["findings"] = len(efindings)

    # ---- suppression claims (source side is env-independent) -------- #
    records = claims_mod.raw_collective_records(repo_root=repo_root)
    sites_by_file: Dict[str, List[Tuple[int, CollectiveSite]]] = {}
    cast_lines: set = set()
    known_axes: set = set()
    for an in analyses.values():
        for c in an.collectives:
            if c.source is not None:
                sites_by_file.setdefault(c.source[0], []).append(
                    (c.source[1], c)
                )
        cast_lines |= an.cast_lines
        known_axes |= an.axes_seen
    cfindings, claim_summary = check_claims(
        records, sites_by_file, cast_lines, known_axes
    )
    findings.extend(cfindings)

    claims_pin = _claims_pin(records)
    pin_rel = os.path.relpath(expected_path, repo_root).replace(
        os.sep, "/"
    )
    exp_claims = expected.get("suppression_claims", {}).get("claims")
    if write or exp_claims is None:
        expected["suppression_claims"] = {
            "kind": "suppression-claims",
            "claims": claims_pin,
            "provenance": "parsed from the inline suppression reasons "
            "(tools/graftlint/claims.py taxonomy)",
        }
        results["suppression_claims"] = {
            "status": "ok" if write else "unpinned",
        }
    elif exp_claims == claims_pin:
        results["suppression_claims"] = {"status": "ok"}
    else:
        gone = {
            k: v for k, v in exp_claims.items() if claims_pin.get(k) != v
        }
        new = {
            k: v for k, v in claims_pin.items() if exp_claims.get(k) != v
        }
        results["suppression_claims"] = {
            "status": "mismatch",
            "detail": (
                "the raw-collective claim inventory drifted from its "
                f"pin: expected {json.dumps(gone, sort_keys=True)} but "
                f"observed {json.dumps(new, sort_keys=True)} — "
                "suppression debt is pinned (file "
                f"{pin_rel}); acknowledge an intentional change with "
                "--audit-write"
            ),
        }

    if write:
        with open(expected_path, "w", encoding="utf-8") as fh:
            json.dump(expected, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return results, findings, claim_summary
