"""graftlint core: findings, suppressions, file contexts, rule registry.

The AST stage walks every python file under the scanned roots
(``distributed_learning_tpu/``, ``examples/``, ``chip_smoke.py``) and
runs each registered :class:`Rule` over it.  A finding is silenced by an inline suppression comment:

    x = lax.psum(h, "model")  # graftlint: disable=raw-collective-in-shard-map -- megatron exit

or, for a whole statement, by a comment on its own line immediately
above the flagged line:

    # graftlint: disable=host-sync-in-hot-path -- probe runs pre-jit
    val = float(probe[0, 0])

Several rules (``requires_reason=True``) reject bare suppressions: the
comment must carry ``-- <reason>`` text naming the invariant the
suppressed line implements (e.g. which Megatron f/g exit or cotangent
rule a raw ``lax.psum`` is).  A disable naming a rule that does not
exist is itself a finding (``bad-suppression``) so typos cannot
silently disarm the linter.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: The trees/files the AST stage audits by default (repo-relative).
DEFAULT_ROOTS = (
    "distributed_learning_tpu",
    "examples",
    "chip_smoke.py",
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str  # repo-relative
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)"
    r"(?:\s+--\s*(?P<reason>\S.*?))?\s*$"
)


@dataclasses.dataclass(frozen=True)
class Suppression:
    rules: frozenset
    reason: Optional[str]
    comment_line: int  # where the comment itself sits (for bad-suppression)


class Suppressions:
    """Per-line suppression map for one file.

    A comment sharing a line with code covers that line; a comment alone
    on its line covers the next line (the ``disable-next-line``
    convention, without needing a second spelling).  An own-line comment
    directly above a DECORATOR chain attaches across it to the ``def``
    line below (single-line decorators only: a decorator whose argument
    list spans lines breaks the chain) — the flagged node of a decorated
    function reports at its ``def`` line, not the decorator's.
    """

    def __init__(self, source: str):
        self.by_line: Dict[int, Suppression] = {}
        lines = source.splitlines()
        try:
            tokens = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _SUPPRESS_RE.match(tok.string)
                if not m:
                    continue
                rules = frozenset(
                    r.strip() for r in m.group(1).split(",") if r.strip()
                )
                sup = Suppression(rules, m.group("reason"), tok.start[0])
                own_line = tok.line[: tok.start[1]].strip() == ""
                target = tok.start[0] + 1 if own_line else tok.start[0]
                while (
                    own_line
                    and target <= len(lines)
                    and lines[target - 1].lstrip().startswith("@")
                ):
                    target += 1
                self.by_line[target] = sup
        except tokenize.TokenError:
            pass  # syntactically broken file: other tooling will complain

    def lookup(self, rule: str, line: int) -> Optional[Suppression]:
        sup = self.by_line.get(line)
        if sup is not None and rule in sup.rules:
            return sup
        return None

    def all(self) -> Iterable[Suppression]:
        return self.by_line.values()


class FileContext:
    """Everything a rule needs about one file, parsed once."""

    def __init__(self, path: str, repo_root: str = REPO_ROOT,
                 source: Optional[str] = None):
        self.path = os.path.abspath(path)
        self.repo_root = repo_root
        self.relpath = os.path.relpath(self.path, repo_root).replace(
            os.sep, "/"
        )
        if source is None:
            with open(self.path, "r", encoding="utf-8") as fh:
                source = fh.read()
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=self.path)
        self.suppressions = Suppressions(source)

    def comments(self) -> List[tuple]:
        """(line, text) for every comment token (used by citation rules)."""
        out = []
        try:
            for tok in tokenize.generate_tokens(
                io.StringIO(self.source).readline
            ):
                if tok.type == tokenize.COMMENT:
                    out.append((tok.start[0], tok.string))
        except tokenize.TokenError:
            pass
        return out


class Rule:
    """Base class: subclasses set ``name`` and implement ``check``."""

    name: str = ""
    #: a suppression for this rule must carry ``-- <reason>`` text
    requires_reason: bool = False
    #: which lint stage produces this rule's findings ("ast" rules run
    #: per-file; "wire-contract" findings come from the cross-language
    #: stage in ``wire_contract.py``, where inline suppressions do not
    #: apply).
    stage: str = "ast"

    def check(self, ctx: FileContext) -> List[Finding]:
        raise NotImplementedError


RULES: Dict[str, Rule] = {}


def register(cls):
    """Class decorator: instantiate and add to the global registry."""
    inst = cls()
    if not inst.name:
        raise ValueError(f"rule {cls.__name__} has no name")
    RULES[inst.name] = inst
    return cls


def dotted_name(node: ast.AST) -> Optional[str]:
    """'jax.lax.psum' for Attribute/Name chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _apply_suppressions(
    ctx: FileContext, findings: List[Finding], rules: Dict[str, Rule]
) -> List[Finding]:
    out = []
    for f in findings:
        sup = ctx.suppressions.lookup(f.rule, f.line)
        if sup is None:
            out.append(f)
            continue
        rule = rules.get(f.rule)
        if rule is not None and rule.requires_reason and not sup.reason:
            out.append(
                Finding(
                    f.rule,
                    f.path,
                    f.line,
                    f"suppression for '{f.rule}' needs a reason: write "
                    f"'# graftlint: disable={f.rule} -- <which invariant "
                    "this line implements>'",
                )
            )
    return out


def _bad_suppression_findings(
    ctx: FileContext, rules: Dict[str, Rule]
) -> List[Finding]:
    out = []
    for sup in ctx.suppressions.all():
        unknown = sorted(r for r in sup.rules if r not in RULES)
        for r in unknown:
            out.append(
                Finding(
                    "bad-suppression",
                    ctx.relpath,
                    sup.comment_line,
                    f"disable names unknown rule '{r}' (known: "
                    f"{', '.join(sorted(RULES))})",
                )
            )
    return out


def lint_file(
    path: str,
    rules: Optional[Dict[str, Rule]] = None,
    repo_root: str = REPO_ROOT,
    source: Optional[str] = None,
) -> List[Finding]:
    """Run the AST rules over one file, honoring suppressions."""
    rules = RULES if rules is None else rules
    try:
        ctx = FileContext(path, repo_root=repo_root, source=source)
    except SyntaxError as exc:
        return [
            Finding(
                "syntax-error",
                os.path.relpath(path, repo_root).replace(os.sep, "/"),
                exc.lineno or 1,
                f"file does not parse: {exc.msg}",
            )
        ]
    findings: List[Finding] = []
    for rule in rules.values():
        findings.extend(rule.check(ctx))
    findings = _apply_suppressions(ctx, findings, rules)
    findings.extend(_bad_suppression_findings(ctx, rules))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def iter_python_files(
    roots: Sequence[str] = DEFAULT_ROOTS, repo_root: str = REPO_ROOT
) -> List[str]:
    """Expand the scanned roots to a sorted list of .py files."""
    out = []
    for root in roots:
        full = os.path.join(repo_root, root)
        if os.path.isfile(full):
            if full.endswith(".py"):
                out.append(full)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return sorted(out)


def lint_paths(
    paths: Optional[Sequence[str]] = None,
    rules: Optional[Dict[str, Rule]] = None,
    repo_root: str = REPO_ROOT,
) -> List[Finding]:
    """Lint explicit paths, or the default roots when none are given."""
    files = (
        iter_python_files(repo_root=repo_root)
        if not paths
        else [p for p in paths if p.endswith(".py") and os.path.isfile(p)]
    )
    findings: List[Finding] = []
    for f in files:
        findings.extend(lint_file(f, rules=rules, repo_root=repo_root))
    return findings
