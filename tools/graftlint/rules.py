"""The AST rule set.

Each rule enforces one of the repo's written-but-previously-unchecked
invariants (CLAUDE.md "Conventions that bite", SURVEY.md §2):

* ``no-pickle`` — the wire/storage contract is the typed binary framing
  of ``comm/framing.py``; the reference's crashes came from untyped
  pickles over TCP (``consensus_tcp/master.py:140``).  Pickle is allowed
  only in the explicit allowlist (CIFAR's upstream on-disk format).
* ``banned-import`` — cvxpy/networkx/torchvision are absent BY DESIGN
  (native solvers, topology builders, and data paths replace them);
  torch is quarantined to ``interop.py``.
* ``raw-collective-in-shard-map`` — a hand-written ``lax.psum`` /
  ``pmean`` / ``pcast`` is exactly where the Megatron f/g and vma
  cotangent hazards live (``training/tp.py`` NOTE, ``training/pp.py``
  ``head_seed``): every such call must carry a suppression naming the
  exit/cotangent rule it implements.
* ``host-sync-in-hot-path`` — ``.item()`` / ``float()`` /
  ``np.asarray()`` inside jit-decorated or scanned step functions force
  a device->host sync per call.
* ``no-print-in-library`` — library code (``distributed_learning_tpu/``)
  reports through the obs layer and named ``logging`` loggers, never
  bare ``print``; stdout belongs to the CLI's emit paths and the
  examples (an exempt tree).  A legitimate library print (a
  CLI subcommand's output, a matplotlib-free fallback) carries a
  reasoned suppression.
* ``wallclock-duration`` — durations/latencies must be measured on a
  monotonic clock (``time.perf_counter`` / ``time.monotonic``), never
  as ``time.time()`` deltas: the wall clock steps under NTP slew and
  leap adjustments, which turns a latency histogram into noise exactly
  on the long-lived agents the straggler profiles watch.  Wall-clock
  *anchors* (``SpanTracer.wall0``-style epoch offsets, cross-process
  staleness against event timestamps) are the legitimate exceptions
  and carry reasoned suppressions.
* ``reference-citation`` — docstring/comment ``file:line`` citations
  must resolve (into ``/root/reference`` when present, else against the
  repo itself) so provenance pointers cannot rot.
* ``wire-code-unique`` — the one-byte message type codes of
  ``comm/protocol.py`` must be unique and every message class must be
  registered in the single ``_REGISTRY`` table: a duplicated code is a
  silent frame-misparse (the receiver unpacks the wrong dataclass from
  a valid frame), and an unregistered class raises only at first send.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set

from tools.graftlint.core import (
    FileContext,
    Finding,
    Rule,
    dotted_name,
    register,
)


def _import_roots(tree: ast.Module) -> Dict[str, str]:
    """alias -> root module for plain imports (``import numpy as np``)."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = a.name.split(".")[0]
    return out


@register
class NoPickle(Rule):
    """Pickle is banned outside the explicit allowlist."""

    name = "no-pickle"
    #: CIFAR's upstream distribution format is python pickle batches;
    #: that is on-disk input parsing, not wire traffic.
    allowlist = frozenset({"distributed_learning_tpu/data/cifar.py"})
    modules = frozenset({"pickle", "cPickle", "_pickle", "dill", "shelve"})

    def check(self, ctx: FileContext) -> List[Finding]:
        if ctx.relpath in self.allowlist:
            return []
        out = []

        def hit(line, what):
            out.append(
                Finding(
                    self.name,
                    ctx.relpath,
                    line,
                    f"{what}: the wire/storage contract is the typed "
                    "binary framing (comm/framing.py) — the reference's "
                    "untyped pickles are what crashed it "
                    "(consensus_tcp/master.py:140)",
                )
            )

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.split(".")[0] in self.modules:
                        hit(node.lineno, f"import of '{a.name}'")
            elif isinstance(node, ast.ImportFrom):
                root = (node.module or "").split(".")[0]
                if root in self.modules:
                    hit(node.lineno, f"import from '{node.module}'")
            elif isinstance(node, ast.Call):
                name = dotted_name(node.func) or ""
                if name.endswith((".read_pickle", ".to_pickle")):
                    hit(node.lineno, f"call to '{name}'")
                for kw in node.keywords:
                    if (
                        kw.arg == "allow_pickle"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True
                    ):
                        hit(node.lineno, "np.load(allow_pickle=True)")
        return out


@register
class BannedImport(Rule):
    """cvxpy/networkx/torchvision anywhere; torch outside interop."""

    name = "banned-import"
    banned = {
        "cvxpy": "the native SDP solver (parallel/fast_averaging.py) "
        "replaces it",
        "networkx": "native topology builders (parallel/topology.py) "
        "replace it",
        "torchvision": "native data paths (data/) replace it",
    }
    torch_allowlist = frozenset({"distributed_learning_tpu/interop.py"})

    def _roots(self, node) -> List[tuple]:
        if isinstance(node, ast.Import):
            return [(a.name.split(".")[0], a.name) for a in node.names]
        if isinstance(node, ast.ImportFrom) and node.module and not node.level:
            return [(node.module.split(".")[0], node.module)]
        return []

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            for root, full in self._roots(node):
                if root in self.banned:
                    out.append(
                        Finding(
                            self.name,
                            ctx.relpath,
                            node.lineno,
                            f"import of '{full}' is banned by design: "
                            f"{self.banned[root]}",
                        )
                    )
                elif root == "torch" and ctx.relpath not in self.torch_allowlist:
                    out.append(
                        Finding(
                            self.name,
                            ctx.relpath,
                            node.lineno,
                            "torch imports live only in interop.py (the "
                            "quarantined interop layer)",
                        )
                    )
        return out


@register
class RawCollectiveInShardMap(Rule):
    """Raw psum/pmean/pcast must declare which transpose rule they are.

    Under shard_map's varying-manual-axes tracking, a raw ``lax.psum``
    at a TP region's exit IS the Megatron f/g pair (training/tp.py
    NOTE), and a missing ``lax.pcast(..., to="varying")`` before a local
    cotangent silently inserts a psum-over-axis into it (training/pp.py
    ``head_seed``).  Both bugs look like one innocuous call, so every
    raw collective of these three kinds must carry a suppression whose
    reason names the rule it implements.
    """

    name = "raw-collective-in-shard-map"
    requires_reason = True
    collectives = frozenset({"psum", "pmean", "pcast"})

    def check(self, ctx: FileContext) -> List[Finding]:
        aliases: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "jax.lax":
                for a in node.names:
                    if a.name in self.collectives:
                        aliases.add(a.asname or a.name)
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None:
                continue
            coll = None
            if name in aliases:
                coll = name
            else:
                parts = name.split(".")
                if (
                    parts[-1] in self.collectives
                    and len(parts) >= 2
                    and parts[-2] == "lax"
                ):
                    coll = parts[-1]
            if coll is None:
                continue
            out.append(
                Finding(
                    self.name,
                    ctx.relpath,
                    node.lineno,
                    f"raw lax.{coll}: annotate which exit/cotangent rule "
                    "this implements — '# graftlint: disable="
                    f"{self.name} -- <reason>' (see the Megatron f/g "
                    "NOTE in training/tp.py and head_seed in "
                    "training/pp.py)",
                )
            )
        return out


_JIT_NAMES = frozenset({"jax.jit", "jit", "jax.pmap", "pmap"})
_PARTIAL_NAMES = frozenset({"functools.partial", "partial"})


@register
class HostSyncInHotPath(Rule):
    """No device->host syncs inside jitted or scanned step functions."""

    name = "host-sync-in-hot-path"
    requires_reason = True
    sync_calls = frozenset(
        {
            "np.asarray",
            "numpy.asarray",
            "np.array",
            "numpy.array",
            "jax.device_get",
        }
    )
    #: Host-side dispatch loops held to the same no-sync discipline even
    #: without a jit/scan marker: the async gossip runtime's per-round
    #: receive/mix path runs once per gossip round per agent — an
    #: accidental device round-trip there stalls the whole fabric the
    #: way a hot-path .item() stalls a compiled step.  Values on these
    #: paths stay numpy end to end by design.
    extra_hot_functions = {
        "distributed_learning_tpu/comm/async_runtime.py": frozenset(
            {
                "_push",
                "_recv_step",
                "_handle_peer_msg",
                "_collect",
                "_collect_choco",
                "_consume",
                "_mix_plain",
                "_needs_fresh",
                "_needs_correction",
            }
        ),
    }

    def _hot_roots(self, ctx: FileContext) -> List[ast.AST]:
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        roots: List[ast.AST] = []
        for fname in self.extra_hot_functions.get(ctx.relpath, ()):
            roots.extend(defs.get(fname, []))

        def add_callable(arg):
            if isinstance(arg, ast.Lambda):
                roots.append(arg)
            elif isinstance(arg, ast.Name):
                roots.extend(defs.get(arg.id, []))
            elif isinstance(arg, (ast.List, ast.Tuple)):
                for el in arg.elts:
                    add_callable(el)

        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    name = dotted_name(dec)
                    if name in _JIT_NAMES:
                        roots.append(node)
                    elif isinstance(dec, ast.Call):
                        cname = dotted_name(dec.func)
                        if cname in _JIT_NAMES:
                            roots.append(node)
                        elif (
                            cname in _PARTIAL_NAMES
                            and dec.args
                            and dotted_name(dec.args[0]) in _JIT_NAMES
                        ):
                            roots.append(node)
            elif isinstance(node, ast.Call):
                name = dotted_name(node.func) or ""
                if name in _JIT_NAMES and node.args:
                    add_callable(node.args[0])
                elif name.endswith("lax.scan") or name == "scan":
                    if node.args:
                        add_callable(node.args[0])
                elif name.endswith("lax.while_loop") or name == "while_loop":
                    for a in node.args[:2]:
                        add_callable(a)
                elif name.endswith("lax.fori_loop") or name == "fori_loop":
                    if len(node.args) >= 3:
                        add_callable(node.args[2])
                elif name.endswith("lax.cond") or name == "cond":
                    for a in node.args[1:3]:
                        add_callable(a)
                elif name.endswith("lax.switch") or name == "switch":
                    if len(node.args) >= 2:
                        add_callable(node.args[1])
        return roots

    @staticmethod
    def _looks_traced(arg: ast.AST) -> bool:
        """float(x)/int(x) is a sync only when x is plausibly a traced
        array: a bare name/attribute/subscript, or an expression built
        from jnp./jax. calls.  Host-side arithmetic on static shapes
        (``float(1.0 / np.sqrt(D))``) is trace-time constant folding."""
        if isinstance(arg, (ast.Name, ast.Attribute, ast.Subscript)):
            return True
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Call):
                name = dotted_name(sub.func) or ""
                if name.split(".")[0] in ("jnp", "jax"):
                    return True
        return False

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        seen: Set[int] = set()

        def msg(line, what):
            out.append(
                Finding(
                    self.name,
                    ctx.relpath,
                    line,
                    f"{what} inside a jitted/scanned step forces a "
                    "device->host sync per call; hoist it out of the hot "
                    "path or keep the value on device",
                )
            )

        for root in self._hot_roots(ctx):
            for node in ast.walk(root):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                name = dotted_name(node.func) or ""
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    msg(node.lineno, ".item()")
                elif name in self.sync_calls:
                    msg(node.lineno, f"{name}()")
                elif (
                    name in ("float", "int")
                    and node.args
                    and self._looks_traced(node.args[0])
                ):
                    msg(node.lineno, f"{name}(...) on a traced value")
        return out


@register
class NoPrintInLibrary(Rule):
    """Bare ``print`` in library code must carry a reasoned suppression.

    The obs layer (``distributed_learning_tpu/obs/``) and named loggers
    (``dlt.comm.*``) are the library's reporting channels — the
    reference's debug-flag prints are exactly the observability this
    repo replaced, and a stray ``print`` in the comm layer would also
    corrupt any driver parsing stdout.  Examples, tools and
    ``chip_smoke.py`` own their stdout; everything else needs
    ``# graftlint: disable=no-print-in-library -- <why this print is
    the interface>``.
    """

    name = "no-print-in-library"
    requires_reason = True
    #: trees/files whose stdout IS their interface.
    exempt_prefixes = ("examples/", "tools/", "tests/")
    exempt_files = frozenset({"chip_smoke.py"})

    def check(self, ctx: FileContext) -> List[Finding]:
        rel = ctx.relpath
        if rel in self.exempt_files or rel.startswith(self.exempt_prefixes):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) != "print":
                continue
            out.append(
                Finding(
                    self.name,
                    rel,
                    node.lineno,
                    "bare print in library code: route diagnostics "
                    "through logging (named 'dlt.*' loggers) or the obs "
                    "registry; if this print IS the interface (CLI "
                    "output), suppress with a reason",
                )
            )
        return out


@register
class WallclockDuration(Rule):
    """Durations via ``perf_counter``/``monotonic``, never ``time.time()``
    deltas.

    Flags a subtraction when either side involves the wall clock: a
    direct ``time.time()`` call (also seen through ``from time import
    time`` aliases) or a local name the enclosing function assigned
    from one (the classic ``t0 = time.time(); ...; dur = time.time() -
    t0``).  Wall-clock anchor arithmetic — epoch offsets, cross-process
    staleness — is what suppressions with reasons are for
    (``requires_reason``): the reason must say why monotonic clocks
    cannot serve that site.
    """

    name = "wallclock-duration"
    requires_reason = True

    def _walltime_aliases(self, ctx: FileContext) -> Set[str]:
        """Names that call the wall clock directly: ``time.time`` plus
        any ``from time import time [as t]`` alias."""
        aliases = {"time.time"}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for a in node.names:
                    if a.name == "time":
                        aliases.add(a.asname or a.name)
        return aliases

    @staticmethod
    def _is_call_to(node: ast.AST, aliases: Set[str]) -> bool:
        return (
            isinstance(node, ast.Call)
            and (dotted_name(node.func) or "") in aliases
        )

    def _contains_wall_call(self, node: ast.AST,
                            aliases: Set[str]) -> bool:
        return any(
            self._is_call_to(sub, aliases) for sub in ast.walk(node)
        )

    def check(self, ctx: FileContext) -> List[Finding]:
        aliases = self._walltime_aliases(ctx)
        # Names assigned from a wall-clock call anywhere in the file
        # (file-scope taint: simple, and a shared name like ``t0``
        # being wall in one function and monotonic in another is
        # exactly the confusion this rule exists to keep out).
        tainted: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Assign)
                    and self._is_call_to(node.value, aliases)):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        tainted.add(tgt.id)
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)):
                continue
            operands = (node.left, node.right)
            direct = any(
                self._contains_wall_call(op, aliases) for op in operands
            )
            via_name = any(
                isinstance(op, ast.Name) and op.id in tainted
                for op in operands
            )
            if direct or via_name:
                out.append(
                    Finding(
                        self.name,
                        ctx.relpath,
                        node.lineno,
                        "duration measured as a time.time() delta: "
                        "the wall clock steps (NTP slew/leap), "
                        "poisoning latency stats — use "
                        "time.perf_counter()/time.monotonic(); a "
                        "legitimate wall-clock anchor needs "
                        f"'# graftlint: disable={self.name} -- "
                        "<why monotonic cannot serve here>'",
                    )
                )
        return out


@register
class WireCodeUnique(Rule):
    """Message TYPE_CODEs must be unique and registered in ONE table.

    ``comm/protocol.py``'s one-byte type codes are the wire's dispatch
    keys: a duplicated code makes ``unpack_message`` deserialize a valid
    frame into the WRONG dataclass — a silent misparse the crc cannot
    catch — and a class missing from ``_REGISTRY`` fails only at first
    send/receive.  With 17+ codes across stacked PRs, this is checked
    statically: every ``TYPE_CODE`` (>= 0) appears once, and the set of
    classes defining one exactly matches the classes enumerated in the
    single ``_REGISTRY`` dict-comprehension table.
    """

    name = "wire-code-unique"
    files = frozenset({"distributed_learning_tpu/comm/protocol.py"})

    @staticmethod
    def _type_code_of(cls: ast.ClassDef):
        """(code, lineno) when the class body assigns TYPE_CODE to an
        int literal, else None."""
        for node in cls.body:
            target = None
            if isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name
            ):
                target = node.target.id
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 and (
                isinstance(node.targets[0], ast.Name)
            ):
                target = node.targets[0].id
            if target != "TYPE_CODE":
                continue
            value = node.value
            code = None
            if isinstance(value, ast.Constant) and isinstance(
                value.value, int
            ):
                code = value.value
            elif (
                isinstance(value, ast.UnaryOp)
                and isinstance(value.op, ast.USub)
                and isinstance(value.operand, ast.Constant)
            ):
                code = -value.operand.value
            if code is not None:
                return code, node.lineno
        return None

    @staticmethod
    def _registry_names(tree: ast.Module):
        """Class names enumerated in the ``_REGISTRY`` dict-comprehension
        table, or None when no such single table exists."""
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
            elif isinstance(node, ast.AnnAssign):  # _REGISTRY: Dict[...] =
                target = node.target
            else:
                continue
            if not (
                isinstance(target, ast.Name)
                and target.id == "_REGISTRY"
                and isinstance(node.value, ast.DictComp)
                and node.value.generators
            ):
                continue
            src = node.value.generators[0].iter
            if isinstance(src, (ast.Tuple, ast.List)):
                names = [
                    el.id for el in src.elts if isinstance(el, ast.Name)
                ]
                return names, node.lineno
        return None

    def check(self, ctx: FileContext) -> List[Finding]:
        if ctx.relpath not in self.files:
            return []
        out: List[Finding] = []
        coded: Dict[int, str] = {}
        class_lines: Dict[str, int] = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            tc = self._type_code_of(node)
            if tc is None:
                continue
            code, lineno = tc
            if code < 0:
                continue  # the Message base's sentinel
            class_lines[node.name] = lineno
            if code in coded:
                out.append(
                    Finding(
                        self.name,
                        ctx.relpath,
                        lineno,
                        f"TYPE_CODE {code} of {node.name} duplicates "
                        f"{coded[code]}: a shared code makes "
                        "unpack_message deserialize valid frames into "
                        "the wrong message class (silent misparse)",
                    )
                )
            else:
                coded[code] = node.name
        reg = self._registry_names(ctx.tree)
        if reg is None:
            if class_lines:
                out.append(
                    Finding(
                        self.name,
                        ctx.relpath,
                        1,
                        "no single _REGISTRY dict-comprehension table "
                        "found: all message classes must register their "
                        "type codes in one place",
                    )
                )
            return out
        names, reg_line = reg
        for cls_name, lineno in sorted(class_lines.items()):
            if cls_name not in names:
                out.append(
                    Finding(
                        self.name,
                        ctx.relpath,
                        lineno,
                        f"{cls_name} defines a TYPE_CODE but is missing "
                        "from the _REGISTRY table: its frames raise "
                        "'unknown message type code' at first receive",
                    )
                )
        for name in names:
            if name not in class_lines:
                out.append(
                    Finding(
                        self.name,
                        ctx.relpath,
                        reg_line,
                        f"_REGISTRY lists '{name}', which defines no "
                        "integer TYPE_CODE in this module",
                    )
                )
        dup_reg = {n for n in names if names.count(n) > 1}
        for name in sorted(dup_reg):
            out.append(
                Finding(
                    self.name,
                    ctx.relpath,
                    reg_line,
                    f"_REGISTRY lists '{name}' more than once",
                )
            )
        # Gap check (ISSUE 15): codes must stay contiguous min..max.  A
        # hole means a message class was deleted without retiring its
        # code explicitly — the freed code is silently reusable, and a
        # stale peer still emitting it would misparse into whatever
        # class claims the number next.  Retiring a code on purpose
        # means renumbering (a wire-contract bump, repinned with
        # --audit-write, which also pins the max code).
        if coded:
            lo, hi = min(coded), max(coded)
            holes = sorted(set(range(lo, hi + 1)) - set(coded))
            if holes:
                out.append(
                    Finding(
                        self.name,
                        ctx.relpath,
                        reg_line,
                        f"TYPE_CODE range {lo}..{hi} has gap(s) at "
                        f"{holes}: a deleted code is silently reusable "
                        "by the next class — renumber contiguously and "
                        "repin the wire contract (--audit-write)",
                    )
                )
        return out


_CITE_RE = re.compile(
    r"(?<![\w/._-])"
    r"(?P<path>(?:[\w.\-]+/)*[\w\-][\w.\-]*\.(?:py|cpp|h|md|sh|ipynb))"
    r":(?P<start>\d{1,5})(?:-(?P<end>\d{1,5}))?"
)


@register
class ReferenceCitation(Rule):
    """``file:line`` citations must point at lines that exist.

    Resolution order: the read-only reference snapshot
    (``/root/reference``) when present, then the repo itself (internal
    citations).  When the reference snapshot is absent, citations whose
    path matches nothing in the repo are skipped (unverifiable) rather
    than flagged.
    """

    name = "reference-citation"
    reference_root = "/root/reference"

    def __init__(self):
        self._index_cache: Dict[str, List[str]] = {}
        self._len_cache: Dict[str, int] = {}

    def _index(self, root: str) -> List[str]:
        if root in self._index_cache:
            return self._index_cache[root]
        files: List[str] = []
        if os.path.isdir(root):
            for dirpath, dirnames, filenames in os.walk(root):
                dirnames[:] = [
                    d
                    for d in dirnames
                    if d not in (".git", "__pycache__", "node_modules")
                ]
                for fn in filenames:
                    files.append(os.path.join(dirpath, fn))
        self._index_cache[root] = files
        return files

    def _line_count(self, path: str) -> int:
        if path not in self._len_cache:
            try:
                with open(path, "rb") as fh:
                    self._len_cache[path] = fh.read().count(b"\n") + 1
            except OSError:
                self._len_cache[path] = 0
        return self._len_cache[path]

    def _candidates(self, root: str, cite_path: str) -> List[str]:
        suffix = "/" + cite_path
        return [
            f
            for f in self._index(root)
            if f.endswith(suffix) or os.path.relpath(f, root) == cite_path
        ]

    def _resolves(self, ctx: FileContext, cite_path: str, end: int):
        """(resolved, verifiable): scanning reference then repo."""
        roots = []
        if os.path.isdir(self.reference_root):
            roots.append(self.reference_root)
        roots.append(ctx.repo_root)
        verifiable = os.path.isdir(self.reference_root)
        for root in roots:
            for cand in self._candidates(root, cite_path):
                verifiable = True
                if self._line_count(cand) >= end:
                    return True, True
        return False, verifiable

    def _texts(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if isinstance(
                node,
                (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef,
                 ast.ClassDef),
            ):
                doc = ast.get_docstring(node, clean=False)
                if doc and node.body:
                    yield node.body[0].lineno, doc
        for line, text in ctx.comments():
            yield line, text

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for base_line, text in self._texts(ctx):
            for m in _CITE_RE.finditer(text):
                start = int(m.group("start"))
                end = int(m.group("end") or start)
                line = base_line + text.count("\n", 0, m.start())
                resolved, verifiable = self._resolves(
                    ctx, m.group("path"), max(start, end)
                )
                if resolved or not verifiable:
                    continue
                out.append(
                    Finding(
                        self.name,
                        ctx.relpath,
                        line,
                        f"citation '{m.group(0)}' does not resolve: no "
                        "matching file has that many lines (checked "
                        "/root/reference and the repo) — stale pointer?",
                    )
                )
        return out
