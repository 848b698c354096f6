"""graftlint tier-1 coverage (AST stage needs no mesh and no jax).

Three layers:

* fixture files proving each rule FIRES on a violating snippet (a lint
  whose rules can silently stop firing is worse than no lint);
* suppression semantics (same-line, line-above, reason-required,
  unknown-rule);
* the tree itself: ``lint_paths()`` over the real scanned roots must
  return zero findings — the repo's invariants hold, machine-checked;
* the jaxpr/HLO audit: each registered entry point's collective
  inventory must match its pin (entries needing a jax API this
  environment lacks skip with the feature named).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.graftlint import RULES, lint_file, lint_paths
from tools.graftlint import jaxpr_audit
from tools.graftlint.core import DEFAULT_ROOTS, REPO_ROOT


def _lint(tmp_path, code, relname="snippet.py", rules=None):
    p = tmp_path / relname
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    rule_map = None if rules is None else {r: RULES[r] for r in rules}
    return lint_file(str(p), rules=rule_map, repo_root=str(tmp_path))


def _rules_of(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------- #
# no-pickle                                                             #
# --------------------------------------------------------------------- #
def test_no_pickle_fires_on_import(tmp_path):
    fs = _lint(tmp_path, "import pickle\n", rules=["no-pickle"])
    assert _rules_of(fs) == ["no-pickle"]
    assert "framing" in fs[0].message


def test_no_pickle_fires_on_from_import_and_calls(tmp_path):
    code = """
    from pickle import loads
    import numpy as np
    df.to_pickle("x.pkl")
    np.load("a.npy", allow_pickle=True)
    """
    fs = _lint(tmp_path, code, rules=["no-pickle"])
    assert len(fs) == 3, fs


def test_no_pickle_allowlists_cifar(tmp_path):
    fs = _lint(
        tmp_path,
        "import pickle\n",
        relname="distributed_learning_tpu/data/cifar.py",
        rules=["no-pickle"],
    )
    assert fs == []


# --------------------------------------------------------------------- #
# banned-import                                                         #
# --------------------------------------------------------------------- #
def test_banned_import_fires_on_each_banned_module(tmp_path):
    code = """
    import cvxpy
    import networkx as nx
    from torchvision.models import resnet18
    import torch
    """
    fs = _lint(tmp_path, code, rules=["banned-import"])
    assert len(fs) == 4, fs


def test_banned_import_allows_torch_in_interop(tmp_path):
    fs = _lint(
        tmp_path,
        "import torch\n",
        relname="distributed_learning_tpu/interop.py",
        rules=["banned-import"],
    )
    assert fs == []


# --------------------------------------------------------------------- #
# raw-collective-in-shard-map                                           #
# --------------------------------------------------------------------- #
def test_raw_collective_fires_without_suppression(tmp_path):
    code = """
    from jax import lax
    def f(x):
        return lax.psum(x, "model")
    """
    fs = _lint(tmp_path, code, rules=["raw-collective-in-shard-map"])
    assert _rules_of(fs) == ["raw-collective-in-shard-map"]
    assert "lax.psum" in fs[0].message


def test_raw_collective_fires_on_bare_import_alias(tmp_path):
    code = """
    from jax.lax import pmean
    def f(x):
        return pmean(x, "agents")
    """
    fs = _lint(tmp_path, code, rules=["raw-collective-in-shard-map"])
    assert len(fs) == 1


def test_raw_collective_bare_suppression_rejected(tmp_path):
    code = """
    from jax import lax
    def f(x):
        return lax.psum(x, "m")  # graftlint: disable=raw-collective-in-shard-map
    """
    fs = _lint(tmp_path, code, rules=["raw-collective-in-shard-map"])
    assert len(fs) == 1 and "needs a reason" in fs[0].message


def test_raw_collective_reasoned_suppression_accepted(tmp_path):
    code = """
    from jax import lax
    def f(x):
        return lax.psum(x, "m")  # graftlint: disable=raw-collective-in-shard-map -- megatron g exit
    """
    fs = _lint(tmp_path, code, rules=["raw-collective-in-shard-map"])
    assert fs == []


def test_suppression_on_line_above(tmp_path):
    code = """
    from jax import lax
    def f(x):
        # graftlint: disable=raw-collective-in-shard-map -- exit psum
        return lax.psum(x, "m")
    """
    fs = _lint(tmp_path, code, rules=["raw-collective-in-shard-map"])
    assert fs == []


def test_unknown_rule_in_suppression_is_a_finding(tmp_path):
    code = "x = 1  # graftlint: disable=not-a-rule\n"
    fs = _lint(tmp_path, code)
    assert _rules_of(fs) == ["bad-suppression"]
    assert "not-a-rule" in fs[0].message


# --------------------------------------------------------------------- #
# host-sync-in-hot-path                                                 #
# --------------------------------------------------------------------- #
def test_host_sync_fires_in_jitted_fn(tmp_path):
    code = """
    import jax
    @jax.jit
    def step(x):
        return x.item()
    """
    fs = _lint(tmp_path, code, rules=["host-sync-in-hot-path"])
    assert _rules_of(fs) == ["host-sync-in-hot-path"]


def test_host_sync_fires_in_scanned_lambda_and_body(tmp_path):
    code = """
    import jax
    import numpy as np
    from jax import lax

    def body(c, t):
        return c, float(c)

    def run(xs):
        lax.scan(body, 0.0, xs)
        lax.scan(lambda c, t: (c, np.asarray(t)), 0.0, xs)
    """
    fs = _lint(tmp_path, code, rules=["host-sync-in-hot-path"])
    assert len(fs) == 2, fs


def test_host_sync_ignores_static_shape_math(tmp_path):
    code = """
    import functools, jax
    import numpy as np
    @functools.partial(jax.jit, static_argnames=("d",))
    def f(x, d):
        scale = float(1.0 / np.sqrt(d))
        return x * scale
    """
    fs = _lint(tmp_path, code, rules=["host-sync-in-hot-path"])
    assert fs == []


def test_host_sync_ignores_cold_paths(tmp_path):
    code = """
    import numpy as np
    def measure(losses):
        return float(np.asarray(losses).mean())
    """
    fs = _lint(tmp_path, code, rules=["host-sync-in-hot-path"])
    assert fs == []


def test_host_sync_covers_async_runtime_dispatch_loop(tmp_path):
    """The async gossip runtime's per-round receive/mix functions are
    hot roots WITHOUT any jit/scan marker (extra_hot_functions): a
    device sync there stalls the fabric once per gossip round.  The
    same code outside the registered functions (or the registered file)
    stays cold."""
    code = """
    import numpy as np

    class AsyncGossipRunner:
        def _mix_plain(self, y):
            return float(y)

        def _collect(self):
            return np.asarray([1.0])

    def elsewhere(y):
        return np.asarray(y)
    """
    fs = _lint(
        tmp_path, code,
        relname="distributed_learning_tpu/comm/async_runtime.py",
        rules=["host-sync-in-hot-path"],
    )
    assert len(fs) == 2, fs
    # Identical code under any other path is not hot.
    fs = _lint(tmp_path, code, rules=["host-sync-in-hot-path"])
    assert fs == []


# --------------------------------------------------------------------- #
# no-print-in-library                                                   #
# --------------------------------------------------------------------- #
def test_no_print_fires_in_library_code(tmp_path):
    code = """
    import sys
    def f():
        print("debugging")
        print("diag", file=sys.stderr)
    """
    fs = _lint(
        tmp_path, code,
        relname="distributed_learning_tpu/comm/thing.py",
        rules=["no-print-in-library"],
    )
    assert _rules_of(fs) == ["no-print-in-library"] * 2
    assert "logging" in fs[0].message


def test_no_print_exempts_examples_tools(tmp_path):
    for relname in (
        "chip_smoke.py",
        "examples/demo.py",
        "tools/helper.py",
    ):
        fs = _lint(
            tmp_path, 'print("ok")\n', relname=relname,
            rules=["no-print-in-library"],
        )
        assert fs == [], relname


def test_no_print_bare_suppression_rejected(tmp_path):
    code = 'print("x")  # graftlint: disable=no-print-in-library\n'
    fs = _lint(
        tmp_path, code,
        relname="distributed_learning_tpu/x.py",
        rules=["no-print-in-library"],
    )
    assert len(fs) == 1 and "needs a reason" in fs[0].message


def test_no_print_reasoned_suppression_accepted(tmp_path):
    code = (
        'print("x")  # graftlint: disable=no-print-in-library'
        " -- CLI output is the interface\n"
    )
    fs = _lint(
        tmp_path, code,
        relname="distributed_learning_tpu/x.py",
        rules=["no-print-in-library"],
    )
    assert fs == []


# --------------------------------------------------------------------- #
# wallclock-duration                                                    #
# --------------------------------------------------------------------- #
def test_wallclock_duration_fires_on_direct_delta(tmp_path):
    code = """
    import time
    def f():
        t0 = time.time()
        work()
        return time.time() - t0
    """
    fs = _lint(tmp_path, code, rules=["wallclock-duration"])
    assert _rules_of(fs) == ["wallclock-duration"]
    assert "perf_counter" in fs[0].message


def test_wallclock_duration_tracks_assigned_names_and_aliases(tmp_path):
    code = """
    from time import time as now
    def g(last_ts):
        a = now()
        return a - last_ts
    """
    fs = _lint(tmp_path, code, rules=["wallclock-duration"])
    assert len(fs) == 1, fs


def test_wallclock_duration_ignores_monotonic_clocks(tmp_path):
    code = """
    import time
    def h():
        t0 = time.perf_counter()
        work()
        return time.perf_counter() - t0
    def m():
        t0 = time.monotonic()
        return time.monotonic() - t0
    def stamps(ev0, ev1):
        return ev1["ts"] - ev0["ts"]  # stored stamps, not clock calls
    """
    assert _lint(tmp_path, code, rules=["wallclock-duration"]) == []


def test_wallclock_duration_bare_suppression_rejected(tmp_path):
    code = """
    import time
    def f():
        t0 = time.time()
        return time.time() - t0  # graftlint: disable=wallclock-duration
    """
    fs = _lint(tmp_path, code, rules=["wallclock-duration"])
    assert len(fs) == 1 and "needs a reason" in fs[0].message


def test_wallclock_duration_reasoned_anchor_accepted(tmp_path):
    code = """
    import time
    def anchor():
        # graftlint: disable=wallclock-duration -- epoch anchor: the absolute wall time of monotonic zero, not a duration
        return time.time() - time.perf_counter()
    """
    assert _lint(tmp_path, code, rules=["wallclock-duration"]) == []


# --------------------------------------------------------------------- #
# wire-code-unique                                                      #
# --------------------------------------------------------------------- #
_PROTOCOL_RELNAME = "distributed_learning_tpu/comm/protocol.py"


def _proto_snippet(codes, registry):
    """A protocol.py-shaped module: one class per (name, code) plus a
    _REGISTRY dict comprehension over ``registry`` names."""
    lines = ["from typing import ClassVar", ""]
    for name, code in codes:
        lines += [
            f"class {name}:",
            f"    TYPE_CODE: ClassVar[int] = {code}",
            "",
        ]
    lines.append(
        "_REGISTRY = {cls.TYPE_CODE: cls for cls in (%s)}"
        % (", ".join(registry) + ("," if registry else ""))
    )
    return "\n".join(lines) + "\n"


def test_wire_code_unique_passes_clean_protocol(tmp_path):
    code = _proto_snippet(
        [("A", 1), ("B", 2), ("C", 3)], ["A", "B", "C"]
    )
    assert _lint(
        tmp_path, code, relname=_PROTOCOL_RELNAME,
        rules=["wire-code-unique"],
    ) == []


def test_wire_code_unique_fires_on_duplicate_code(tmp_path):
    code = _proto_snippet([("A", 1), ("B", 1)], ["A", "B"])
    fs = _lint(
        tmp_path, code, relname=_PROTOCOL_RELNAME,
        rules=["wire-code-unique"],
    )
    assert _rules_of(fs) == ["wire-code-unique"]
    assert "duplicates" in fs[0].message and "misparse" in fs[0].message


def test_wire_code_unique_fires_on_unregistered_class(tmp_path):
    code = _proto_snippet([("A", 1), ("B", 2)], ["A"])
    fs = _lint(
        tmp_path, code, relname=_PROTOCOL_RELNAME,
        rules=["wire-code-unique"],
    )
    assert len(fs) == 1 and "missing from the _REGISTRY" in fs[0].message


def test_wire_code_unique_fires_on_phantom_and_double_registration(tmp_path):
    code = _proto_snippet([("A", 1)], ["A", "A", "Ghost"])
    fs = _lint(
        tmp_path, code, relname=_PROTOCOL_RELNAME,
        rules=["wire-code-unique"],
    )
    msgs = " | ".join(f.message for f in fs)
    assert "'Ghost'" in msgs and "more than once" in msgs


def test_wire_code_unique_fires_on_type_code_gap(tmp_path):
    """ISSUE 15 satellite: a hole in the TYPE_CODE range means a deleted
    code is silently reusable by the next class."""
    code = _proto_snippet([("A", 1), ("B", 2), ("D", 4)], ["A", "B", "D"])
    fs = _lint(
        tmp_path, code, relname=_PROTOCOL_RELNAME,
        rules=["wire-code-unique"],
    )
    assert _rules_of(fs) == ["wire-code-unique"]
    assert "gap(s) at [3]" in fs[0].message
    assert "renumber contiguously" in fs[0].message


def test_wire_code_unique_fires_when_registry_table_is_missing(tmp_path):
    code = (
        "from typing import ClassVar\n"
        "class A:\n    TYPE_CODE: ClassVar[int] = 1\n"
    )
    fs = _lint(
        tmp_path, code, relname=_PROTOCOL_RELNAME,
        rules=["wire-code-unique"],
    )
    assert len(fs) == 1 and "one place" in fs[0].message


def test_wire_code_unique_ignores_negative_sentinel_and_other_files(tmp_path):
    # The Message base's -1 sentinel is not a wire code.
    code = _proto_snippet([("Message", -1), ("A", 1)], ["A"])
    assert _lint(
        tmp_path, code, relname=_PROTOCOL_RELNAME,
        rules=["wire-code-unique"],
    ) == []
    # Scoped: the same duplicate codes elsewhere are not this rule's job.
    dup = _proto_snippet([("A", 1), ("B", 1)], ["A", "B"])
    assert _lint(
        tmp_path, dup, relname="distributed_learning_tpu/other.py",
        rules=["wire-code-unique"],
    ) == []


def test_wire_code_unique_real_protocol_is_clean_and_complete():
    """The shipped protocol.py passes, and the rule actually SEES all
    17+ codes (a rule that silently matches nothing is worse than none)."""
    import ast as ast_mod

    from tools.graftlint.rules import WireCodeUnique

    path = os.path.join(
        REPO_ROOT, "distributed_learning_tpu", "comm", "protocol.py"
    )
    fs = lint_file(path, rules={"wire-code-unique": RULES["wire-code-unique"]})
    assert [f for f in fs if f.rule == "wire-code-unique"] == []
    tree = ast_mod.parse(open(path).read())
    codes = [
        WireCodeUnique._type_code_of(n)[0]
        for n in ast_mod.walk(tree)
        if isinstance(n, ast_mod.ClassDef)
        and WireCodeUnique._type_code_of(n) is not None
        and WireCodeUnique._type_code_of(n)[0] >= 0
    ]
    assert len(codes) >= 17 and len(set(codes)) == len(codes)
    names, _ = WireCodeUnique._registry_names(tree)
    assert len(names) == len(codes)


# --------------------------------------------------------------------- #
# reference-citation                                                    #
# --------------------------------------------------------------------- #
@pytest.fixture
def fake_reference(tmp_path, monkeypatch):
    ref = tmp_path / "refroot"
    (ref / "utils").mkdir(parents=True)
    (ref / "utils" / "mixer.py").write_text("\n".join(["x"] * 50) + "\n")
    monkeypatch.setattr(
        RULES["reference-citation"], "reference_root", str(ref)
    )
    return ref


def test_reference_citation_resolves_good_cite(tmp_path, fake_reference):
    code = '"""Parity: ``utils/mixer.py:18-41`` semantics."""\n'
    fs = _lint(tmp_path, code, rules=["reference-citation"])
    assert fs == []


def test_reference_citation_fires_on_stale_line(tmp_path, fake_reference):
    code = '"""See ``mixer.py:999`` for the loop."""\n'
    fs = _lint(tmp_path, code, rules=["reference-citation"])
    assert _rules_of(fs) == ["reference-citation"]
    assert "mixer.py:999" in fs[0].message


def test_reference_citation_fires_on_missing_file(tmp_path, fake_reference):
    code = "# as in no_such_module.py:12\n"
    fs = _lint(tmp_path, code, rules=["reference-citation"])
    assert len(fs) == 1


def test_reference_citation_skips_unverifiable(tmp_path, monkeypatch):
    monkeypatch.setattr(
        RULES["reference-citation"],
        "reference_root",
        str(tmp_path / "absent"),
    )
    fs = _lint(
        tmp_path, "# see unknowable.py:7\n", rules=["reference-citation"]
    )
    assert fs == []


# --------------------------------------------------------------------- #
# the tree itself                                                       #
# --------------------------------------------------------------------- #
def test_tree_has_zero_unsuppressed_findings():
    findings = lint_paths(None)
    assert findings == [], "\n".join(str(f) for f in findings)


# --------------------------------------------------------------------- #
# CLI rot-guard (the tests/test_config_cli.py-style smoke)              #
# --------------------------------------------------------------------- #
def _cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        cwd=cwd, capture_output=True, text=True, timeout=120,
    )


def test_cli_list_rules():
    out = _cli("--list-rules")
    assert out.returncode == 0, out.stderr
    for rule in ("no-pickle", "no-print-in-library", "reference-citation"):
        assert rule in out.stdout


def test_cli_exits_nonzero_on_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import cvxpy\n")
    out = _cli(str(bad))
    assert out.returncode == 1, (out.stdout, out.stderr)
    assert "banned-import" in out.stdout


def test_cli_clean_tree_exits_zero_and_changed_mode_runs():
    out = _cli("--rules", "banned-import,no-pickle")
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-500:])
    out = _cli("--changed")
    # --changed lints whatever is currently modified: rc 0/1 are both
    # valid states; anything else is a harness break.
    assert out.returncode in (0, 1), out.stderr
    assert "graftlint:" in out.stderr


def test_cli_rejects_unknown_rule():
    out = _cli("--rules", "bogus-rule")
    assert out.returncode == 2
    assert "unknown rule" in out.stderr


# --------------------------------------------------------------------- #
# --list-rules --json golden (ISSUE 10: docs/CI cannot silently drift   #
# from the registered rule set)                                         #
# --------------------------------------------------------------------- #
#: The registered rule set, pinned.  Adding/removing/renaming a rule
#: means updating THIS list and docs/static_analysis.md together.
GOLDEN_RULES = [
    "banned-import",
    "blocking-in-async",
    "branch-divergent-collective",
    "collective-order-drift",
    "dead-message",
    "donation-alias",
    "host-sync-in-hot-path",
    "no-pickle",
    "no-print-in-library",
    "protocol-liveness",
    "protocol-model-pin",
    "raw-collective-in-shard-map",
    "reference-citation",
    "sched-model-pin",
    "schedule-deadlock",
    "schedule-nondeterminism",
    "suppression-claim",
    "task-shared-mutation",
    "turn-discipline-claim",
    "unawaited-coroutine",
    "unhandled-message",
    "vma-discipline",
    "wallclock-duration",
    "wire-code-unique",
    "wire-contract-drift",
    "wire-contract-pin",
]

#: Rules whose suppression must carry a reason, pinned.
GOLDEN_REQUIRES_REASON = [
    "blocking-in-async",
    "host-sync-in-hot-path",
    "no-print-in-library",
    "raw-collective-in-shard-map",
    "task-shared-mutation",
    "unawaited-coroutine",
    "wallclock-duration",
]


def test_cli_list_rules_json_golden():
    out = _cli("--list-rules", "--json")
    assert out.returncode == 0, out.stderr
    payload = json.loads(out.stdout)
    assert [r["name"] for r in payload["rules"]] == GOLDEN_RULES
    assert [
        r["name"] for r in payload["rules"] if r["requires_reason"]
    ] == GOLDEN_REQUIRES_REASON
    assert payload["stages"] == [
        "ast", "wire-contract", "audit", "dataflow", "proto", "sched",
        "native-san"
    ]
    assert "disable=<rule>" in payload["suppression"]
    for r in payload["rules"]:
        assert r["summary"], f"rule {r['name']} has no docstring summary"
        assert r["stage"] in (
            "ast", "wire-contract", "dataflow", "proto", "sched"
        )
    # The human docs must mention every registered rule.
    doc = open(os.path.join(REPO_ROOT, "docs", "static_analysis.md")).read()
    missing = [r for r in GOLDEN_RULES if f"`{r}`" not in doc]
    assert not missing, f"docs/static_analysis.md lacks rows for {missing}"


# --------------------------------------------------------------------- #
# --changed robustness (ISSUE 10 fix: deleted/renamed files)            #
# --------------------------------------------------------------------- #
def test_changed_files_partitions_deleted_paths(tmp_path):
    """A file deleted from the working tree appears in the diff but must
    land in the 'missing' bucket, never be opened."""
    from tools.graftlint.__main__ import _changed_files

    repo = tmp_path / "repo"
    (repo / "examples").mkdir(parents=True)
    keep = repo / "examples" / "keep.py"
    gone = repo / "examples" / "gone.py"
    keep.write_text("x = 1\n")
    gone.write_text("y = 2\n")
    env = {
        **os.environ,
        "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
        "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t",
    }
    for cmd in (
        ["git", "init", "-q"],
        ["git", "add", "-A"],
        ["git", "commit", "-qm", "seed"],
    ):
        subprocess.run(cmd, cwd=repo, env=env, check=True,
                       capture_output=True)
    gone.unlink()
    keep.write_text("x = 3\n")
    scoped, missing, changed = _changed_files(repo_root=str(repo))
    assert scoped == [str(keep)]
    assert missing == ["examples/gone.py"]
    assert "examples/gone.py" in changed


def test_cli_changed_notices_deleted_paths(monkeypatch, capsys):
    """main() with a diff of only-deleted paths: notice + rc 0, no
    crash, no full-tree fallback lint."""
    import tools.graftlint.__main__ as cli

    monkeypatch.setattr(
        cli, "_changed_files",
        lambda repo_root=None: ([], ["examples/gone.py"],
                                ["examples/gone.py"]),
    )
    rc = cli.main(["--changed"])
    err = capsys.readouterr().err
    assert rc == 0
    assert "skipping deleted/renamed path(s): examples/gone.py" in err


def test_cli_explicit_missing_path_notices_and_continues(tmp_path):
    good = tmp_path / "ok.py"
    good.write_text("x = 1\n")
    out = _cli(str(good), str(tmp_path / "missing.py"))
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "skipping non-existent path(s)" in out.stderr


def test_cli_all_missing_paths_never_fall_back_to_full_tree(
    monkeypatch, capsys
):
    """An explicit selection that filtered down to nothing lints
    NOTHING — the empty-selection/default-roots ambiguity must not turn
    a typo'd path into a silent whole-tree run."""
    import tools.graftlint.__main__ as cli

    def _no_full_tree(paths, rules=None):
        assert paths, "explicit empty selection must not lint the tree"
        return []

    monkeypatch.setattr(cli, "lint_paths", _no_full_tree)
    rc = cli.main(["/nonexistent/a.py"])
    err = capsys.readouterr().err
    assert rc == 0 and "skipping non-existent path(s)" in err


# --------------------------------------------------------------------- #
# tools/precommit.sh (ISSUE 10 satellite)                               #
# --------------------------------------------------------------------- #
def test_precommit_clean_tree_exits_zero():
    out = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "precommit.sh")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-500:])
    assert "graftlint:" in out.stderr


def test_precommit_fails_on_seeded_violation(tmp_path):
    """The violation is planted in a checkout of its own (the scanned
    roots and ``tools/``, committed in a fresh git repository): a file
    planted in the shared tree is seen, and then missed, by whatever
    another test worker lints or audits meanwhile."""
    import shutil

    root = tmp_path / "checkout"
    skip = shutil.ignore_patterns("__pycache__", "*.so", ".san_cache")
    for name in ("tools", *DEFAULT_ROOTS):
        src = os.path.join(REPO_ROOT, name)
        if os.path.isdir(src):
            shutil.copytree(src, root / name, ignore=skip)
        else:
            shutil.copy(src, root / name)
    git = lambda *args: subprocess.run(
        ["git", "-c", "user.name=t", "-c", "user.email=t@t", *args],
        cwd=root, check=True, capture_output=True, timeout=60)
    git("init", "-q")
    git("add", "-A")
    git("commit", "-q", "-m", "clean")
    seed = root / "examples" / "_precommit_seed_tmp.py"
    seed.write_text("import cvxpy\n")
    out = subprocess.run(
        ["bash", str(root / "tools" / "precommit.sh")],
        cwd=root, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 1, (out.stdout, out.stderr)
    assert "banned-import" in out.stdout
    assert "_precommit_seed_tmp.py" in out.stdout
    assert not os.path.exists(
        os.path.join(REPO_ROOT, "examples", "_precommit_seed_tmp.py"))


# --------------------------------------------------------------------- #
# --report-unverified (ISSUE 10 satellite)                              #
# --------------------------------------------------------------------- #
def test_report_unverified_lists_shim_pins_with_provenance(tmp_path):
    """The library path, against a fixture pin file: verified entries
    are silent, shim-pinned ones carry provenance + a re-verify line
    (skipped on jaxes without the feature; live-matched on newer ones),
    stale entry names are called out."""
    exp = tmp_path / "expected.json"
    exp.write_text(json.dumps({
        "tp_train_step": {"kind": "hlo", "inventory": {}, "verified": True},
        "async_stale_mix": {
            "kind": "jaxpr",
            "inventory": {"all_gather|agents": 2},
            "verified": False,
            "provenance": "shim-pinned: fixture",
        },
        "ghost_entry": {
            "kind": "jaxpr", "inventory": {}, "verified": False,
        },
        "wire_contract": {"kind": "wire-contract", "contract": {}},
    }))
    report = jaxpr_audit.report_unverified(expected_path=str(exp))
    assert sorted(report) == ["async_stale_mix", "ghost_entry"]
    entry = report["async_stale_mix"]
    assert entry["provenance"] == "shim-pinned: fixture"
    assert entry["reverify"].startswith(("ok:", "MISMATCH:", "skipped:"))
    assert "no longer registered" in report["ghost_entry"]["reverify"]
    assert "provenance" in report["ghost_entry"]  # unrecorded default
    # Reporting must never flip verified flags (that is --audit-write's
    # job): the fixture file is untouched.
    assert json.loads(exp.read_text())["async_stale_mix"]["verified"] is False


def test_report_unverified_cli_smoke():
    out = _cli("--report-unverified", "--rules", "no-pickle")
    # rc 1 is reserved for a live re-verify MISMATCH — a real defect.
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-500:])
    for name in ("async_stale_mix", "choco_run_fused", "pp_1f1b_head_fn",
                 "robust_mix"):
        assert f"unverified pin: {name}" in out.stdout
    assert "provenance:" in out.stdout and "re-verify:" in out.stdout


# --------------------------------------------------------------------- #
# jaxpr/HLO audit                                                       #
# --------------------------------------------------------------------- #
def test_normalize_primitive_prefixes():
    assert jaxpr_audit.normalize_primitive("psum") == "psum"
    assert jaxpr_audit.normalize_primitive("psum_invariant") == "psum"
    assert jaxpr_audit.normalize_primitive("psum2") == "psum"
    assert jaxpr_audit.normalize_primitive("all_gather_invariant") == (
        "all_gather"
    )
    assert jaxpr_audit.normalize_primitive("pvary") is None
    assert jaxpr_audit.normalize_primitive("pcast") is None
    assert jaxpr_audit.normalize_primitive("dot_general") is None


def test_collector_counts_injected_psum():
    """The collector must see through jit/shard_map/scan nesting — and
    an injected psum must CHANGE the inventory (the property the pinned
    entries rely on).  Uses whichever shard_map this jax provides."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    if hasattr(jax, "shard_map"):
        shard_map = jax.shard_map
        kw = {}
    else:
        from jax.experimental.shard_map import shard_map as _sm

        shard_map = _sm
        kw = {"check_rep": False}
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("a",))
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def make(extra_psum):
        def f(x):
            def body(c, t):
                c = lax.ppermute(c, "a", perm)
                if extra_psum:
                    c = c + lax.psum(c, "a")
                return c, t

            c, _ = lax.scan(body, x, jnp.arange(3))
            return c + lax.psum(x, "a")

        sm = shard_map(
            f, mesh=mesh, in_specs=P("a"), out_specs=P("a"), **kw
        )
        return jax.make_jaxpr(jax.jit(sm))(jnp.ones((8, 4)))

    base = jaxpr_audit.collect_collectives(make(False).jaxpr)
    assert base[("psum", ("a",))] == 1
    assert base[("ppermute", ("a",))] == 1
    injected = jaxpr_audit.collect_collectives(make(True).jaxpr)
    assert injected[("psum", ("a",))] == 2, (
        "an injected raw lax.psum must change the collective inventory"
    )


def test_audit_mismatch_reports_drift(tmp_path):
    """The comparison logic end to end against a stub entry point."""
    from collections import Counter

    name = "_stub_entry"
    jaxpr_audit.ENTRY_POINTS[name] = jaxpr_audit.EntryPoint(
        name, "jaxpr", (), lambda: Counter({("psum", ("m",)): 2})
    )
    try:
        exp = tmp_path / "expected.json"
        exp.write_text(json.dumps(
            {name: {"kind": "jaxpr", "inventory": {"psum|m": 1}}}
        ))
        res = jaxpr_audit.audit([name], expected_path=str(exp))[name]
        assert res["status"] == "mismatch"
        assert "audit-write" in res["detail"]
        # and the regeneration path repins:
        res = jaxpr_audit.audit(
            [name], write=True, expected_path=str(exp)
        )[name]
        assert res["status"] == "ok"
        assert json.loads(exp.read_text())[name]["inventory"] == {
            "psum|m": 2
        }
    finally:
        del jaxpr_audit.ENTRY_POINTS[name]


@pytest.mark.parametrize("name", sorted(jaxpr_audit.ENTRY_POINTS))
def test_audit_entry_inventory_pinned(name):
    """The acceptance property: each registered SPMD entry point's
    collective inventory matches its pin, so an injected collective
    turns tier-1 red with the entry, op, and axis named."""
    ep = jaxpr_audit.ENTRY_POINTS[name]
    missing = ep.missing_features()
    if missing:
        pytest.skip(
            f"jax lacks {missing} — {name} traces only on the new "
            "shard_map API (jax >= 0.7); the pin stays recorded in "
            "audit_expected.json"
        )
    res = jaxpr_audit.audit([name])[name]
    assert res["status"] == "ok", res


def _equations(jaxpr, name: str):
    """Every `name` equation, descending into sub-jaxprs (while/scan/
    pjit bodies) the same way collect_collectives does."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else [val]
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _equations(inner, name)
                elif hasattr(v, "eqns"):
                    yield from _equations(v, name)


def _count_primitive(jaxpr, name: str) -> int:
    return sum(1 for _ in _equations(jaxpr, name))


def test_dense_mix_until_mixes_the_leaves_where_they_lie():
    """The dense program property (runs on any jax): the eps-stopping
    gossip loop of a 60-leaf tree never packs.  No ``concatenate`` joins
    one leaf to another (the only ones stack a leaf's own N mixed rows
    along the agent axis), no leaf is reshaped to ``(N, size)``, and the
    round is the weighted sum of a leaf's rows: no ``dot_general``.  The
    per-leaf oracle (``fused=False``) carries one ``dot_general`` per
    leaf, on the leaf's 2-D reshape."""
    import jax
    import jax.numpy as jnp

    from distributed_learning_tpu.parallel.consensus import ConsensusEngine
    from distributed_learning_tpu.parallel.topology import Topology

    n = 8
    x = {
        f"l{i:02d}": jnp.ones((n, 3 + (i % 5), 2 + (i % 3)), jnp.float32)
        for i in range(60)
    }
    W = Topology.ring(n).metropolis_weights()

    def trace(engine):
        return jax.make_jaxpr(
            lambda s: engine.mix_until(s, eps=1e-6, max_rounds=32)[0]
        )(x)

    dense = trace(ConsensusEngine(W)).jaxpr
    leaf_shapes = {v.shape for v in x.values()}
    joins = list(_equations(dense, "concatenate"))
    assert len(joins) == 60
    for eqn in joins:
        assert eqn.params["dimension"] == 0
        assert [v.aval.shape[0] for v in eqn.invars] == [1] * n
        assert eqn.outvars[0].aval.shape in leaf_shapes
    assert _count_primitive(dense, "dot_general") == 0
    for eqn in _equations(dense, "reshape"):
        shape = eqn.outvars[0].aval.shape
        assert not (len(shape) == 2 and shape[0] == n), shape
    perleaf = list(_equations(
        trace(ConsensusEngine(W, fused=False)).jaxpr, "dot_general"
    ))
    assert len(perleaf) == 60
    assert all(len(eqn.invars[1].aval.shape) == 2 for eqn in perleaf)


@pytest.mark.skipif(
    not __import__("jax").__dict__.get("shard_map"),
    reason="sharded fused engine needs the jax.shard_map API (jax >= 0.7)",
)
def test_fused_mix_until_sharded_one_ppermute_per_matching():
    """The audit pin's property stated directly: the fused sharded
    mix_until moves ONE ppermute per matching (ring(8) Metropolis has 2
    matchings — one per ring direction) regardless of leaf count, where
    the per-leaf program pays matchings x leaves."""
    import jax
    import jax.numpy as jnp

    from distributed_learning_tpu.parallel.consensus import (
        ConsensusEngine,
        make_agent_mesh,
    )
    from distributed_learning_tpu.parallel.topology import Topology

    W = Topology.ring(8).metropolis_weights()
    mesh = make_agent_mesh(8)
    x = {f"l{i:02d}": jnp.ones((8, 2), jnp.float32) for i in range(12)}

    def inventory(engine):
        jx = jax.make_jaxpr(
            lambda s: engine.mix_until(s, eps=1e-6, max_rounds=32)[0]
        )(x)
        return jaxpr_audit.collect_collectives(jx.jaxpr)

    fused = inventory(ConsensusEngine(W, mesh=mesh))
    matchings = ConsensusEngine(W).schedule.num_rounds
    assert matchings == 2
    assert fused[("ppermute", ("agents",))] == matchings  # one per direction
    perleaf = inventory(ConsensusEngine(W, mesh=mesh, fused=False))
    assert perleaf[("ppermute", ("agents",))] == matchings * 12


def test_fused_choco_selection_is_per_bucket_not_per_leaf():
    """The ISSUE 5 jaxpr proof (dense route — runs on any jax): a
    compressed gossip round on the fused carry executes O(dtype-buckets)
    selection + scatter ops — exactly ONE top_k and ONE selection
    scatter per bucket on this uniform-span tree (one size class per
    bucket) — where the per-leaf oracle pays one of each PER LEAF.  The
    counts come from the scan body, so they are per ROUND."""
    import jax
    import jax.numpy as jnp

    from distributed_learning_tpu.parallel.compression import (
        ChocoGossipEngine,
        top_k,
    )
    from distributed_learning_tpu.parallel.topology import Topology

    leaves, n = 12, 8
    x = {
        f"l{i:02d}": jnp.ones(
            (n, 16), jnp.bfloat16 if i % 2 else jnp.float32
        )
        for i in range(leaves)
    }
    W = Topology.ring(n).metropolis_weights()

    def counts(fused):
        eng = ChocoGossipEngine(W, top_k(0.25), fused=fused)
        jx = jax.make_jaxpr(lambda s: eng.run(s, 3)[0].x)(eng.init(x))
        return {
            "top_k": _count_primitive(jx.jaxpr, "top_k"),
            "scatter": _count_primitive(jx.jaxpr, "scatter"),
        }

    buckets = 2
    fused = counts(True)
    assert fused["top_k"] == buckets, fused
    assert fused["scatter"] == buckets, fused
    perleaf = counts(False)
    assert perleaf["top_k"] == leaves, perleaf
    assert perleaf["scatter"] == leaves, perleaf


def _is_gossip_gemm(eqn, n: int) -> bool:
    """A ``dot_general`` whose lhs is the (n, n) mixing matrix: the
    per-leaf oracle's round.  Model GEMMs never contract an (n, n) lhs
    (the vmapped step's operands carry batch/feature dims)."""
    return eqn.primitive.name == "dot_general" and tuple(
        getattr(eqn.invars[0].aval, "shape", ())
    ) == (n, n)


def _is_gossip_stack(eqn, n: int) -> bool:
    """The ``concatenate`` that stacks a leaf's n mixed rows back along
    the agent axis: the dense engine's leafwise round, once per leaf."""
    return (
        eqn.primitive.name == "concatenate"
        and eqn.params["dimension"] == 0
        and [v.aval.shape[0] for v in eqn.invars] == [1] * n
    )


def _count_weighted_gossip_rounds(
    jaxpr, n: int, is_round=_is_gossip_gemm, *, mult: int = 1
) -> int:
    """Executed-count of per-leaf gossip rounds (``is_round`` equations),
    descending into sub-jaxprs with scan counts multiplied by their trip
    length."""
    total = 0
    for eqn in jaxpr.eqns:
        if is_round(eqn, n):
            total += mult
        inner_mult = mult
        if eqn.primitive.name == "scan":
            inner_mult = mult * int(eqn.params.get("length", 1))
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else [val]
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += _count_weighted_gossip_rounds(
                        inner, n, is_round, mult=inner_mult
                    )
                elif hasattr(v, "eqns"):
                    total += _count_weighted_gossip_rounds(
                        v, n, is_round, mult=inner_mult
                    )
    return total


def test_superstep_has_exactly_k_gossip_round_bodies():
    """The superstep fusion proof (dense route): with the round count
    now a TRACED operand (mix_times_program's fori_loop — the schedule
    lift), a K=3 superstep program carries exactly K x leaf_count
    per-leaf gossip rounds — the epoch scan's mix branch traces ONE
    round per leaf inside the round loop body (trip count is data, not
    unroll) and the scan runs K times.  Zero would mean fusion HOISTED
    gossip out of the epoch loop (mixing once for K epochs); more would
    mean the round body was duplicated (e.g. a branch re-specializing
    per round count); zero outside the scan means nothing leaked to a
    per-superstep position.  The dense engine's round is the leafwise
    one (found by the stack of its n rows, with no contraction against
    the mixing matrix), the per-leaf oracle's (fused=False) one GEMM per
    leaf and no stack — which of the two is engaged inside the superstep
    is part of the pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_learning_tpu.training.trainer import GossipTrainer

    n, k, mix_times = 3, 3, 2
    rng = np.random.default_rng(0)
    train = {
        i: (
            rng.normal(size=(32, 6)).astype(np.float32),
            rng.integers(0, 2, size=(32,)).astype(np.int32),
        )
        for i in range(n)
    }

    def trace(fused):
        tr = GossipTrainer(
            node_names=list(range(n)),
            model="mlp",
            model_kwargs={"hidden_dim": 8, "output_dim": 2},
            weights=np.full((n, n), 1.0 / n),
            train_data=train,
            batch_size=8,
            epoch_len=2,
            mix_times=mix_times,
            dropout=False,
            fused_consensus=fused,
            superstep=k,
        )
        tr.initialize_nodes()
        idx = tr._superstep_indices(0, k)
        modes = jnp.asarray(
            [tr._epoch_mode(j) for j in range(k)], dtype=jnp.int32
        )
        fn = tr._make_superstep_fn(k)
        jx = jax.make_jaxpr(fn)(
            tr.state, tr._superstep_carry(), tr._Xs, tr._ys, idx, modes,
            tr._superstep_sched(0, k),
        )
        leaves = len(jax.tree.leaves(tr.state[0]))
        return jx, leaves

    fused_jx, leaves = trace(fused=True)
    perleaf_jx, leaves = trace(fused=False)
    assert leaves > 1
    # (the step stacks a few per-node statistics of its own: the
    # per-leaf program, which mixes by GEMM, counts those alone)
    other_stacks = _count_weighted_gossip_rounds(
        perleaf_jx.jaxpr, n, _is_gossip_stack
    )
    assert _count_weighted_gossip_rounds(
        fused_jx.jaxpr, n, _is_gossip_stack
    ) == k * leaves + other_stacks
    assert _count_weighted_gossip_rounds(fused_jx.jaxpr, n) == 0
    # Top-level (outside every scan): nothing hoisted.
    assert not any(
        _is_gossip_stack(eqn, n) or _is_gossip_gemm(eqn, n)
        for eqn in fused_jx.jaxpr.eqns
    )
    assert _count_weighted_gossip_rounds(perleaf_jx.jaxpr, n) == k * leaves
