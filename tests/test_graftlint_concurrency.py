"""graftlint concurrency stage (ISSUE 10): the async rules fire on
seeded fixtures, stay quiet on sanctioned patterns, and the REAL comm
tree passes with only reasoned suppressions.

Layers (the ``tests/test_graftlint.py`` pattern):

* fixture snippets proving each rule fires (a lint whose rules silently
  stop firing is worse than no lint);
* the allowlists/disambiguations (``create_task`` wrapping, awaited
  calls, ambiguous names, nested sync defs, unregistered files);
* suppression-comment edge cases: disable-above attached across a
  decorator chain, multiple rules in one comment, the mandatory reason
  on all three concurrency rules;
* the shipped ``comm/`` tree: zero unsuppressed findings, and the two
  real cross-group mutations in ``async_runtime.py`` carry reasons.
"""

import os
import textwrap

from tools.graftlint import RULES, lint_file
from tools.graftlint.core import REPO_ROOT, Finding, Rule, register

_CONC_RULES = (
    "blocking-in-async",
    "unawaited-coroutine",
    "task-shared-mutation",
)

_RUNTIME_RELNAME = "distributed_learning_tpu/comm/async_runtime.py"


def _lint(tmp_path, code, relname="snippet.py", rules=None):
    p = tmp_path / relname
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    rule_map = None if rules is None else {r: RULES[r] for r in rules}
    return lint_file(str(p), rules=rule_map, repo_root=str(tmp_path))


def _rules_of(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------- #
# blocking-in-async                                                     #
# --------------------------------------------------------------------- #
def test_blocking_fires_on_each_blocking_class(tmp_path):
    code = """
    import time, socket, subprocess

    async def loop(x, p):
        time.sleep(0.1)
        open("state.bin")
        p.read_text()
        socket.create_connection(("h", 1))
        subprocess.run(["ls"])
        x.block_until_ready()
    """
    fs = _lint(tmp_path, code, rules=["blocking-in-async"])
    assert len(fs) == 6, fs
    assert all(f.rule == "blocking-in-async" for f in fs)
    assert "event loop" in fs[0].message


def test_blocking_sees_time_sleep_import_alias(tmp_path):
    code = """
    from time import sleep as snooze

    async def f():
        snooze(1)
    """
    fs = _lint(tmp_path, code, rules=["blocking-in-async"])
    assert len(fs) == 1 and "time.sleep" in fs[0].message


def test_blocking_ignores_sync_functions_and_nested_sync_defs(tmp_path):
    code = """
    import time

    def cold():
        time.sleep(1)  # plain sync code: not this rule's business

    async def dispatch():
        def executor_target():
            time.sleep(1)  # runs off-loop via run_in_executor
        return executor_target
    """
    assert _lint(tmp_path, code, rules=["blocking-in-async"]) == []


def test_blocking_covers_registered_hot_coroutines(tmp_path):
    """The extra_hot_coroutines table: sync dispatch-loop functions of
    async_runtime.py are held to the async discipline; identical code in
    an unregistered file stays cold."""
    code = """
    import time

    class AsyncGossipRunner:
        def _mix_plain(self, y):
            time.sleep(0.01)
            return y
    """
    fs = _lint(
        tmp_path, code, relname=_RUNTIME_RELNAME,
        rules=["blocking-in-async"],
    )
    assert len(fs) == 1 and "hot coroutine _mix_plain" in fs[0].message
    assert _lint(tmp_path, code, rules=["blocking-in-async"]) == []


# --------------------------------------------------------------------- #
# unawaited-coroutine                                                   #
# --------------------------------------------------------------------- #
def test_unawaited_fires_on_discarded_local_and_asyncio_coroutines(tmp_path):
    code = """
    import asyncio

    class R:
        async def push(self):
            pass

        async def round(self):
            self.push()
            asyncio.sleep(1)
    """
    fs = _lint(tmp_path, code, rules=["unawaited-coroutine"])
    assert len(fs) == 2, fs
    assert "never runs" in fs[0].message


def test_unawaited_allows_await_create_task_and_bindings(tmp_path):
    code = """
    import asyncio

    class R:
        async def push(self):
            pass

        async def round(self):
            await self.push()
            asyncio.create_task(self.push())
            asyncio.ensure_future(self.push())
            task = self.push()  # bound: the caller awaits it later
            await task
    """
    assert _lint(tmp_path, code, rules=["unawaited-coroutine"]) == []


def test_unawaited_skips_names_shadowed_by_sync_defs(tmp_path):
    """A name bound by BOTH an async def and a plain def (the nested
    'async def main' next to a module-level 'def main') is
    ambiguous and must not fire."""
    code = """
    import asyncio

    def run():
        async def main():
            pass
        return asyncio.run(main())

    def main():
        run()

    main()
    """
    assert _lint(tmp_path, code, rules=["unawaited-coroutine"]) == []


# --------------------------------------------------------------------- #
# task-shared-mutation                                                  #
# --------------------------------------------------------------------- #
def _runner_snippet(body):
    return f"""
    class AsyncGossipRunner:
        def __init__(self):
            self._poked = set()
            self._pub_value = None
            self._pub_round = 0
            self._round = 0
            self._inbox = {{}}

{textwrap.indent(textwrap.dedent(body), "        ")}
    """


def test_shared_mutation_fires_on_cross_group_writes(tmp_path):
    code = _runner_snippet(
        """
        def _handle_peer_msg(self, token, msg, src):
            self._poked.discard(token)

        async def _handle_master(self, msg):
            del self._inbox["x"]
            self._pub_value = None
        """
    )
    fs = _lint(
        tmp_path, code, relname=_RUNTIME_RELNAME,
        rules=["task-shared-mutation"],
    )
    assert len(fs) == 3, fs
    assert "task group 'dispatch'" in fs[0].message
    assert "FIFO/lock" in fs[0].message


def test_shared_mutation_allows_owner_group_and_init(tmp_path):
    code = _runner_snippet(
        """
        async def begin_round(self, value):
            self._round += 1
            self._pub_value, self._pub_round = value, self._round

        async def _poke(self, token):
            self._poked.add(token)
        """
    )
    assert _lint(
        tmp_path, code, relname=_RUNTIME_RELNAME,
        rules=["task-shared-mutation"],
    ) == []


def test_shared_mutation_only_in_annotated_files(tmp_path):
    code = _runner_snippet(
        """
        def _handle_peer_msg(self, token, msg, src):
            self._poked.discard(token)
        """
    )
    assert _lint(tmp_path, code, rules=["task-shared-mutation"]) == []


# --------------------------------------------------------------------- #
# suppression-comment edge cases                                        #
# --------------------------------------------------------------------- #
def test_suppress_multiple_rules_in_one_comment(tmp_path):
    code = """
    import asyncio, time

    class R:
        async def push(self):
            pass

        async def warmup(self):
            # graftlint: disable=blocking-in-async,unawaited-coroutine -- startup-only warm path: the loop has no other coroutines yet and the push is re-sent by the first round
            time.sleep(0.01); self.push()
    """
    assert _lint(tmp_path, code, rules=list(_CONC_RULES)) == []


def test_missing_mandatory_reason_on_each_concurrency_rule(tmp_path):
    code = """
    import time

    class R:
        async def push(self):
            pass

        async def a(self):
            time.sleep(1)  # graftlint: disable=blocking-in-async

        async def b(self):
            self.push()  # graftlint: disable=unawaited-coroutine
    """
    shared = _runner_snippet(
        """
        def _handle_peer_msg(self, token):
            self._poked.discard(token)  # graftlint: disable=task-shared-mutation
        """
    )
    fs = _lint(tmp_path, code, rules=list(_CONC_RULES))
    assert len(fs) == 2 and all("needs a reason" in f.message for f in fs)
    fs = _lint(
        tmp_path, shared, relname=_RUNTIME_RELNAME,
        rules=["task-shared-mutation"],
    )
    assert len(fs) == 1 and "needs a reason" in fs[0].message


def test_disable_above_line_attaches_across_decorator(tmp_path):
    """An own-line disable directly above a decorator chain covers the
    ``def`` line it decorates (where flagged nodes of a decorated
    function report), pinned with a def-line-firing probe rule."""

    @register
    class _ProbeDefRule(Rule):
        """Probe: flags every function named ``flagged_fn``."""

        name = "probe-flagged-def"

        def check(self, ctx):
            import ast

            return [
                Finding(self.name, ctx.relpath, n.lineno, "flagged def")
                for n in ast.walk(ctx.tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n.name == "flagged_fn"
            ]

    try:
        bare = """
        import functools

        @functools.lru_cache
        def flagged_fn():
            pass
        """
        fs = _lint(tmp_path, bare, rules=["probe-flagged-def"])
        assert _rules_of(fs) == ["probe-flagged-def"]
        suppressed = """
        import functools

        # graftlint: disable=probe-flagged-def -- probe fixture
        @functools.lru_cache
        @functools.wraps(flagged_fn)
        def flagged_fn():
            pass
        """
        assert _lint(tmp_path, suppressed, rules=["probe-flagged-def"]) == []
    finally:
        RULES.pop("probe-flagged-def", None)


# --------------------------------------------------------------------- #
# the real comm tree                                                    #
# --------------------------------------------------------------------- #
def test_real_comm_tree_passes_with_reasoned_suppressions_only():
    comm = os.path.join(REPO_ROOT, "distributed_learning_tpu", "comm")
    rule_map = {r: RULES[r] for r in _CONC_RULES}
    for fn in sorted(os.listdir(comm)):
        if not fn.endswith(".py"):
            continue
        fs = lint_file(os.path.join(comm, fn), rules=rule_map)
        assert fs == [], (fn, [str(f) for f in fs])


def test_real_async_runtime_suppressions_carry_discipline_reasons():
    """The two sanctioned cross-group mutations must stay REASONED: the
    suppression text names the serializing discipline, so a future edit
    cannot silently widen it into a bare disable."""
    path = os.path.join(
        REPO_ROOT, "distributed_learning_tpu", "comm", "async_runtime.py"
    )
    src = open(path).read()
    count = src.count("disable=task-shared-mutation --")
    assert count >= 2, (
        "async_runtime.py's cross-group mutations must carry reasoned "
        "task-shared-mutation suppressions"
    )
