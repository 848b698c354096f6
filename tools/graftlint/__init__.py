"""graftlint: static analysis enforcing this repo's SPMD, wire-format,
concurrency, and dependency invariants.

Seven stages (full reference: ``docs/static_analysis.md``):

* AST (``rules.py`` + ``concurrency.py``): pluggable source rules over
  ``distributed_learning_tpu/``, ``examples/`` and
  ``chip_smoke.py``, with ``# graftlint: disable=<rule>[ -- reason]`` inline
  suppressions.  Imports no jax — safe and fast anywhere.
* Wire contract (``wire_contract.py``): the Python<->C++ drift checker
  for the native wire engine's hand-maintained constants, pinned next
  to the collective inventories in ``audit_expected.json``.  Also
  jax-free (regex + ``ast``, no compiler).
* jaxpr/HLO audit (``jaxpr_audit.py``, ``--audit``): traces the
  registered SPMD entry points on the 8-virtual-device CPU mesh and
  pins their collective inventories (+ cost columns).
* Dataflow verify (``jaxpr_verify.py`` + ``claims.py``, ``--audit``):
  branch-uniform collective sequences, ordered scan/while pins,
  suppression-claim verification against the traced program, vma
  discipline, and donation aliasing; the suppression inventory itself
  is jax-free (``--suppressions``).
* Protocol model (``proto_extract.py`` + ``proto_spec.py`` +
  ``proto_model.py``, ``--proto`` or under ``--audit``): extracts the
  per-role send/handle message sets from the comm modules, cross-checks
  them against ``protocol.py``'s registry, pins the role model in
  ``audit_expected.json``, and bounded-model-checks the protocol specs
  for safety + liveness (with the PR 8 bugs re-seeded as mutations the
  checker must find).  Jax-free.
* Schedule exploration (``schedsim.py`` + ``sched_corpus.py``,
  ``--sched`` or on full runs): drives the real comm control plane on
  a controlled event loop (virtual clock, seeded/exhaustive schedule
  policies), verifies every task-shared-mutation suppression's
  serialization claim on every explored schedule, detects deadlocks
  and lost wakeups with replayable schedule traces, checks same-seed
  trace determinism, pins the hot coroutines' await-point model in
  ``audit_expected.json``, and self-tests its power on seeded race
  mutations it must keep catching.  Jax-free.
* Sanitizer replay (``native_san.py``, ``--native``): rebuilds the
  native libs under ASan/UBSan into a separate cache and replays the
  wire fuzz corpus + oracle matrix; any report fails lint.

CLI: ``python -m tools.graftlint`` (see ``--help``); pre-commit gate:
``tools/precommit.sh``; tier-1 coverage: ``tests/test_graftlint.py``,
``tests/test_graftlint_concurrency.py``, ``tests/test_wire_contract.py``,
``tests/test_native_san.py``, ``tests/test_jaxpr_verify.py``,
``tests/test_proto_model.py``, ``tests/test_schedsim.py``.
"""

from tools.graftlint.core import (  # noqa: F401
    DEFAULT_ROOTS,
    REPO_ROOT,
    RULES,
    FileContext,
    Finding,
    Rule,
    Suppressions,
    iter_python_files,
    lint_file,
    lint_paths,
    register,
)
import tools.graftlint.rules  # noqa: F401  (registers the rule set)
import tools.graftlint.concurrency  # noqa: F401  (async-concurrency rules)
import tools.graftlint.jaxpr_verify  # noqa: F401  (dataflow-stage rules;
#   the module import is jax-free — tracing stays behind --audit)
import tools.graftlint.proto_extract  # noqa: F401  (proto-stage rules)
import tools.graftlint.proto_model  # noqa: F401  (protocol-liveness rule)
import tools.graftlint.schedsim  # noqa: F401  (sched-stage rules; the
#   module import is jax-free — the corpus run stays behind --sched)
