"""The readers of the program's own names in a trace, on recorded traces.

``hlo_scopes`` (the ``jax.named_scope`` path of every HLO instruction,
from the trace's ``/host:metadata`` plane), ``host_spans`` (the host
plane, where the program's ``TraceAnnotation``s lie) and the two reducers
built on them, ``scope_time`` and ``idle_under_span``.  The numbers were
read off the traces by hand with ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import json
import os
from types import SimpleNamespace

import pytest

from chipbench import hlo_scopes, host_spans
from chipbench import trace as tr
from chipbench.reducers import idle_under_span, scope_time
from test_chipbench_harness import _run_cell, copy_with_toys  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "chipbench")
#: three ``mix_until`` calls of the toy consensus cell (11 rounds each) on
#: one v5e, recorded by PR 24: a program without spans or scopes
OLD = os.path.join(HERE, "trace", "toy-wrn.consensus.xplane.pb")
OLD_MODULE = "jit_wrapped(11514002476643753601)"


def _ctx(path, **window):
    return SimpleNamespace(trace=tr.load(path), window=window, xplane=path)


def _args(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json"),
              encoding="utf-8") as fh:
        return json.load(fh)["args"]


def test_hlo_scopes_reads_the_op_names_out_of_the_metadata_plane():
    scopes = hlo_scopes.load(OLD)
    assert len(scopes) == 178
    assert {module for module, _ in scopes} == {
        OLD_MODULE, "jit_convert_element_type(15388027131515875373)"}
    assert scopes[OLD_MODULE, "reduce_sum.19"] == (
        "jit(wrapped)/while/body/reduce_sum")
    # a fusion carries its root's name
    assert scopes[OLD_MODULE, "fusion.12"] == (
        "jit(wrapped)/while/body/dot_general")
    # an instruction the compiler made itself has none
    assert (OLD_MODULE, "copy.52") not in scopes


def test_scope_time_sums_self_time_by_op_name():
    ctx = _ctx(OLD, calls=3, rounds=33)
    # inside the loop: %fusion.12 272,459 ns, %multiply_reduce_fusion.3
    # 34,077, %sqrt_reduce_fusion.3 11,299, %broadcast_multiply_fusion.3
    # 1,819 (33 events each); the %while itself is "jit(wrapped)/while"
    want = 1e-6 * (272459 + 34077 + 11299 + 1819) / 33
    got = scope_time.reduce(ctx, module="^jit_wrapped", scope="while/body",
                            per="rounds")
    assert got == pytest.approx(want)
    # the %while's own time is not its body's: 390,016 ns in 3 events less
    # its children (the four above and %copy.52, 13,517: no op_name)
    whole = scope_time.reduce(ctx, module="^jit_wrapped", scope="while",
                              per="rounds")
    assert whole == pytest.approx(want + 1e-6 * (390016 - 319654 - 13517) / 33)
    assert scope_time.reduce(ctx, module="^jit_wrapped", scope="while",
                             exclude="body", per="rounds") == pytest.approx(
        whole - want)
    # a program that names no such scope, a module that did not run
    assert scope_time.reduce(ctx, module="^jit_wrapped",
                             scope=r"consensus\.round", per="rounds") is None
    assert scope_time.reduce(ctx, module="^jit_epoch_fn", scope="while",
                             per="rounds") is None


def test_idle_under_span_lays_host_spans_over_device_gaps():
    ctx = _ctx(OLD, calls=3)
    spans = host_spans.of(ctx)
    assert spans == sorted(spans, key=lambda s: (s.start_ns, -s.end_ns))
    calls = [s for s in spans if s.name == "PjitFunction(wrapped)"]
    assert len(calls) == 6 and {s.thread for s in calls} == {"python"}
    # host and device share a timebase: the first call's launch starts on
    # the device (48,760,028) inside the host's call (46,210,745 .. 48,919,194)
    launch = ctx.trace.line(tr.MODULES)[0][3]
    assert launch.name == OLD_MODULE
    assert calls[0].start_ns < launch.start_ns < calls[0].end_ns
    # the device's two long gaps, 48,933,649..55,324,977 and
    # 55,497,528..61,720,257, under the union of the calls' spans:
    # 2,528,193 + 37,095 + 2,369,004 ns, and 448 ns of sub-microsecond
    # gaps between operations inside the first call
    want = 1e-6 * (2528193 + 37095 + 2369004 + 448) / 3
    got = idle_under_span.reduce(
        ctx, span=r"^PjitFunction\(wrapped\)$", per="calls")
    assert got == pytest.approx(want)
    # the program recorded here has no spans of its own: nothing to read
    assert idle_under_span.reduce(ctx, **_args("idle_in_program_ms.cons")) is None


def test_label_gaps_names_the_innermost_span_or_the_caller():
    S = host_spans.Span
    spans = [
        S("trainer.epoch", 100, 900, "python"),
        S("trainer.dispatch", 150, 300, "python"),
        S("trainer.flush", 300, 700, "python"),
        S("trainer.epoch", 1000, 1800, "python"),
        S("trainer.indices", 1010, 1100, "python"),
    ]
    gaps = [
        (0, 90),       # before anything of the program's
        (160, 290),    # inside dispatch, inside epoch: the innermost
        (280, 420),    # 20 of dispatch, 120 of flush
        (720, 880),    # the epoch's own time, after its last child
        (850, 1050),   # 50 epoch, 100 nobody, 10 epoch, 40 indices
        (880, 1100),   # 20 epoch, 100 nobody, 10 epoch, 90 indices: a tie
                       # on neither; nobody has most
    ]
    assert host_spans.label_gaps(gaps, spans) == [
        "caller", "trainer.dispatch", "trainer.flush", "trainer.epoch",
        "caller", "caller",
    ]
    assert host_spans.owners_ns(850, 1050, spans) == {
        "trainer.epoch": 60.0, "caller": 100.0, "trainer.indices": 40.0}
    assert host_spans.label_gaps([(5, 6)], []) == ["caller"]


def test_the_readers_find_this_runs_trace_by_themselves(tmp_path, monkeypatch):
    """Without ``ctx.xplane`` (``run.py`` gives none) the newest trace under
    the checkout's ``.chipbench_out/trace/*/`` is this run's."""
    import shutil

    import chipbench

    root = tmp_path / "checkout"
    (root / "chipbench").mkdir(parents=True)
    monkeypatch.setattr(chipbench, "__file__",
                        str(root / "chipbench" / "__init__.py"))
    ctx = SimpleNamespace(trace=None, window={})
    assert hlo_scopes.xplane_of(ctx) is None
    assert hlo_scopes.of(ctx) == {} and host_spans.of(ctx) == []
    for i, cell in enumerate(("older", "newer")):
        where = root / ".chipbench_out" / "trace" / cell / "plugins" / "profile" / "t"
        where.mkdir(parents=True)
        shutil.copy(OLD, where / "host.xplane.pb")
        os.utime(where / "host.xplane.pb", (1000 + i, 1000 + i))
    ctx = SimpleNamespace(trace=None, window={})
    assert hlo_scopes.xplane_of(ctx) == str(
        root / ".chipbench_out" / "trace" / "newer" / "plugins" / "profile"
        / "t" / "host.xplane.pb")
    assert len(hlo_scopes.of(ctx)) == 178
    assert hlo_scopes.of(ctx) is hlo_scopes.of(ctx)  # read once per run


# ---------------------------------------------------------------------- #
# a trace of the program as it is now: spans and scopes                  #
# ---------------------------------------------------------------------- #
#: three epochs of the toy cell ``toy-ann.names`` (the 4-layer MLP, 4 agents,
#: 2 steps an epoch, one dense mix) recorded on one v5e by this harness
#: (PR 26), byte for byte as the chip wrote it, gzipped to keep it small
NEW = os.path.join(HERE, "trace", "toy-ann.names.xplane.pb.gz")


@pytest.fixture(scope="module")
def new_ctx(tmp_path_factory):
    import gzip

    path = str(tmp_path_factory.mktemp("trace") / "toy-ann.names.xplane.pb")
    with gzip.open(NEW, "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())
    return _ctx(path, epochs=3, steps=6, gossips=3)


#: what the run that recorded the trace printed for the cell's new metrics
PRINTED = {
    "idle_in_program_ms.img": 4.480458666666666,
    "idle_dispatch_ms.img": 1.05491,
    "fwd_ms.img": 0.002289666666666667,
    "bwd_ms.img": 0.002496,
    "opt_ms.img": 0.0008545000000000001,
}


@pytest.mark.parametrize("metric", sorted(PRINTED))
def test_a_metric_file_reads_the_recorded_trace_as_the_chip_run_did(
        new_ctx, metric):
    import importlib

    with open(os.path.join(BENCH, "metrics", metric + ".json"),
              encoding="utf-8") as fh:
        m = json.load(fh)
    reducer = importlib.import_module("chipbench.reducers." + m["reducer"])
    assert reducer.reduce(new_ctx, **m["args"]) == pytest.approx(
        PRINTED[metric], rel=1e-9)


def test_both_reducers_end_to_end_on_the_recorded_trace(new_ctx):
    # by hand: the operations under ``opt`` are the four momentum updates,
    # %multiply_add_fusion.33/.35/.37 (1,276 ns in 6 events each) and .39
    # (1,299), all named ".../vmap(opt)/add"
    assert scope_time.reduce(new_ctx, **_args("opt_ms.img")) == pytest.approx(
        1e-6 * (3 * 1276 + 1299) / 6)
    # forward and backward share no operation, and with the optimizer stay
    # inside the epoch program's own time (56,487 + 56,390 + ... ns)
    parts = [scope_time.reduce(new_ctx, **_args(m))
             for m in ("fwd_ms.img", "bwd_ms.img", "opt_ms.img")]
    launches = [e for e in new_ctx.trace.line(tr.MODULES)[0]
                if e.name.startswith("jit_epoch_fn")]
    assert len(launches) == 3
    assert 0 < sum(parts) < 1e-6 * sum(e.dur_ns for e in launches) / 6
    both = scope_time.reduce(new_ctx, module="^jit_epoch_fn", scope="fwd_bwd",
                             per="steps")
    assert both == pytest.approx(parts[0] + parts[1])
    # the mix program's scopes: one round a gossip, its pack and unpack
    mix = {
        m: scope_time.reduce(new_ctx, **{**_args(m), "per": "gossips"})
        for m in ("mix_round_ms.cons", "mix_residual_ms.cons",
                  "mix_pack_ms.cons")
    }
    assert mix["mix_round_ms.cons"] > 0 and mix["mix_pack_ms.cons"] > 0
    assert mix["mix_residual_ms.cons"] is None  # engine.mix computes none
    # the deviation read-out is another module, and all residual
    assert scope_time.reduce(new_ctx, module="^jit__lambda",
                             scope=r"consensus\.residual", per="epochs") > 0

    # by hand: the device's gaps under the six trainer.indices and
    # trainer.dispatch spans, 3,164,730 ns over 3 epochs
    assert idle_under_span.reduce(
        new_ctx, **_args("idle_dispatch_ms.img")) == pytest.approx(
        1e-6 * 3164730 / 3)
    # the engine's spans lie inside the trainer's, so they add nothing
    assert idle_under_span.reduce(
        new_ctx, span=r"^(trainer|consensus)\.", per="epochs"
    ) == pytest.approx(PRINTED["idle_in_program_ms.img"])
    assert 0 < idle_under_span.reduce(
        new_ctx, span=r"^consensus\.layout$", per="epochs"
    ) < idle_under_span.reduce(new_ctx, span=r"^consensus\.", per="epochs")


def test_the_recorded_spans_nest_and_label_the_gaps(new_ctx):
    spans = [s for s in host_spans.of(new_ctx)
             if s.name.startswith(("trainer.", "consensus."))]
    assert {s.thread for s in spans} == {"python"}
    epochs = [s for s in spans if s.name == "trainer.epoch"]
    assert len(epochs) == 3
    for epoch in epochs:
        inside = [s.name for s in spans if s is not epoch
                  and epoch.start_ns <= s.start_ns and s.end_ns <= epoch.end_ns]
        assert inside == [
            "trainer.indices", "trainer.dispatch", "trainer.mix",
            "consensus.mix", "consensus.layout", "consensus.operands",
            "consensus.dispatch", "trainer.flush", "trainer.stats",
            "trainer.deviation",
        ]
    # the five longest gaps of this (launch-bound) toy, by hand from the
    # spans' times: 42,962,633..44,906,734 lies over the first epoch's
    # indices (562 us), dispatch (796) and the start of its mix
    ops = new_ctx.trace.line(tr.OPS)[0]
    gaps = sorted(idle_under_span.idle_gaps(ops), key=lambda g: g[0] - g[1])[:5]
    assert gaps[0] == (42962633.0, 44906734.0)
    assert host_spans.label_gaps(gaps, spans) == [
        "trainer.dispatch", "trainer.deviation", "trainer.flush",
        "consensus.dispatch", "trainer.indices",
    ]
    owners = host_spans.owners_ns(*gaps[0], spans)
    assert owners["trainer.indices"] == pytest.approx(562350.0)
    assert owners["trainer.dispatch"] == pytest.approx(795570.0)
    assert sum(owners.values()) == pytest.approx(gaps[0][1] - gaps[0][0])


def test_the_toy_cell_runs_traced_on_the_cpu_and_leaves_the_new_metrics_out(
        copy_with_toys, tmp_path):
    """A trace without device planes (the CPU's) has nothing for the new
    readers: they return nothing, raise nothing, and the line leaves their
    metrics out, as on a program that lacks the spans and scopes."""
    result = _run_cell(copy_with_toys, "toy-ann.names", True, tmp_path)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"compile_s", "toy_units"}
