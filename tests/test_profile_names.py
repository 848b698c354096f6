"""The program's own names in a profile (docs/observability.md, "Reading a
profile"): host spans as ``jax.profiler.TraceAnnotation``s on the
profiler's clock, ``jax.named_scope``s in the device programs' op names,
names on the Pallas kernels.  A plain run shows them: no option, no
``obs=True``.  The chip benchmark's reducers read these names
(``chipbench/metrics/*.json``); the last test holds the two together.
"""

from __future__ import annotations

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_obs import _tiny_trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]+)"', text)


# ---------------------------------------------------------------------- #
# host spans                                                             #
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The program's spans on the host plane of one CPU profile: an epoch,
    a two-epoch superstep and a ``mix_until`` call of a tiny trainer with
    ``obs`` left at its default."""
    from jax.profiler import ProfileData

    trainer = _tiny_trainer(None)
    trainer.train_epochs(1)  # compile outside the profile
    trainer.train_epochs(2)
    logdir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        trainer.train_epochs(1)
        trainer.train_epochs(2)
        jax.block_until_ready(
            trainer.engine.mix_until(trainer.state[0], eps=1e-6))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("trainer.", "consensus.")):
                    spans.append({
                        "name": e.name, "start": e.start_ns,
                        "end": e.start_ns + e.duration_ns,
                        "thread": line.name, **dict(e.stats),
                    })
    return sorted(spans, key=lambda s: (s["start"], -s["end"]))


def _children(spans, parent):
    return [
        s for s in spans
        if s is not parent and s["thread"] == parent["thread"]
        and parent["start"] <= s["start"] and s["end"] <= parent["end"]
    ]


def test_a_plain_epoch_shows_its_spans_nested_under_trainer_epoch(profiled):
    (epoch,) = [s for s in profiled if s["name"] == "trainer.epoch"]
    inside = _children(profiled, epoch)
    steps = [s["name"] for s in inside if s["name"].startswith("trainer.")]
    # each names one thing the host does, in the order it does them
    assert steps == [
        "trainer.indices", "trainer.dispatch", "trainer.mix",
        "trainer.flush", "trainer.stats", "trainer.deviation",
    ]
    # the spans of one epoch share its identifier
    assert {s["epoch"] for s in inside if "epoch" in s} == {epoch["epoch"]}
    # siblings do not overlap: none wraps another's work
    own = [s for s in inside if s["name"] in steps]
    assert all(a["end"] <= b["start"] for a, b in zip(own, own[1:]))
    # the gossip the epoch enqueues is the engine's call, inside trainer.mix
    (mix,) = [s for s in inside if s["name"] == "trainer.mix"]
    assert "consensus.mix_until" in {s["name"] for s in _children(profiled, mix)}


def test_a_superstep_shows_its_spans_nested_under_trainer_superstep(profiled):
    (chunk,) = [s for s in profiled if s["name"] == "trainer.superstep"]
    assert chunk["k"] == 2
    inside = _children(profiled, chunk)
    assert [s["name"] for s in inside] == [
        "trainer.indices", "trainer.dispatch", "trainer.flush",
        "trainer.stats",
    ]
    assert {s["epoch"] for s in inside} == {chunk["epoch"]}


def test_an_engine_call_shows_layout_operands_and_dispatch(profiled):
    calls = [s for s in profiled if s["name"] == "consensus.mix_until"]
    assert len(calls) == 2  # the epoch's gossip, then the direct call
    assert calls[0]["call"] < calls[1]["call"]
    for call in calls:
        inside = _children(profiled, call)
        assert [s["name"] for s in inside] == [
            "consensus.layout", "consensus.operands", "consensus.dispatch",
        ]
        assert {s["call"] for s in inside} == {call["call"]}
        # the span opens at the top of the method: the layout accounting
        # is inside it, not before it
        assert call["start"] <= inside[0]["start"]


def test_spans_add_no_launch():
    """The re-cut spans changed what the host names, not what it launches:
    the trainer still counts three dispatches an epoch (epoch program,
    gossip, deviation read-out)."""
    from distributed_learning_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    trainer = _tiny_trainer(reg)
    trainer.train_epochs(1)
    assert reg.run_report()["counters"]["trainer.dispatches"] == 3


def test_obs_imports_without_jax_profiler_until_the_first_span():
    import subprocess
    import sys

    code = (
        "import sys; import distributed_learning_tpu.obs.spans as s; "
        "assert 'jax.profiler' not in sys.modules and 'jax' not in sys.modules; "
        "t = s.SpanTracer(); "
        "cm = t.span('x', call=1); cm.__enter__(); cm.__exit__(None, None, None); "
        "assert 'jax.profiler' in sys.modules; "
        "assert t.aggregate()['x']['count'] == 1"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


# ---------------------------------------------------------------------- #
# scopes in the device programs                                          #
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def epoch_op_names():
    trainer = _tiny_trainer(None).initialize_nodes()
    compiled = trainer._jit_epoch.lower(
        trainer._state, trainer._Xs, trainer._ys, trainer._epoch_indices(0)
    ).compile()
    return _op_names(compiled.as_text())


@pytest.mark.parametrize("scope, pattern", [
    ("gather", r"/gather/"),
    ("fwd_bwd, forward", r"^(?!.*transpose\().*jvp\(fwd_bwd\)"),
    ("fwd_bwd, backward", r"transpose\(jvp\(fwd_bwd\)\)"),
    ("carry", r"\bcarry\b"),
    ("opt", r"\bopt\b"),
])
def test_the_compiled_epoch_program_names_every_scope(epoch_op_names, scope,
                                                      pattern):
    assert any(re.search(pattern, n) for n in epoch_op_names), scope


def test_the_compiled_mix_until_program_names_every_scope():
    trainer = _tiny_trainer(None).initialize_nodes()
    fn = trainer.engine._get_jitted("mix_until")
    text = fn.lower(
        trainer._state[0], jnp.float32(1e-5), jnp.int32(0), jnp.int32(100)
    ).compile().as_text()
    assert "jit_wrapped" in text  # the module's name is the benchmark's key
    names = _op_names(text)
    for scope in ("consensus.round", "consensus.residual"):
        assert any(scope in n for n in names), scope
    # a dense program never packs: those scopes are the sharded programs'
    for scope in ("consensus.pack", "consensus.unpack"):
        assert not any(scope in n for n in names), scope
    # the residual is named inside the loop and for the first check before it
    assert any(re.search(r"while/body/consensus\.residual", n) for n in names)
    assert any(re.search(r"while/body/consensus\.round", n) for n in names)


def test_the_sharded_round_and_residual_carry_the_same_names():
    from distributed_learning_tpu.parallel.consensus import (
        ConsensusEngine,
        make_agent_mesh,
    )
    from distributed_learning_tpu.parallel.topology import Topology

    engine = ConsensusEngine(
        Topology.ring(4).metropolis_weights(), mesh=make_agent_mesh(4))
    x = engine.shard({"w": jnp.arange(32.0).reshape(4, 8),
                      "b": jnp.arange(8.0).reshape(4, 2)})
    text = jax.jit(engine.mix_until_program(eps=1e-5)).lower(x).as_text(
        debug_info=True)
    assert "collective_permute" in text  # the ppermute round
    for scope in ("consensus.pack", "consensus.round", "consensus.residual",
                  "consensus.unpack"):
        assert scope in text, scope


def test_augment_and_the_supersteps_mix_and_compress_are_named():
    from distributed_learning_tpu.training.trainer import GossipTrainer

    rng = np.random.default_rng(0)
    images = {
        i: (rng.standard_normal((32, 32, 32, 3)).astype(np.float32),
            rng.integers(0, 10, 32).astype(np.int32))
        for i in range(2)
    }
    trainer = GossipTrainer(
        node_names=[0, 1], model="lenet", model_args=[10],
        train_data=images, batch_size=16, epoch=1, dropout=False,
        augment=True,
    ).initialize_nodes()
    text = trainer._jit_epoch.lower(
        trainer._state, trainer._Xs, trainer._ys, trainer._epoch_indices(0)
    ).as_text(debug_info=True)
    assert re.search(r"\baugment\b", text)

    vectors = {
        i: (rng.standard_normal((64, 8)).astype(np.float32),
            rng.integers(0, 3, 64).astype(np.int32))
        for i in range(4)
    }
    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    choco = GossipTrainer(
        node_names=list(range(4)),
        model=ANNModel(hidden_dim=8, output_dim=3), error="cross_entropy",
        weights=Topology.ring(4), train_data=vectors, batch_size=16,
        epoch=4, dropout=False, mix_times=2, compression="topk:0.3",
        compression_gamma=0.3,
    ).initialize_nodes()
    text = choco._build_superstep(2).lower(
        choco._state, choco._superstep_carry(), choco._Xs, choco._ys,
        choco._superstep_indices(0, 2), jnp.asarray([1, 1], jnp.int32),
        choco._superstep_sched(0, 2),
    ).as_text(debug_info=True)
    assert re.search(r'"mix/cond', text)  # the switch over the epoch's mode
    assert re.search(r'"mix/cond/branch_1_fun/compress/', text)
    # the superstep embeds the epoch body, so it inherits its scopes
    assert "jvp(fwd_bwd)" in text and re.search(r"\bopt\b", text)


def test_the_flash_kernels_are_named():
    from distributed_learning_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 256, 2, 64), jnp.float32)

    def loss(q):
        out = flash_attention(q, q, q, causal=True, interpret=True)
        return out.astype(jnp.float32).sum()

    # (1, 256, 2, 64): two heads of 64 fill a lane block, K/V fit VMEM:
    # the resident pair; one head more (192 columns) and it streams.
    names = lambda x: set(re.findall(
        r"name=(flash_(?:fwd|bwd)\w*)",
        str(jax.make_jaxpr(jax.grad(loss))(x))))
    assert names(q) == {"flash_fwd_resident", "flash_bwd_dq_dkv_resident"}
    assert names(jnp.ones((1, 256, 3, 64), jnp.float32)) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    flash_ms = re.compile(_metric("flash_ms.tok")["args"]["scope"])
    assert flash_ms.pattern == _metric("flash_ms.hyb")["args"]["scope"]
    assert all(flash_ms.search(n) for n in names(q))


# ---------------------------------------------------------------------- #
# the benchmark's patterns against the names the program writes          #
# ---------------------------------------------------------------------- #
def _metric(name):
    path = os.path.join(REPO, "chipbench", "metrics", name + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", ["fwd_ms.img", "bwd_ms.img", "opt_ms.img"])
def test_a_scope_metric_finds_its_scope_in_the_epoch_program(
        epoch_op_names, name):
    args = _metric(name)["args"]
    assert re.search(args["module"], "jit_epoch_fn(123)")
    hit = [
        n for n in epoch_op_names
        if re.search(args["scope"], n)
        and not (args.get("exclude") and re.search(args["exclude"], n))
    ]
    assert hit, name
    others = {"fwd_ms.img": "transpose(", "bwd_ms.img": None,
              "opt_ms.img": "fwd_bwd"}[name]
    if others:  # the three scopes share no operation
        assert not any(others in n for n in hit)


@pytest.mark.parametrize("name, spans", [
    ("idle_in_program_ms.img", {"trainer.epoch", "trainer.flush"}),
    ("idle_dispatch_ms.img", {"trainer.indices", "trainer.dispatch"}),
    ("idle_in_program_ms.cons", {"consensus.mix_until", "consensus.layout"}),
    ("idle_layout_ms.cons", {"consensus.layout"}),
    ("idle_dispatch_ms.cons", {"consensus.operands", "consensus.dispatch"}),
])
def test_a_span_metric_finds_its_spans_in_a_profile(profiled, name, spans):
    rx = re.compile(_metric(name)["args"]["span"])
    seen = {s["name"] for s in profiled if rx.search(s["name"])}
    assert spans <= seen, name
    if name.startswith("idle_dispatch") or name.startswith("idle_layout"):
        assert seen == spans  # and nothing else
