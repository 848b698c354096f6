"""The chunked delta rule off the slow path (``ops/gated_delta.py``): the
chunk's unit-lower-triangular solve as block products (the XLA path), and
on a TPU the whole rule, each chunk's preparation, solve and step, as a
fused Pallas kernel pair (here under ``interpret=True``: the kernels'
arithmetic, not their speed).  Both are held to what they replaced; the
last tests guard what tracing and lowering them costs, and the lines the
chip benchmark's fault controls patch."""

from __future__ import annotations

import inspect

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.ops import gated_delta as gd
from distributed_learning_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------- #
# A: the solve as products                                               #
# ---------------------------------------------------------------------- #
def _system(C, strong, seed=0, lead=(3, 2), width=24):
    """``strict`` as the rule builds it (beta k_i . k_j times the decay
    between i and j, strictly lower) and a right-hand side.  ``strong``:
    beta near one, hardly any decay and neighbouring keys nearly parallel,
    so the solution's entries grow along the chunk."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    k = unit(jax.random.normal(ks[0], lead + (C, 32)))
    if strong:
        k = unit(k + 3.0 * jnp.roll(k, 1, axis=-2))
    beta = jax.nn.sigmoid(
        jax.random.normal(ks[1], lead + (C,)) + (4.0 if strong else 0.0))
    g = -(0.01 if strong else 1.0) * jax.nn.softplus(
        jax.random.normal(ks[2], lead + (C,)))
    gc = jnp.cumsum(g, -1)
    decay = jnp.exp(jnp.minimum(gc[..., :, None] - gc[..., None, :], 0.0))
    kk = jnp.einsum("...id,...jd->...ij", k * beta[..., None], k,
                    precision="highest")
    return (jnp.tril(kk * decay, -1),
            jax.random.normal(ks[3], lead + (C, width)))


def _library_solve(strict, rhs):
    return jax.scipy.linalg.solve_triangular(
        strict, rhs, lower=True, unit_diagonal=True)


@pytest.mark.parametrize("C", [16, 64, 24])  # 24: no power of two
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_product_solve_matches_the_triangular_solve(C, strong):
    strict, rhs = _system(C, strong)
    got, want = gd._unit_lower_solve(strict, rhs), _library_solve(strict, rhs)
    assert _rel(got, want) < 2e-6
    # and solves the system as well as the library's does
    resid = lambda x: _rel(x + jnp.matmul(strict, x, precision="highest"), rhs)
    assert resid(got) < max(2 * resid(want), 1e-6)


@pytest.mark.parametrize("C", [16, 64])
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_product_solve_gradients_match_the_triangular_solves(C, strong):
    strict, rhs = _system(C, strong, seed=1)
    grad = lambda solve: jax.grad(
        lambda s, r: jnp.sum(jnp.sin(solve(s, r))), argnums=(0, 1))(strict, rhs)
    (d_strict, d_rhs), (want_strict, want_rhs) = (
        grad(gd._unit_lower_solve), grad(_library_solve))
    # only the strictly lower part of ``strict`` is read by either
    assert _rel(jnp.tril(d_strict, -1), jnp.tril(want_strict, -1)) < 1e-5
    assert _rel(d_rhs, want_rhs) < 1e-5


# ---------------------------------------------------------------------- #
# B: the fused kernel pair, interpreted                                  #
# ---------------------------------------------------------------------- #
def _rule_inputs(t, seed=0, decay=0.5, B=1, H=2, Dk=128, Dv=128, lead=(),
                 Hk=None):
    """q, k at ``Hk`` key heads (default ``H``), v, g, beta at ``H``."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = lead + (B, t, H)
    keys = lead + (B, t, Hk or H)
    return (
        unit(jax.random.normal(ks[0], keys + (Dk,))) * Dk ** -0.5,
        unit(jax.random.normal(ks[1], keys + (Dk,))),
        jax.random.normal(ks[2], shape + (Dv,)),
        -decay * jax.nn.softplus(jax.random.normal(ks[3], shape)),
        jax.nn.sigmoid(jax.random.normal(ks[4], shape)),
    )


def _recurrence(q, k, v, g, beta):
    """The oracle at the rule's own heads: q, k repeated to v's."""
    r = v.shape[-2] // q.shape[-2]
    return gated_delta_recurrence(
        jnp.repeat(q, r, axis=-2), jnp.repeat(k, r, axis=-2), v, g, beta)


@pytest.fixture
def kernel_path(monkeypatch):
    """The rule as it runs on a TPU, its kernels interpreted: the backend
    test answers yes, and ``interpret`` (never on by default) is on."""
    compiled = gd._rule_kernels
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        gd, "_rule_kernels", lambda *a: compiled(*a[:-1], True))


def _scan_path(*args, **kw):
    assert not gd._on_tpu()
    return gated_delta_rule(*args, **kw)


_ALL = (0, 1, 2, 3, 4)
_loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))


# T a multiple of the chunk and not; a state that forgets within a chunk
# and one that carries over all of them; (B, key heads, value heads): a key
# head to each value head, one key head read by two, a grid step of two key
# heads' four value heads, and two sequences
@pytest.mark.parametrize("t, decay, B, Hk, H", [
    *((t, decay, 1, Hk, 2) for Hk in (2, 1) for decay in (2.0, 0.01)
      for t in (64, 40)),
    (64, 0.01, 1, 2, 4), (40, 0.01, 2, 1, 2),
])
def test_kernel_pair_matches_recurrence_and_scan(kernel_path, monkeypatch,
                                                 t, decay, B, Hk, H):
    args = _rule_inputs(t, decay=decay, B=B, Hk=Hk, H=H)
    rule = lambda *a: gated_delta_rule(*a, chunk=16, precision="highest")
    dgot = jax.grad(_loss(rule), _ALL)(*args)
    dwant = jax.grad(_loss(_recurrence), _ALL)(*args)
    out = rule(*args)
    np.testing.assert_allclose(out, _recurrence(*args), atol=2e-6)
    for g, w in zip(dgot, dwant):
        assert _rel(g, w) < 1e-5
    # the scan is the same algorithm: the two agree closer than either
    # does with the recurrence
    monkeypatch.undo()
    scan = lambda *a: _scan_path(*a, chunk=16, precision="highest")
    np.testing.assert_allclose(out, scan(*args), atol=2e-7)
    dscan = jax.grad(_loss(scan), _ALL)(*args)
    for g, w in zip(dgot, dscan):
        assert _rel(g, w) < 1e-6


def test_kernel_pair_under_vmap_and_remat(kernel_path):
    """As the trainer runs it: an agent axis vmapped over the whole
    forward-and-gradient, the rule inside a rematerialised flax block, a
    key head read by two value heads."""
    args = _rule_inputs(32, seed=2, lead=(2,), Hk=1)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, g, beta):
            scale = self.param("scale", nn.initializers.ones, (), jnp.float32)
            return scale * gated_delta_rule(
                q, k, v, g, beta, chunk=16, precision="highest")

    def grads(block, rule_args):
        params = block.init(jax.random.key(0), *rule_args)
        return jax.grad(
            lambda p, *a: jnp.sum(jnp.sin(block.apply(p, *a))),
            argnums=(1, 2, 3, 4, 5))(params, *rule_args)

    got = jax.vmap(lambda *a: grads(nn.remat(Block)(), a))(*args)
    want = jax.vmap(lambda *a: jax.grad(_loss(_recurrence), _ALL)(*a))(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and _rel(g, w) < 1e-5


def test_default_precision_rounds_the_products_operands_to_bf16(kernel_path):
    """``precision=None`` on a TPU is one bf16 pass with f32 accumulation:
    the kernels round their operands themselves, so interpreted on the
    CPU they differ from the f32 scan by bf16 rounding, and no more."""
    args = _rule_inputs(64, seed=3)
    got = gated_delta_rule(*args, chunk=16)
    want = gated_delta_recurrence(*args)
    assert 1e-4 < _rel(got, want) < 1e-2
    dgot = jax.grad(_loss(lambda *a: gated_delta_rule(*a, chunk=16)), _ALL)(*args)
    dwant = jax.grad(_loss(gated_delta_recurrence), _ALL)(*args)
    for g, w in zip(dgot, dwant):
        assert _rel(g, w) < 3e-2


@pytest.mark.parametrize("C, Dk, Dv, runs", [
    (64, 128, 128, True), (16, 256, 128, True),
    (64, 64, 128, False),   # a key head that does not fill the lanes
    (64, 128, 96, False),   # nor a value head
    (12, 128, 128, False),  # a chunk that does not fill the sublanes
])
def test_dispatch_by_shape_and_backend(monkeypatch, C, Dk, Dv, runs):
    assert not gd._scan_kernel_runs(C, Dk, Dv)  # never off a TPU
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    assert gd._scan_kernel_runs(C, Dk, Dv) is runs


def test_refused_shapes_take_the_scan(monkeypatch):
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    monkeypatch.setattr(gd, "_rule_kernels", lambda *a: pytest.fail(
        "the kernel pair was reached at a shape it refuses"))
    args = _rule_inputs(32, Dk=16, Dv=32)
    np.testing.assert_allclose(
        gated_delta_rule(*args, chunk=16), gated_delta_recurrence(*args),
        atol=2e-6)


# ---------------------------------------------------------------------- #
# what tracing and lowering cost: nothing may grow with T                #
# ---------------------------------------------------------------------- #
#: one chunk's arithmetic and its ref reads and writes, per kernel body
KERNEL_BODY_CEILING = 400
#: equations of the product-form solve (10 products at the chunk of 64)
SOLVE_CEILING = 100


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _walk(jaxpr):
    """Every equation, nested ones included (kernel bodies apart)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in _subjaxprs(eqn):
                yield from _walk(sub)


def _count(jaxpr):
    return sum(1 + sum(_count(s) for s in _subjaxprs(e)) for e in jaxpr.eqns)


def _kernel_stats(rule, t, monkeypatch):
    """``{kernel name: [body equation counts]}`` and the total number of
    equations outside kernels, of the rule's forward-and-gradient traced
    as on a TPU at the cell's head shape (nothing runs)."""
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, t, 2, 128), (1, t, 2, 128), (1, t, 2, 128), (1, t, 2), (1, t, 2))]
    jaxpr = jax.make_jaxpr(jax.grad(_loss(rule), _ALL))(*shapes).jaxpr
    kernels: dict = {}
    outside = 0
    for eqn in _walk(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            kernels.setdefault(name, []).append(_count(eqn.params["jaxpr"]))
        else:
            outside += 1
    return kernels, outside


def _guard(rule, monkeypatch):
    short, outside_short = _kernel_stats(rule, 256, monkeypatch)
    long, outside_long = _kernel_stats(rule, 4096, monkeypatch)
    assert set(short) == {"gdn_scan_fwd", "gdn_scan_bwd"}, short
    assert short == long, (
        f"the kernels' number or size depends on T: {short} at 256, "
        f"{long} at 4,096")
    for name, bodies in long.items():
        assert len(bodies) == 1, f"{name} reached {len(bodies)} times a call"
        assert bodies[0] <= KERNEL_BODY_CEILING, (name, bodies)
    assert outside_short == outside_long, (
        f"the traced rule grows with T: {outside_short} equations at 256, "
        f"{outside_long} at 4,096")


def test_set_up_guard_kernels_and_trace_do_not_grow_with_t(monkeypatch):
    _guard(gated_delta_rule, monkeypatch)


def test_set_up_guard_fails_an_unrolled_variant(monkeypatch):
    """The seeded fault: the same kernels reached once a chunk from a
    Python loop."""
    compiled = gd._rule_kernels

    def unrolled(q, k, v, gc, beta, precision, interpret):
        C = gc.shape[-1]
        return jnp.concatenate([
            compiled(*(x[:, n * C:(n + 1) * C] for x in (q, k, v)),
                     gc[:, n:n + 1], beta[:, n:n + 1], precision, interpret)
            for n in range(gc.shape[1])], axis=1)

    monkeypatch.setattr(gd, "_rule_kernels", unrolled)
    with pytest.raises(AssertionError, match="depends on T"):
        _guard(gated_delta_rule, monkeypatch)


@pytest.mark.parametrize("C", [16, 64])
def test_solve_equations_depend_on_the_chunk_alone(C):
    counts = {
        lead: _count(jax.make_jaxpr(gd._unit_lower_solve)(
            jax.ShapeDtypeStruct(lead + (C, C), jnp.float32),
            jax.ShapeDtypeStruct(lead + (C, 256), jnp.float32)).jaxpr)
        for lead in [(4, 1, 2), (64, 1, 2)]  # (T / chunk, B, H)
    }
    assert len(set(counts.values())) == 1, counts
    assert max(counts.values()) <= SOLVE_CEILING, counts
    text = str(jax.make_jaxpr(gd._unit_lower_solve)(
        jnp.zeros((C, C)), jnp.zeros((C, 8))))
    assert "triangular_solve" not in text and "custom_call" not in text


# ---------------------------------------------------------------------- #
# the lines the chip benchmark's fault controls patch                    #
# ---------------------------------------------------------------------- #
PINNED = [
    "        x = x.astype(f32)\n",
    "    gc = jnp.cumsum(g, axis=-1)  # decay",
    "    u, w = sol[..., :Dv], sol[..., Dv:]\n",
    "    q_in = q * jnp.exp(gc)[..., None]  # the query",
    "        S = S * jnp.exp(end_i)[..., None, None] + jnp.einsum(\n",
    "        return S, o\n",
]


@pytest.mark.parametrize("line", PINNED)
def test_lines_the_fault_controls_patch_stand_once(line):
    count = inspect.getsource(gated_delta_rule).count(line)
    assert count == 1, (
        f"{line!r} stands {count} times in gated_delta_rule's source: "
        "tests/chipbench_tests/faults.py rewrites that line (bf16_state, "
        "rule_bf16, no_chunk_decay, lost_chunk) and asserts it stands "
        "exactly once; faults.py is under the benchmark's paths and only "
        "a benchmark PR may edit it, so keep the line, and keep `step` and "
        "the lax.scan in this function as the off-chip path"
    )
