"""Gossip-SGD trainer: the reference's documented ``MasterNode`` surface,
rebuilt as one jitted SPMD program.

The reference's gossip-CIFAR driver (``utils/master_node.py`` /
``utils/consensus_node.py``) is **absent from its snapshot** — only its full
constructor surface survives, documented in ``Man_Colab.ipynb`` cell 21:

    MasterNode(node_names, model, model_args, optimizer, optimizer_kwargs,
               error, weights, train_loaders, test_loader, stat_step, epoch,
               epoch_len, epoch_cons_num)
    master.initialize_nodes(); master.start_consensus()
    node.show_graphs() for node in master.network.values()

Semantics (per the notebook's comments): train each named node for an epoch
on its own loader, then average parameters per the ``weights`` topology dict,
starting from epoch ``epoch_cons_num``; record per-node statistics every
``stat_step`` batches; evaluate every node on the common test loader.

TPU-native design: all N node replicas live as a leading *agent* axis
(stacked pytrees).  An epoch is a ``lax.scan`` over batches of a ``vmap``-ped
train step — N forward/backward passes batched onto the MXU — and mixing is
a :class:`~distributed_learning_tpu.parallel.consensus.ConsensusEngine`
round.  Only *parameters* are mixed; optimizer slots and BatchNorm running
stats stay per-node (parity: torch ``model.parameters()`` excludes buffers,
``mixer.py:68-69``).  All nodes start from one shared init, matching
``master.initialize_nodes()`` (averaging differently-initialized nets is
destructive under permutation symmetry).
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, Hashable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import numpy as np
import optax

from distributed_learning_tpu.models import get_model
from distributed_learning_tpu.obs import (
    MetricsRegistry,
    SpanTracer,
    flush_chunk,
    global_norm as obs_global_norm,
)
from distributed_learning_tpu.obs.carry import collect_counters
from distributed_learning_tpu.ops import mixing as ops
from distributed_learning_tpu.parallel.consensus import (
    AsyncGossipState,
    ConsensusEngine,
)
from distributed_learning_tpu.parallel.schedule import chebyshev_omegas
from distributed_learning_tpu.parallel.topology import Topology, gamma as mixing_gamma
from distributed_learning_tpu.utils.profiling import annotate
from distributed_learning_tpu.utils.telemetry import TelemetryProcessor

Pytree = Any

__all__ = [
    "MasterNode",
    "ConsensusNode",
    "GossipTrainer",
    "make_optimizer",
    "get_loss",
    "resolve_mixing_matrix",
]


# ---------------------------------------------------------------------- #
# Loss / optimizer registries                                            #
# ---------------------------------------------------------------------- #
def get_loss(error: Any) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Resolve the reference's ``error`` argument (a loss) to a function
    ``(logits, labels) -> scalar``.

    ``'cross_entropy'`` (integer labels; the reference uses
    ``nn.CrossEntropyLoss``) and ``'binary_logistic'`` ({-1,+1} labels, the
    Titanic loss) are built in; custom callables ``(logits, y) -> scalar``
    pass through unchanged.
    """
    if error is None or error == "cross_entropy":
        return lambda logits, y: optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()
    if error == "binary_logistic":
        return lambda margin, y: jnp.mean(jax.nn.softplus(-y * margin.squeeze(-1)))
    if callable(error):
        return error
    raise ValueError(f"unknown loss {error!r}")


def get_metric(error: Any) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Accuracy metric matching the loss: multiclass argmax for
    cross-entropy-style losses, sign agreement for the binary {-1,+1}
    margin loss.  For custom callable losses the output width decides
    (static under jit): single-output models are margin models."""

    def sign_acc(margin, y):
        return jnp.mean((jnp.sign(margin.squeeze(-1)) == y).astype(jnp.float32))

    def argmax_acc(logits, y):
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    if error == "binary_logistic":
        return sign_acc
    if error is None or error == "cross_entropy":
        return argmax_acc
    return lambda out, y: (
        sign_acc(out, y) if out.ndim >= 1 and out.shape[-1] == 1 else argmax_acc(out, y)
    )


def make_optimizer(
    optimizer: Any = "sgd",
    optimizer_kwargs: Optional[Mapping[str, Any]] = None,
    learning_rate: float | optax.Schedule = 0.02,
) -> optax.GradientTransformation:
    """Resolve the reference's ``optimizer`` / ``optimizer_kwargs`` pair.

    Accepts optax transformations directly, factory callables
    ``f(learning_rate, **kwargs)``, or the names ``'sgd'`` / ``'adam'`` with
    torch-style kwargs (``momentum``, ``weight_decay``, ``nesterov``) — the
    reference passes ``optim.SGD`` with
    ``{'momentum': 0.9, 'weight_decay': 5e-4}`` (Man_Colab cell 19).
    """
    kw = dict(optimizer_kwargs or {})
    learning_rate = kw.pop("lr", kw.pop("learning_rate", learning_rate))
    if isinstance(optimizer, optax.GradientTransformation):
        if dict(optimizer_kwargs or {}):
            raise ValueError(
                "optimizer_kwargs cannot be applied to an already-built "
                "optax transformation; bake them into the transformation or "
                "pass the optimizer by name/factory"
            )
        return optimizer
    wd = 0.0
    if isinstance(optimizer, str):
        wd = kw.pop("weight_decay", 0.0)
        name = optimizer.lower()
        if name == "sgd":
            momentum = kw.pop("momentum", 0.0) or None
            tx = optax.sgd(
                learning_rate, momentum=momentum, nesterov=kw.pop("nesterov", False)
            )
        elif name == "adam":
            tx = optax.adam(learning_rate, **kw)
        elif name == "adamw":
            tx = optax.adamw(learning_rate, weight_decay=wd, **kw)
            wd = 0.0
        else:
            raise ValueError(f"unknown optimizer {optimizer!r}")
    elif callable(optimizer):
        # torch-style class or optax factory: try factory(lr, **kwargs).
        # All kwargs (including weight_decay) pass through untouched — the
        # factory owns their semantics (e.g. optax.adamw's decoupled decay).
        tx = optimizer(learning_rate, **kw)
    else:
        raise ValueError(f"cannot interpret optimizer {optimizer!r}")
    if wd:
        # torch SGD weight_decay == L2 added to the gradient before momentum;
        # optax.add_decayed_weights before the optimizer reproduces it.
        tx = optax.chain(optax.add_decayed_weights(wd), tx)
    return tx


def resolve_mixing_matrix(weights: Any, node_names: Sequence[Hashable]) -> np.ndarray:
    """Resolve MasterNode's ``weights`` argument to an (n, n) mixing matrix
    aligned with ``node_names`` order.

    Accepts the reference's ``{agent: {neighbor: weight}}`` topology dict
    (``Man_Colab.ipynb`` cell 14), a :class:`Topology` (-> Metropolis
    weights), an explicit matrix, or ``None`` (isolated nodes).
    """
    n = len(node_names)
    if weights is None:
        return np.eye(n)
    if isinstance(weights, Mapping):
        topo, W = Topology.from_neighbor_dict(weights)
        if set(topo.tokens) != set(node_names):
            raise ValueError(
                "weights topology must cover exactly the trainer's "
                f"node_names; topology has {sorted(map(str, topo.tokens))}, "
                f"trainer has {sorted(map(str, node_names))}"
            )
        order = [topo.tokens.index(t) for t in node_names]
        return W[np.ix_(order, order)]
    if isinstance(weights, Topology):
        W = weights.metropolis_weights()
        if set(weights.tokens) == set(node_names):
            # Align the topology's token order with node_names (same
            # contract as the Mapping branch).
            order = [weights.tokens.index(t) for t in node_names]
            return W[np.ix_(order, order)]
        if set(weights.tokens) == set(range(n)):
            # Positional indices (in any order — from_edges orders tokens by
            # first appearance): index i maps to node_names[i].
            order = [weights.tokens.index(i) for i in range(n)]
            return W[np.ix_(order, order)]
        raise ValueError(
            "weights Topology tokens must either match node_names or "
            f"be 0..n-1 positional indices; topology has "
            f"{sorted(map(str, weights.tokens))}, trainer has "
            f"{sorted(map(str, node_names))}"
        )
    W = np.asarray(weights, dtype=np.float64)
    if W.shape != (n, n):
        raise ValueError(f"mixing matrix shape {W.shape} != ({n}, {n})")
    return W


# ---------------------------------------------------------------------- #
# Trainer                                                                #
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class _EpochStats:
    """Host-side per-node training curves (what show_graphs plots)."""

    steps: List[int] = dataclasses.field(default_factory=list)
    train_loss: List[float] = dataclasses.field(default_factory=list)
    train_acc: List[float] = dataclasses.field(default_factory=list)
    test_acc: List[float] = dataclasses.field(default_factory=list)
    test_epochs: List[int] = dataclasses.field(default_factory=list)


class ConsensusNode:
    """Per-node stats holder (parity: the reference's ``ConsensusNode``
    surface used by ``node.show_graphs()``, Man_Colab cell 24)."""

    def __init__(self, name: Hashable):
        self.name = name
        self.stats = _EpochStats()

    def show_graphs(self, show: bool = False):
        """Plot per-node loss/accuracy curves; returns the figure.  Falls
        back to a text summary when matplotlib is unavailable."""
        try:
            import matplotlib

            matplotlib.use("Agg", force=False)
            import matplotlib.pyplot as plt
        except Exception:  # pragma: no cover - matplotlib is present in CI
            # graftlint: disable=no-print-in-library -- show_graphs' matplotlib-free fallback: the summary IS the user-requested output
            print(self.summary())
            return None
        fig, axes = plt.subplots(1, 2, figsize=(10, 4))
        axes[0].plot(self.stats.steps, self.stats.train_loss)
        axes[0].set_title(f"{self.name}: train loss")
        axes[0].set_xlabel("batch")
        axes[1].plot(self.stats.steps, self.stats.train_acc, label="train")
        if self.stats.test_acc:
            axes[1].plot(
                [e for e in self.stats.test_epochs],
                self.stats.test_acc,
                label="test (per epoch)",
            )
        axes[1].set_title(f"{self.name}: accuracy")
        axes[1].legend()
        if show:  # pragma: no cover
            plt.show()
        return fig

    def summary(self) -> str:
        s = self.stats
        last_loss = s.train_loss[-1] if s.train_loss else float("nan")
        last_acc = s.test_acc[-1] if s.test_acc else float("nan")
        return (
            f"node {self.name}: {len(s.steps)} stat points, "
            f"final train loss {last_loss:.4f}, final test acc {last_acc:.4f}"
        )


class GossipTrainer:
    """Core stacked-replica gossip-SGD trainer.

    Parameters mirror the MasterNode surface (see module docstring) but take
    in-memory arrays: ``train_data[name] = (X, y)`` and
    ``test_data = (X, y)``.
    """

    def __init__(
        self,
        *,
        node_names: Sequence[Hashable],
        model: Any,
        model_args: Sequence[Any] = (),
        model_kwargs: Optional[Mapping[str, Any]] = None,
        optimizer: Any = "sgd",
        optimizer_kwargs: Optional[Mapping[str, Any]] = None,
        learning_rate: float = 0.02,
        error: Any = "cross_entropy",
        weights: Any = None,
        train_data: Mapping[Hashable, Tuple[np.ndarray, np.ndarray]],
        test_data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        stat_step: int = 100,
        epoch: int = 10,
        epoch_len: Optional[int] = None,
        epoch_cons_num: int = 1,
        batch_size: int = 128,
        mix_times: int = 1,
        mix_eps: Optional[float] = None,
        topology_schedule: Optional[Callable[[int], Any]] = None,
        chebyshev: bool = False,
        global_avg_every: Optional[int] = None,
        mix_times_schedule: Optional[Callable[[int], int]] = None,
        compression: Any = None,
        compression_gamma: float = 0.2,
        compression_budget: str = "per-leaf",
        compression_error_feedback: bool = False,
        fused_consensus: bool = True,
        superstep: int = 1,
        async_gossip: Any = None,
        robust_mixing: Any = None,
        adaptive_comm: Any = None,
        mesh=None,
        telemetry: Optional[TelemetryProcessor] = None,
        obs: Any = None,
        profile_costs: bool = False,
        timer_every_n: int = 0,
        seed: int = 0,
        dropout: bool = True,
        augment: bool = False,
        augment_pad_value: Any = 0.0,
        remat: bool = False,
        donate_state: bool = True,
        eval_batch_size: int = 1024,
        moe_aux_coef: float = 0.01,
    ):
        self.eval_batch_size = int(eval_batch_size)
        self.node_names = list(node_names)
        n = len(self.node_names)
        if n == 0:
            raise ValueError("need at least one node")
        if train_data is None:
            raise ValueError(
                "train_data (MasterNode: train_loaders) is required: a dict "
                "mapping each node name to its (X, y) shard"
            )
        missing = [t for t in self.node_names if t not in train_data]
        if missing:
            raise ValueError(f"train_data missing for nodes: {missing}")

        self.model = (
            get_model(model, *model_args, **dict(model_kwargs or {}))
            if isinstance(model, str)
            else model
        )
        self.loss_fn = get_loss(error)
        self.metric_fn = get_metric(error)
        self.tx = make_optimizer(optimizer, optimizer_kwargs, learning_rate)
        self.telemetry = telemetry
        # Observability (obs/): None disables host-side flushing, True
        # uses the process-wide default registry/tracer, or pass a
        # MetricsRegistry.  The device-side carry (per-step loss / acc /
        # grad-norm traces) is part of the compiled chunk EITHER WAY, so
        # toggling obs cannot change the computation — obs-on training
        # is bit-identical to obs-off (tests/test_obs.py oracle).
        if obs is None or obs is False:
            self._obs_registry = None
            self._obs_tracer = None
        elif obs is True:
            from distributed_learning_tpu.obs import get_registry, get_tracer

            self._obs_registry = get_registry()
            self._obs_tracer = get_tracer()
        elif isinstance(obs, MetricsRegistry):
            self._obs_registry = obs
            self._obs_tracer = SpanTracer(registry=obs)
        else:
            raise ValueError(
                "obs must be None/False (off), True (default registry), "
                f"or a MetricsRegistry; got {obs!r}"
            )
        # Device-cost observatory (obs/cost.py).  ``profile_costs=True``
        # registers the compiled epoch/superstep programs' CostProfiles
        # on first use (AOT lower+compile of the SAME program the train
        # path runs — extraction only, the training dispatch is
        # untouched).  ``timer_every_n=N`` (off at 0, the default)
        # samples one chunk dispatch in N with an explicit
        # block_until_ready at the chunk boundary — the declared 1-in-N
        # sync of the sampled step timer; neither knob changes the
        # compiled program (the obs on/off bit-identity oracle covers
        # both).
        self.profile_costs = bool(profile_costs)
        self._cost_profiled: set = set()
        self._cost_timer = None
        if int(timer_every_n) > 0:
            from distributed_learning_tpu.obs.cost import (
                SampledDispatchTimer,
            )

            self._cost_timer = SampledDispatchTimer(
                int(timer_every_n), name="trainer.epoch",
                registry=self._obs_registry,
            )
        self.stat_step = int(stat_step)
        self.num_epochs = int(epoch)
        self.epoch_cons_num = int(epoch_cons_num)
        self.batch_size = int(batch_size)
        self.mix_times = int(mix_times)
        self.mix_eps = mix_eps
        self.seed = seed
        self.dropout = dropout
        self.augment = bool(augment)
        self.augment_pad_value = augment_pad_value
        self.remat = bool(remat)
        self.donate_state = bool(donate_state)
        # MoE router balancing: coefficient on the sown
        # moe_stats/load_balance_loss (Switch default 0.01,
        # arXiv:2101.03961 §2.2).  No-op for dense models.
        self.moe_aux_coef = float(moe_aux_coef)

        # Mixing matrix: MasterNode's `weights` topology dict, a Topology
        # (-> Metropolis), an explicit matrix, or None (isolated nodes).
        # With a topology_schedule, epoch e mixes with
        # resolve_mixing_matrix(topology_schedule(e)) through the engine's
        # traced-W path (time-varying graphs, BASELINE config 5); `weights`
        # then only seeds the engine (residual metrics, mesh placement).
        self.topology_schedule = topology_schedule
        self.chebyshev = bool(chebyshev)
        if self.chebyshev and mix_eps is not None:
            raise ValueError(
                "mix_eps (eps-stopping) and chebyshev (fixed accelerated "
                "schedule) are mutually exclusive; pick one stopping rule"
            )
        if global_avg_every is not None and global_avg_every < 1:
            raise ValueError("global_avg_every must be >= 1")
        self.global_avg_every = global_avg_every
        self.mix_times_schedule = mix_times_schedule
        # CHOCO-SGD (arXiv:1902.00340 via parallel/compression.py): gossip
        # only compressed corrections between epochs; estimates persist
        # across the whole run.  Exclusive with the other mixing variants —
        # the compressed recurrence has its own step size and no eps-stop.
        self._choco = None
        self._choco_xhat = None
        if isinstance(compression, str) and compression.partition(":")[
            0
        ].strip().lower() in ("none", "identity"):
            # Trainer-level "none" means DISABLED (the plain dense gossip
            # path), not CHOCO-with-identity-compressor: the latter would
            # silently mix gamma-damped (x + gamma*(Wx - x)), ~1/gamma
            # slower per round than engine.mix.  Lets a CLI/config override
            # clear a saved compression setting.
            compression = None
        elif isinstance(compression, str) and not compression.strip():
            raise ValueError(
                "empty compression spec; use None or 'none' to disable"
            )
        # Async gossip simulation (docs/async_runtime.md): the device-
        # side model of the straggler-tolerant runtime — stale-weighted
        # double-buffered mixing via ConsensusEngine.mix_async, carry
        # threaded across epochs.  Accepts a mapping with
        # `staleness_bound` (tau, default 0) and `publish_period` (int
        # or per-agent sequence, default 1).  Neutral knobs (tau=0,
        # periods all 1) are bit-identical to the plain-mix path.
        self._async_sim = None
        if async_gossip is not None and async_gossip is not False:
            if not isinstance(async_gossip, Mapping):
                raise ValueError(
                    "async_gossip must be a mapping with keys "
                    "'staleness_bound' and/or 'publish_period', got "
                    f"{async_gossip!r}"
                )
            unknown = set(async_gossip) - {
                "staleness_bound", "publish_period"
            }
            if unknown:
                raise ValueError(
                    f"unknown async_gossip keys: {sorted(unknown)}"
                )
            if (
                self.chebyshev
                or mix_eps is not None
                or topology_schedule is not None
                or global_avg_every is not None
                or compression is not None
            ):
                raise ValueError(
                    "async_gossip applies to the plain-mix config only; "
                    "it is mutually exclusive with chebyshev, mix_eps, "
                    "topology_schedule, global_avg_every, and compression "
                    "(mix_times_schedule composes: it sets the per-epoch "
                    "async round budget)"
                )
            # ``staleness_bound`` may be a callable ``epoch -> tau``
            # (resolved per epoch, like mix_times_schedule): the bound
            # is a traced operand of the async round body, so a tau
            # schedule compiles into the superstep as data.
            self._async_sim = {
                "tau": async_gossip.get("staleness_bound", 0),
                "periods": async_gossip.get("publish_period", 1),
            }
            if not callable(self._async_sim["tau"]):
                self._async_sim["tau"] = int(self._async_sim["tau"])
        self._async_state = None
        # Byzantine-robust mixing (docs/robustness.md): route the gossip
        # phase through parallel/robust.py's clipped / trimmed / median
        # consensus programs.  Accepts anything as_robust_config does —
        # a kind string ("clip" / "trim" / "median"), a mapping
        # ({"kind": "clip", "radius": 2.0, "adaptive": True}), or a
        # RobustConfig.  Neutral knobs (radius=inf, trim=0) are
        # bit-identical to the plain mix / mix_async path.  Composes
        # with async_gossip (the stale-weighted robust programs).
        self._robust_cfg = None
        if robust_mixing is not None and robust_mixing is not False:
            from distributed_learning_tpu.parallel.robust import (
                as_robust_config,
            )

            self._robust_cfg = as_robust_config(robust_mixing)
            if (
                self.chebyshev
                or mix_eps is not None
                or topology_schedule is not None
                or global_avg_every is not None
                or compression is not None
            ):
                raise ValueError(
                    "robust_mixing applies to the plain-mix (optionally "
                    "async_gossip) config only; it is mutually exclusive "
                    "with chebyshev, mix_eps, topology_schedule, "
                    "global_avg_every, and compression"
                )
        # Redirected-mass device scalar from the epoch's robust gossip;
        # materialized at the chunk flush boundary (one sync per epoch).
        self._robust_mass = None
        if compression is not None:
            if self.chebyshev or topology_schedule is not None or mix_eps is not None:
                raise ValueError(
                    "compression is mutually exclusive with chebyshev, "
                    "topology_schedule, and mix_eps"
                )
            if isinstance(compression, str):
                from distributed_learning_tpu.parallel.compression import (
                    compressor_from_spec,
                )

                compression = compressor_from_spec(compression)
        self._compression = compression
        self._compression_gamma = float(compression_gamma)
        self._compression_ef = bool(compression_error_feedback)
        if self._compression_ef and compression is None:
            raise ValueError(
                "compression_error_feedback=True needs a compression "
                "config (it banks the mass the compressor drops)"
            )
        self._choco_ef = None
        self._choco_key = None
        # Compression budget of the fused CHOCO path: "per-leaf" keeps
        # each tensor's k/scale contract (the oracle-identical default),
        # "global" spends one budget across each fused dtype bucket
        # (better kept mass at equal bytes; parallel/compression.py).
        self._compression_budget = str(compression_budget)
        # Epoch superstep (train_epochs): compile K epochs of local SGD +
        # gossip into ONE donated dispatch — start_consensus then runs the
        # schedule in chunks of K.  1 = the per-epoch path.  EVERY config
        # compiles into the superstep: per-epoch schedules
        # (mix_times_schedule / topology_schedule / a tau schedule) ride
        # as traced per-epoch data vectors, and the CHOCO estimates, the
        # async double-buffer, and the robust redirected mass thread
        # through the outer scan as explicit carries.
        self.superstep = int(superstep)
        if self.superstep < 1:
            raise ValueError(f"superstep must be >= 1, got {superstep}")
        self._superstep_cache: Dict[int, Any] = {}
        # Residual-adaptive communication (arXiv:1910.13598 — adapt the
        # averaging/communication budget to consensus drift): each
        # epoch's gossip round budget is the configured/scheduled count
        # scaled by last epoch's post-mix residual relative to `target`
        # (`1 + gain*(res/target - 1)`, rounded, clipped to
        # [min_times, max_times]).  gain=0 is bit-identical to the
        # static schedule (the oracle).  The controller runs in-program
        # inside the superstep (the residual is the scan carry) and has
        # an exact host mirror on the per-epoch path — both read the
        # same consensus.residual trace the obs registry records.
        self._adaptive_cfg = None
        self._adaptive_res = None
        if adaptive_comm is not None and adaptive_comm is not False:
            if not isinstance(adaptive_comm, Mapping):
                raise ValueError(
                    "adaptive_comm must be a mapping with 'target' and "
                    "optional 'gain'/'min_times'/'max_times', got "
                    f"{adaptive_comm!r}"
                )
            unknown = set(adaptive_comm) - {
                "target", "gain", "min_times", "max_times"
            }
            if unknown:
                raise ValueError(
                    f"unknown adaptive_comm keys: {sorted(unknown)}"
                )
            if "target" not in adaptive_comm:
                raise ValueError(
                    "adaptive_comm needs 'target': the consensus "
                    "residual the controller steers toward"
                )
            target = float(adaptive_comm["target"])
            if not target > 0.0:
                raise ValueError(
                    f"adaptive_comm target must be > 0, got {target}"
                )
            lo = int(adaptive_comm.get("min_times", 1))
            hi = int(adaptive_comm.get("max_times", 10_000))
            if lo < 1 or hi < lo:
                raise ValueError(
                    "adaptive_comm needs 1 <= min_times <= max_times, "
                    f"got [{lo}, {hi}]"
                )
            if self.chebyshev:
                raise ValueError(
                    "adaptive_comm is mutually exclusive with chebyshev: "
                    "the accelerated omega schedule is derived for a "
                    "fixed round count, not a residual-modulated one"
                )
            self._adaptive_cfg = {
                "target": target,
                "gain": float(adaptive_comm.get("gain", 1.0)),
                "min_times": lo,
                "max_times": hi,
            }
            # Seed the feedback at the target: the first epoch runs the
            # unmodified schedule (mult == 1 exactly) on both paths.
            self._adaptive_res = np.float32(target)
        # Fused consensus.  Under a mesh the engines ravel each device's
        # shard into one buffer per dtype (ops/mixing.py::flatten_stacked)
        # once per call, and every gossip round inside it moves O(dtype-
        # buckets) messages instead of O(leaves).  Without one (agents
        # stacked on a chip) the gossip engine never packs: a relayout
        # of every parameter twice an epoch, 43 of the 108 ms one round
        # cost on 4 x GPT-2 small (PERF.md), buys nothing where no
        # collective is saved, so it mixes each leaf where it lies; the
        # CHOCO codec keeps its packed buffers.  False restores the
        # per-leaf oracle programs (equal up to accumulation order;
        # tests/test_trainer.py pins the equivalence).
        self.fused_consensus = bool(fused_consensus)

        if weights is None and topology_schedule is not None:
            weights = topology_schedule(0)
        W = resolve_mixing_matrix(weights, self.node_names)
        if (n > 1 and topology_schedule is None
                and np.allclose(W, np.eye(n))):
            # With a topology_schedule the epoch-0 graph may legitimately
            # be edgeless (time-varying B-connected schedules); only the
            # static case is a guaranteed no-gossip run.
            # Documented (weights=None -> isolated nodes), but silently
            # training n disconnected replicas while train_epoch reports
            # mixed=True is the kind of footgun that wastes a run: say so
            # once, loudly.
            warnings.warn(
                "GossipTrainer: mixing matrix is the identity (weights=None"
                " or an edgeless topology) — nodes will train in isolation"
                " with no gossip. Pass weights=Topology.ring(n) (or any"
                " connected topology/matrix) for consensus training.",
                stacklevel=2,
            )
        self.engine = ConsensusEngine(W, mesh=mesh, fused=self.fused_consensus)
        if self._compression is not None:
            from distributed_learning_tpu.parallel.compression import (
                ChocoGossipEngine,
            )

            self._choco = ChocoGossipEngine(
                W,
                self._compression,
                gamma=self._compression_gamma,
                mesh=mesh,
                fused=self.fused_consensus,
                budget=self._compression_budget,
                error_feedback=self._compression_ef,
            )
        if (
            self.chebyshev
            and topology_schedule is None
            and n > 1
            and not (0.0 <= self.engine.gamma < 1.0)
        ):
            raise ValueError(
                "chebyshev=True needs a connected mixing graph with "
                f"gamma < 1; got gamma={self.engine.gamma} (weights="
                f"{'None (isolated nodes)' if weights is None else 'given'})"
            )

        # Static per-node data (truncated to a common batch grid); under
        # a mesh each agent's shard lives on its agent's device.
        self._Xs, self._ys = self.engine.shard(
            self._stack_data(train_data, batch_size)
        )
        if self.augment and self._Xs.shape[2:] != (32, 32, 3):
            raise ValueError(
                "augment=True needs (32, 32, 3) image inputs; got per-sample "
                f"shape {tuple(self._Xs.shape[2:])}"
            )
        max_len = self._Xs.shape[1] // batch_size
        self.epoch_len = min(epoch_len or max_len, max_len)
        if self.epoch_len < 1:
            raise ValueError(
                f"shards of {self._Xs.shape[1]} samples cannot fill one "
                f"batch of {batch_size}"
            )
        self.test_data = None
        if test_data is not None:
            self.test_data = (
                jnp.asarray(test_data[0]),
                jnp.asarray(test_data[1]),
            )

        self.network: Dict[Hashable, ConsensusNode] = {
            name: ConsensusNode(name) for name in self.node_names
        }
        self._state = None
        self._global_step = 0
        self._epochs_done = 0
        self._build_jitted()

    # ------------------------------------------------------------------ #
    def _stack_data(self, train_data, batch_size):
        n = len(self.node_names)
        lens = [len(train_data[t][0]) for t in self.node_names]
        m = min(lens)
        m -= m % batch_size
        if m == 0:
            raise ValueError(
                f"smallest shard ({min(lens)}) is below batch_size {batch_size}"
            )
        if max(lens) > m:
            import warnings

            if max(lens) > min(lens):
                msg = (
                    f"node shards are imbalanced ({min(lens)}..{max(lens)} "
                    f"samples); every shard is truncated to {m} (the "
                    "smallest, batch-aligned) so the stacked epoch has a "
                    "common batch grid"
                )
            else:
                # Equal shards merely not batch-aligned: still worth a
                # notice (samples are dropped), but not "imbalanced".
                msg = (
                    f"node shards ({min(lens)} samples) are not a multiple "
                    f"of batch_size; each is truncated to {m} so the "
                    "stacked epoch has a whole number of batches"
                )
            warnings.warn(msg, stacklevel=3)
        Xs = jnp.stack(
            [jnp.asarray(train_data[t][0][:m]) for t in self.node_names]
        )
        ys = jnp.stack(
            [jnp.asarray(train_data[t][1][:m]) for t in self.node_names]
        )
        return Xs, ys

    def _build_jitted(self):
        from distributed_learning_tpu.models.moe import (
            collect_load_balance_loss,
        )

        model, tx, loss_fn = self.model, self.tx, self.loss_fn
        metric_fn = self.metric_fn
        n = len(self.node_names)
        has_dropout = self.dropout
        moe_aux_coef = self.moe_aux_coef

        def init_node(rng, x0):
            variables = model.init(rng, x0, train=False)
            return variables

        augment = self.augment
        aug_pad = self.augment_pad_value
        remat = self.remat

        # The jax.named_scope blocks below name the device program's
        # parts in a profile (gather / augment / fwd_bwd / carry / opt;
        # docs/observability.md).  They are metadata on the ops: the
        # compiled program is the same with or without them.
        def train_step(params, batch_stats, opt_state, x, y, rng):
            if augment:
                # Jitted RandomCrop(32, pad 4) + flip fused into the step
                # (the torchvision train transforms of Man_Colab cell 16;
                # pass augment_pad_value=normalized_pad_value(dataset) for
                # crop borders that match its crop-before-normalize order).
                from distributed_learning_tpu.data.cifar import augment_batch

                with jax.named_scope("augment"):
                    rng, k_aug = jax.random.split(rng)
                    x = augment_batch(k_aug, x, pad_value=aug_pad)

            def lossf(p):
                variables = {"params": p}
                if batch_stats is not None:
                    variables["batch_stats"] = batch_stats
                mutable = ["moe_stats", "counters"] + (
                    ["batch_stats"] if batch_stats is not None else []
                )
                # Inside lossf, so that JAX names the forward ops
                # jvp(fwd_bwd) and the backward transpose(jvp(fwd_bwd)).
                with jax.named_scope("fwd_bwd"):
                    logits, mut = model.apply(
                        variables,
                        x,
                        train=True,
                        rngs={"dropout": rng} if has_dropout else {},
                        mutable=mutable,
                    )
                    loss = loss_fn(logits, y)
                    aux = collect_load_balance_loss(mut)
                    if aux is not None:
                        loss = loss + moe_aux_coef * aux
                    acc = metric_fn(logits, y)
                    # integer scalars the model counted (obs/carry.py);
                    # {} for every model that sows none
                    counters = collect_counters(mut)
                return loss, (mut.get("batch_stats", None), acc, counters)

            if remat:
                # Rematerialize activations in the backward pass: trades
                # FLOPs for HBM, buying batch/model headroom at WRN scale.
                lossf = jax.checkpoint(lossf)
            (loss, (new_bs, acc, counters)), grads = jax.value_and_grad(
                lossf, has_aux=True
            )(params)
            # Device-side metrics carry (obs/carry.py): the grad norm is
            # computed on device and stacked by the epoch scan; the host
            # reads it once per chunk alongside the loss trace.
            with jax.named_scope("carry"):
                gnorm = obs_global_norm(grads)
            with jax.named_scope("opt"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, new_bs, opt_state, loss, acc, gnorm, counters

        engine = self.engine

        def per_chip(fn):
            """``fn`` takes and returns arrays with the agents on their
            leading axis, and no agent reads another's.  Under a mesh each
            chip then runs it on its own agents' operands (``shard_map``
            over the agent axis, no collective inside): left to the
            partitioner, a Pallas kernel in the step has no partitioning
            rule and is fed all-gathered operands on every chip.  The
            mesh is read when the program is traced; without one this is
            ``fn`` itself."""
            def run(*operands):
                if engine.mesh is None:
                    return fn(*operands)
                spec = P(engine.axis_name)
                return jax.shard_map(
                    fn, mesh=engine.mesh, in_specs=spec, out_specs=spec,
                    check_vma=False,
                )(*operands)

            return run

        vstep = per_chip(jax.vmap(train_step))
        take = per_chip(jax.vmap(lambda X, i: jnp.take(X, i, axis=0)))

        def epoch_fn(state, Xs, ys, idx):
            """scan over epoch_len steps of the vmapped train step.

            ``Xs``: (n, m, ...) resident per-node shards; ``ys``: (n, m, ...);
            ``idx``: (steps, n, B) int32 shuffle indices.  Each step gathers
            its batch from the resident shards inside the scan, so the
            permuted epoch tensor is never materialized and the only
            per-epoch host->device transfer is the index array.
            Returns state plus (steps, n) loss/acc/grad-norm traces (the
            device-side metrics carry) and ``{name: (steps, n)}`` of the
            model's own counters (``{}`` where it counts nothing).
            """
            def body(carry, idx_t):
                params, bs, opt, rng = carry
                with jax.named_scope("gather"):
                    x = take(Xs, idx_t)
                    y = take(ys, idx_t)
                rng, *subs = jax.random.split(rng, n + 1)
                subkeys = jnp.stack(subs)
                params, bs, opt, loss, acc, gnorm, counters = vstep(
                    params, bs, opt, x, y, subkeys
                )
                return (params, bs, opt, rng), (loss, acc, gnorm, counters)

            (params, bs, opt, rng), (losses, accs, gnorms, counters) = (
                jax.lax.scan(body, state, idx)
            )
            return (params, bs, opt, rng), losses, accs, gnorms, counters

        # Donating the carried state lets XLA reuse its buffers in place —
        # at WRN scale the stacked params/opt slots dominate HBM, so the
        # epoch step must not hold two copies.  Consequence: references to
        # a PREVIOUS epoch's state (e.g. a saved `trainer.state`) are dead
        # arrays after the next train_epoch on an accelerator; read state
        # after training, or pass donate_state=False to keep old states
        # alive.  (CPU ignores donation and warns per call, so only donate
        # on accelerators.)
        self._donate_active = (
            self.donate_state and jax.default_backend() != "cpu"
        )
        # The raw epoch body is kept for the superstep path, which embeds
        # it (plus the gossip program) inside its own jitted scan.
        self._epoch_fn = epoch_fn
        self._superstep_cache = {}
        self._jit_epoch = jax.jit(
            epoch_fn, donate_argnums=(0,) if self._donate_active else ()
        )

        def eval_fn(params, batch_stats, X, y, mask):
            """Per-node SUM of the metric over the masked batch.

            ``X``/``y`` are padded to a fixed ``eval_batch_size`` so every
            test batch — including the ragged tail — reuses one compiled
            executable; ``mask`` zeroes the padding.  The metric is applied
            per example (``metric_fn`` on a length-1 slice), which is exact
            for any metric that is a mean of per-example scores.
            """

            def one(p, b):
                variables = {"params": p}
                if b is not None:
                    variables["batch_stats"] = b
                logits = model.apply(variables, X, train=False)
                per = jax.vmap(lambda l, yy: metric_fn(l[None], yy[None]))(
                    logits, y
                )
                return jnp.sum(per * mask)

            if batch_stats is None:
                return jax.vmap(lambda p: one(p, None))(params)
            return jax.vmap(one)(params, batch_stats)

        self._jit_eval = jax.jit(eval_fn)
        self._jit_init = jax.jit(init_node)

    def _eval_accuracy(self, params, bs) -> np.ndarray:
        """Per-node test accuracy, batched over the test set so activations
        for n_nodes x eval_batch never all materialize at once.  The ragged
        tail batch is zero-padded to ``eval_batch_size`` and masked out, so
        the whole eval reuses a single compiled executable."""
        X, y = self.test_data
        ebs = self.eval_batch_size
        total = np.zeros(len(self.node_names))
        seen = 0
        for s in range(0, len(X), ebs):
            xb, yb = X[s : s + ebs], y[s : s + ebs]
            k = len(xb)
            if k < ebs:
                xb = jnp.concatenate(
                    [xb, jnp.zeros((ebs - k,) + xb.shape[1:], xb.dtype)]
                )
                yb = jnp.concatenate(
                    [yb, jnp.zeros((ebs - k,) + yb.shape[1:], yb.dtype)]
                )
            mask = (jnp.arange(ebs) < k).astype(jnp.float32)
            total += np.asarray(self._jit_eval(params, bs, xb, yb, mask))
            seen += k
        return total / max(seen, 1)

    # ------------------------------------------------------------------ #
    def _on_every_chip(self, x):
        """The state's one unsharded leaf (the step key) where the epoch
        program leaves it: replicated over the mesh.  Left on one device,
        the first epoch's program is compiled for that placement and the
        second epoch's again for the one the first returned: a compile
        inside a caller's timed window."""
        mesh = self.engine.mesh
        return x if mesh is None else jax.device_put(
            x, NamedSharding(mesh, P()))

    def initialize_nodes(self):
        """Create the shared init and per-node optimizer/batch-stat state
        (parity: ``master.initialize_nodes()``)."""
        rng = jax.random.key(self.seed)
        x0 = self._Xs[0, : self.batch_size]
        mesh = self.engine.mesh
        if mesh is not None:
            # The shared init runs on ONE device, from a host copy of the
            # sample: a slice of the sharded shards lives on the whole
            # mesh, the init program would be partitioned over it, and a
            # Pallas kernel in the model's forward cannot be.
            x0 = np.asarray(x0)
        variables = self._jit_init(rng, x0)
        params0 = variables["params"]
        bs0 = variables.get("batch_stats", None)
        n = len(self.node_names)
        stack = lambda t: jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (n,) + v.shape), t
        )

        def replicas(params0, bs0):
            params = stack(params0)
            return (params, stack(bs0) if bs0 is not None else None,
                    jax.vmap(self.tx.init)(params))

        if mesh is None:
            params, batch_stats, opt_state = self.engine.shard(
                replicas(params0, bs0))
        else:
            # Every stacked leaf is made where its agent lives (one device
            # per agent): optimizer slots and BatchNorm stats too, not
            # only the params.  Stacked on one device first, four replicas
            # of a 1.7 GB model and their Adam moments are 20 GB.
            params, batch_stats, opt_state = jax.jit(
                replicas, out_shardings=NamedSharding(
                    mesh, P(self.engine.axis_name)),
            )(params0, bs0)
        self._state = (
            params,
            batch_stats,
            opt_state,
            self._on_every_chip(jax.random.key(self.seed + 1)),
        )
        self._choco_xhat = None  # fresh run: CHOCO estimates restart at 0
        self._choco_ef = None
        self._async_state = None  # fresh run: async publish buffer restarts
        self._robust_mass = None
        if self._adaptive_cfg is not None:
            self._adaptive_res = np.float32(self._adaptive_cfg["target"])
        return self

    # ------------------------------------------------------------------ #
    def _epoch_perm(self, epoch_idx: int) -> np.ndarray:
        """Host-side (steps, n, B) shuffle indices for one epoch — one
        ``np.random.default_rng(seed*1000 + epoch)`` stream per epoch, so
        the trajectory is a pure function of (seed, epoch) regardless of
        whether epochs run one per dispatch or K per superstep."""
        n, m = self._Xs.shape[0], self._Xs.shape[1]
        steps = self.epoch_len
        rng = np.random.default_rng(self.seed * 1000 + epoch_idx)
        idx = np.stack(
            [rng.permutation(m)[: steps * self.batch_size] for _ in range(n)]
        ).astype(np.int32)
        return idx.reshape(n, steps, self.batch_size).swapaxes(0, 1)

    def _epoch_indices(self, epoch_idx: int) -> jax.Array:
        """Per-node shuffle indices for one epoch, laid out (steps, n, B).

        Only these int32 indices cross host->device; the batches themselves
        are gathered from the resident shards inside the jitted epoch."""
        return jnp.asarray(self._epoch_perm(epoch_idx))

    def _superstep_indices(self, epoch0: int, k: int) -> jax.Array:
        """Shuffle indices for ``k`` consecutive epochs, laid out
        (k, steps, n, B) and transferred host->device ONCE per superstep —
        per-epoch streams identical to :meth:`_epoch_indices`, so a
        superstep samples exactly the batches the per-epoch loop would."""
        return jnp.asarray(
            np.stack([self._epoch_perm(epoch0 + j) for j in range(k)])
        )

    def _gossip(self, epoch_idx: int, params: Pytree):
        """One epoch's consensus phase; returns ``(params, rounds_run)``.

        ``rounds_run`` is the gossip-round count this epoch actually
        executed — a static python int for fixed-count paths, the
        **device scalar** from the eps-stopping ``lax.while_loop`` for
        ``mix_eps`` paths.  The caller materializes it at the same chunk
        boundary as ``flush_chunk`` (one host sync region per epoch):
        reading it back here, between the gossip dispatch and the trace
        flush, would insert a second blocking round-trip per epoch.

        With ``fused_consensus`` (default) every engine call here under a
        mesh runs on the fused flat-buffer layout: each device's params
        are raveled into one contiguous buffer per dtype INSIDE the
        jitted program — once per epoch, since gossip is one engine call
        per epoch — and all rounds of the epoch's ``while_loop``/``scan``
        move O(dtype-buckets) messages per round instead of O(leaves).
        Without a mesh the engine mixes the leaves where they lie.
        """
        mix_times = self.mix_times
        if self.mix_times_schedule is not None:
            # Adaptive averaging period (arXiv:1910.13598 — communicate
            # less early, more as training converges, or vice versa).
            mix_times = int(self.mix_times_schedule(epoch_idx))
            if mix_times < 1:
                raise ValueError(
                    f"mix_times_schedule({epoch_idx}) returned "
                    f"{mix_times}; must be >= 1 (0 would silently skip "
                    "gossip while reporting a mixed epoch)"
                )
        if self._adaptive_cfg is not None:
            # Host mirror of the superstep's in-program controller —
            # same float32 op order, fed by last epoch's residual
            # (``self._adaptive_res``), so both paths compute the same
            # round budget bit-for-bit.  For eps configs this modulates
            # the round FLOOR (min_times); eps still decides the stop.
            mix_times = self._adaptive_times_host(mix_times)
        rounds = mix_times
        consensus_epochs = epoch_idx + 1 - self.epoch_cons_num
        if self._async_sim is not None:
            # Asynchronous stale-weighted gossip (docs/async_runtime.md):
            # the double-buffer carry (published params, publish ages,
            # round counter) threads across epochs so a straggler's
            # publish cadence is continuous over the whole run.  With
            # neutral knobs this is bit-identical to engine.mix.
            if self._robust_cfg is not None:
                # Robust estimator on the stale-weighted neighbor set
                # (docs/robustness.md); the redirected-mass device scalar
                # joins ``rounds`` at the chunk-flush sync boundary.
                params, self._async_state, self._robust_mass = (
                    self.engine.mix_async_robust(
                        params,
                        self._async_state,
                        spec=self._robust_cfg,
                        tau=self._async_tau(epoch_idx),
                        periods=self._async_sim["periods"],
                        times=mix_times,
                    )
                )
            else:
                params, self._async_state = self.engine.mix_async(
                    params,
                    self._async_state,
                    tau=self._async_tau(epoch_idx),
                    periods=self._async_sim["periods"],
                    times=mix_times,
                )
            return params, rounds
        if self._robust_cfg is not None:
            # Byzantine-robust synchronous gossip: clipped / trimmed /
            # median mixing (parallel/robust.py).  Mutually exclusive
            # with every other special-mix config (constructor check),
            # so this dispatch owns the epoch.
            params, self._robust_mass = self.engine.mix_robust(
                params, self._robust_cfg, times=mix_times
            )
            return params, rounds
        if (
            self.global_avg_every is not None
            and consensus_epochs % self.global_avg_every
            == self.global_avg_every - 1
        ):
            # Gossip-PGA (arXiv:2105.09080): every H-th consensus epoch
            # is one exact all-reduce, zeroing the consensus residual.
            params = self.engine.global_average(params)
            rounds = 1
            # CHOCO estimates tracked the pre-all-reduce iterates; kept,
            # they would push the now-identical params apart again next
            # epoch.  Reset — error feedback re-converges from zero.
            self._choco_xhat = None
            self._choco_ef = None
        elif self.topology_schedule is not None:
            # Time-varying graph: resample, resolve, mix via the
            # traced-W path (no recompilation per epoch).
            W_e = resolve_mixing_matrix(
                self.topology_schedule(epoch_idx), self.node_names
            )
            if self.chebyshev:
                g_e = mixing_gamma(W_e)
                if not (0.0 <= g_e < 1.0):
                    raise ValueError(
                        f"topology_schedule({epoch_idx}) produced a "
                        f"graph with gamma={g_e}; Chebyshev acceleration "
                        "needs a connected graph with gamma < 1"
                    )
                omegas = chebyshev_omegas(g_e, mix_times)
                params = self.engine.mix_chebyshev_with(params, W_e, omegas)
            elif self.mix_eps is not None:
                # Eps-stopping composed with the traced-W path: the
                # resampled graph still gossips until the residual
                # drops below eps (at least mix_times rounds).
                params, t, _ = self.engine.mix_until_with(
                    params, W_e, eps=self.mix_eps, min_times=mix_times
                )
                rounds = t  # device scalar; materialized at the flush
            else:
                params = self.engine.mix_with(params, W_e, times=mix_times)
        elif self._choco is not None:
            # CHOCO-SGD: compressed-correction gossip; the public
            # estimates persist across epochs (reset only by a fresh
            # initialize_nodes / checkpoint restore — error feedback
            # re-converges them).
            from distributed_learning_tpu.parallel.compression import (
                ChocoState,
            )

            if self._choco_xhat is None:
                cstate = self._choco.init(params, seed=self.seed + 2)
            else:
                cstate = ChocoState(
                    x=params, xhat=self._choco_xhat, key=self._choco_key,
                    ef=self._choco_ef,
                )
            cstate, _ = self._choco.run(cstate, mix_times)
            params = cstate.x
            self._choco_xhat = cstate.xhat
            self._choco_key = cstate.key
            self._choco_ef = cstate.ef
        elif self.chebyshev:
            params = self.engine.mix_chebyshev(params, times=mix_times)
        elif self.mix_eps is None:
            params = self.engine.mix(params, times=mix_times)
        else:
            params, t, _ = self.engine.mix_until(
                params, eps=self.mix_eps, min_times=mix_times
            )
            rounds = t  # device scalar; materialized at the flush
        return params, rounds

    def _async_tau(self, epoch_idx: int) -> int:
        """This epoch's staleness bound: the static int, or the tau
        schedule resolved at ``epoch_idx`` (validated >= 0)."""
        tau = self._async_sim["tau"]
        if callable(tau):
            tau = int(tau(epoch_idx))
            if tau < 0:
                raise ValueError(
                    f"staleness_bound({epoch_idx}) returned {tau}; "
                    "must be >= 0"
                )
            return tau
        return int(tau)

    def _adaptive_times_host(self, t: int) -> int:
        """Host mirror of the superstep's residual-adaptive round
        budget: ``clip(round(t * (1 + gain*(res/target - 1))),
        min_times, max_times)`` in float32, fed by the previous epoch's
        post-mix consensus residual.  gain=0 returns ``t`` exactly."""
        c = self._adaptive_cfg
        mult = np.float32(1.0) + np.float32(c["gain"]) * (
            np.float32(self._adaptive_res) / np.float32(c["target"])
            - np.float32(1.0)
        )
        te = np.floor(np.float32(t) * mult + np.float32(0.5))
        return int(np.clip(te, c["min_times"], c["max_times"]))

    def _span(self, name: str, **ids):
        """One thing the host does, named.  Always a
        ``jax.profiler.TraceAnnotation`` (a profile of any run shows the
        trainer's spans on the host plane, ``ids`` among their stats);
        with obs on it is also a wall-clock span on the trainer's
        tracer."""
        if self._obs_tracer is None:
            return annotate(name, **ids)
        return self._obs_tracer.span(name, **ids)

    def cost_profile(self, k: Optional[int] = None):
        """:class:`~distributed_learning_tpu.obs.cost.CostProfile` of
        the compiled epoch program (``k`` None/1) or the ``k``-epoch
        superstep, registered process-wide as ``trainer.epoch`` /
        ``trainer.superstep<k>`` (gauges land in the metrics registry,
        so profiles ride run reports and obs deltas).

        Extraction is the AOT ``lower().compile()`` of the SAME traced
        program the train path dispatches — it never executes anything
        and never changes what a later train call compiles."""
        from distributed_learning_tpu.obs.cost import profile_fn

        if self._state is None:
            self.initialize_nodes()
        registry = self._obs_registry
        if k is None or int(k) <= 1:
            return profile_fn(
                self._jit_epoch, self._state, self._Xs, self._ys,
                self._epoch_indices(self._epochs_done),
                name="trainer.epoch", registry=registry,
            )
        k = int(k)
        epoch0 = self._epochs_done
        modes = jnp.asarray(
            [self._epoch_mode(epoch0 + j) for j in range(k)],
            dtype=jnp.int32,
        )
        return profile_fn(
            self._build_superstep(k), self._state,
            self._superstep_carry(), self._Xs, self._ys,
            self._superstep_indices(epoch0, k), modes,
            self._superstep_sched(epoch0, k),
            name=f"trainer.superstep{k}", registry=registry,
        )

    def _maybe_profile_costs(self, k: Optional[int] = None) -> None:
        """Register this program's cost profile once (``profile_costs``)."""
        key = "epoch" if k is None or int(k) <= 1 else f"superstep{k}"
        if not self.profile_costs or key in self._cost_profiled:
            return
        self._cost_profiled.add(key)
        self.cost_profile(k)

    def train_epoch(self) -> Dict[str, Any]:
        """One epoch: local SGD on every node, then (maybe) gossip."""
        with self._span("trainer.epoch", epoch=self._epochs_done):
            return self._train_epoch()

    def _count_dispatch(self, n: int = 1) -> None:
        """Obs counter of train-path XLA program launches (epoch chunk /
        superstep, gossip, deviation readout — eval and checkpoint IO are
        reporting, not the train path).  The superstep's headline claim —
        host dispatches per epoch drop from >=3 to 1/K — is asserted off
        this counter (``tests/test_trainer.py``)."""
        if self._obs_registry is not None:
            self._obs_registry.inc("trainer.dispatches", n)

    def _train_epoch(self) -> Dict[str, Any]:
        if self._state is None:
            self.initialize_nodes()
        self._maybe_profile_costs()
        epoch_idx = self._epochs_done
        with self._span("trainer.indices", epoch=epoch_idx):
            idx = self._epoch_indices(epoch_idx)
        mixed = False
        rounds: Any = 0
        # Sampled dispatch timer (obs/cost.py): tick is two host integer
        # ops; a sampled chunk closes with ONE block_until_ready at the
        # boundary the carry flush already syncs at.
        timer = self._cost_timer
        sampled = timer.tick() if timer is not None else False
        t0 = time.perf_counter() if sampled else 0.0
        try:
            with self._span("trainer.dispatch", epoch=epoch_idx):
                self._state, losses, accs, gnorms, counters = (
                    self._jit_epoch(self._state, self._Xs, self._ys, idx)
                )
            self._count_dispatch()
            # Consensus from epoch_cons_num onward (parity: Man_Colab
            # cell 21 "the first epoch from which consensus begins";
            # 1-based epochs).  Dispatched BEFORE the chunk flush so
            # the eps path's device-side round count materializes at
            # the same host boundary as the metric traces — one sync
            # region per epoch, not a flush sync plus a blocking
            # ``int(t)`` readback.
            params, bs, opt, rng = self._state
            if (epoch_idx + 1 >= self.epoch_cons_num
                    and len(self.node_names) > 1):
                with self._span("trainer.mix", epoch=epoch_idx):
                    params, rounds = self._gossip(epoch_idx, params)
                self._count_dispatch()
                mixed = True
                self._state = (params, bs, opt, rng)
            # Materialize inside the try: dispatch is async, so an
            # execution failure (e.g. OOM) surfaces here, not at the
            # calls above.  flush_chunk is the carry's single
            # per-chunk host materialization; with obs enabled the
            # same arrays also land in the registry as series.
            # ``trainer.flush`` is the host waiting for the device.
            with self._span("trainer.flush", epoch=epoch_idx):
                arrs = flush_chunk(
                    self._obs_registry,
                    {"loss": losses, "acc": accs, "grad_norm": gnorms,
                     **counters},
                    step0=self._global_step,
                    node_names=self.node_names,
                )
                losses = arrs["loss"]  # (steps, n)
                accs = arrs["acc"]
                gnorms = arrs["grad_norm"]
                counters = {name: arrs[name] for name in counters}
                mix_rounds = int(np.asarray(rounds))
                # Robust gossip's redirected-mass scalar shares the same
                # single per-epoch sync region (see _gossip docstring).
                robust_mass = None
                if self._robust_mass is not None:
                    robust_mass = float(np.asarray(self._robust_mass))
                    self._robust_mass = None
                if sampled:
                    # The declared 1-in-N chunk-boundary sample: drain
                    # the (possibly still in-flight) state and record
                    # step time + MFU/bytes-per-sec off the registered
                    # trainer.epoch profile.  loop_steps: XLA counts the
                    # per-step scan body once; the epoch runs it
                    # epoch_len times.
                    timer.measure(
                        self._state, t0, name="trainer.epoch",
                        loop_steps=self.epoch_len,
                        step=self._global_step,
                    )
        except BaseException:
            # BaseException: KeyboardInterrupt mid-epoch must also drop the
            # state, or the next call crashes on deleted arrays.
            if self._donate_active:
                # The donated input buffers may already be invalidated (e.g.
                # OOM mid-execution); drop the dangling reference so the next
                # call re-initializes or restores instead of crashing on
                # deleted arrays.
                self._state = None
            raise

        # Stats every stat_step batches.
        with self._span("trainer.stats", epoch=epoch_idx):
            for s in range(0, losses.shape[0], self.stat_step):
                chunk = slice(s, min(s + self.stat_step, losses.shape[0]))
                for a, name in enumerate(self.node_names):
                    node = self.network[name]
                    node.stats.steps.append(self._global_step + chunk.stop)
                    node.stats.train_loss.append(
                        float(losses[chunk, a].mean())
                    )
                    node.stats.train_acc.append(
                        float(accs[chunk, a].mean())
                    )
        self._global_step += losses.shape[0]
        self._epochs_done += 1

        test_accs = None
        if self.test_data is not None:
            with self._span("trainer.eval", epoch=epoch_idx):
                test_accs = self._eval_accuracy(params, bs)
            for a, name in enumerate(self.node_names):
                node = self.network[name]
                node.stats.test_acc.append(float(test_accs[a]))
                node.stats.test_epochs.append(self._global_step)

        self._count_dispatch()  # the deviation readout
        with self._span("trainer.deviation", epoch=epoch_idx):
            deviation = float(self.engine.max_deviation(params))
        payload = {
            "epoch": epoch_idx,
            "mixed": mixed,
            "train_loss": losses.mean(axis=0),
            "train_acc": accs.mean(axis=0),
            "grad_norm": gnorms.mean(axis=0),
            "test_acc": test_accs,
            "mix_rounds": mix_rounds,
            "deviation": deviation,
        }
        if counters:
            # the model's own integer counters, (steps, n) each
            payload["counters"] = counters
        if self._adaptive_cfg is not None:
            # Feed the controller: next epoch's round budget is scaled
            # by this epoch's post-mix residual (float -> float32 is
            # exact, so the mirror matches the superstep's carry).
            self._adaptive_res = np.float32(payload["deviation"])
        if self._obs_registry is not None:
            # Per-chunk consensus metrics (the arXiv 2105.09080 headline
            # traces): residual after mixing, rounds spent getting there.
            self._obs_registry.observe(
                "consensus.residual", payload["deviation"],
                step=self._global_step,
            )
            if mixed:
                self._obs_registry.inc("consensus.rounds_run", mix_rounds)
            if robust_mass is not None:
                # Cumulative redirected edge mass — the defense's
                # detection signal (docs/robustness.md): ~0 in honest
                # runs, grows whenever a peer is being clipped/trimmed.
                self._obs_registry.inc(
                    "consensus.robust.clipped_mass", robust_mass
                )
                self._obs_registry.observe(
                    "consensus.robust.mass", robust_mass,
                    step=self._global_step,
                )
            if test_accs is not None:
                self._obs_registry.observe(
                    "eval.test_acc", float(np.mean(test_accs)),
                    step=self._global_step,
                )
        if self.telemetry is not None:
            # Telemetry flushes once per jitted chunk (this method IS one
            # chunk), so long runs stream metrics; the abstract
            # TelemetryProcessor interface is unchanged — the payload
            # only gained keys (grad_norm, mix_rounds).
            # Sampled step-time/MFU gauges ride the payloads only when
            # the timer is configured (keys appear, never change the
            # base schema; None on unsampled chunks).
            cost_keys = (
                {}
                if self._cost_timer is None
                else {
                    "step_time_s": (
                        self._cost_timer.last_step_time_s if sampled
                        else None
                    ),
                    "mfu": self._cost_timer.last_mfu if sampled else None,
                }
            )
            with self._span("trainer.telemetry", epoch=epoch_idx):
                for a, name in enumerate(self.node_names):
                    self.telemetry.process(
                        name,
                        {
                            "epoch": epoch_idx,
                            "train_loss": float(payload["train_loss"][a]),
                            "train_acc": float(payload["train_acc"][a]),
                            "grad_norm": float(payload["grad_norm"][a]),
                            "test_acc": None
                            if test_accs is None
                            else float(test_accs[a]),
                            "mix_rounds": mix_rounds,
                            "deviation": payload["deviation"],
                            **cost_keys,
                        },
                    )
        return payload

    # ------------------------------------------------------------------ #
    # Epoch superstep: K epochs of local SGD + gossip, ONE dispatch      #
    # ------------------------------------------------------------------ #
    def _epoch_mode(self, epoch_idx: int) -> int:
        """Static per-epoch gossip mode — the host-side gating of
        :meth:`_train_epoch`/:meth:`_gossip` as data: 0 = no gossip
        (before ``epoch_cons_num``, or a single node), 1 = this config's
        mixing program (mix / mix_until / chebyshev), 2 = the Gossip-PGA
        exact all-reduce epoch (``global_avg_every``)."""
        if len(self.node_names) <= 1 or epoch_idx + 1 < self.epoch_cons_num:
            return 0
        consensus_epochs = epoch_idx + 1 - self.epoch_cons_num
        if (
            self.global_avg_every is not None
            and consensus_epochs % self.global_avg_every
            == self.global_avg_every - 1
        ):
            return 2
        return 1

    def _adaptive_times_traced(self, t: jax.Array, res: jax.Array):
        """In-program residual-adaptive round budget — the traced twin
        of :meth:`_adaptive_times_host` (same float32 op order, so the
        two paths agree bit-for-bit).  Identity when the controller is
        off."""
        c = self._adaptive_cfg
        if c is None:
            return t
        mult = jnp.float32(1.0) + jnp.float32(c["gain"]) * (
            res / jnp.float32(c["target"]) - jnp.float32(1.0)
        )
        te = jnp.floor(t.astype(jnp.float32) * mult + jnp.float32(0.5))
        return jnp.clip(
            te, jnp.float32(c["min_times"]), jnp.float32(c["max_times"])
        ).astype(jnp.int32)

    def _superstep_carry(self):
        """The superstep's cross-epoch gossip carry ``{"mix": ...,
        "res": f32}`` seeded from the trainer's host mirrors: the CHOCO
        estimate/key/EF trees, the async double-buffer, or ``()`` for
        carry-free configs, plus the adaptive controller's last
        residual.  Fresh CHOCO/async carries are built exactly as the
        per-epoch path's lazy init would (zeros estimates and
        ``key(seed+2)``; an all-publish-at-round-0 buffer — zeros, NOT
        an aliased copy of params, so donating the carry never aliases
        the donated state)."""
        params = self._state[0]
        if self._choco is not None:
            if self._choco_xhat is None:
                xhat = jax.tree.map(jnp.zeros_like, params)
                key = jax.random.key(self.seed + 2)
                ef = (
                    jax.tree.map(jnp.zeros_like, params)
                    if self._choco.error_feedback else None
                )
            else:
                xhat, key, ef = (
                    self._choco_xhat, self._choco_key, self._choco_ef
                )
            mix = {"xhat": xhat, "key": key, "ef": ef}
        elif self._async_sim is not None:
            mix = self._async_state
            if mix is None:
                # Round 0 publishes every agent (0 is a multiple of all
                # periods) before any read, so the zeros never survive
                # a mix — bit-identical to init_async_state's copy.
                mix = AsyncGossipState(
                    pub=jax.tree.map(jnp.zeros_like, params),
                    age=jnp.zeros((len(self.node_names),), jnp.int32),
                    rnd=jnp.int32(0),
                )
        else:
            mix = ()
        res0 = (
            self._adaptive_res if self._adaptive_res is not None
            else np.float32(0.0)
        )
        return {"mix": mix, "res": jnp.float32(res0)}

    def _superstep_sched(self, epoch0: int, k: int):
        """Per-epoch schedule data for one superstep — the host-side
        schedules resolved for epochs ``[epoch0, epoch0+k)`` and stacked
        into traced arrays the scan body indexes: ``times`` (k,) always;
        ``W`` (k, n, n) and (chebyshev) ``omegas`` (k, Tmax) under a
        ``topology_schedule``; ``omegas`` alone for chebyshev with a
        ``mix_times_schedule``; ``tau`` (k,) for async gossip.  Epochs
        the mode vector routes away from the mixing branch (mode 0)
        get dead rows and skip schedule validation — exactly the epochs
        the per-epoch path never resolves a schedule for."""
        n = len(self.node_names)
        modes = [self._epoch_mode(epoch0 + j) for j in range(k)]
        times = []
        for j in range(k):
            t = self.mix_times
            if self.mix_times_schedule is not None and modes[j] != 0:
                t = int(self.mix_times_schedule(epoch0 + j))
                if t < 1:
                    raise ValueError(
                        f"mix_times_schedule({epoch0 + j}) returned "
                        f"{t}; must be >= 1 (0 would silently skip "
                        "gossip while reporting a mixed epoch)"
                    )
            times.append(t)
        sched = {"times": jnp.asarray(times, dtype=jnp.int32)}
        tmax = max(times)
        if self.topology_schedule is not None:
            Ws, omegas = [], []
            for j in range(k):
                if modes[j] != 1:
                    Ws.append(np.eye(n, dtype=np.float32))
                    omegas.append(np.zeros(tmax, np.float32))
                    continue
                W_e = resolve_mixing_matrix(
                    self.topology_schedule(epoch0 + j), self.node_names
                )
                Ws.append(np.asarray(W_e, dtype=np.float32))
                if self.chebyshev:
                    g_e = mixing_gamma(W_e)
                    if not (0.0 <= g_e < 1.0):
                        raise ValueError(
                            f"topology_schedule({epoch0 + j}) produced a "
                            f"graph with gamma={g_e}; Chebyshev "
                            "acceleration needs a connected graph with "
                            "gamma < 1"
                        )
                    omegas.append(
                        np.asarray(
                            chebyshev_omegas(g_e, tmax), dtype=np.float32
                        )
                    )
            sched["W"] = jnp.asarray(np.stack(Ws))
            if self.chebyshev:
                sched["omegas"] = jnp.asarray(np.stack(omegas))
        elif self.chebyshev and self.mix_times_schedule is not None:
            # Static graph, scheduled round count: one omega row serves
            # every epoch (the prefix property — omegas depend only on
            # gamma, and the masked recurrence freezes after t rounds).
            om = np.asarray(
                chebyshev_omegas(self.engine.gamma, tmax),
                dtype=np.float32,
            )
            sched["omegas"] = jnp.asarray(
                np.broadcast_to(om, (k, tmax)).copy()
            )
        if self._async_sim is not None:
            sched["tau"] = jnp.asarray(
                [
                    self._async_tau(epoch0 + j) if modes[j] else 0
                    for j in range(k)
                ],
                dtype=jnp.int32,
            )
        return sched

    def _make_superstep_fn(self, k: int):
        """The raw (unjitted) K-epoch superstep program.

        An outer ``lax.scan`` over ``k`` epochs; each iteration runs the
        SAME epoch body the per-epoch path jits (``self._epoch_fn`` — the
        per-step scan of the vmapped train step) followed by this
        config's gossip program body (the traced-knob ``*_program``
        bodies of ``parallel/consensus.py`` / ``compression.py`` /
        ``robust.py`` — the same computations the top-level engine entry
        points jit, with round counts / matrices / omega rows / tau as
        per-epoch DATA from the ``sched`` operand), selected per epoch
        by the traced ``modes`` vector so ``epoch_cons_num`` gating and
        the Gossip-PGA cadence keep their per-epoch semantics inside one
        compiled program.  Cross-epoch gossip state (CHOCO estimates,
        the async double-buffer) and the previous epoch's consensus
        residual (the adaptive controller's input) thread through the
        scan as the ``gcarry`` operand.  The per-epoch
        loss/acc/grad-norm traces stack to ``(k, steps, n)`` in the scan
        ys (the metrics carry, ``obs/carry.py``), the per-epoch gossip
        round counts to ``(k,)``, the robust redirected mass to ``(k,)``,
        and the post-mix consensus residual is computed in-program every
        epoch (branch-uniformly, after the switch) — so one dispatch
        plus one flush covers everything K calls of ``train_epoch``
        would read.
        """
        engine = self.engine
        adapt = self._adaptive_times_traced
        zero_mass = lambda: jnp.float32(0.0)

        # -- branch 1: this config's mixing program, knobs from sched --- #
        if self._async_sim is not None:
            periods = self._async_sim["periods"]
            if self._robust_cfg is not None:
                prog = engine.robust_async_times_program(
                    self._robust_cfg, periods=periods
                )

                def mix_branch(op):
                    p, mix, sch, res = op
                    t = adapt(sch["times"], res)
                    p, mix, mass = prog(p, mix, t, sch["tau"])
                    return p, mix, t, mass
            else:
                prog = engine.async_gossip_times_program(periods=periods)

                def mix_branch(op):
                    p, mix, sch, res = op
                    t = adapt(sch["times"], res)
                    p, mix = prog(p, mix, t, sch["tau"])
                    return p, mix, t, zero_mass()
        elif self._robust_cfg is not None:
            prog = engine.robust_mix_times_program(self._robust_cfg)

            def mix_branch(op):
                p, mix, sch, res = op
                t = adapt(sch["times"], res)
                p, mass = prog(p, t)
                return p, mix, t, mass
        elif self.topology_schedule is not None:
            if self.chebyshev:
                prog = engine.chebyshev_masked_with_program()

                def mix_branch(op):
                    p, mix, sch, res = op
                    t = sch["times"]  # adaptive excluded with chebyshev
                    p = prog(p, sch["W"], sch["omegas"], t)
                    return p, mix, t, zero_mass()
            elif self.mix_eps is not None:
                prog = engine.mix_until_with_times_program(eps=self.mix_eps)

                def mix_branch(op):
                    p, mix, sch, res = op
                    mn = adapt(sch["times"], res)
                    p, t, _res = prog(p, sch["W"], mn)
                    return p, mix, t, zero_mass()
            else:
                prog = engine.mix_with_times_program()

                def mix_branch(op):
                    p, mix, sch, res = op
                    t = adapt(sch["times"], res)
                    p = prog(p, sch["W"], t)
                    return p, mix, t, zero_mass()
        elif self._choco is not None:
            from distributed_learning_tpu.parallel.compression import (
                ChocoState,
            )

            layout = None
            if self._choco.fused:
                # The fused layout is a static program property; derive
                # it from the concrete stacked params ONCE at build time
                # (exactly what ChocoGossipEngine.run does per call).
                if self._state is None:
                    self.initialize_nodes()
                layout = ops.fused_layout(self._state[0])
            prog = self._choco.superstep_program(layout)

            def mix_branch(op):
                p, mix, sch, res = op
                t = adapt(sch["times"], res)
                with jax.named_scope("compress"):
                    cs = prog(
                        ChocoState(
                            x=p, xhat=mix["xhat"], key=mix["key"],
                            ef=mix["ef"],
                        ),
                        t,
                    )
                return (
                    cs.x,
                    {"xhat": cs.xhat, "key": cs.key, "ef": cs.ef},
                    t,
                    zero_mass(),
                )
        elif self.chebyshev:
            if self.mix_times_schedule is not None:
                prog = engine.chebyshev_masked_program()

                def mix_branch(op):
                    p, mix, sch, res = op
                    t = sch["times"]
                    return prog(p, sch["omegas"], t), mix, t, zero_mass()
            else:
                body = engine.chebyshev_program(self.mix_times)

                def mix_branch(op):
                    p, mix, sch, res = op
                    return body(p), mix, sch["times"], zero_mass()
        elif self.mix_eps is not None:
            prog = engine.mix_until_times_program(eps=self.mix_eps)

            def mix_branch(op):
                p, mix, sch, res = op
                mn = adapt(sch["times"], res)
                p, t, _res = prog(p, mn)
                return p, mix, t, zero_mass()
        else:
            prog = engine.mix_times_program()

            def mix_branch(op):
                p, mix, sch, res = op
                t = adapt(sch["times"], res)
                return prog(p, t), mix, t, zero_mass()

        # -- branches 0 / 2: skip, and the Gossip-PGA all-reduce -------- #
        def skip_branch(op):
            p, mix, sch, res = op
            return p, mix, jnp.int32(0), zero_mass()

        gavg_body = engine.global_average_program()
        if self._choco is not None:
            seed = self.seed
            ef_on = self._choco.error_feedback

            def gavg_branch(op):
                p, mix, sch, res = op
                p = gavg_body(p)
                # Host parity (_gossip's mode 2): the estimates tracked
                # the pre-all-reduce iterates — reset to the state a
                # fresh lazy init would build next epoch.
                mix = {
                    "xhat": jax.tree.map(jnp.zeros_like, p),
                    "key": jax.random.key(seed + 2),
                    "ef": (
                        jax.tree.map(jnp.zeros_like, p)
                        if ef_on else None
                    ),
                }
                return p, mix, jnp.int32(1), zero_mass()
        else:

            def gavg_branch(op):
                p, mix, sch, res = op
                return gavg_body(p), mix, jnp.int32(1), zero_mass()

        branches = [skip_branch, mix_branch, gavg_branch]
        max_dev = engine.max_deviation_program()
        epoch_fn = self._epoch_fn

        def superstep_fn(state, gcarry, Xs, ys, idx, modes, sched):
            def body(carry, inp):
                state, gc = carry
                idx_e, mode_e, sched_e = inp
                state, losses, accs, gnorms, counters = epoch_fn(
                    state, Xs, ys, idx_e
                )
                params, bs, opt, rng = state
                with jax.named_scope("mix"):
                    params, mix, rounds, mass = jax.lax.switch(
                        mode_e, branches,
                        (params, gc["mix"], sched_e, gc["res"]),
                    )
                # Post-mix residual, branch-uniform (outside the
                # switch): the per-epoch consensus trace AND the
                # adaptive controller's next-epoch input.
                res = max_dev(params)
                return (
                    ((params, bs, opt, rng), {"mix": mix, "res": res}),
                    (losses, accs, gnorms, rounds, mass, res, counters),
                )

            (state, gcarry), ys_out = jax.lax.scan(
                body, (state, gcarry), (idx, modes, sched)
            )
            losses, accs, gnorms, rounds, masses, devs, counters = ys_out
            return (
                state, gcarry, losses, accs, gnorms, rounds, masses,
                devs, counters,
            )

        return superstep_fn

    def _build_superstep(self, k: int):
        """Jitted superstep for chunk size ``k`` (cached per k; the index
        array's leading axis is part of the program shape).  The carried
        state AND the gossip carry are donated exactly like
        ``_jit_epoch``'s state — across the whole superstep the stacked
        params/opt/estimate buffers are updated in place."""
        fn = self._superstep_cache.get(k)
        if fn is None:
            fn = jax.jit(
                self._make_superstep_fn(k),
                donate_argnums=(0, 1) if self._donate_active else (),
            )
            self._superstep_cache[k] = fn
        return fn

    def train_epochs(self, k: int) -> List[Dict[str, Any]]:
        """Run ``k`` epochs as ONE compiled superstep dispatch; returns
        the per-epoch payloads (same schema as :meth:`train_epoch`).

        The trajectory is bit-identical to ``k`` calls of
        :meth:`train_epoch` — same shuffle streams, same step/gossip
        programs, same PRNG threading — for EVERY gossip config: plain
        ``mix_times``, ``mix_eps``, ``chebyshev``, ``global_avg_every``,
        ``mix_times_schedule``, ``topology_schedule``, ``compression``
        (CHOCO), ``async_gossip``, ``robust_mixing``, and the
        ``adaptive_comm`` controller (per-epoch schedules ride as traced
        data; cross-epoch gossip state threads through the scan carry).
        One reporting difference: test-set evaluation is produced once
        per superstep (at the boundary, on the final state) rather than
        per epoch — intermediate payloads carry ``test_acc=None``.  The
        consensus residual is computed in-program every epoch, so every
        payload carries its ``deviation``.
        """
        k = int(k)
        if k < 1:
            raise ValueError(f"train_epochs needs k >= 1, got {k}")
        if k == 1:
            # One epoch needs no outer scan; the per-epoch program is
            # already compiled (and is the oracle the superstep is
            # measured against).
            return [self.train_epoch()]
        with self._span("trainer.superstep", epoch=self._epochs_done, k=k):
            return self._train_superstep(k)

    def _train_superstep(self, k: int) -> List[Dict[str, Any]]:
        if self._state is None:
            self.initialize_nodes()
        self._maybe_profile_costs(k)
        epoch0 = self._epochs_done
        # The chunk's operands: shuffle indices (ONE host->device copy),
        # per-epoch modes and gossip schedule, the gossip carry.
        with self._span("trainer.indices", epoch=epoch0):
            idx = self._superstep_indices(epoch0, k)
            modes_host = [self._epoch_mode(epoch0 + j) for j in range(k)]
            modes = jnp.asarray(modes_host, dtype=jnp.int32)
            sched = self._superstep_sched(epoch0, k)
            gcarry = self._superstep_carry()
        fn = self._build_superstep(k)
        timer = self._cost_timer
        sampled = timer.tick() if timer is not None else False
        t0 = time.perf_counter() if sampled else 0.0
        try:
            with self._span("trainer.dispatch", epoch=epoch0):
                (
                    self._state, gcarry, losses, accs, gnorms, rounds,
                    masses, devs, counters,
                ) = fn(
                    self._state, gcarry, self._Xs, self._ys, idx, modes,
                    sched,
                )
            self._count_dispatch()
            # The superstep's single host boundary: traces, per-epoch
            # round counts / residuals / robust mass all materialize
            # here (flush_chunk collapses the (k, steps, n) traces to
            # one k*steps-step chunk for the registry).
            with self._span("trainer.flush", epoch=epoch0):
                arrs = flush_chunk(
                    self._obs_registry,
                    {"loss": losses, "acc": accs, "grad_norm": gnorms,
                     **counters},
                    step0=self._global_step,
                    node_names=self.node_names,
                )
                losses = arrs["loss"]  # (k, steps, n)
                accs = arrs["acc"]
                gnorms = arrs["grad_norm"]
                counters = {name: arrs[name] for name in counters}
                rounds_host = np.asarray(rounds)  # (k,)
                devs_host = np.asarray(devs)  # (k,)
                masses_host = (
                    np.asarray(masses)
                    if self._robust_cfg is not None else None
                )
                if sampled:
                    from distributed_learning_tpu.obs.cost import (
                        get_profile,
                    )

                    # One sample covers the whole K-epoch dispatch (the
                    # superstep IS the chunk); MFU comes from the
                    # matching superstep profile when registered.
                    # loop_steps: the nested epoch-over-step scans run
                    # the (once-counted) body k * epoch_len times.
                    timer.measure(
                        self._state, t0, name="trainer.superstep",
                        profile=get_profile(f"trainer.superstep{k}"),
                        loop_steps=k * self.epoch_len,
                        step=self._global_step,
                    )
        except BaseException:
            # Same donation discipline as _train_epoch: the donated input
            # buffers may already be gone; drop the dangling references
            # (the gossip carry is donated too — its host mirrors may
            # hold deleted arrays).
            if self._donate_active:
                self._state = None
                self._choco_xhat = None
                self._choco_ef = None
                self._async_state = None
            raise

        # Sync the host mirrors from the returned carry, so per-epoch
        # calls (or a checkpoint) interleaved with supersteps continue
        # the same trajectory.
        if self._choco is not None:
            self._choco_xhat = gcarry["mix"]["xhat"]
            self._choco_key = gcarry["mix"]["key"]
            self._choco_ef = gcarry["mix"]["ef"]
        elif self._async_sim is not None:
            self._async_state = gcarry["mix"]
        if self._adaptive_cfg is not None:
            self._adaptive_res = np.float32(devs_host[-1])

        steps = losses.shape[1]
        params, bs, _opt, _rng = self._state
        test_accs = None
        if self.test_data is not None:
            # Evaluated once per superstep, on the boundary state.
            with self._span("trainer.eval", epoch=epoch0):
                test_accs = self._eval_accuracy(params, bs)

        payloads: List[Dict[str, Any]] = []
        with self._span("trainer.stats", epoch=epoch0):
            for j in range(k):
                epoch_idx = epoch0 + j
                final = j == k - 1
                step_base = self._global_step
                for s in range(0, steps, self.stat_step):
                    chunk = slice(s, min(s + self.stat_step, steps))
                    for a, name in enumerate(self.node_names):
                        node = self.network[name]
                        node.stats.steps.append(step_base + chunk.stop)
                        node.stats.train_loss.append(
                            float(losses[j, chunk, a].mean())
                        )
                        node.stats.train_acc.append(
                            float(accs[j, chunk, a].mean())
                        )
                self._global_step += steps
                self._epochs_done += 1
                payloads.append({
                    "epoch": epoch_idx,
                    "mixed": modes_host[j] != 0,
                    "train_loss": losses[j].mean(axis=0),
                    "train_acc": accs[j].mean(axis=0),
                    "grad_norm": gnorms[j].mean(axis=0),
                    "test_acc": test_accs if final else None,
                    "mix_rounds": int(rounds_host[j]),
                    "deviation": float(devs_host[j]),
                    **({"counters": {c: v[j] for c, v in counters.items()}}
                       if counters else {}),
                })
                if self._obs_registry is not None:
                    # Per-epoch consensus traces, as on the per-epoch path
                    # (the adaptive controller's readout; arXiv 2105.09080
                    # headline residual series).
                    self._obs_registry.observe(
                        "consensus.residual", float(devs_host[j]),
                        step=self._global_step,
                    )
                    if modes_host[j]:
                        self._obs_registry.inc(
                            "consensus.rounds_run", int(rounds_host[j])
                        )
                        if masses_host is not None:
                            mass_j = float(masses_host[j])
                            self._obs_registry.inc(
                                "consensus.robust.clipped_mass", mass_j
                            )
                            self._obs_registry.observe(
                                "consensus.robust.mass", mass_j,
                                step=self._global_step,
                            )
        if test_accs is not None:
            for a, name in enumerate(self.node_names):
                node = self.network[name]
                node.stats.test_acc.append(float(test_accs[a]))
                node.stats.test_epochs.append(self._global_step)

        if self._obs_registry is not None:
            if test_accs is not None:
                self._obs_registry.observe(
                    "eval.test_acc", float(np.mean(test_accs)),
                    step=self._global_step,
                )
        if self.telemetry is not None:
            cost_keys = (
                {}
                if self._cost_timer is None
                else {
                    "step_time_s": (
                        self._cost_timer.last_step_time_s if sampled
                        else None
                    ),
                    "mfu": self._cost_timer.last_mfu if sampled else None,
                }
            )
            with self._span("trainer.telemetry", epoch=epoch0):
                for payload in payloads:
                    for a, name in enumerate(self.node_names):
                        self.telemetry.process(
                            name,
                            {
                                "epoch": payload["epoch"],
                                "train_loss": float(payload["train_loss"][a]),
                                "train_acc": float(payload["train_acc"][a]),
                                "grad_norm": float(payload["grad_norm"][a]),
                                "test_acc": None
                                if payload["test_acc"] is None
                                else float(payload["test_acc"][a]),
                                "mix_rounds": payload["mix_rounds"],
                                "deviation": payload["deviation"],
                                **cost_keys,
                            },
                        )
        return payloads

    def start_consensus(self) -> List[Dict[str, Any]]:
        """Run the full training schedule (parity:
        ``master.start_consensus()``) — in superstep chunks of
        ``self.superstep`` epochs when configured (one compiled dispatch
        per chunk; a short final chunk compiles once more)."""
        results: List[Dict[str, Any]] = []
        while self._epochs_done < self.num_epochs:
            k = min(self.superstep, self.num_epochs - self._epochs_done)
            results.extend(self.train_epochs(k))
        return results

    # ------------------------------------------------------------------ #
    @property
    def state(self):
        """Current (params, batch_stats, opt_state, rng) tuple.

        With ``donate_state=True`` (default) the arrays are donated to the
        next ``train_epoch`` on accelerators — read state AFTER training,
        not across epochs.
        """
        return self._state

    def node_parameters(self) -> Dict[Hashable, Pytree]:
        params = self._state[0]
        trees = ops.unstack_tree(params, len(self.node_names))
        return dict(zip(self.node_names, trees))

    def parameter_deviation(self) -> float:
        return float(self.engine.max_deviation(self._state[0]))

    # -- checkpointing ------------------------------------------------- #
    def save_checkpoint(self, path: str) -> None:
        from distributed_learning_tpu.training.checkpoint import save_checkpoint

        if self._state is None:
            self.initialize_nodes()
        params, bs, opt, rng = self._state
        tree = {
            "params": params,
            "batch_stats": bs if bs is not None else {},
            "opt_state": opt,
            "rng": jax.random.key_data(rng),
            "epochs_done": self._epochs_done,
            "global_step": self._global_step,
        }
        if self._choco is not None:
            # Compressed runs checkpoint the CHOCO error-feedback state:
            # resuming with fresh (zero) estimates would re-converge, but
            # the resumed trajectory would silently diverge from the
            # uninterrupted one.  The tree shape is config-determined
            # (compression on/off), so templates stay structural.
            tree["choco"] = self._choco_tree()
        save_checkpoint(path, tree)

    def _choco_tree(self):
        """CHOCO state as a checkpointable subtree; ``present`` records
        whether estimates exist yet (no gossip round has run before the
        first consensus epoch)."""
        params = self._state[0]
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
        if self._choco_xhat is not None:
            tree = {
                "present": 1,
                "xhat": self._choco_xhat,
                "key": jax.random.key_data(self._choco_key),
            }
            if self._choco.error_feedback:
                tree["ef"] = (
                    self._choco_ef if self._choco_ef is not None
                    else zeros()
                )
            return tree
        tree = {
            "present": 0,
            "xhat": zeros(),
            "key": jax.random.key_data(jax.random.key(self.seed + 2)),
        }
        if self._choco.error_feedback:
            # EF banks restart at zero with the estimates; the subtree
            # shape stays config-determined (error_feedback on/off).
            tree["ef"] = zeros()
        return tree

    def restore_checkpoint(self, path: str) -> None:
        from distributed_learning_tpu.training.checkpoint import (
            restore_checkpoint,
            saved_tree_metadata,
        )

        if self._state is None:
            self.initialize_nodes()
        params, bs, opt, rng = self._state
        template = {
            "params": params,
            "batch_stats": bs if bs is not None else {},
            "opt_state": opt,
            "rng": jax.random.key_data(rng),
            "epochs_done": 0,
            "global_step": 0,
        }
        # The tree on disk says whether it carries CHOCO state; the
        # template is built to match it, so a trainer of either kind
        # reads a checkpoint of either kind.
        saved_choco = saved_tree_metadata(path).get("choco")
        if self._choco is not None and saved_choco is not None:
            template["choco"] = self._choco_tree()
        elif self._choco is not None:
            # Checkpoint saved before CHOCO state was checkpointed (or
            # by a dense trainer): old semantics — estimates reset,
            # error feedback re-converges.
            warnings.warn(
                "checkpoint has no CHOCO state (saved by an older "
                "version or a dense trainer); estimates reset to zero "
                "and error feedback re-converges over the next few "
                "epochs"
            )
        elif saved_choco is not None:
            # Dense trainer reading a compressed run's checkpoint:
            # restore the training state and ignore the CHOCO subtree
            # (orbax wants the whole on-disk structure in the template).
            warnings.warn(
                "checkpoint contains CHOCO state but this trainer has "
                "no compression; the estimates are ignored"
            )
            template["choco"] = jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype),
                saved_choco,
            )
        restored = restore_checkpoint(path, template)
        if self._choco is None:
            restored.pop("choco", None)
        self._state = (
            restored["params"],
            restored["batch_stats"] if bs is not None else None,
            restored["opt_state"],
            self._on_every_chip(jax.random.wrap_key_data(restored["rng"])),
        )
        self._choco_xhat = None
        self._choco_ef = None
        choco_tree = restored.get("choco")
        if choco_tree is not None and int(choco_tree["present"]):
            self._choco_xhat = choco_tree["xhat"]
            self._choco_key = jax.random.wrap_key_data(choco_tree["key"])
            if "ef" in choco_tree:
                self._choco_ef = choco_tree["ef"]
        self._epochs_done = int(restored["epochs_done"])
        self._global_step = int(restored["global_step"])


class MasterNode(GossipTrainer):
    """Exact constructor parity with the documented reference surface
    (``Man_Colab.ipynb`` cell 21).  ``train_loaders``/``test_loader`` accept
    ``(X, y)`` arrays (this framework's pipelines) and are forwarded to
    :class:`GossipTrainer` as ``train_data``/``test_data``."""

    def __init__(
        self,
        node_names,
        model,
        model_args=(),
        optimizer="sgd",
        optimizer_kwargs=None,
        error="cross_entropy",
        weights=None,
        train_loaders=None,
        test_loader=None,
        stat_step=100,
        epoch=10,
        epoch_len=None,
        epoch_cons_num=1,
        **kwargs,
    ):
        super().__init__(
            node_names=list(node_names),
            model=model,
            model_args=model_args,
            optimizer=optimizer,
            optimizer_kwargs=optimizer_kwargs,
            error=error,
            weights=weights,
            train_data=train_loaders,
            test_data=test_loader,
            stat_step=stat_step,
            epoch=epoch,
            epoch_len=epoch_len,
            epoch_cons_num=epoch_cons_num,
            **kwargs,
        )
