"""ExperimentConfig + CLI tests: JSON round-trips, builds, and the
reference main.py flag surface end to end (train -> checkpoint -> resume
-> testOnly)."""

import json
import os

import numpy as np
import pytest

from distributed_learning_tpu.cli import build_parser, config_from_args, main
from distributed_learning_tpu.training import DATASET_DEFAULTS, ExperimentConfig


def test_config_json_roundtrip(tmp_path):
    cfg = ExperimentConfig(
        node_names=[0, 1, 2],
        topology="complete",
        model="ann",
        model_args=[10],
        dataset="cifar10",
        epoch=2,
        batch_size=16,
        mix_times=3,
    )
    path = tmp_path / "cfg.json"
    cfg.save(str(path))
    back = ExperimentConfig.load(str(path))
    assert back == cfg
    with pytest.raises(ValueError, match="unknown config fields"):
        ExperimentConfig.from_json(json.dumps({"bogus_field": 1}))


def test_config_build_and_train_epoch():
    cfg = ExperimentConfig(
        node_names=[0, 1, 2, 3],
        topology="ring",
        weight_mode="sdp",
        model="ann",
        model_args=[10],
        model_kwargs={"hidden_dim": 16},
        dataset="cifar10",
        n_train=256,
        epoch=1,
        batch_size=16,
        stat_step=2,
        dropout=False,
    )
    master = cfg.build()
    master.initialize_nodes()
    out = master.train_epoch()
    assert out["mixed"] and np.isfinite(out["deviation"])


def test_config_topology_families_default_args():
    for name in ("ring", "chain", "complete", "star", "watts_strogatz",
                 "erdos_renyi", "grid2d", "torus2d"):
        cfg = ExperimentConfig(node_names=list(range(6)), topology=name)
        assert cfg.build_topology().n_agents == 6, name
    # Exact-cover validation: mismatched families fail loudly, up front.
    assert ExperimentConfig(
        node_names=list(range(8)), topology="hypercube"
    ).build_topology().n_agents == 8
    with pytest.raises(ValueError, match="power-of-two"):
        ExperimentConfig(node_names=list(range(6)), topology="hypercube").build_topology()
    with pytest.raises(ValueError, match="factorization"):
        ExperimentConfig(node_names=list(range(5)), topology="torus2d").build_topology()
    with pytest.raises(ValueError, match="unknown topology"):
        ExperimentConfig(topology="petersen").build_topology()


def test_config_file_not_clobbered_by_cli_defaults(tmp_path):
    """--config fields survive unless a flag is explicitly given."""
    cfg = ExperimentConfig(
        node_names=list(range(8)), topology="complete", model="wide-resnet",
        model_args=[100], model_kwargs={"depth": 10, "widen_factor": 1,
                                        "dropout_rate": 0.0},
        dataset="cifar100", learning_rate=0.05, epoch=7, batch_size=32,
        mix_times=5,
    )
    path = tmp_path / "exp.json"
    cfg.save(str(path))
    args = build_parser().parse_args(["--config", str(path)])
    resolved = config_from_args(args)
    assert resolved.topology == "complete"
    assert resolved.model == "wide-resnet"
    assert resolved.model_kwargs["depth"] == 10
    assert len(resolved.node_names) == 8
    assert resolved.learning_rate == 0.05
    assert resolved.epoch == 7 and resolved.batch_size == 32
    assert resolved.mix_times == 5
    # An explicit flag still overrides...
    args = build_parser().parse_args(
        ["--config", str(path), "--epochs", "3", "--net_type", "ann"]
    )
    resolved = config_from_args(args)
    assert resolved.epoch == 3
    # ...and switching net type rebuilds the model spec (no WRN kwargs leak).
    assert resolved.model == "ann" and resolved.model_kwargs == {}
    assert resolved.model_args == [100]  # cifar100 classes


def test_no_donate_flag_disables_state_donation():
    args = build_parser().parse_args(["--no-donate"])
    assert config_from_args(args).donate_state is False
    args = build_parser().parse_args([])
    assert config_from_args(args).donate_state is True


def test_wrn_schedule_short_runs_compound_collisions():
    from distributed_learning_tpu.training import wrn_lr_schedule

    sched = wrn_lr_schedule(1.0, 2, 10)  # 30%/60% collide at step 0/10
    assert float(sched(0)) == 1.0  # no decay at step 0
    # Steps past every boundary: compounded factors, none silently lost.
    assert float(sched(100)) == pytest.approx(0.2 * 0.2)


def test_config_rejects_sdp_with_time_varying():
    cfg = ExperimentConfig(
        node_names=[0, 1, 2], weight_mode="sdp", time_varying_p=0.5,
        dataset="cifar10", n_train=64, batch_size=8, model="ann",
        model_args=[10],
    )
    with pytest.raises(ValueError, match="time_varying_p"):
        cfg.build()


def test_cli_dump_config(tmp_path, capsys):
    out = tmp_path / "dumped.json"
    rc = main([
        "--net_type", "wide-resnet", "--depth", "10", "--widen_factor", "1",
        "--dataset", "cifar100", "--nodes", "8", "--topology", "torus2d",
        "--dump-config", str(out),
    ])
    assert rc == 0
    cfg = ExperimentConfig.load(str(out))
    assert cfg.model == "wide-resnet"
    assert cfg.model_kwargs["depth"] == 10
    assert cfg.model_args == [100]
    assert cfg.epoch == DATASET_DEFAULTS["cifar100"]["num_epochs"]
    assert len(cfg.node_names) == 8 and cfg.topology == "torus2d"


def test_cli_train_checkpoint_resume_testonly(tmp_path, capsys, monkeypatch):
    """The reference main.py workflow: train, auto-checkpoint, --resume
    continues from the saved epoch, -t evaluates only."""
    # The trainer branch is an entry point and places the compile cache;
    # with the variable set it leaves this process's JAX config alone.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    ckpt = str(tmp_path / "ckpt")
    base = [
        "--net_type", "ann", "--dataset", "cifar10", "--nodes", "2",
        "--epochs", "1", "--batch-size", "16", "--n-train", "128",
        "--stat-step", "2", "--checkpoint-dir", ckpt, "--dropout", "0",
    ]
    assert main(base) == 0
    assert os.path.exists(ckpt)
    out1 = capsys.readouterr().out
    assert "epoch   1/1" in out1

    # Resume with a higher target: starts from epoch 2.
    assert main(base[:-4] + ["--epochs", "2", "--resume",
                             "--checkpoint-dir", ckpt, "--dropout", "0"]) == 0
    out2 = capsys.readouterr().out
    assert "restored checkpoint" in out2 and "epoch   2/2" in out2

    assert main(base + ["--testOnly"]) == 0
    out3 = capsys.readouterr().out
    assert "test acc" in out3


def test_config_compression_builds_choco_trainer(tmp_path):
    from distributed_learning_tpu.training.config import ExperimentConfig

    cfg = ExperimentConfig(
        node_names=[0, 1], dataset="titanic", model="ann",
        model_args=[2], epoch=1, batch_size=8, n_train=32,
        compression="topk:0.5", compression_gamma=0.25,
    )
    # JSON roundtrip keeps the spec.
    path = tmp_path / "c.json"
    cfg.save(path)
    cfg2 = ExperimentConfig.load(path)
    assert cfg2.compression == "topk:0.5"
    trainer = cfg2.build()
    assert trainer._choco is not None
    assert trainer._choco.gamma == 0.25
