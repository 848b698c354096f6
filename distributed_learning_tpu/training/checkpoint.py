"""Checkpoint/resume via orbax (capability absent from the reference source;
its only checkpointing lived in the external submodule's ``main.py``, driven
by ``CIFAR_10_Baseline.ipynb`` cell 7).

Saved state: stacked per-node params, optimizer slots, BatchNorm stats, PRNG
key data, and the epoch/step counters — everything needed to resume a gossip
run bit-exactly.
"""

from __future__ import annotations

import os
from typing import Any

import jax

__all__ = ["save_checkpoint", "restore_checkpoint", "saved_tree_metadata"]


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def save_checkpoint(path: str, state: Any) -> None:
    """Write ``state`` (a pytree) to ``path`` (a directory), overwriting
    atomically: the new checkpoint is fully written to a sibling tmp dir
    before the old one is replaced, so a failed save never destroys the
    previous checkpoint."""
    import shutil

    path = os.path.abspath(path)
    tmp = path + ".tmp-save"
    old = path + ".old-save"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    ckptr = _checkpointer()
    ckptr.save(tmp, state)
    ckptr.wait_until_finished()
    # Two renames instead of rmtree-then-rename: at every instant either
    # ``path`` or a fully written sibling holds a complete checkpoint.
    if os.path.exists(path):
        if os.path.exists(old):
            shutil.rmtree(old)
        os.replace(path, old)
    os.replace(tmp, path)
    if os.path.exists(old):
        shutil.rmtree(old)


def restore_checkpoint(path: str, template: Any) -> Any:
    """Read a pytree with the shapes/dtypes of ``template`` from ``path``."""
    ckptr = _checkpointer()
    return ckptr.restore(os.path.abspath(path), template)


def saved_tree_metadata(path: str) -> dict:
    """The tree a checkpoint at ``path`` holds, as orbax's per-leaf
    metadata (``.shape``/``.dtype``) — what lets a caller build a
    restore template for subtrees it does not own."""
    return _checkpointer().metadata(os.path.abspath(path)).item_metadata.tree
