"""Checkpointing: disk ("remote storage") and in-memory (Gemini-style
neighbour copies). The TrainMover runtime uses both: unexpected-failure
recovery pulls from a neighbour's in-memory checkpoint when redundancy
exists, else from remote storage (§7 State Synchronization).
"""
from __future__ import annotations

import os
import pickle
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core import tracing


def _flatten(tree) -> Tuple[list, Any]:
    leaves, treedef = jax.tree.flatten(tree)
    return [np.asarray(l) for l in leaves], treedef


def tree_bytes(tree) -> int:
    """Bytes of every leaf, read from array metadata: a device array is
    sized where it lives, never copied to the host to be measured.
    Python scalars size as the numpy array they would become."""
    return sum(l.nbytes if hasattr(l, "nbytes") else np.asarray(l).nbytes
               for l in jax.tree.leaves(tree))


def save(path: str, tree, step: int) -> int:
    """Write a checkpoint; returns bytes written."""
    leaves, treedef = _flatten(tree)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    payload = {"step": step, "treedef": treedef,
               "leaves": leaves}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        pickle.dump(payload, f, protocol=4)
    os.replace(tmp, path)
    return sum(l.nbytes for l in leaves)


def load(path: str) -> Tuple[Any, int]:
    with open(path, "rb") as f:
        payload = pickle.load(f)
    tree = jax.tree.unflatten(payload["treedef"], payload["leaves"])
    return tree, payload["step"]


class InMemoryCheckpoint:
    """Per-iteration host-memory checkpoint with neighbour redundancy.

    Each logical node keeps its own latest state plus a copy of its ring
    neighbour's — a failed node's state is then recoverable from the
    surviving neighbour at RDMA speed (paper refs [48, 49]).

    A state is whatever `engine.get_state` snapshots. On the flat-buffer
    path that is the flat optimizer state (Adam's moments, the fp32
    master weights, Adam's step), the step and the stage, and no params:
    `engine.set_state` rebuilds them bitwise from the master weights.
    """

    def __init__(self):
        self._own: Dict[int, Tuple[int, Any]] = {}
        # owner -> (holder_node, step, state): replica of `owner`'s state
        # living in `holder`'s host memory.
        self._replica: Dict[int, Tuple[int, int, Any]] = {}

    def put(self, node: int, step: int, state, ring: list) -> None:
        with tracing.span("tm:imc_put"):
            host = jax.tree.map(np.asarray, state)
        self._own[node] = (step, host)
        if len(ring) > 1:
            holder = ring[(ring.index(node) + 1) % len(ring)]
            self._replica[node] = (holder, step, host)

    def get(self, node: int):
        """Recover `node`'s state: own copy, else surviving replica."""
        if node in self._own:
            return self._own[node]
        if node in self._replica:
            holder, step, state = self._replica[node]
            if holder in self._own or any(
                    h == holder for h, _, _ in self._replica.values()):
                return (step, state)
        return None

    def drop_node(self, node: int) -> None:
        """Simulate node loss: its host memory (own copy + any replicas
        it holds for peers) disappears."""
        self._own.pop(node, None)
        for owner in [o for o, (h, _, _) in self._replica.items()
                      if h == node]:
            self._replica.pop(owner)

    def bytes_for(self, node: int) -> int:
        hit = self.get(node)
        return 0 if hit is None else tree_bytes(hit[1])
