"""Elastic scaling: rebuild the mesh for a changed device count and
reshard a checkpointed state onto the new topology.

Mesh builders are pure functions of device count, and checkpoints are
topology-free (plain host arrays), so elasticity reduces to:

    state_np  = gather(state)                  # topology-free
    new_mesh  = choose_mesh(len(live_devices))
    new_state = shard(state_np, new_specs(new_mesh))

The round-trip 8 -> 4 -> 8 devices is covered by
tests/test_distributed.py::test_elastic_reshard_roundtrip.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

PyTree = Any


def choose_mesh_shape(n_devices: int, model_parallel: int = 1) -> Tuple[int, int]:
    """(data, model) for the live device count; model axis capped at the
    configured TP degree, remainder goes to data."""
    model = 1
    for cand in range(min(model_parallel, n_devices), 0, -1):
        if n_devices % cand == 0:
            model = cand
            break
    return n_devices // model, model


def gather_state(state: PyTree) -> PyTree:
    """Device state -> host numpy (topology-free)."""
    return jax.tree.map(lambda x: np.asarray(x), state)


def reshard(state_np: PyTree, specs: PyTree, mesh) -> PyTree:
    """Host state -> device state under a (new) mesh + spec tree."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(
        put, state_np, specs,
        is_leaf=lambda x: isinstance(x, np.ndarray))


def elastic_transition(state: PyTree, old_mesh, new_mesh, specs_for):
    """Full transition: gather off old topology, reshard to new.
    ``specs_for(mesh, abstract_state)`` returns the spec tree."""
    host = gather_state(state)
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), host)
    specs = specs_for(new_mesh, abstract)
    return reshard(host, specs, new_mesh)
