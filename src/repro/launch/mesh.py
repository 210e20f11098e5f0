"""(data × model) mesh construction over an explicit device list.

A function, not a module constant, so importing this module never touches
jax device state: multi-device callers set XLA_FLAGS before the first jax
device query, and smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations

import jax


def mesh_from_devices(devices, n_data: int, n_model: int):
    """(data × model) mesh over an explicit device list.

    ``repro.dist.bootstrap`` passes the GLOBAL device list of a
    ``jax.distributed`` bring-up, producing a process-spanning mesh with
    the same axis names — everything downstream (shard_map supersteps,
    PartitionSpecs, ALB budgets) is mesh-shape-agnostic and runs unchanged.
    Devices are laid out row-major, so with the default one-device-per-
    process bring-up consecutive model columns land on consecutive
    processes (the feature-shard ↔ process map ``repro.dist.bootstrap.
    column_process_map`` reads back).
    """
    n = n_data * n_model
    devices = list(devices)[:n]
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices).reshape(n_data, n_model),
                             ("data", "model"))
