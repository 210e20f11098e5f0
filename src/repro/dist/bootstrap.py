"""Multi-process bring-up: ``jax.distributed`` + process-spanning meshes.

This is the layer that takes the solver from a single-process mesh of fake
devices to N real OS processes (one per node), each owning its addressable
shard of a GLOBAL (data × model) mesh:

    ctx  = bootstrap.initialize()          # env-var driven; no-op if solo
    mesh = bootstrap.make_dist_mesh()      # (1, M) over ALL processes
    solver = GLMSolver(X, y, mesh=mesh, ...)

Contracts:

  * **env-var and CLI driven** — ``initialize()`` reads
    ``REPRO_DIST_COORD`` / ``REPRO_DIST_NPROCS`` / ``REPRO_DIST_PROCID``
    (set by ``repro.dist.launcher`` and ``launch/dist_run.py``), or takes
    the same values as keyword arguments.  When neither names more than
    one process it returns a single-process context WITHOUT touching
    ``jax.distributed`` — every existing entry point runs unchanged.
  * **CPU collectives** — cross-process collectives on the CPU backend
    need the gloo implementation; ``initialize()`` flips
    ``jax_cpu_collectives_implementation`` BEFORE the backend is created
    (it must run before the first jax device query, like the
    XLA_FLAGS contract in ``launch/mesh.py``).
  * **global placement** — host arrays cannot be ``device_put`` onto a
    sharding that spans non-addressable devices; ``put_global`` routes
    through ``jax.make_array_from_callback`` (each process contributes the
    shards it owns from its replicated host copy), and ``gather_to_host``
    is the inverse: an all-gather-to-replicated jitted identity whose
    output every process can read.  Both degenerate to plain
    ``device_put`` / ``np.asarray`` on a single-process mesh, so
    ``core/solver.py`` calls them unconditionally.
  * **barriers / KV store** — thin wrappers over the jax distributed
    runtime client used by telemetry exchange, coordinator-only
    checkpointing and the fault guard.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import numpy as np

ENV_COORD = "REPRO_DIST_COORD"
ENV_NPROCS = "REPRO_DIST_NPROCS"
ENV_PROCID = "REPRO_DIST_PROCID"

_CONTEXT: Optional["DistContext"] = None


@dataclasses.dataclass(frozen=True)
class DistContext:
    """What one process knows about the job it is part of."""
    process_id: int
    num_processes: int
    coordinator: Optional[str]          # None in single-process runs

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0

    @property
    def multiprocess(self) -> bool:
        return self.num_processes > 1


def context() -> DistContext:
    """The active context (single-process default until ``initialize``)."""
    global _CONTEXT
    if _CONTEXT is None:
        _CONTEXT = DistContext(0, 1, None)
    return _CONTEXT


def initialize(*, coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               timeout_s: float = 60.0) -> DistContext:
    """Bring up ``jax.distributed`` from env vars or explicit arguments.

    Must run before the first jax backend use (it configures the CPU
    collectives implementation).  Idempotent: a second call returns the
    existing context.  With ``num_processes`` ≤ 1 this is a no-op
    single-process fallback — the same entry point works launched solo or
    under ``repro.dist.launcher``.
    """
    global _CONTEXT
    if _CONTEXT is not None and _CONTEXT.multiprocess:
        return _CONTEXT
    coordinator = coordinator or os.environ.get(ENV_COORD)
    if num_processes is None:
        num_processes = int(os.environ.get(ENV_NPROCS, "1"))
    if process_id is None:
        process_id = int(os.environ.get(ENV_PROCID, "0"))
    if num_processes <= 1 or coordinator is None:
        _CONTEXT = DistContext(0, 1, None)
        return _CONTEXT

    import jax
    try:
        # cross-process CPU collectives (psum/all-gather through shard_map)
        # run on gloo; must be set before backend initialization
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass  # non-CPU backends bring their own collectives
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=int(timeout_s))
    _CONTEXT = DistContext(process_id, num_processes, coordinator)
    return _CONTEXT


def _reset_for_tests():
    global _CONTEXT
    _CONTEXT = None


# ---------------------------------------------------------------------------
# process-spanning mesh construction (layered onto launch/mesh.py)
# ---------------------------------------------------------------------------

def make_dist_mesh(n_data: int = 1, n_model: Optional[int] = None):
    """(data × model) mesh over ALL global devices of the job.

    Defaults to the paper layout: one row of feature blocks,
    ``n_model = total device count`` — with the launcher's
    one-device-per-process bring-up that is exactly one feature shard per
    process.  Single-process runs get the ordinary local mesh, so code
    written against this helper runs anywhere.
    """
    import jax

    from repro.launch import mesh as mesh_lib
    devices = jax.devices()
    if n_model is None:
        if len(devices) % n_data:
            raise ValueError(
                f"{len(devices)} global devices do not split into "
                f"n_data={n_data} rows")
        n_model = len(devices) // n_data
    return mesh_lib.mesh_from_devices(devices, n_data, n_model)


def column_process_map(mesh, axis_model: str = "model") -> np.ndarray:
    """(M,) process index owning each model column of ``mesh``.

    The feature-shard ↔ process bookkeeping behind telemetry-driven ALB:
    node speeds are measured per PROCESS, tile budgets are spent per model
    COLUMN.  A column spanning several processes (D > 1 across process
    boundaries) reports the FIRST owner; per-column budgets are identical
    down a mesh column anyway.
    """
    axes = list(mesh.axis_names)
    dev = np.moveaxis(np.asarray(mesh.devices), axes.index(axis_model), -1)
    dev = dev.reshape(-1, dev.shape[-1])
    return np.asarray([d.process_index for d in dev[0]], np.int64)


def local_columns(mesh, axis_model: str = "model") -> list:
    """Model-column indices with at least one addressable device — the
    per-process addressable-shard bookkeeping ``GLMSolver`` records."""
    import jax
    axes = list(mesh.axis_names)
    dev = np.moveaxis(np.asarray(mesh.devices), axes.index(axis_model), -1)
    dev = dev.reshape(-1, dev.shape[-1])
    me = jax.process_index()
    return [m for m in range(dev.shape[1])
            if any(d.process_index == me for d in dev[:, m])]


def is_multiprocess_mesh(mesh) -> bool:
    return len({d.process_index for d in mesh.devices.flat}) > 1


# ---------------------------------------------------------------------------
# global placement / host gather
# ---------------------------------------------------------------------------

def put_global(arr, mesh, spec):
    """Place a host array (or pytree thereof) onto a possibly
    process-spanning mesh.

    Every process passes the SAME full host array (the replicated-host
    data model; ``StreamingDesign.process_slice`` is the beyond-host-memory
    path) and contributes only the shards its devices own.  On a
    single-process mesh this is exactly ``jax.device_put``.
    """
    import jax
    from jax.sharding import NamedSharding

    def _put_one(a, s):
        sharding = NamedSharding(mesh, s)
        if not is_multiprocess_mesh(mesh):
            # lint: allow DIST001 — this IS put_global's single-process path
            return jax.device_put(a, sharding)
        a = np.asarray(a)
        return jax.make_array_from_callback(a.shape, sharding,
                                            lambda idx: a[idx])

    if isinstance(spec, jax.sharding.PartitionSpec):
        return _put_one(arr, spec)
    return jax.tree.map(_put_one, arr, spec)


_GATHER_CACHE: dict = {}


def gather_to_host(x) -> np.ndarray:
    """Host numpy copy of a (possibly non-addressable) global array.

    Fully-addressable and fully-replicated arrays read back directly; a
    cross-process sharded array goes through a cached jitted identity with
    replicated output sharding (an all-gather collective — every process
    must call this, like any other collective).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if not isinstance(x, jax.Array) or x.is_fully_addressable \
            or x.is_fully_replicated:
        return np.asarray(x)
    mesh = x.sharding.mesh
    key = id(mesh)
    fn = _GATHER_CACHE.get(key)
    if fn is None:
        fn = jax.jit(lambda a: a,
                     out_shardings=NamedSharding(mesh, P()))
        _GATHER_CACHE[key] = fn
    return np.asarray(fn(x))


# ---------------------------------------------------------------------------
# distributed runtime client: KV store + barriers
# ---------------------------------------------------------------------------

def _client():
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "jax.distributed is not initialized — call "
            "repro.dist.bootstrap.initialize() (or run under "
            "repro.dist.launcher) first")
    return client


def kv_set(key: str, value: str):
    _client().key_value_set(key, value)


def kv_get(key: str, timeout_s: float = 30.0) -> str:
    out = _client().blocking_key_value_get(key, int(timeout_s * 1000))
    return out.decode() if isinstance(out, bytes) else out


_BARRIER_SEQ: int = 0


class BarrierTagMismatch(RuntimeError):
    """Processes reached the same barrier slot with DIFFERENT tags.

    This is the fail-fast form of the classic SPMD deadlock: a control-flow
    divergence (one process took an early return, skipped a checkpoint, or
    ran an extra rebalance round) sends the processes to different barriers,
    and without the tag exchange each side hangs until the barrier timeout
    with no hint of why.  The tag exchange names both tags instead.
    """


def barrier(tag: str = "repro", timeout_s: float = 60.0):
    """Process barrier through the distributed runtime's KV service.

    No-op in single-process runs.  Barrier ids are suffixed with a global
    sequence number so repeated barriers never collide — and, unlike a
    per-tag counter, processes whose control flow diverged meet at the
    SAME slot with different tags instead of different slots with the
    same tag.  Before waiting, every process publishes its tag for the
    slot and checks it against process 0's; a divergence raises
    :class:`BarrierTagMismatch` naming both tags immediately rather than
    hanging to the barrier timeout.  Plain timeouts (a peer died or
    wedged) still raise the runtime's error —
    ``repro.dist.faults.guarded_barrier`` turns those into a diagnosable
    ``DeadProcessError`` while letting ``BarrierTagMismatch`` through
    untouched.
    """
    global _BARRIER_SEQ
    if not context().multiprocess:
        return
    seq = _BARRIER_SEQ
    _BARRIER_SEQ += 1
    pid = context().process_id
    kv_set(f"repro/barrier_tag/{seq}/{pid}", tag)
    ref = (tag if pid == 0 else
           kv_get(f"repro/barrier_tag/{seq}/0", timeout_s=timeout_s))
    if ref != tag:
        raise BarrierTagMismatch(
            f"barrier slot {seq}: process {pid} arrived with tag {tag!r} "
            f"but process 0 arrived with {ref!r} — SPMD control flow has "
            "diverged (every process must execute the same barrier "
            "sequence; see lint rule DIST002)")
    _client().wait_at_barrier(f"{tag}/{seq}", int(timeout_s * 1000))
