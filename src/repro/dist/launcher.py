"""Spawn-N-local-processes runner: the one-machine stand-in for a cluster
scheduler.

Tests, CI and the straggler benchmark all need "run this program as N
coordinated processes" without MPI or Kubernetes; ``run_local`` provides
exactly that:

    result = launcher.run_local(2, "path/to/prog.py", args=["--x", "1"])
    assert result.ok and "PARITY_OK" in result.outputs[0]

Each worker gets the ``REPRO_DIST_*`` env vars (`bootstrap.initialize()`
reads them), one CPU device (``JAX_PLATFORMS=cpu``,
``XLA_FLAGS=--xla_force_host_platform_device_count=1`` unless the caller
overrides), and a fresh coordinator port.  When any worker exits non-zero
the rest are killed after ``grace_s`` — a dead process must fail the JOB,
not leave N−1 peers wedged at a collective (their own ``guarded_barrier``
timeouts fire first when they hit one).
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import socket
import subprocess
import sys
import time
from typing import List, Optional, Sequence

_SRC = pathlib.Path(__file__).resolve().parents[2]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass
class JobResult:
    returncodes: List[int]
    outputs: List[str]          # merged stdout+stderr per process

    @property
    def ok(self) -> bool:
        return all(rc == 0 for rc in self.returncodes)

    def summary(self, tail: int = 4000) -> str:
        return "\n".join(
            f"--- process {i} (exit {rc}) ---\n{out[-tail:]}"
            for i, (rc, out) in enumerate(zip(self.returncodes,
                                              self.outputs)))


def worker_env(process_id: int, num_processes: int, coordinator: str, *,
               devices_per_process: int = 1) -> dict:
    """Env block one worker needs; exposed so callers embedding workers in
    other harnesses (pytest-xdist, shell scripts) can reuse it.

    Local workers are fake-device CPU simulations of a multi-host job, so
    each one is pinned to ``JAX_PLATFORMS=cpu``: a worker started next to a
    parent that holds the accelerator must never try to claim it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_DIST_COORD"] = coordinator
    env["REPRO_DIST_NPROCS"] = str(num_processes)
    env["REPRO_DIST_PROCID"] = str(process_id)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={devices_per_process}").strip()
    env.setdefault("PYTHONPATH", str(_SRC))
    return env


def run_local(num_processes: int, script, *, args: Sequence[str] = (),
              timeout_s: float = 900.0, devices_per_process: int = 1,
              grace_s: float = 15.0,
              coordinator: Optional[str] = None) -> JobResult:
    """Run ``script`` as ``num_processes`` coordinated local processes.

    Streams nothing; collects each process's merged output.  Kills the
    stragglers ``grace_s`` after the first non-zero exit (a crashed peer
    leaves the others blocked inside a collective with no way out — the
    job-level guard lives here, the in-process one in ``faults``).
    """
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    procs = []
    for pid in range(num_processes):
        procs.append(subprocess.Popen(
            [sys.executable, str(script), *map(str, args)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=worker_env(pid, num_processes, coordinator,
                           devices_per_process=devices_per_process)))

    deadline = time.monotonic() + timeout_s
    fail_deadline = None
    while True:
        states = [p.poll() for p in procs]
        if all(s is not None for s in states):
            break
        now = time.monotonic()
        if any(s not in (None, 0) for s in states) and fail_deadline is None:
            fail_deadline = now + grace_s
        if now > deadline or (fail_deadline and now > fail_deadline):
            for p in procs:
                if p.poll() is None:
                    p.kill()
        time.sleep(0.1)

    outputs = []
    for p in procs:
        out, _ = p.communicate()
        outputs.append(out or "")
    return JobResult([p.returncode for p in procs], outputs)
