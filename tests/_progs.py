"""Runner for the multi-device test programs in tests/progs/.

XLA_FLAGS / device-count overrides are deliberately NOT set in the test
process — smoke tests must see the real single CPU device.  Multi-device
tests run the programs in subprocesses that set XLA_FLAGS before
importing jax."""
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src"
PROGS = pathlib.Path(__file__).parent / "progs"


def run_prog(name: str, *args, devices: int = 8, timeout: int = 900):
    """Run tests/progs/<name>.py in a subprocess with N fake devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(SRC)
    out = subprocess.run(
        [sys.executable, str(PROGS / f"{name}.py"), *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, \
        f"{name} failed:\nSTDOUT:\n{out.stdout[-4000:]}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout
