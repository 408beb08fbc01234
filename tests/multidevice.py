"""The multi-device launch recipe for tests: a script in a subprocess with
fake CPU host devices.  A module of its own, not `conftest`, because
`bench/tests` has a `conftest.py` too and the two share one module name in
a test process."""
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = Path(__file__).resolve().parent.parent / "src"


def run_multidevice(script: str, n_dev: int) -> str:
    """Run `script` in a subprocess with n_dev fake CPU host devices -- THE
    multi-device launch recipe (XLA_FLAGS must be set before jax initialises,
    so multi-device semantics tests cannot run in this process).  Asserts the
    script exits 0 and returns its stdout."""
    code = textwrap.dedent(script)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        env={
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
            "PYTHONPATH": str(SRC),
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu",
            "HOME": "/tmp",
        },
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout
