"""Run a snippet in a subprocess with N forced host devices.

JAX locks the device count at first init, so multi-device tests (which
must not pollute the 1-device smoke environment) execute in children.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Execute `code` with n host devices; raises on nonzero exit.
    The snippet should print its assertions/outputs; stdout is returned."""
    preamble = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"     # forced host devices: a CPU rehearsal
    proc = subprocess.run(
        [sys.executable, "-c", preamble + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode}):\n"
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}")
    return proc.stdout
