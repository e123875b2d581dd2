"""Helpers of perf/tests: run one cell in a child process on the CPU."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PRELUDE = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={chips}"
os.environ["HEAT_TPU_X64"] = "0"
sys.path.insert(0, {root!r})
{patch}
from perf import run
sys.exit(run.main({argv!r}))
"""


def run_cell(workload, chips=1, trace=0, seconds=0.5, seed=2147483777, patch="",
             rehearse=True):
    """(return code, last stdout line parsed or None, stderr)."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)] + (["--rehearse-cpu"] if rehearse else [])
    code = _PRELUDE.format(chips=chips, root=ROOT, patch=patch, argv=argv)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          cwd=ROOT, env=env, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except ValueError:
            result = None
    return proc.returncode, result, proc.stderr
