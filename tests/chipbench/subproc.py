"""Shared by the tests that run the one command as a subprocess."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(argv, timeout=900):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
