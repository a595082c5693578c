"""Every runnable example executes end-to-end (slow tier; subprocess per
script, on the CPU: the examples run on whatever platform JAX selects, and
JAX_PLATFORMS=cpu is passed from outside)."""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(_REPO, "examples"))
    if f.endswith(".py"))


@pytest.mark.slow
@pytest.mark.parametrize("script", _EXAMPLES)
def test_example_runs(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", script)],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    assert r.returncode == 0, (
        f"{script} failed\nstdout:\n{r.stdout[-2000:]}\n"
        f"stderr:\n{r.stderr[-2000:]}")
