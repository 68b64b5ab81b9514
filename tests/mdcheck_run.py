"""Runs one ``repro.testing.mdchecks`` check in a subprocess with fake CPU
devices (XLA_FLAGS is never set in the pytest process — the tests there see
one device).  The checks are split over several test files so that pytest
workers, which take whole files, run them side by side."""
import os
import pathlib
import subprocess
import sys

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_mdcheck(check: str) -> None:
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.testing.mdchecks", check],
        env=env, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, \
        f"{check} failed:\n{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    assert "PASS" in r.stdout
