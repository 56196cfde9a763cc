"""The benchmark never measures a CPU: on a host without a TPU it exits
non-zero and prints no result line."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def test_run_refuses_a_cpu_only_host():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "paper_cnn_b20.poisson", "--seed", str(2 ** 31 + 3),
         "--seconds", "10", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "refused" in p.stderr
    assert '"metrics"' not in p.stdout and "{" not in p.stdout
