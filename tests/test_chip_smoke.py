"""`chip_smoke.py` must refuse to run without a TPU: no CPU fallback, no
result line, and no dataset built before it refuses."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok": true' not in r.stdout
    assert "platform cpu" in r.stdout
    assert "refusing to run" in r.stderr
    assert "data:" not in r.stdout          # refused before any set-up
